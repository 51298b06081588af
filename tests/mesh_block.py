"""One Mamba block, forward and backward in float32, on one CPU device and
on the (data=1, model=4) mesh of four, in a process of its own:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python tests/mesh_block.py '<ModelConfig fields as JSON>'

Prints one JSON line: the relative gap between the mesh's and the one
device's output (`y`) and gradients (`in_proj`, `conv_w`, `conv_b`, `x`),
the op_names of each compiled program (`ops_one`, `ops_mesh`), and the
collective-permutes that move in_proj's columns in the whole model's
gradient on the mesh, with and without its remat (`moves`).
`run(fields)` starts that process from a test.
"""

import functools
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_auto_mesh
from repro.models import build_model, scopes, ssm
from repro.runtime.sharding import params_shardings


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _moves(cfg: ModelConfig, remat: bool) -> int:
    """Collective-permutes inside `in_proj` in the compiled gradient of the
    whole model, with or without its remat."""
    model = build_model(cfg, impl="naive", remat=remat)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 32), jnp.int32)

    def loss(p):
        return model.apply(p, {"tokens": tokens})[0].sum()

    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    return sum(scopes.in_scope(name, "in_proj") for name in re.findall(
        r' collective-permute(?:-start)?\(.*op_name="([^"]*)"', text))


def run(fields: dict) -> dict:
    """main(fields), in a process of its own with four CPU devices."""
    return _run(json.dumps(fields, sort_keys=True))


@functools.lru_cache(maxsize=None)
def _run(fields: str) -> dict:
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        fields],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def main(fields: dict) -> dict:
    cfg = ModelConfig(**fields)
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ssm.mamba_init(k[0], cfg))
    params["conv_b"] = jax.random.normal(k[1], params["conv_b"].shape)
    x = jax.random.normal(k[2], (2, 32, cfg.d_model))
    probe = jax.random.normal(k[3], x.shape)

    def loss(p, x):
        y = ssm.mamba_block(p, x, cfg)
        return (y * probe).sum(), y

    grad = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
    with jax.default_matmul_precision("highest"):
        one = grad.lower(params, x).compile()
        mesh = make_auto_mesh((1, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            placed = jax.device_put(params, params_shardings(mesh, params))
            four = grad.lower(placed, x).compile()
            (g4, gx4), y4 = four(placed, x)
            out = {"moves": {"remat": _moves(cfg, True),
                             "plain": _moves(cfg, False)}}
        (g1, gx1), y1 = one(params, x)
    out.update({"y": _rel(y4, y1), "x": _rel(gx4, gx1)})
    out.update({name: _rel(g4[name], g1[name])
                for name in ("in_proj", "conv_w", "conv_b")})
    for key, compiled in (("ops_one", one), ("ops_mesh", four)):
        out[key] = sorted(set(
            scopes.program_ops(compiled.as_text())["ops"].values()))
    return out


if __name__ == "__main__":
    print(json.dumps(main(json.loads(sys.argv[1]))))
