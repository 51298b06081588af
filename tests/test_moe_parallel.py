"""Expert-parallel / TP-ff MoE vs the GSPMD oracle.

The shard_map paths need >1 device, and jax pins the device count at
first init, so the comparison runs in a subprocess with
xla_force_host_platform_device_count=8 (per the no-global-flags rule).
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, dataclasses, numpy as np
from repro.configs import ARCHS, reduced
from repro.models.moe import (moe_block_gspmd, moe_block_expert_parallel,
                              moe_block_tp_ff, moe_init)
from repro.runtime.parallel import ParallelContext
from repro.launch.mesh import make_auto_mesh

cfg = dataclasses.replace(reduced(ARCHS["kimi-k2-1t-a32b"]), n_experts=8,
                          experts_per_token=2, moe_d_ff=32, d_model=64,
                          unit=())
params = moe_init(jax.random.PRNGKey(0), cfg)
mesh = make_auto_mesh((2, 4), ("data", "model"))
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
ctx = ParallelContext(capacity_factor=8.0)   # high capacity: no drops
with jax.set_mesh(mesh):
    y_ref, _ = jax.jit(lambda p, x: moe_block_gspmd(p, x, cfg))(params, x)
    y_ep, _ = jax.jit(
        lambda p, x: moe_block_expert_parallel(p, x, cfg, ctx))(params, x)
    y_tp, _ = jax.jit(
        lambda p, x: moe_block_tp_ff(p, x, cfg, ctx))(params, x)
    # gradients flow through the shard_map paths
    g = jax.jit(jax.grad(
        lambda p: moe_block_expert_parallel(p, x, cfg, ctx)[0].astype(
            jnp.float32).sum()))(params)
ep = float(jnp.abs(y_ep - y_ref).max())
tp = float(jnp.abs(y_tp - y_ref).max())
assert ep < 1e-5, f"expert-parallel mismatch {ep}"
assert tp < 1e-4, f"tp-ff mismatch {tp}"
gn = max(float(jnp.abs(v).max()) for v in jax.tree.leaves(g))
assert np.isfinite(gn) and gn > 0
print("MOE_PARALLEL_OK")
"""


@pytest.mark.slow
def test_moe_parallel_matches_oracle():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "MOE_PARALLEL_OK" in out.stdout, out.stdout + out.stderr
