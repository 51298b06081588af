"""Compile-only checks of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described and not present.  That refuses what
interpret mode accepts (unaligned blocks, unsupported primitives, too much
VMEM), so each kernel is compiled here at the widths the models run it
at.  Nothing executes; results are checked by tests/test_kernels.py
(interpret mode) and on the chip by chip_smoke.py.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import contextlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.ssd.ops import ssd
from repro.models import scopes, ssm


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiled(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_smollm_train(one_chip):
    cfg = ARCHS["smollm-360m"]
    B, S = 8, 1024
    bf16, i32 = jnp.bfloat16, jnp.int32
    q = _spec(one_chip, (B, S, cfg.n_heads, cfg.head_dim), bf16)
    kv = _spec(one_chip, (B, S, cfg.n_kv_heads, cfg.head_dim), bf16)
    pos = _spec(one_chip, (S,), i32)
    _assert_kernel_compiled(
        lambda q, k, v, qp, kp: flash_attention(q, k, v, qp, kp,
                                                interpret=False),
        q, kv, kv, pos, pos)


def test_flash_attention_grad_compiles_smollm_train(one_chip):
    """jax.grad through the kernels at smollm-360m's training shape: the
    forward and both backward kernels, and no S x S score buffer."""
    cfg = ARCHS["smollm-360m"]
    B, S = 8, 2048
    bf16, i32 = jnp.bfloat16, jnp.int32
    q = _spec(one_chip, (B, S, cfg.n_heads, cfg.head_dim), bf16)
    kv = _spec(one_chip, (B, S, cfg.n_kv_heads, cfg.head_dim), bf16)
    pos = _spec(one_chip, (S,), i32)

    def loss(q, k, v, qp, kp):
        out = flash_attention(q, k, v, qp, kp, interpret=False)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, pos, pos).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(re.search(rf"%{name}(\.\d+)? = ", line)
                   for line in calls)
    assert f"{S},{S}]" not in text


# every config with attention, at blocks of 512 (the largest folded tile:
# G query heads x 512 rows), with its window and softcap; seamless's
# cross-attention is non-causal with fewer keys than queries
ATTN_CASES = [(a, False) for a, c in ARCHS.items() if c.family != "ssm"] + [
    ("seamless-m4t-large-v2", True)]


@pytest.mark.parametrize("arch,cross", ATTN_CASES,
                         ids=[a + ("-cross" if x else "")
                              for a, x in ATTN_CASES])
def test_flash_attention_grad_compiles_each_config(one_chip, arch, cross):
    cfg = ARCHS[arch]
    S = 1024
    T = S // 2 if cross else S
    bf16, i32 = jnp.bfloat16, jnp.int32
    q = _spec(one_chip, (1, S, cfg.n_heads, cfg.head_dim), bf16)
    kv = _spec(one_chip, (1, T, cfg.n_kv_heads, cfg.head_dim), bf16)

    def loss(q, k, v, qp, kp):
        out = flash_attention(q, k, v, qp, kp, window=cfg.sliding_window,
                              softcap=cfg.attn_softcap, causal=not cross,
                              interpret=False)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, _spec(one_chip, (S,), i32),
        _spec(one_chip, (T,), i32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3


@pytest.mark.parametrize("devices,kernels", [(1, True), (4, False)])
def test_train_step_auto_attention_on_a_mesh(v5e, devices, kernels,
                                             monkeypatch):
    """A train step through sdpa(impl="auto") on the TPU at
    PALLAS_MIN_T keys: the flash kernels on a one-device mesh; on the
    host's (1, 4) mesh, which Mosaic kernels cannot be partitioned over,
    the plain path, and the step compiles."""
    from repro.data.pipeline import DataConfig, batch_for_model
    from repro.configs import reduced
    from repro.launch.mesh import make_auto_mesh
    from repro.models.attention import PALLAS_MIN_T
    from repro.runtime.parallel import ParallelContext, parallel_context
    from repro.runtime.sharding import state_shardings
    from repro.runtime.train import TrainConfig, make_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = reduced(ARCHS["smollm-360m"])
    step_fn, init_fn = make_train_step(cfg, TrainConfig())
    batch = batch_for_model(cfg, DataConfig(seq_len=PALLAS_MIN_T,
                                            global_batch=4,
                                            vocab_size=cfg.vocab_size), 0)
    mesh = make_auto_mesh((1, devices), ("data", "model"),
                          devices=v5e[:devices])
    with jax.set_mesh(mesh), parallel_context(ParallelContext()):
        abstract = jax.eval_shape(lambda: init_fn(jax.random.PRNGKey(0)))
        st_sh = state_shardings(mesh, abstract, "adamw")
        state = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            abstract, st_sh)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep)
                 for k, v in batch.items()}
        text = jax.jit(step_fn).lower(state, batch).compile().as_text()
    assert ("tpu_custom_call" in text) == kernels


def test_ssd_compiles_mamba2_widths(one_chip):
    cfg = ARCHS["mamba2-130m"]
    b, L = 8, 1024
    H, P, N = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    f32, bf16 = jnp.float32, jnp.bfloat16
    _assert_kernel_compiled(
        lambda x, dt, A, B, C: ssd(x, dt, A, B, C, chunk=cfg.ssm_chunk,
                                   interpret=False)[0],
        _spec(one_chip, (b, L, H, P), bf16), _spec(one_chip, (b, L, H), f32),
        _spec(one_chip, (H,), f32), _spec(one_chip, (b, L, N), bf16),
        _spec(one_chip, (b, L, N), bf16))


def test_rmsnorm_compiles_d960(one_chip):
    d = ARCHS["smollm-360m"].d_model
    _assert_kernel_compiled(
        lambda x, s: rmsnorm(x, s, interpret=False),
        _spec(one_chip, (8, 1024, d), jnp.bfloat16),
        _spec(one_chip, (d,), jnp.bfloat16))


def _mamba_grad_text(cfg, mesh, x_sharding, param_sharding):
    """The compiled gradient of one Mamba block at cfg's widths, 1 x 1024
    tokens, its parameters placed by `param_sharding(name, shape)`."""
    params = jax.eval_shape(lambda: ssm.mamba_init(jax.random.PRNGKey(0),
                                                   cfg))
    params = {k: jax.tree.map(lambda a, k=k: _spec(param_sharding(k, a.shape),
                                                   a.shape, a.dtype), v)
              for k, v in params.items()}
    x = _spec(x_sharding, (1, 1024, cfg.d_model), jnp.bfloat16)

    def loss(p, x):
        return ssm.mamba_block(p, x, cfg).astype(jnp.float32).sum()

    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile().as_text()


COLLECTIVE = re.compile(r"= (\S+|\(.*?\)) (all-to-all|all-gather|all-reduce|"
                        r"collective-permute|reduce-scatter)(?:-start)?\(")


def test_mamba_block_moves_in_proj_columns_on_a_mesh(v5e):
    """zamba2-7b's Mamba block, forward and backward, on the (1, 4) mesh
    that splits in_proj's 14704 columns in blocks of 3676 across its
    parts: no collective carries in_proj's output (a shape with 14704 or
    3676 columns), and no all-gather is as large as a whole part of the
    weight, bf16[3584, 7168].  The columns move to each chip's heads."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_auto_mesh
    from repro.runtime.sharding import param_spec
    cfg = ARCHS["zamba2-7b"]
    mesh = make_auto_mesh((1, 4), ("data", "model"), devices=v5e)
    text = _mamba_grad_text(
        cfg, mesh, NamedSharding(mesh, P("data")),
        lambda name, shape: NamedSharding(mesh, param_spec(mesh, name,
                                                           shape)))
    found = COLLECTIVE.findall(text)
    assert any(op == "collective-permute" for _, op in found)
    for shape, op in found:
        dims = [int(d) for d in re.findall(r"\d+", " ".join(
            re.findall(r"\[([\d,]*)\]", shape)))]
        assert not {14704, 3676} & set(dims), (op, shape)
        if op == "all-gather":
            assert math.prod(dims) < 3584 * 7168, shape
    assert any(scopes.in_scope(n, "in_proj")
               for n in scopes.program_ops(text)["ops"].values())


def test_mamba_block_on_one_chip_keeps_the_fused_projection(one_chip):
    """mamba2-130m's Mamba block on one chip: no mesh cuts in_proj, so the
    block multiplies by it whole and the `in_proj` scope never appears."""
    text = _mamba_grad_text(ARCHS["mamba2-130m"], None, one_chip,
                            lambda name, shape: one_chip)
    assert not any(scopes.in_scope(n, "in_proj")
                   for n in scopes.program_ops(text)["ops"].values())
