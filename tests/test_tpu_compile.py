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

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.ssd.ops import ssd


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


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
