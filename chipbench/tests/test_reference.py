"""The plain references against the program, on the CPU at a tiny size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import weights
from reference import common, dense, ssm
from tiny import TINY_DENSE, TINY_SSM


def _program_logits(config, params, tokens):
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    model = build_model(ModelConfig(**config["model"]))
    return np.asarray(model.apply(params, {"tokens": tokens})[0])


@pytest.mark.parametrize("config,ref", [(TINY_DENSE, dense), (TINY_SSM, ssm)])
def test_forward_matches_program(config, ref):
    params = jax.jit(weights.maker(config))(weights.key_data(2**40 + 3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(common.to_f32(params), tokens,
                                      config["model"],
                                      common.quantizer("f32")))
    got = _program_logits(config, params, tokens)
    # the program rounds activations to bfloat16 (2^-8 relative) at every
    # layer; logits have unit scale
    assert np.abs(got - want).max() < 0.1
    assert np.abs(got - want).mean() < 0.02


def test_recurrence_matches_chunked_scan():
    """The plain recurrence and the program's chunked SSD scan agree in
    float32 to rounding."""
    from repro.models.ssm import ssd_scan
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    b, S, H, P, N = 2, 64, 3, 4, 8
    x = jax.random.normal(k[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, S, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (b, S, N))
    Cm = jax.random.normal(k[4], (b, S, N))
    with jax.default_matmul_precision("highest"):
        want, _ = ssd_scan(x, dt, A, Bm, Cm, 16)
        got = ssm.recurrence(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_control_precision_rounds():
    q = common.quantizer("fp8")
    x = jnp.linspace(-3.0, 3.0, 101)
    want = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    np.testing.assert_array_equal(jax.jit(q)(x), want)
    g = jax.grad(lambda v: jnp.sum(q(v) * 2.0))(x)
    np.testing.assert_array_equal(g, 2.0)          # straight through


def test_store_rounds_to_bfloat16_under_jit():
    x = jnp.float32(1.0) - jnp.float32(3e-4)
    assert float(jax.jit(lambda v: common.store(v, jnp.bfloat16))(x)) == 1.0
    assert float(common.store(x, jnp.float32)) == float(x)
