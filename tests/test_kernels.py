"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret
mode on CPU): shapes x dtypes x feature flags."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,T,H,K,D,causal,window,softcap", [
    (1, 128, 128, 4, 2, 64, True, None, None),
    (2, 256, 256, 8, 4, 64, True, None, 50.0),
    (1, 200, 200, 4, 4, 48, True, 128, None),     # unpadded + window
    (1, 128, 384, 4, 2, 64, True, None, None),    # longer KV (decode-ish)
    (1, 128, 128, 4, 1, 64, False, None, None),   # MQA + non-causal
    (1, 130, 130, 2, 2, 32, True, None, None),    # awkward sizes
    (1, 520, 520, 4, 2, 64, True, None, None),    # S not a block multiple
    (1, 100, 300, 2, 2, 64, True, None, None),    # T > S, offset queries
    (1, 640, 640, 2, 2, 64, True, 100, None),     # window < one block
    (1, 256, 256, 4, 1, 256, True, None, 30.0),   # MQA + softcap, D > 128
])
def test_flash_attention_matches_ref(dtype, B, S, T, H, K, D, causal,
                                     window, softcap):
    """Output and dq/dk/dv against jax.vjp of the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, K, D), dtype)
    v = jax.random.normal(ks[2], (B, T, K, D), dtype)
    g = jax.random.normal(ks[3], (B, S, H, D), dtype)
    qp = jnp.arange(T - S, T, dtype=jnp.int32)
    kp = jnp.arange(T, dtype=jnp.int32)
    t = (0, 2, 1, 3)
    out, vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, qp, kp, window=window,
                                        softcap=softcap, causal=causal,
                                        interpret=True), q, k, v)
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: attention_ref(
            q.transpose(t), k.transpose(t), v.transpose(t), qp, kp,
            scale=D ** -0.5, causal=causal, window=window,
            softcap=softcap).transpose(t), q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    # gradients: bf16 results carry two ulps of their own magnitude
    for name, a, b in zip(("dq", "dk", "dv"), vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol,
                                   rtol=tol, err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_rows_that_see_no_key(dtype):
    """Queries at negative positions see no key: zeros out, no gradient.
    Their first q block skips every pair; the second straddles key block
    0 with such rows in it.  The other rows match the oracle."""
    S, H, K, D, blind = 640, 2, 1, 64, 200
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (1, S, H, D), dtype)
    k = jax.random.normal(ks[1], (1, S, K, D), dtype)
    v = jax.random.normal(ks[2], (1, S, K, D), dtype)
    g = jax.random.normal(ks[3], (1, S, H, D), dtype).at[:, :blind].set(0)
    qp = jnp.arange(S, dtype=jnp.int32) - blind
    kp = jnp.arange(S, dtype=jnp.int32)
    t = (0, 2, 1, 3)
    out, vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, qp, kp, interpret=True),
        q, k, v)
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: attention_ref(
            q.transpose(t), k.transpose(t), v.transpose(t), qp, kp,
            scale=D ** -0.5).transpose(t), q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    out = np.asarray(out, np.float32)
    assert not out[:, :blind].any()
    np.testing.assert_allclose(out[:, blind:],
                               np.asarray(ref, np.float32)[:, blind:],
                               atol=tol)
    # g is zero on the blind rows, so the oracle's gradients take nothing
    # from its average over their masked keys
    for name, a, b in zip(("dq", "dk", "dv"), vjp(g), ref_vjp(g)):
        a = np.asarray(a, np.float32)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, np.asarray(b, np.float32), atol=tol,
                                   rtol=tol, err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,L,H,P,N,chunk", [
    (1, 64, 4, 16, 16, 16),
    (2, 256, 8, 32, 32, 128),
    (1, 100, 4, 16, 32, 32),       # L not a chunk multiple
    (1, 128, 1, 64, 128, 64),      # single head, wide state
])
def test_ssd_matches_sequential_ref(dtype, b, L, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = (jax.random.normal(ks[0], (b, L, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, L, N)) * 0.5).astype(dtype)
    C = (jax.random.normal(ks[0], (b, L, N)) * 0.5).astype(dtype)
    y, _ = ssd(x, dt, A, B, C, chunk=chunk, interpret=True)
    y_ref, _ = ssd_ref(x.astype(jnp.float32), dt, A,
                       B.astype(jnp.float32), C.astype(jnp.float32))
    tol = 1e-3 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 64, 128), (300, 96), (1, 1, 256),
                                   (257, 384)])
def test_rmsnorm_matches_ref(dtype, shape):
    x = jax.random.normal(jax.random.PRNGKey(2), shape, dtype)
    s = jnp.asarray(np.linspace(0.5, 1.5, shape[-1]), dtype)
    out = rmsnorm(x, s, interpret=True)
    ref = rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


def test_flash_attention_grad_flows():
    """The kernel participates in autodiff (interpret mode lowers to
    differentiable lax ops)."""
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 2, 64))
    kv = jax.random.normal(jax.random.PRNGKey(4), (1, 128, 2, 64))
    pos = jnp.arange(128, dtype=jnp.int32)

    def f(q):
        return flash_attention(q, kv, kv, pos, pos, interpret=True).sum()

    g = jax.grad(f)(q)
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0
