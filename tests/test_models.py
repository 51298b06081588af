"""Per-architecture smoke tests (reduced configs) + model invariants.

Every assigned arch: instantiate the reduced config of the same family,
run one forward and one train step on CPU, assert output shapes and
finiteness.  Plus decode-vs-forward consistency (the KV-cache/SSM-state
decode path must reproduce the full-sequence forward logits) and causality
(future tokens cannot influence past logits).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import attention, build_model
from repro.models.attention import PALLAS_MIN_T
from repro.optim.optimizers import OptimizerConfig
from repro.runtime.train import TrainConfig, make_train_step

from arch_batch import arch_batch

ARCH_NAMES = list(ARCHS)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_forward(arch):
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    batch = arch_batch(cfg)
    logits, aux = model.apply(params, batch)
    S = 32
    assert logits.shape == (2, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), arch
    assert bool(jnp.isfinite(aux)), arch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_train_step(arch):
    cfg = reduced(ARCHS[arch])
    tcfg = TrainConfig(optimizer=OptimizerConfig(lr=1e-3, warmup_steps=1,
                                                 total_steps=10),
                       remat=False)
    step_fn, init_fn = make_train_step(cfg, tcfg)
    state = init_fn(jax.random.PRNGKey(0))
    state2, metrics = jax.jit(step_fn)(state, arch_batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(state2["step"]) == 1
    # parameters actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        state["params"], state2["params"])
    assert max(jax.tree.leaves(moved)) > 0


# bf16 decode-vs-forward tolerance.  Attention caches are read-only, so
# decode differs from forward only in reduction *order* and stays within
# a few bf16 ulps of the ~[2,4)-binade logits (ulp 2^-7): 0.15 covers it.
# Recurrent SSM state is different: decode updates the state token by
# token while the forward pass runs a blocked scan, so the state drifts
# by O(ulp) per step and the drift compounds over the sequence before
# the vocab projection amplifies it.  For the zamba2 hybrid (a mamba
# block per layer, two of them fed by shared attention blocks) the
# observed error grows with t up to ~0.16 at S=12; we bound it by
# S * n_layers * ulp = 12 * 4 * 2^-6 = 0.75 (one sign-flip of a 2-ulp
# state perturbation per layer per step, at the [4,8) logit binade).
_DECODE_TOL = {"zamba2-7b": 0.75}


@pytest.mark.parametrize("arch", ["smollm-360m", "gemma2-2b",
                                  "mixtral-8x22b", "mamba2-130m",
                                  "zamba2-7b", "chatglm3-6b"])
def test_decode_matches_forward(arch):
    """Token-by-token decode must reproduce the full forward logits."""
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, impl="naive", remat=False)
    params = model.init(jax.random.PRNGKey(1))
    B, S = 1, 12
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    full_logits, _ = model.apply(params, {"tokens": toks})

    cache = model.init_cache(B, S + 1)
    dec = jax.jit(model.decode)
    errs = []
    for t in range(S):
        lg, cache = dec(params, cache, toks[:, t:t + 1], jnp.int32(t))
        errs.append(float(jnp.abs(
            lg[:, 0] - full_logits[:, t]).max()))
    tol = _DECODE_TOL.get(arch, 0.15)  # bf16 accumulation tolerance
    assert max(errs) < tol, (arch, errs)


def test_causality():
    """Perturbing future tokens must not change past logits."""
    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg, impl="naive", remat=False)
    params = model.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)), jnp.int32)
    toks2 = toks.at[0, 12:].set((toks[0, 12:] + 7) % cfg.vocab_size)
    l1, _ = model.apply(params, {"tokens": toks})
    l2, _ = model.apply(params, {"tokens": toks2})
    np.testing.assert_allclose(np.asarray(l1[:, :12]),
                               np.asarray(l2[:, :12]), atol=1e-5)


def test_sliding_window_limits_context():
    """With window w, logits at t depend only on tokens in [t-w+1, t]."""
    import dataclasses
    base = reduced(ARCHS["mixtral-8x22b"])
    cfg = dataclasses.replace(base, sliding_window=4, unit=())
    model = build_model(cfg, impl="naive", remat=False)
    params = model.init(jax.random.PRNGKey(4))
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)), jnp.int32)
    # change token 0: positions >= layers*window away cannot see it.
    toks2 = toks.at[0, 0].set((toks[0, 0] + 3) % cfg.vocab_size)
    l1, _ = model.apply(params, {"tokens": toks})
    l2, _ = model.apply(params, {"tokens": toks2})
    # information propagates at most `window-1` per attention layer
    # (moe-family units pair every attention with an expert block, so the
    # attention count equals n_layers)
    n_attn = cfg.n_layers
    horizon = n_attn * (cfg.sliding_window - 1) + 1
    if horizon < 16:
        np.testing.assert_allclose(np.asarray(l1[:, horizon:]),
                                   np.asarray(l2[:, horizon:]), atol=1e-5)


def test_chunked_equals_naive_attention():
    cfg = reduced(ARCHS["qwen2.5-32b"])
    model_n = build_model(cfg, impl="naive", remat=False)
    model_c = build_model(cfg, impl="chunked", remat=False)
    params = model_n.init(jax.random.PRNGKey(6))
    toks = jnp.asarray(np.arange(64)[None, :] % cfg.vocab_size, jnp.int32)
    l1, _ = model_n.apply(params, {"tokens": toks})
    l2, _ = model_c.apply(params, {"tokens": toks})
    # Chunked attention renormalises its accumulator with the *running*
    # row max (online softmax), so whenever the max moves between chunks
    # the partial sums are rescaled in bf16 — a few-ulp reordering drift
    # on the affected logits.  Bound: 2 ulps at the top logit binade
    # [8, 16), i.e. 2 * 8 * 2^-8 = 0.125 (observed worst offender: one
    # logit in 16384 off by 0.0547 = 7 ulps at [2, 4)).
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=0.125, rtol=1e-2)


@pytest.mark.parametrize("backend,T,pos_ndim,want", [
    ("tpu", PALLAS_MIN_T, 1, "pallas"),
    ("tpu", 4 * PALLAS_MIN_T, 1, "pallas"),
    ("tpu", PALLAS_MIN_T - 1, 1, "naive"),
    ("tpu", PALLAS_MIN_T, 2, "naive"),      # per-sequence positions
    ("cpu", PALLAS_MIN_T, 1, "naive"),
    ("cpu", 4096, 1, "chunked"),
])
def test_auto_attention_routing(backend, T, pos_ndim, want, monkeypatch):
    """impl="auto" runs the flash kernels on the TPU from PALLAS_MIN_T
    keys up, and keeps naive/chunked elsewhere (the CPU: tier-1)."""
    from repro.kernels.flash_attention import ops
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    calls = []

    def stub(q, k, v, *args, **kwargs):
        calls.append(k.shape[1])
        return q

    monkeypatch.setattr(ops, "flash_attention", stub)
    pos = jnp.zeros((1,) * (pos_ndim - 1) + (T,), jnp.int32)
    assert attention.auto_impl(T, pos) == want
    q = jax.ShapeDtypeStruct((1, T, 2, 8), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, T, 1, 8), jnp.bfloat16)
    jax.eval_shape(lambda q, k: attention.sdpa(q, k, k, pos, pos, None,
                                               None, 0.125), q, kv)
    assert calls == ([T] if want == "pallas" else [])


@pytest.mark.parametrize("model,want", [(1, "pallas"), (4, "naive")])
def test_auto_attention_routing_on_a_mesh(model, want, monkeypatch):
    """A call partitioned over a multi-device mesh keeps the plain path:
    Mosaic kernels have no partitioning rule."""
    from jax.sharding import AbstractMesh, AxisType, use_abstract_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = AbstractMesh((1, model), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    with use_abstract_mesh(mesh):
        got = attention.auto_impl(PALLAS_MIN_T,
                                  jnp.zeros((PALLAS_MIN_T,), jnp.int32))
    assert got == want


def test_decode_never_takes_the_kernels(monkeypatch):
    """One-query decode keeps sdpa_naive, however long the cache."""
    from repro.kernels.flash_attention import ops

    def refuse(*args, **kwargs):
        raise AssertionError("decode reached the flash kernels")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "flash_attention", refuse)
    cfg = reduced(ARCHS["smollm-360m"])
    params = attention.attention_init(jax.random.PRNGKey(0), cfg)
    cache = attention.init_kv_cache(cfg, 2, 2 * PALLAS_MIN_T)
    x = jnp.ones((2, 1, cfg.d_model), jnp.bfloat16)
    y, _ = jax.eval_shape(lambda x: attention.decode_attention(
        params, x, cache, cfg, jnp.array([3, PALLAS_MIN_T + 5])), x)
    assert y.shape == x.shape


def test_param_count_analytic_matches_tree():
    """ModelConfig.param_count() (used for MODEL_FLOPS) vs the real tree."""
    from repro.models import param_count
    for arch in ["smollm-360m", "gemma2-2b", "mixtral-8x22b",
                 "mamba2-130m", "seamless-m4t-large-v2"]:
        cfg = reduced(ARCHS[arch])
        model = build_model(cfg, remat=False)
        params = model.init(jax.random.PRNGKey(0))
        real = param_count(params)
        pred = cfg.param_count()
        assert abs(real - pred) / real < 0.12, (arch, real, pred)
