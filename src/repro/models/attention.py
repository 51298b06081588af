"""Attention: GQA, RoPE (partial), QKV bias, logit softcap, sliding window,
full-sequence (train/prefill) and single-token decode with KV cache.

Three interchangeable inner implementations, all numerically equivalent
(tests assert allclose):

- "naive":   materialises (B, K, G, S, T) scores — smoke tests / short seq.
- "chunked": lax.scan over KV chunks with an online softmax — O(S*chunk)
             memory, for long sequences off the TPU (this is what makes the
             long-context cells lowerable without an S x S buffer).
- "pallas":  the flash-attention TPU kernels from repro.kernels (VMEM-tiled
             forward and backward); validated in interpret mode on CPU.

`impl="auto"` picks "pallas" on the TPU for key lengths from PALLAS_MIN_T
up when the call is not partitioned over a multi-device mesh, else
"chunked" above 2048 keys and "naive" below.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import _dense_init, apply_rope, rope_frequencies
from .scopes import scope, scoped

Params = Dict[str, jnp.ndarray]
NEG_INF = -2.0 ** 30
# the flash kernels beat the plain XLA paths, forward + backward, at every
# key length that chip_smoke.py's sdpa phase measures on one TPU v5e (512
# to 4096); shorter sequences keep the plain paths, unmeasured
PALLAS_MIN_T = 512


def attention_init(key, cfg: ModelConfig) -> Params:
    """q, k, v read `cfg.d_attn_in` features; o writes `d_model`."""
    d, d_in, h = cfg.d_model, cfg.d_attn_in, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(k1, (d_in, cfg.n_heads * h)),
        "wk": _dense_init(k2, (d_in, cfg.n_kv_heads * h)),
        "wv": _dense_init(k3, (d_in, cfg.n_kv_heads * h)),
        "wo": _dense_init(k4, (cfg.n_heads * h, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * h,), jnp.bfloat16)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * h,), jnp.bfloat16)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * h,), jnp.bfloat16)
    return p


def _project_qkv(params: Params, x: jnp.ndarray, cfg: ModelConfig
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B, S, _ = x.shape
    h = cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"])
    k = jnp.einsum("bsd,de->bse", x, params["wk"])
    v = jnp.einsum("bsd,de->bse", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return (q.reshape(B, S, cfg.n_heads, h),
            k.reshape(B, S, cfg.n_kv_heads, h),
            v.reshape(B, S, cfg.n_kv_heads, h))


def _mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
          window: Optional[int], causal: bool = True) -> jnp.ndarray:
    """(S, T) boolean from (S,) / (T,) positions, or (B, S, T) from
    per-sequence (B, S) / (B, T) positions: causal, optionally
    sliding-window."""
    if not causal:
        return jnp.ones((1, 1), bool)
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= (q_pos[..., :, None] - k_pos[..., None, :]) < window
    return m


def _softcap(scores: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return scores
    return jnp.tanh(scores / cap) * cap


def sdpa_naive(q, k, v, q_pos, k_pos, window, softcap, scale,
               causal: bool = True) -> jnp.ndarray:
    """q: (B,S,H,D); k/v: (B,T,K,D) -> (B,S,H,D)."""
    B, S, H, D = q.shape
    K = k.shape[2]
    qg = q.reshape(B, S, K, H // K, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = _softcap(scores * scale, softcap)
    mask = _mask(q_pos, k_pos, window, causal)
    if mask.ndim == 3:                 # per-sequence: (B, 1, 1, S, T)
        mask = mask[:, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v)
    return out.reshape(B, S, H, D)


def sdpa_chunked(q, k, v, q_pos, k_pos, window, softcap, scale,
                 chunk: int = 1024, causal: bool = True) -> jnp.ndarray:
    """Online-softmax streaming over KV chunks: O(S*chunk) score memory."""
    B, S, H, D = q.shape
    K = k.shape[2]
    T = k.shape[1]
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=jnp.iinfo(jnp.int32).max)
    nc = (T + pad) // chunk
    qg = q.reshape(B, S, K, H // K, D)
    kc = k.reshape(B, nc, chunk, K, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, chunk, K, D).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(nc, chunk)

    def body(carry, xs):
        m_run, l_run, acc = carry
        kb, vb, pb = xs
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kb).astype(jnp.float32)
        s = _softcap(s * scale, softcap)
        s = jnp.where(_mask(q_pos, pb, window, causal), s, NEG_INF)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(q.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, K, H // K, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, H // K, S), jnp.float32)
    a0 = jnp.zeros((B, K, H // K, S, D), jnp.float32)
    # the backward pass recomputes each chunk's scores from the carry: a
    # plain scan would keep every chunk's, S x T in all
    (m, lsum, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                     (kc, vc, pc))
    out = acc / jnp.maximum(lsum[..., None], 1e-37)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D).astype(q.dtype)


def _one_device() -> bool:
    """Whether the call runs unpartitioned: Mosaic kernels have no
    partitioning rule, so a multi-device mesh cannot compile them."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.empty or mesh.size == 1


def auto_impl(T: int, q_pos: jnp.ndarray) -> str:
    """What impl="auto" runs for T keys: from the backend, the mesh and
    the shapes.  The kernels take one position vector shared by the
    batch."""
    if (jax.default_backend() == "tpu" and T >= PALLAS_MIN_T
            and q_pos.ndim == 1 and _one_device()):
        return "pallas"
    return "chunked" if T > 2048 else "naive"


def sdpa(q, k, v, q_pos, k_pos, window, softcap, scale,
         impl: str = "auto", causal: bool = True) -> jnp.ndarray:
    if impl == "auto":
        impl = auto_impl(k.shape[1], q_pos)
    with scope("sdpa"):
        if impl == "pallas":
            from repro.kernels.flash_attention.ops import flash_attention
            return flash_attention(q, k, v, q_pos, k_pos, window=window,
                                   softcap=softcap, scale=scale,
                                   causal=causal)
        if impl == "chunked":
            return sdpa_chunked(q, k, v, q_pos, k_pos, window, softcap,
                                scale, causal=causal)
        return sdpa_naive(q, k, v, q_pos, k_pos, window, softcap, scale,
                          causal=causal)


@scoped("attn")
def attention(params: Params, x: jnp.ndarray, cfg: ModelConfig,
              positions: jnp.ndarray, window: Optional[int] = None,
              impl: str = "auto", kv_override=None,
              causal: bool = True) -> jnp.ndarray:
    """Full-sequence attention (training / prefill).

    positions: (S,) int32.  kv_override: (k, v, k_pos) for cross-attention.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg)
    if kv_override is None:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.rope_fraction,
                                    cfg.rope_theta, positions)
        q = apply_rope(q, cos, sin, cfg.rope_fraction)
        k = apply_rope(k, cos, sin, cfg.rope_fraction)
        k_pos = positions
    else:
        k, v, k_pos = kv_override
        cos, sin = rope_frequencies(cfg.head_dim, cfg.rope_fraction,
                                    cfg.rope_theta, positions)
        q = apply_rope(q, cos, sin, cfg.rope_fraction)
        window = None
    scale = cfg.attn_scale
    out = sdpa(q, k, v, positions, k_pos, window, cfg.attn_softcap, scale,
               impl, causal=causal)
    return jnp.einsum("bse,ed->bsd", out.reshape(B, S, -1), params["wo"])


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Ring-buffer KV cache; sliding-window layers cap it at the window."""
    L = min(max_len, window) if window else max_len
    shape = (batch, L, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.bfloat16),
            "v": jnp.zeros(shape, jnp.bfloat16)}


@scoped("attn")
def decode_attention(params: Params, x: jnp.ndarray, cache: Dict,
                     cfg: ModelConfig, pos: jnp.ndarray,
                     window: Optional[int] = None,
                     cross: bool = False
                     ) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x: (B, 1, d); pos: (B,) int32, each sequence's
    own position (a scalar is shared by all).

    The cache is a ring buffer of length min(max_len, window): sub-quadratic
    long-context decode for SWA layers holds O(window) state.  Each row
    holds one sequence, which writes its own ring slot and sees only the
    slots it has written since its position 0, so a row reused by a new
    request never attends to what its predecessor left there.
    """
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k_new, v_new = _project_qkv(params, x, cfg)
    L = cache["k"].shape[1]
    if not cross:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.rope_fraction,
                                    cfg.rope_theta, pos[:, None])
        q = apply_rope(q, cos, sin, cfg.rope_fraction)
        k_new = apply_rope(k_new, cos, sin, cfg.rope_fraction)
        slot = jnp.mod(pos, L)
        rows = jnp.arange(B)
        ck = cache["k"].at[rows, slot].set(k_new[:, 0])
        cv = cache["v"].at[rows, slot].set(v_new[:, 0])
        cache = {"k": ck, "v": cv}
        # absolute positions held in each ring slot, per sequence
        slots = jnp.arange(L, dtype=jnp.int32)[None, :]
        wrap = ((pos // L) * L)[:, None]
        k_pos = jnp.where(slots <= slot[:, None], wrap + slots,
                          wrap - L + slots)
        k_pos = jnp.where(k_pos < 0, jnp.iinfo(jnp.int32).max, k_pos)
    else:
        # cross-attention: cache holds the (fixed) encoder projections and
        # every encoder position is visible (no causal mask, no RoPE).
        ck, cv = cache["k"], cache["v"]
        k_pos = jnp.arange(L, dtype=jnp.int32)
    scale = cfg.attn_scale
    with scope("sdpa"):
        out = sdpa_naive(q, ck, cv, pos[:, None], k_pos, window,
                         cfg.attn_softcap, scale, causal=not cross)
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, 1, -1), params["wo"])
    return y, cache
