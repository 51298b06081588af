"""Public wrapper: layout adaptation and padding around the kernels.

Model code calls `flash_attention(q, k, v, ...)` in (B, S, H, D) layout;
this wrapper transposes to the kernels' (B, K, G, S, D) for queries (the
G query heads of each KV head together) and (B, K, T, D) for keys and
values, pads S and T to multiples of 128 (the head dim is never padded),
and un-pads the result.
The gradient is the Pallas backward of flash_attention.py (`flash_bwd`),
fed the forward's output and logsumexp.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import LANES, PAD_POS, flash_bwd, flash_fwd


def _to_kernel(x, pad, K):
    """(B, S, H, D) -> (B, K, H / K, S + pad, D): query heads by KV group
    (K = H for keys and values)."""
    B, S, H, D = x.shape
    x = x.reshape(B, S, K, H // K, D).transpose(0, 2, 3, 1, 4)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    return x


def _from_kernel(x, n):
    """(B, K, G, S + pad, D) -> (B, S, K * G, D)."""
    B, K, G, _, D = x.shape
    return x[:, :, :, :n].transpose(0, 3, 1, 2, 4).reshape(B, n, K * G, D)


def _pads(S, T):
    return (-S) % LANES, (-T) % LANES


def _positions(q_pos, k_pos, pq, pk):
    # padded queries repeat the last position (finite, and discarded);
    # padded keys sit at PAD_POS, which no query sees
    q_pos = jnp.pad(q_pos, (0, pq), mode="edge") if pq else q_pos
    k_pos = jnp.pad(k_pos, (0, pk), constant_values=PAD_POS) if pk else k_pos
    return q_pos, k_pos


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention(q, k, v, q_pos, k_pos, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None, causal: bool = True,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, H, D); k/v: (B, T, K, D); positions int32 (S,), (T,).
    -> (B, S, H, D)."""
    return _fa_fwd(q, k, v, q_pos, k_pos, window, softcap, scale, causal,
                   interpret)[0]


def _fa_fwd(q, k, v, q_pos, k_pos, window, softcap, scale, causal,
            interpret):
    S, D = q.shape[1], q.shape[3]
    pq, pk = _pads(S, k.shape[1])
    scale = D ** -0.5 if scale is None else scale
    qp, kp = _positions(q_pos, k_pos, pq, pk)
    K = k.shape[2]
    qt = _to_kernel(q, pq, K)
    kt, vt = _to_kernel(k, pk, K)[:, :, 0], _to_kernel(v, pk, K)[:, :, 0]
    o, lse = flash_fwd(qt, kt, vt, qp, kp, scale=scale, causal=causal,
                       window=window, softcap=softcap, interpret=interpret)
    return _from_kernel(o, S), (qt, kt, vt, o, lse, q_pos, k_pos)


def _fa_bwd(window, softcap, scale, causal, interpret, res, g):
    qt, kt, vt, o, lse, q_pos, k_pos = res
    S, T, D = q_pos.shape[0], k_pos.shape[0], qt.shape[-1]
    pq, pk = _pads(S, T)
    scale = D ** -0.5 if scale is None else scale
    qp, kp = _positions(q_pos, k_pos, pq, pk)
    dq, dk, dv = flash_bwd(qt, kt, vt, qp, kp, o, lse,
                           _to_kernel(g, pq, kt.shape[1]),
                           scale=scale, causal=causal, window=window,
                           softcap=softcap, interpret=interpret)
    return (_from_kernel(dq, S), _from_kernel(dk[:, :, None], T),
            _from_kernel(dv[:, :, None], T), None, None)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
