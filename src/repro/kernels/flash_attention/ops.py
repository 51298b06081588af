"""Jit'd public wrapper: layout adaptation + padding around the kernel.

Model code calls `flash_attention(q, k, v, ...)` in (B, S, H, D) layout;
this wrapper transposes to the kernel's (B, H, S, D), pads S to the
128-block grid and D to the lane width, and un-pads the result.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import BK, BQ, flash_attention_kernel


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention(q, k, v, q_pos, k_pos, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None, causal: bool = True,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, H, D); k/v: (B, T, K, D); positions int32. -> (B, S, H, D).

    custom_vjp: the forward pass is the Pallas kernel; the backward pass
    differentiates the reference formulation (a dedicated backward kernel
    is a further optimization — the contract here is correctness parity,
    asserted in tests)."""
    return _flash_attention_fwd_impl(q, k, v, q_pos, k_pos, window, softcap,
                                     scale, causal, interpret)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "causal", "interpret"))
def _flash_attention_fwd_impl(q, k, v, q_pos, k_pos, window=None,
                              softcap=None, scale=None, causal=True,
                              interpret=False) -> jnp.ndarray:
    B, S, H, D = q.shape
    T = k.shape[1]
    scale = D ** -0.5 if scale is None else scale

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    pq = (-S) % BQ
    pk = (-T) % BK
    pd = (-D) % 128
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=0)
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        # padded keys land at +inf position: masked away by causality
        k_pos = jnp.pad(k_pos, (0, pk),
                        constant_values=jnp.iinfo(jnp.int32).max)
    if pd:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, pd)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, 0), (0, pd)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, 0), (0, pd)))

    out = flash_attention_kernel(qt, kt, vt, q_pos, k_pos, scale=scale,
                                 causal=causal, window=window,
                                 softcap=softcap, interpret=interpret)
    out = out[:, :, :S, :D]
    return out.transpose(0, 2, 1, 3)


def _ref_call(q, k, v, q_pos, k_pos, window, softcap, scale, causal):
    from .ref import attention_ref
    D = q.shape[-1]
    return attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), q_pos, k_pos,
        scale=D ** -0.5 if scale is None else scale,
        causal=causal, window=window, softcap=softcap).transpose(0, 2, 1, 3)


def _fa_fwd(q, k, v, q_pos, k_pos, window, softcap, scale, causal,
            interpret):
    out = _flash_attention_fwd_impl(q, k, v, q_pos, k_pos, window, softcap,
                                    scale, causal, interpret)
    return out, (q, k, v, q_pos, k_pos)


def _fa_bwd(window, softcap, scale, causal, interpret, res, g):
    q, k, v, q_pos, k_pos = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref_call(q_, k_, v_, q_pos, k_pos, window,
                                     softcap, scale, causal), q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)
