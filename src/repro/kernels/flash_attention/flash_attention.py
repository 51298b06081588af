"""Flash attention Pallas TPU kernel.

Design for TPU (DESIGN.md hardware-adaptation):
- grid = (batch, q_heads, Sq/BQ, Skv/BK); the KV-block axis is innermost
  and "arbitrary" (sequential) so the online-softmax running state lives
  in VMEM scratch across KV iterations.
- BQ = BK = 128 and the head dim is processed whole: every matmul hits the
  MXU with 128-aligned contraction/output dims.
- GQA without materialising repeated KV: the K/V BlockSpec index_map folds
  the query head -> kv head mapping (h // group), so each KV block is
  fetched once per group from HBM.
- masking (causal + sliding window) is computed from positions that ride
  along as tiny 2-D VMEM blocks, (BQ, 1) for queries and (1, BK) for keys
  (Mosaic refuses 1-D int32 blocks), so the kernel never touches an
  S x S mask.

Oracle: ref.py (pure jnp); parity across shapes/dtypes is asserted in
tests/test_kernels.py with interpret=True on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
BQ = 128
BK = 128


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
            window: Optional[int], softcap: Optional[float], nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    if causal:
        qp = qpos_ref[...]                         # (BQ, 1)
        kp = kpos_ref[...]                         # (1, BK)
        mask = kp <= qp
        if window is not None:
            mask &= (qp - kp) < window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        lse = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / lse[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "softcap", "interpret"))
def flash_attention_kernel(q, k, v, q_pos, k_pos, *, scale: float,
                           causal: bool = True,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, Sq, D); k/v: (B, K, Sk, D); positions int32 (Sq,), (Sk,).

    Sq/Sk must be multiples of 128 and D a multiple of 8 (the ops.py
    wrapper pads).  Returns (B, H, Sq, D).
    """
    B, H, Sq, D = q.shape
    K = k.shape[1]
    Sk = k.shape[2]
    G = H // K
    nq, nk = Sq // BQ, Sk // BK
    grid = (B, H, nq, nk)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BQ, 1), lambda b, h, iq, ik: (iq, 0)),
            pl.BlockSpec((1, BK), lambda b, h, iq, ik: (0, ik)),
            pl.BlockSpec((1, 1, BQ, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, BK, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, BK, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BQ, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ,), jnp.float32),     # running max
            pltpu.VMEM((BQ,), jnp.float32),     # running denominator
            pltpu.VMEM((BQ, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q_pos.reshape(Sq, 1), k_pos.reshape(1, Sk), q, k, v)
    return out
