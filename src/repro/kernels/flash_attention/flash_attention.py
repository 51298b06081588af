"""Flash attention Pallas TPU kernels: the forward pass and the two
FlashAttention-2 backward kernels (dQ over query blocks, dK/dV over key
blocks).

Design for TPU:
- Layout: queries (B, K, G, S, D), the G query heads of each of the K KV
  heads together; keys and values (B, K, T, D).  One grid step takes a
  q block of all G heads of a group, folded to (G * bq, D), against one
  K/V block: each K/V block is fetched once per group, the grid is G
  times shorter, and in the dK/dV kernel the matrix products themselves
  sum the group's heads.  The head dim is one whole block (D = 64 is not
  padded: a block that spans an array's whole last dim is legal).
- Block sizes come from the shapes: the largest of 512, 384, 256, 128
  that divides the (128-padded) sequence.  512 x 512 blocks measured
  fastest on a TPU v5e at smollm-360m's widths (PERF.md, section 6).
- Operands stay in their dtype (bfloat16 on the model path) and every
  `dot_general` accumulates in float32; scores, the running max, the
  denominator, the logsumexp and the accumulators are float32; the
  probabilities are cast to v's dtype for the PV product.  Per-row
  statistics live lane-replicated, (rows, 128), so that they meet a
  score tile by whole-vreg copies, not by per-row lane broadcasts.
- Masking (causal + sliding window; padded keys sit at PAD_POS) is decided
  per block pair from the min/max position of each block, prefetched as
  scalars: a pair with no visible entry is skipped (`pl.when`), and its
  K/V (or Q) block index is clamped to a block that runs, so the pipeline
  fetches nothing for it.  Every pair that runs applies the element mask,
  from positions that ride along as (N, 1) / (1, N) VMEM blocks (a second
  copy of the step without it, for wholly visible pairs, measured no
  faster on a TPU v5e).  The decision reads positions, not indices, so
  offset queries (T != S) and arbitrary positions stay exact.
- The forward returns the per-row logsumexp (float32, lane-dense rows of
  each q block's folded queries) for the backward, which recomputes the
  probabilities from it; D = rowsum(dO * O) is one XLA pass.  The dK/dV
  kernel works in the transposed orientation (keys on sublanes), so it
  reads logsumexp and D as rows and transposes no score tile.

A row that sees no key at all returns zeros and passes no gradient (the
reference averages V over all the masked keys instead); no model path
produces such a row.

Oracle: ref.py (pure jnp); forward and gradient parity across shapes,
dtypes and masks is asserted in tests/test_kernels.py (interpret=True).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30                       # running max before any key
# masked scores sit below NEG_INF, so exp(masked - running max) is 0 even
# for a row that has seen no key yet
MASKED = 2 * NEG_INF
PAD_POS = int(jnp.iinfo(jnp.int32).max)    # position of a padded key
LANES = 128
_NT = (((1,), (1,)), ((), ()))             # a @ b.T
# above the default scoped VMEM: a folded score tile of a large group
# (G = 16 heads x 512 rows x 512 keys, float32) alone is 16 MiB
_VMEM_LIMIT = 64 * 1024 * 1024


def block_size(n: int) -> int:
    """Largest of 512/384/256/128 dividing n (a multiple of 128)."""
    return next(b for b in (512, 384, 256, 128) if n % b == 0)


def _block_bounds(pos, b):
    """(min, max) position of each block of b; a block holding padded
    keys has PAD_POS as its max."""
    blocks = pos.reshape(-1, b)
    return blocks.min(axis=1), blocks.max(axis=1)


def _pair_runs(qlo, qhi, klo, khi, causal, window):
    """Whether a (q block, k block) pair can hold a visible entry."""
    if not causal:
        return jnp.ones(jnp.broadcast_shapes(jnp.shape(qlo), jnp.shape(klo)),
                        bool)
    run = klo <= qhi
    if window is not None:
        run &= (qlo - khi) < window
    return run


def _visible(qp, kp, causal, window):
    """Element mask from broadcastable query / key positions."""
    if not causal:
        return kp != PAD_POS
    m = kp <= qp
    if window is not None:
        m &= (qp - kp) < window
    return m


def _plan(q_pos, k_pos, bq, bk, causal, window):
    """Scalar-prefetch operands: block bounds and, per q block, the first
    and last k block that runs (and per k block the first and last q
    block), used to clamp the index_maps of skipped pairs."""
    qlo, qhi = _block_bounds(q_pos, bq)
    klo, khi = _block_bounds(k_pos, bk)
    runs = _pair_runs(qlo[:, None], qhi[:, None], klo[None, :],
                      khi[None, :], causal, window)

    def first_last(r):
        n = r.shape[1]
        return (jnp.argmax(r, axis=1).astype(jnp.int32),
                (n - 1 - jnp.argmax(r[:, ::-1], axis=1)).astype(jnp.int32))

    kfirst, klast = first_last(runs)
    qfirst, qlast = first_last(runs.T)
    return qlo, qhi, klo, khi, kfirst, klast, qfirst, qlast


def _runs(qlo, qhi, klo, khi, iq, ik, causal, window):
    """Whether the pair (iq, ik) runs, from the prefetched bounds."""
    return _pair_runs(qlo[iq], qhi[iq], klo[ik], khi[ik], causal, window)


def _scores(a, b, scale, softcap):
    """a @ b.T * scale in float32, softcapped; also tanh for the chain
    rule (None without a softcap)."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    s = s * scale
    if softcap is None:
        return s, None
    t = jnp.tanh(s / softcap)
    return t * softcap, t


def _lanes(x, n):
    """(N, 128) statistics, each row's value in every lane -> (N, n)."""
    reps = -(-n // LANES)
    x = jnp.tile(x, (1, reps)) if reps > 1 else x
    return x if n == x.shape[1] else x[:, :n]


def _lane_stats(row):
    """(1, N) row -> (N, 128), each row's value in every lane."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _dims(*sem):
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _fold(x):
    """(G, n, D) -> (G * n, D): a KV group's query heads stacked."""
    return x.reshape(-1, x.shape[-1])


def _folded_positions(q_pos, bq, G):
    """Query positions repeated for each head of a group, per q block:
    columns (nq, G * bq, 1) and rows (nq, 1, G * bq)."""
    blocks = jnp.tile(q_pos.reshape(-1, 1, bq), (1, G, 1))
    return blocks.reshape(-1, G * bq, 1), blocks.reshape(-1, 1, G * bq)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(qlo, qhi, klo, khi, kfirst, klast,
                qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, causal,
                window, softcap):
    iq, ik, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_runs(qlo, qhi, klo, khi, iq, ik, causal, window))
    def _step():
        v = v_ref[...]
        s, _ = _scores(_fold(q_ref[...]), k_ref[...], scale, softcap)
        s = jnp.where(_visible(qpos_ref[...], kpos_ref[...], causal, window),
                      s, MASKED)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
        acc_scr[...] = _lanes(alpha, acc_scr.shape[1]) * acc_scr[...] + (
            jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[...] = (acc_scr[...] / _lanes(l, acc_scr.shape[1])).astype(
            o_ref.dtype).reshape(o_ref.shape)
        lse_ref[...] = (m_scr[...] + jnp.log(l)).T[:1]


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "softcap", "interpret"))
def flash_fwd(q, k, v, q_pos, k_pos, *, scale: float, causal: bool = True,
              window: Optional[int] = None, softcap: Optional[float] = None,
              interpret: bool = False):
    """q: (B, K, G, Sq, D), the G query heads of each of K KV heads;
    k/v: (B, K, Sk, D); positions int32 (Sq,), (Sk,).

    Sq/Sk multiples of 128 (ops.py pads; padded keys at PAD_POS, which no
    query sees).  Returns the
    output (B, K, G, Sq, D) and the logsumexp, float32, as rows of each
    q block's G * bq folded queries: (B, K, Sq / bq, 1, G * bq).
    """
    B, K, G, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = block_size(Sq), block_size(Sk)
    nq = Sq // bq
    plan = _plan(q_pos, k_pos, bq, bk, causal, window)[:6]
    qpos_cols, _ = _folded_positions(q_pos, bq, G)

    def kv_map(b, kh, iq, ik, qlo, qhi, klo, khi, kfirst, klast):
        return (b, kh, jnp.clip(ik, kfirst[iq], klast[iq]), 0)

    def kpos_map(b, kh, iq, ik, qlo, qhi, klo, khi, kfirst, klast):
        return (0, jnp.clip(ik, kfirst[iq], klast[iq]))

    def q_map(b, kh, iq, ik, *_):
        return (b, kh, 0, iq, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(plan),
        grid=(B, K, nq, Sk // bk),
        in_specs=[
            pl.BlockSpec((None, G * bq, 1),
                         lambda b, kh, iq, ik, *_: (iq, 0, 0)),
            pl.BlockSpec((1, bk), kpos_map),
            pl.BlockSpec((None, None, G, bq, D), q_map),
            pl.BlockSpec((None, None, bk, D), kv_map),
            pl.BlockSpec((None, None, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, G, bq, D), q_map),
            pl.BlockSpec((None, None, None, 1, G * bq),
                         lambda b, kh, iq, ik, *_: (b, kh, iq, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G * bq, LANES), jnp.float32),     # running max
            pltpu.VMEM((G * bq, LANES), jnp.float32),     # denominator
            pltpu.VMEM((G * bq, D), jnp.float32),     # output accumulator
        ])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, K, nq, 1, G * bq), jnp.float32)],
        compiler_params=_dims("parallel", "parallel", "parallel",
                              "arbitrary"),
        interpret=interpret, name="flash_fwd",
    )(*plan, qpos_cols, k_pos.reshape(1, Sk), q, k, v)


# --------------------------------------------------------------------------
# backward: dQ over query blocks, dK/dV over key blocks
# --------------------------------------------------------------------------

def _dq_kernel(qlo, qhi, klo, khi, kfirst, klast,
               qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               di_ref, dq_ref, lse_scr, di_scr, acc_scr, *, scale, causal,
               window, softcap):
    iq, ik, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        lse_scr[...] = _lane_stats(lse_ref[...])
        di_scr[...] = _lane_stats(di_ref[...])

    @pl.when(_runs(qlo, qhi, klo, khi, iq, ik, causal, window))
    def _step():
        k = k_ref[...]
        s, t = _scores(_fold(q_ref[...]), k, scale, softcap)
        s = jnp.where(_visible(qpos_ref[...], kpos_ref[...], causal, window),
                      s, MASKED)
        bk = s.shape[1]
        p = jnp.exp(s - _lanes(lse_scr[...], bk))
        dp = jax.lax.dot_general(_fold(do_ref[...]), v_ref[...], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di_scr[...], bk))
        if t is not None:
            ds = ds * (1.0 - t * t)
        acc_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[...] = (acc_scr[...] * scale).astype(dq_ref.dtype).reshape(
            dq_ref.shape)


def _dkv_kernel(qlo, qhi, klo, khi, qfirst, qlast,
                qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                di_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                window, softcap):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_runs(qlo, qhi, klo, khi, iq, ik, causal, window))
    def _step():
        q, do = _fold(q_ref[...]), _fold(do_ref[...])
        # transposed orientation: keys on rows, the group's queries on
        # lanes, so the products below also sum over the group's heads
        s, t = _scores(k_ref[...], q, scale, softcap)       # (bk, G * bq)
        s = jnp.where(_visible(qpos_ref[...], kpos_ref[...], causal, window),
                      s, MASKED)
        p = jnp.exp(s - lse_ref[...])
        dv_scr[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[...], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dk_scr[...] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "softcap", "interpret"))
def flash_bwd(q, k, v, q_pos, k_pos, o, lse, do, *, scale: float,
              causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None, interpret: bool = False):
    """Gradients (dq, dk, dv) of flash_fwd's output, given its output o,
    logsumexp and the output's cotangent do (shapes as flash_fwd)."""
    B, K, G, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = block_size(Sq), block_size(Sk)
    nq, nk = Sq // bq, Sk // bk
    qlo, qhi, klo, khi, kfirst, klast, qfirst, qlast = _plan(
        q_pos, k_pos, bq, bk, causal, window)
    qpos_cols, qpos_rows = _folded_positions(q_pos, bq, G)
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = di.reshape(B, K, G, nq, bq).transpose(0, 1, 3, 2, 4).reshape(
        lse.shape)                                  # as lse: folded rows
    kw = dict(scale=scale, causal=causal, window=window, softcap=softcap)

    # dQ: grid (B, K, q blocks, k blocks), k blocks sequential
    def kv_map(b, kh, iq, ik, qlo, qhi, klo, khi, kfirst, klast):
        return (b, kh, jnp.clip(ik, kfirst[iq], klast[iq]), 0)

    def kpos_map(b, kh, iq, ik, qlo, qhi, klo, khi, kfirst, klast):
        return (0, jnp.clip(ik, kfirst[iq], klast[iq]))

    def q_map(b, kh, iq, ik, *_):
        return (b, kh, 0, iq, 0)

    def row_map(b, kh, iq, ik, *_):
        return (b, kh, iq, 0, 0)

    q_spec = pl.BlockSpec((None, None, G, bq, D), q_map)
    row_spec = pl.BlockSpec((None, None, None, 1, G * bq), row_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B, K, nq, nk),
            in_specs=[
                pl.BlockSpec((None, G * bq, 1),
                             lambda b, kh, iq, ik, *_: (iq, 0, 0)),
                pl.BlockSpec((1, bk), kpos_map),
                q_spec,
                pl.BlockSpec((None, None, bk, D), kv_map),
                pl.BlockSpec((None, None, bk, D), kv_map),
                q_spec, row_spec, row_spec,
            ],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((G * bq, LANES), jnp.float32),
                            pltpu.VMEM((G * bq, LANES), jnp.float32),
                            pltpu.VMEM((G * bq, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_dims("parallel", "parallel", "parallel",
                              "arbitrary"),
        interpret=interpret, name="flash_bwd_dq",
    )(qlo, qhi, klo, khi, kfirst, klast, qpos_cols, k_pos.reshape(1, Sk),
      q, k, v, do, lse, di)

    # dK/dV: grid (B, K, k blocks, q blocks), q blocks sequential
    def qc_map(b, kh, ik, iq, qlo, qhi, klo, khi, qfirst, qlast):
        return (b, kh, 0, jnp.clip(iq, qfirst[ik], qlast[ik]), 0)

    def rowc_map(b, kh, ik, iq, qlo, qhi, klo, khi, qfirst, qlast):
        return (b, kh, jnp.clip(iq, qfirst[ik], qlast[ik]), 0, 0)

    def qpos_map(b, kh, ik, iq, qlo, qhi, klo, khi, qfirst, qlast):
        return (jnp.clip(iq, qfirst[ik], qlast[ik]), 0, 0)

    def k_map(b, kh, ik, iq, *_):
        return (b, kh, ik, 0)

    qc_spec = pl.BlockSpec((None, None, G, bq, D), qc_map)
    rowc_spec = pl.BlockSpec((None, None, None, 1, G * bq), rowc_map)
    k_spec = pl.BlockSpec((None, None, bk, D), k_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B, K, nk, nq),
            in_specs=[
                pl.BlockSpec((None, 1, G * bq), qpos_map),
                pl.BlockSpec((bk, 1), lambda b, kh, ik, iq, *_: (ik, 0)),
                qc_spec, k_spec, k_spec, qc_spec, rowc_spec, rowc_spec,
            ],
            out_specs=[k_spec, k_spec],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_dims("parallel", "parallel", "parallel",
                              "arbitrary"),
        interpret=interpret, name="flash_bwd_dkv",
    )(qlo, qhi, klo, khi, qfirst, qlast, qpos_rows, k_pos.reshape(Sk, 1),
      q, k, v, do, lse, di)
    return dq, dk, dv
