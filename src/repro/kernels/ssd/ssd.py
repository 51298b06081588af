"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the SSD algorithm (DESIGN.md): the grid is (batch, head,
chunk) with the chunk axis innermost and sequential; each head's
inter-chunk recurrent state (N, P) lives in VMEM scratch and is carried
across grid steps, so HBM traffic per chunk is exactly the chunk's inputs
+ outputs (the state never round-trips).  Within a chunk every product is
a 2-D matmul for the MXU: the (Q, Q) decay-gated score product, the
(Q, N) x (N, P) state read-out and the (N, Q) x (Q, P) state update, with
Q = 128 tokens per chunk by default.  The in-chunk cumulative decay is a
masked triangular reduction (Mosaic has no cumsum), exact in f32.

Oracle: ref.py; parity asserted over shapes/dtypes in tests/test_kernels.py
(interpret mode on CPU), compile-checked for v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(dadt_ref, x_ref, bt_ref, c_ref, y_ref, state_scr):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    dadt = dadt_ref[...].astype(jnp.float32)  # (Q, 2): [dt * A, dt]
    da, dt = dadt[:, 0:1], dadt[:, 1:2]       # (Q, 1) each
    x = x_ref[...].astype(jnp.float32)        # (Q, P)
    bt = bt_ref[...].astype(jnp.float32)      # (N, Q)
    c = c_ref[...].astype(jnp.float32)        # (Q, N)

    Q = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # cum_r[i, j] = sum_{k <= j} da[k] (same in every row); cum_c = cum_r.T
    cum_r = jnp.broadcast_to(
        jnp.sum(jnp.where(rows <= cols, da, 0.0), axis=0, keepdims=True),
        (Q, Q))
    cum_c = cum_r.T
    cum = cum_c[:, 0:1]                       # (Q, 1) inclusive cumsum
    total = cum[Q - 1:Q, :]                   # (1, 1) whole-chunk decay

    # intra-chunk: decay-gated quadratic attention within the chunk
    tri = rows >= cols
    lmat = jnp.exp(jnp.where(tri, cum_c - cum_r, -jnp.inf))  # (Q, Q)
    scores = jnp.dot(c, bt, preferred_element_type=jnp.float32)
    y_diag = jnp.dot(scores * lmat, x * dt,
                     preferred_element_type=jnp.float32)     # (Q, P)

    # inter-chunk: contribution of the carried state
    st = state_scr[...]                                      # (N, P)
    y_off = jnp.exp(cum) * jnp.dot(c, st,
                                   preferred_element_type=jnp.float32)

    # state update for the next chunk
    w = jnp.exp(total - cum) * dt                            # (Q, 1)
    # (1, 1) -> (1, P) first: Mosaic broadcasts one of sublanes/lanes at a time
    chunk_decay = jnp.exp(jnp.broadcast_to(total, (1, st.shape[1])))
    state_scr[...] = st * chunk_decay + jnp.dot(
        bt, x * w, preferred_element_type=jnp.float32)

    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_kernel(x, dt, A, B, C, *, chunk: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """x: (b, L, H, P); dt: (b, L, H) (post-softplus); A: (H,) negative;
    B/C: (b, L, N).  L must be a multiple of `chunk` (ops.py pads).
    Returns y: (b, L, H, P)."""
    b, L, H, P = x.shape
    N = B.shape[-1]
    dtf = dt.astype(jnp.float32)
    dadt = jnp.stack([dtf * A, dtf], axis=-1).transpose(0, 2, 1, 3)
    xt = x.transpose(0, 2, 1, 3)                             # (b, H, L, P)
    bt = B.transpose(0, 2, 1)                                # (b, N, L)

    y = pl.pallas_call(
        _kernel,
        grid=(b, H, L // chunk),
        in_specs=[
            pl.BlockSpec((None, None, chunk, 2),
                         lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((None, None, chunk, P),
                         lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((None, N, chunk), lambda i, h, c: (i, 0, c)),
            pl.BlockSpec((None, chunk, N), lambda i, h, c: (i, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, P),
                               lambda i, h, c: (i, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b, H, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dadt, xt, bt, C)
    return y.transpose(0, 2, 1, 3)
