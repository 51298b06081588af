"""Fused RMSNorm Pallas TPU kernel: one HBM read, one write per row block.

Rows are tiled (BR, d) into VMEM; the mean-square reduction and scale
multiply fuse into a single pass (unfused XLA on small models emits a
separate reduce + mul with an intermediate HBM round-trip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BR = 256


def _kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm_kernel(x, scale, *, eps: float = 1e-6,
                   interpret: bool = False) -> jnp.ndarray:
    """x: (R, d) rows; scale: (d,). R padded to the row block by ops.py."""
    R, d = x.shape
    grid = (R // BR,)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((BR, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((BR, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        interpret=interpret,
    )(x, scale)
