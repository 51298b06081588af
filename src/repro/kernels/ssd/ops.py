"""Jit'd public wrapper for the SSD kernel: pads L to the chunk grid."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ssd import ssd_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = False):
    """x: (b, L, H, P); dt: (b, L, H); A: (H,); B/C: (b, L, N).
    Returns (y (b, L, H, P), None)."""
    b, L, H, P = x.shape
    pad = (-L) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y = ssd_kernel(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    return y[:, :L], None
