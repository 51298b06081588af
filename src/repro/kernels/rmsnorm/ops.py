"""Jit'd public wrapper: flattens leading dims, pads rows to the block."""

import functools

import jax
import jax.numpy as jnp

from .rmsnorm import BR, rmsnorm_kernel


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6, interpret: bool = False):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    pad = (-x2.shape[0]) % BR
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = rmsnorm_kernel(x2, scale, eps=eps, interpret=interpret)
    return y[:x2.shape[0] - pad].reshape(shape)
