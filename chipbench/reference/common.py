"""Pieces shared by the plain references and their weight makers.

`quantizer(name)` gives the rounding applied to both operands of every
matrix product: none for "f32", and rounding to float8 e4m3's 3 mantissa
and 4 exponent bits for "fp8", the control's precision.  The control
rounds the forward pass only (the gradient passes straight through), so
that it is the milder of the two ways a lower precision could enter.

Rounding is `lax.reduce_precision`: XLA may drop a round trip of casts
(f32 -> bf16 -> f32) as excess precision, and does so on the TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16


def mat(key, shape, fan_in, gain=1.0):
    """A weight matrix made from `key`: normal / sqrt(fan_in) times gain,
    held in bfloat16."""
    return (jax.random.normal(key, shape, jnp.float32)
            * (gain / np.sqrt(fan_in))).astype(BF16)


def quantizer(name: str):
    if name == "f32":
        return lambda x: x
    if name == "fp8":
        def q(x):
            r = jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
            return x + jax.lax.stop_gradient(r - x)
        return q
    raise ValueError(name)


def matmul(q):
    def mm(eq, a, b):
        return jnp.einsum(eq, q(a), q(b))
    return mm


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def store(x, dtype):
    """x rounded to what a tensor of `dtype` can hold, kept in float32."""
    if dtype == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if dtype != F32:
        raise ValueError(f"no rounding for {dtype}")
    return x


def to_f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)
