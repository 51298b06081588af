"""Shared layer primitives: norms, RoPE, MLPs, embeddings.

Pure-functional: every layer is `f(params, x, ...) -> y` with params a
nested dict of jnp arrays.  Initialisers return the matching dict.
Compute dtype is bf16 with fp32 reductions (norm/softmax accumulate in
fp32), matching TPU mixed-precision practice.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .scopes import scope, scoped

Params = Dict[str, jnp.ndarray]
DTYPE = jnp.bfloat16


def _dense_init(key, shape, scale_axis=0):
    scale = 1.0 / jnp.sqrt(jnp.maximum(1, shape[scale_axis]))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(DTYPE)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def rmsnorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), DTYPE)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE (supports partial application — chatglm's "2d" rope rotates half)
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, fraction: float, theta: float,
                     positions: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables (..., rot_dim/2) for given positions (any shape)."""
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               fraction: float = 1.0) -> jnp.ndarray:
    """x: (B, S, H, D); cos/sin: (B?, S, rot/2) broadcast over heads."""
    rot = cos.shape[-1] * 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    c = cos[..., None, :].astype(jnp.float32)
    s = sin[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    y1 = x1f * c - x2f * s
    y2 = x2f * c + x1f * s
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([yr, xp], axis=-1) if xp.shape[-1] else yr


# --------------------------------------------------------------------------
# MLP (SiLU-gated / GeGLU, tanh or exact erf / plain GeLU)
# --------------------------------------------------------------------------

GATED = {"silu": jax.nn.silu, "geglu": jax.nn.gelu,
         "geglu_erf": functools.partial(jax.nn.gelu, approximate=False)}


def mlp_init(key, d: int, d_ff: int, activation: str) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_up": _dense_init(k1, (d, d_ff)),
         "w_down": _dense_init(k2, (d_ff, d))}
    if activation in GATED:
        p["w_gate"] = _dense_init(k3, (d, d_ff))
    return p


def adapter_init(key, d: int, d_ff: int, rank: int) -> Params:
    """A rank-`rank` adapter on a gated MLP's gate and up projections."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {"adapter_in": _dense_init(k1, (d, rank)),
            "adapter_gate": _dense_init(k2, (rank, d_ff)),
            "adapter_up": _dense_init(k3, (rank, d_ff))}


@scoped("mlp")
def mlp(params: Params, x: jnp.ndarray, activation: str,
        adapter: Params | None = None) -> jnp.ndarray:
    """With `adapter`, gate and up each add (x @ adapter_in) @ adapter_*."""
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    if activation in GATED:
        gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
        if adapter is not None:
            with scope("adapter"):
                r = jnp.einsum("...d,dr->...r", x, adapter["adapter_in"])
                gate = gate + jnp.einsum("...r,rf->...f", r,
                                         adapter["adapter_gate"])
                up = up + jnp.einsum("...r,rf->...f", r,
                                     adapter["adapter_up"])
        act = GATED[activation]
        h = act(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------

def embedding_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"table": _dense_init(k1, (cfg.vocab_size, cfg.d_model), 1)}
    if not cfg.tie_embeddings:
        p["unembed"] = _dense_init(k2, (cfg.d_model, cfg.vocab_size))
    return p


def embed(params: Params, tokens: jnp.ndarray,
          cfg: ModelConfig) -> jnp.ndarray:
    x = params["table"][tokens]
    if cfg.tie_embeddings and cfg.scale_tied_embedding:
        # gemma-style embedding scaling keeps tied logits well-conditioned
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["table"])
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["unembed"])
    if cfg.final_softcap:
        cap = cfg.final_softcap
        logits = jnp.tanh(logits.astype(jnp.float32) / cap) * cap
        return logits
    return logits.astype(jnp.float32)
