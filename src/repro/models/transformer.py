"""Decoder-only LM assembled from pattern units.

The repeating pattern unit (cfg.unit) is the `lax.scan` body; parameters
are stacked (n_units, ...) so a 61-layer MoE lowers as one unit body + a
scan — critical for CPU-host compile times in the 512-device dry-run and
the standard TPU practice anyway.

Hybrid (zamba2-style) models scan over super-units of `shared_attn_every`
mamba blocks followed by ONE shared attention+MLP block whose weights live
outside the scan and are reused by every application (the Zamba trick).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .attention import (attention, decode_attention, init_kv_cache,
                        attention_init)
from .layers import (embed, embedding_init, mlp, mlp_init, rmsnorm,
                     rmsnorm_init, unembed)
from .moe import moe_block, moe_init
from .scopes import scope
from .ssm import decode_mamba, init_ssm_cache, mamba_block, mamba_init

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _block_init(key, spec, cfg: ModelConfig) -> Params:
    kn, kb = jax.random.split(key)
    p = {"norm": rmsnorm_init(cfg.d_model)}
    if spec.kind == "attn":
        p["attn"] = attention_init(kb, cfg)
    elif spec.kind == "mlp":
        p["mlp"] = mlp_init(kb, cfg.d_model, spec.d_ff or cfg.d_ff,
                            cfg.activation)
    elif spec.kind == "moe":
        p["moe"] = moe_init(kb, cfg)
    elif spec.kind == "mamba":
        p["mamba"] = mamba_init(kb, cfg)
    return p


def _stacked(key, n: int, init_fn) -> Params:
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": embedding_init(keys[0], cfg),
        "final_norm": rmsnorm_init(cfg.d_model),
    }
    if cfg.shared_attn_every:
        # hybrid: (U_outer, every) stacked mamba + one shared block
        u_outer = cfg.n_layers // cfg.shared_attn_every

        def unit_init(k):
            ks = jax.random.split(k, cfg.shared_attn_every)
            return jax.vmap(
                lambda kk: _block_init(kk, cfg.unit[0], cfg))(ks)

        params["units"] = _stacked(keys[1], u_outer, unit_init)
        params["shared"] = {
            "norm1": rmsnorm_init(cfg.d_model),
            "attn": attention_init(keys[2], cfg),
            "norm2": rmsnorm_init(cfg.d_model),
            "mlp": mlp_init(keys[3], cfg.d_model, cfg.d_ff, cfg.activation),
        }
    else:
        def unit_init(k):
            ks = jax.random.split(k, len(cfg.unit))
            return {f"b{j}": _block_init(ks[j], spec, cfg)
                    for j, spec in enumerate(cfg.unit)}

        params["units"] = _stacked(keys[1], cfg.n_units, unit_init)
    return params


# --------------------------------------------------------------------------
# full-sequence forward (training / prefill)
# --------------------------------------------------------------------------

def _apply_block(p: Params, spec, x, cfg: ModelConfig, positions, impl,
                 aux):
    from repro.runtime.parallel import shard_batch
    x = shard_batch(x)
    with scope("norm"):
        h = rmsnorm(p["norm"], x, cfg.norm_eps)
    if spec.kind == "attn":
        y = attention(p["attn"], h, cfg, positions, window=spec.window,
                      impl=impl)
    elif spec.kind == "mlp":
        y = mlp(p["mlp"], h, cfg.activation)
    elif spec.kind == "moe":
        y, a = moe_block(p["moe"], h, cfg)
        aux = aux + a
    elif spec.kind == "mamba":
        y = mamba_block(p["mamba"], h, cfg, impl=impl)
    return x + y, aux


def forward(params: Params, inputs: jnp.ndarray, cfg: ModelConfig,
            impl: str = "auto", remat: bool = True) -> Tuple[jnp.ndarray,
                                                             jnp.ndarray]:
    """inputs: (B, S) int tokens, or (B, S, d) embeddings for frontend
    stubs.  Returns (logits fp32 (B, S, V), aux_loss scalar)."""
    if inputs.ndim == 2:
        with scope("embed"):
            x = embed(params["embed"], inputs, cfg)
    else:
        x = inputs.astype(jnp.bfloat16)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    if cfg.shared_attn_every:
        shared = params["shared"]

        def unit_fn(x, unit_params):
            def inner(xc, mp):
                xc, _ = _apply_block(mp, cfg.unit[0], xc, cfg, positions,
                                     impl, 0.0)
                return xc, None
            x, _ = jax.lax.scan(inner, x, unit_params)
            with scope("norm"):
                h = rmsnorm(shared["norm1"], x, cfg.norm_eps)
            x = x + attention(shared["attn"], h, cfg, positions, impl=impl)
            with scope("norm"):
                h = rmsnorm(shared["norm2"], x, cfg.norm_eps)
            x = x + mlp(shared["mlp"], h, cfg.activation)
            return x, 0.0
    else:
        def unit_fn(x, unit_params):
            aux = 0.0
            for j, spec in enumerate(cfg.unit):
                x, aux = _apply_block(unit_params[f"b{j}"], spec, x, cfg,
                                      positions, impl, aux)
            return x, aux

    body = unit_fn
    if remat:
        body = jax.checkpoint(unit_fn,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def scan_body(carry, unit_params):
        x, aux = carry
        x, a = body(x, unit_params)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)),
                               params["units"])
    with scope("norm"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    with scope("head"):
        return unembed(params["embed"], x, cfg), aux


# --------------------------------------------------------------------------
# decode: KV/SSM caches stacked over units, scanned
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Stacked per-unit caches (leading axis = scan axis)."""
    def one_block_cache(spec):
        if spec.kind == "attn":
            return init_kv_cache(cfg, batch, max_len, spec.window)
        if spec.kind == "mamba":
            return init_ssm_cache(cfg, batch)
        return None

    def stack(tree, n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                            tree)

    if cfg.shared_attn_every:
        u_outer = cfg.n_layers // cfg.shared_attn_every
        return {
            "units": stack(stack(one_block_cache(cfg.unit[0]),
                                 cfg.shared_attn_every), u_outer),
            "shared": stack(init_kv_cache(cfg, batch, max_len), u_outer),
        }
    cache = {}
    for j, spec in enumerate(cfg.unit):
        c = one_block_cache(spec)
        if c is not None:
            cache[f"b{j}"] = stack(c, cfg.n_units)
    return {"units": cache}


def _decode_block(p, spec, cache_b, x, cfg, pos):
    with scope("norm"):
        h = rmsnorm(p["norm"], x, cfg.norm_eps)
    if spec.kind == "attn":
        y, cache_b = decode_attention(p["attn"], h, cache_b, cfg, pos,
                                      window=spec.window)
    elif spec.kind == "mamba":
        y, cache_b = decode_mamba(p["mamba"], h, cache_b, cfg, pos)
    elif spec.kind == "moe":
        y, _ = moe_block(p["moe"], h, cfg)
    else:
        y = mlp(p["mlp"], h, cfg.activation)
    return x + y, cache_b


def decode_step(params: Params, cache: Params, token: jnp.ndarray,
                pos: jnp.ndarray, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, Params]:
    """token: (B, 1) int32 (or (B, 1, d) embeddings); pos: (B,) int32,
    each row's position in its own sequence (a scalar is shared by all).
    Returns (logits (B, 1, V) fp32, new cache)."""
    if token.ndim == 2:
        with scope("embed"):
            x = embed(params["embed"], token, cfg)
    else:
        x = token.astype(jnp.bfloat16)

    if cfg.shared_attn_every:
        shared = params["shared"]

        def unit_fn(x, xs):
            unit_params, cache_u, shared_kv = xs

            def inner(xc, ys):
                mp, cb = ys
                xc, cb = _decode_block(mp, cfg.unit[0], cb, xc, cfg, pos)
                return xc, cb
            x, new_inner = jax.lax.scan(inner, x, (unit_params, cache_u))
            with scope("norm"):
                h = rmsnorm(shared["norm1"], x, cfg.norm_eps)
            y, shared_kv = decode_attention(shared["attn"], h, shared_kv,
                                            cfg, pos)
            x = x + y
            with scope("norm"):
                h = rmsnorm(shared["norm2"], x, cfg.norm_eps)
            x = x + mlp(shared["mlp"], h, cfg.activation)
            return x, (new_inner, shared_kv)

        x, (new_units, new_shared) = jax.lax.scan(
            unit_fn, x, (params["units"], cache["units"], cache["shared"]))
        new_cache = {"units": new_units, "shared": new_shared}
    else:
        def unit_fn(x, xs):
            unit_params, cache_u = xs
            new_cache_u = {}
            for j, spec in enumerate(cfg.unit):
                cb = cache_u.get(f"b{j}")
                x, cb = _decode_block(unit_params[f"b{j}"], spec, cb, x,
                                      cfg, pos)
                if f"b{j}" in cache_u:
                    new_cache_u[f"b{j}"] = cb
            return x, new_cache_u

        x, new_units = jax.lax.scan(unit_fn, x,
                                    (params["units"], cache["units"]))
        new_cache = {"units": new_units}

    with scope("norm"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    with scope("head"):
        return unembed(params["embed"], x, cfg), new_cache
