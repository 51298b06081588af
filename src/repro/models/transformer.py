"""Decoder-only LM assembled from pattern units.

The repeating pattern unit (cfg.unit) is the `lax.scan` body; parameters
are stacked (n_units, ...) so a 61-layer MoE lowers as one unit body + a
scan — which keeps compile times flat in depth, the standard TPU
practice.

Hybrid (zamba2) models stack a Mamba2 block per layer as `units`, and
before each layer of `cfg.hybrid_layer_ids` the k-th such layer applies
shared attention+MLP block k % num_mem_blocks (weights reused by every
application, the Zamba trick) to [x, x0], x0 the embedding output, with
that layer's own adapter on the MLP's gate/up and its own `linear`:

    t = linear_k(MLP_b(norm(Attn_b(norm([x, x0]))); adapter_k))
    x = x + Mamba_l(norm(x + t))

The runs of plain layers between hybrid layers are scans over slices of
the stack; the hybrid layers are unrolled.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .attention import (attention, decode_attention, init_kv_cache,
                        attention_init)
from .layers import (_dense_init, adapter_init, embed, embedding_init, mlp,
                     mlp_init, rmsnorm, rmsnorm_init, unembed)
from .moe import moe_block, moe_init
from .scopes import scope
from .ssm import MOVED, decode_mamba, init_ssm_cache, mamba_block, mamba_init

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


def _shared_block_init(key, cfg: ModelConfig) -> Params:
    ka, km = jax.random.split(key)
    return {"norm_in": rmsnorm_init(cfg.d_attn_in),
            "attn": attention_init(ka, cfg),
            "norm_ff": rmsnorm_init(cfg.d_model),
            "mlp": mlp_init(km, cfg.d_model, cfg.d_ff, cfg.activation)}


def _hybrid_init(key, cfg: ModelConfig) -> Params:
    kl, ka = jax.random.split(key)
    return {"linear": _dense_init(kl, (cfg.d_model, cfg.d_model)),
            "adapter": adapter_init(ka, cfg.d_model, cfg.d_ff,
                                    cfg.adapter_rank)}


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": embedding_init(keys[0], cfg),
        "final_norm": rmsnorm_init(cfg.d_model),
    }

    def unit_init(k):
        ks = jax.random.split(k, len(cfg.unit))
        return {f"b{j}": _block_init(ks[j], spec, cfg)
                for j, spec in enumerate(cfg.unit)}

    params["units"] = _stacked(keys[1], cfg.n_units, unit_init)
    if cfg.hybrid_layer_ids:
        params["shared"] = [
            _shared_block_init(k, cfg)
            for k in jax.random.split(keys[2], cfg.num_mem_blocks)]
        params["hybrid"] = [
            _hybrid_init(k, cfg)
            for k in jax.random.split(keys[3], len(cfg.hybrid_layer_ids))]
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


def _layers(tree, start: int, stop: int):
    """Layers start..stop-1 of a tree stacked over layers."""
    return jax.tree.map(lambda a: a[start:stop], tree)


def _runs(cfg: ModelConfig):
    """[(start, stop, k)]: plain layers start..stop-1, then the k-th hybrid
    layer `stop` (k None after the last one)."""
    out, start = [], 0
    for k, layer in enumerate(cfg.hybrid_layer_ids):
        out.append((start, layer, k))
        start = layer + 1
    return out + [(start, cfg.n_layers, None)]


def _hybrid_input(block: Params, hyb: Params, x, x0, cfg: ModelConfig,
                  attend):
    """x + t @ linear: the hybrid layer's Mamba input before its norm.
    `attend(attn_params, h)` is the shared block's attention."""
    with scope("hybrid"):
        h = rmsnorm(block["norm_in"], jnp.concatenate([x, x0], axis=-1),
                    cfg.norm_eps)
    a = attend(block["attn"], h)
    with scope("norm"):
        a = rmsnorm(block["norm_ff"], a, cfg.norm_eps)
    t = mlp(block["mlp"], a, cfg.activation, adapter=hyb["adapter"])
    with scope("hybrid"):
        return x + jnp.einsum("...d,de->...e", t, hyb["linear"])


def _hybrid_layer(x, x0, block: Params, hyb: Params, layer: Params,
                  cfg: ModelConfig, positions, impl):
    from repro.runtime.parallel import shard_batch
    x = shard_batch(x)
    xt = _hybrid_input(block, hyb, x, x0, cfg, lambda p, h: attention(
        p, h, cfg, positions, impl=impl))
    with scope("norm"):
        h = rmsnorm(layer["b0"]["norm"], xt, cfg.norm_eps)
    return x + mamba_block(layer["b0"]["mamba"], h, cfg, impl=impl)


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

    def unit_fn(x, unit_params):
        aux = 0.0
        for j, spec in enumerate(cfg.unit):
            x, aux = _apply_block(unit_params[f"b{j}"], spec, x, cfg,
                                  positions, impl, aux)
        return x, aux

    def remat_(fn):
        if not remat:
            return fn
        return jax.checkpoint(fn, policy=jax.checkpoint_policies
                              .save_only_these_names(MOVED))

    body = remat_(unit_fn)

    def scan_body(carry, unit_params):
        x, aux = carry
        x, a = body(x, unit_params)
        return (x, aux + a), None

    carry = (x, jnp.zeros((), jnp.float32))
    if cfg.hybrid_layer_ids:
        x0, units = x, params["units"]
        hybrid = remat_(functools.partial(_hybrid_layer, cfg=cfg,
                                          positions=positions, impl=impl))
        for start, stop, k in _runs(cfg):
            if stop > start:
                carry, _ = jax.lax.scan(scan_body, carry,
                                        _layers(units, start, stop))
            if k is not None:
                block = params["shared"][k % cfg.num_mem_blocks]
                layer = jax.tree.map(lambda a: a[stop], units)
                carry = (hybrid(carry[0], x0, block, params["hybrid"][k],
                                layer), carry[1])
        x, aux = carry
    else:
        (x, aux), _ = jax.lax.scan(scan_body, carry, params["units"])
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

    cache = {}
    for j, spec in enumerate(cfg.unit):
        c = one_block_cache(spec)
        if c is not None:
            cache[f"b{j}"] = stack(c, cfg.n_units)
    if cfg.hybrid_layer_ids:
        # each application of a shared block keeps its own K/V
        return {"units": cache,
                "hybrid": [init_kv_cache(cfg, batch, max_len)
                           for _ in cfg.hybrid_layer_ids]}
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

    if cfg.hybrid_layer_ids:
        x0, units, ucache = x, params["units"], cache["units"]
        new_u, new_kv = [], []
        for start, stop, k in _runs(cfg):
            if stop > start:
                x, c = jax.lax.scan(unit_fn, x, (_layers(units, start, stop),
                                                 _layers(ucache, start, stop)))
                new_u.append(c)
            if k is None:
                continue
            block = params["shared"][k % cfg.num_mem_blocks]
            kv = cache["hybrid"][k]

            def attend(p, h, kv=kv):
                y, new = decode_attention(p, h, kv, cfg, pos)
                new_kv.append(new)
                return y
            xt = _hybrid_input(block, params["hybrid"][k], x, x0, cfg,
                               attend)
            layer = jax.tree.map(lambda a: a[stop], units)
            with scope("norm"):
                h = rmsnorm(layer["b0"]["norm"], xt, cfg.norm_eps)
            y, c = decode_mamba(layer["b0"]["mamba"], h,
                                jax.tree.map(lambda a: a[stop], ucache["b0"]),
                                cfg, pos)
            x = x + y
            new_u.append({"b0": jax.tree.map(lambda a: a[None], c)})
        new_cache = {"units": jax.tree.map(
            lambda *a: jnp.concatenate(a), *new_u), "hybrid": new_kv}
    else:
        x, new_units = jax.lax.scan(unit_fn, x,
                                    (params["units"], cache["units"]))
        new_cache = {"units": new_units}

    with scope("norm"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    with scope("head"):
        return unembed(params["embed"], x, cfg), new_cache
