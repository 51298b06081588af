"""Encoder-decoder model (SeamlessM4T backbone).

Encoder: bidirectional self-attention + MLP over precomputed frame
embeddings (the speech frontend is a stub: the batch's `src_embeds`
supplies (B, S_src, d) embeddings).
Decoder: causal self-attention + cross-attention + MLP, standard KV-cache
decode with the cross K/V computed once from the encoder output.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .attention import (attention, attention_init, decode_attention,
                        init_kv_cache, _project_qkv)
from .layers import (embed, embedding_init, mlp, mlp_init, rmsnorm,
                     rmsnorm_init, unembed)

Params = Dict[str, Any]


def init_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)

    def enc_unit(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        return {"norm1": rmsnorm_init(cfg.d_model),
                "attn": attention_init(k1, cfg),
                "norm2": rmsnorm_init(cfg.d_model),
                "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.activation)}

    def dec_unit(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"norm1": rmsnorm_init(cfg.d_model),
                "self_attn": attention_init(k1, cfg),
                "norm_x": rmsnorm_init(cfg.d_model),
                "cross_attn": attention_init(k2, cfg),
                "norm2": rmsnorm_init(cfg.d_model),
                "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.activation)}

    return {
        "embed": embedding_init(ks[0], cfg),
        "enc_units": jax.vmap(enc_unit)(
            jax.random.split(ks[1], cfg.n_encoder_layers)),
        "dec_units": jax.vmap(dec_unit)(
            jax.random.split(ks[2], cfg.n_layers)),
        "enc_norm": rmsnorm_init(cfg.d_model),
        "final_norm": rmsnorm_init(cfg.d_model),
    }


def encode(params: Params, src_embeds: jnp.ndarray, cfg: ModelConfig,
           impl: str = "auto", remat: bool = True) -> jnp.ndarray:
    """src_embeds: (B, S_src, d) -> encoder states (B, S_src, d)."""
    x = src_embeds.astype(jnp.bfloat16)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    def unit(x, p):
        from repro.runtime.parallel import shard_batch
        x = shard_batch(x)
        h = rmsnorm(p["norm1"], x, cfg.norm_eps)
        # bidirectional self-attention (encoder is non-causal)
        y = attention(p["attn"], h, cfg, positions, impl=impl, causal=False)
        x = x + y
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        return x + mlp(p["mlp"], h, cfg.activation), None

    body = jax.checkpoint(unit) if remat else unit
    x, _ = jax.lax.scan(lambda c, p: body(c, p), x, params["enc_units"])
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def forward(params: Params, src_embeds: jnp.ndarray,
            dec_tokens: jnp.ndarray, cfg: ModelConfig,
            impl: str = "auto", remat: bool = True
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Teacher-forced training forward. Returns (logits, aux=0)."""
    enc = encode(params, src_embeds, cfg, impl, remat)
    from repro.runtime.parallel import shard_batch
    enc = shard_batch(enc)
    x = embed(params["embed"], dec_tokens, cfg)
    S = x.shape[1]
    S_src = enc.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    enc_pos = jnp.arange(S_src, dtype=jnp.int32)

    def unit(x, p):
        from repro.runtime.parallel import shard_batch
        x = shard_batch(x)
        h = rmsnorm(p["norm1"], x, cfg.norm_eps)
        x = x + attention(p["self_attn"], h, cfg, positions, impl=impl)
        h = rmsnorm(p["norm_x"], x, cfg.norm_eps)
        ck, cv = _rope_kv_cross(p["cross_attn"], enc, cfg)
        x = x + attention(p["cross_attn"], h, cfg, positions, impl=impl,
                          kv_override=(ck, cv, enc_pos), causal=False)
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        return x + mlp(p["mlp"], h, cfg.activation), None

    body = jax.checkpoint(unit) if remat else unit
    x, _ = jax.lax.scan(lambda c, p: body(c, p), x, params["dec_units"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return unembed(params["embed"], x, cfg), jnp.zeros((), jnp.float32)


def _rope_kv_cross(attn_params, enc, cfg):
    """Cross-attention keys/values from encoder states (no RoPE)."""
    _, k, v = _project_qkv(attn_params, enc, cfg)
    return k, v


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               src_len: int) -> Params:
    """Self-attention ring caches + cross K/V (filled by `prefill_cross`)."""
    def stack(tree, n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                            tree)
    self_kv = stack(init_kv_cache(cfg, batch, max_len), cfg.n_layers)
    cross_kv = stack(init_kv_cache(cfg, batch, src_len), cfg.n_layers)
    return {"self": self_kv, "cross": cross_kv}


def prefill_cross(params: Params, src_embeds: jnp.ndarray,
                  cfg: ModelConfig, cache: Params) -> Params:
    """Run the encoder once and store per-layer cross K/V."""
    enc = encode(params, src_embeds, cfg)

    def per_unit(p):
        k, v = _rope_kv_cross(p["cross_attn"], enc, cfg)
        return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}

    cross = jax.vmap(per_unit)(params["dec_units"])
    return {"self": cache["self"], "cross": cross}


def decode_step(params: Params, cache: Params, token: jnp.ndarray,
                pos: jnp.ndarray, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, Params]:
    x = embed(params["embed"], token, cfg)

    def unit(x, xs):
        p, self_c, cross_c = xs
        h = rmsnorm(p["norm1"], x, cfg.norm_eps)
        y, self_c = decode_attention(p["self_attn"], h, self_c, cfg, pos)
        x = x + y
        h = rmsnorm(p["norm_x"], x, cfg.norm_eps)
        y, _ = decode_attention(p["cross_attn"], h, cross_c, cfg, pos,
                                cross=True)
        x = x + y
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        return x + mlp(p["mlp"], h, cfg.activation), self_c

    x, new_self = jax.lax.scan(
        unit, x, (params["dec_units"], cache["self"], cache["cross"]))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return unembed(params["embed"], x, cfg), {"self": new_self,
                                              "cross": cache["cross"]}
