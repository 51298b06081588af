"""Serving: batched prefill + single-token decode steps.

`decode_step` decodes one new token per sequence against a KV cache of
max_len positions.  A tiny continuous-batching scheduler drives it in
launch/serve.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import build_model


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 2048
    attention_impl: str = "auto"
    temperature: float = 0.0          # 0 => greedy


def make_serve_fns(cfg: ModelConfig, scfg: ServeConfig):
    model = build_model(cfg, impl=scfg.attention_impl, remat=False)

    def prefill(params, batch) -> Tuple[jnp.ndarray, Any]:
        """Full-sequence forward; returns last-position logits + nothing
        cache-ful: decode builds its cache with `init_cache`."""
        logits, _ = model.apply(params, batch)
        return logits[:, -1]

    def decode_step(params, cache, token, pos):
        logits, cache = model.decode(params, cache, token, pos)
        if scfg.temperature == 0.0:
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        else:
            key = jax.random.PRNGKey(0)
            nxt = jax.random.categorical(
                key, logits[:, -1] / scfg.temperature).astype(jnp.int32)
        return nxt[:, None], logits, cache

    def init_cache(batch_size: int, max_len: int = None, src_len: int = 1024):
        return model.init_cache(batch_size, max_len or scfg.max_len,
                                src_len)

    return prefill, decode_step, init_cache


def generate(params, cfg: ModelConfig, prompt: jnp.ndarray, n_tokens: int,
             scfg: ServeConfig | None = None) -> jnp.ndarray:
    """Greedy generation loop (example driver; jit per step)."""
    scfg = scfg if scfg is not None else ServeConfig()
    prefill, decode_step, init_cache = make_serve_fns(cfg, scfg)
    B, P = prompt.shape
    cache = init_cache(B, P + n_tokens + 1)
    dec = jax.jit(decode_step)
    # feed the prompt through decode steps (simple, cache-exact)
    tok = prompt[:, :1]
    out = [tok]
    for i in range(P + n_tokens - 1):
        nxt, _, cache = dec(params, cache, tok, jnp.int32(i))
        tok = prompt[:, i + 1:i + 2] if i + 1 < P else nxt
        out.append(tok)
    return jnp.concatenate(out, axis=1)
