"""Model facade: one uniform interface over decoder-only and enc-dec archs.

    model = build_model(cfg)
    params = model.init(key)
    logits, aux = model.apply(params, batch)          # train / prefill
    cache = model.init_cache(batch_size, max_len)
    logits, cache = model.decode(params, cache, token, pos)

`batch` is a dict: {"tokens"} or {"embeds"} (frontend stubs), plus
{"src_embeds"} for enc-dec.  This is the surface the runtime and the
launchers program against.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from repro.configs.base import ModelConfig
from . import encdec, transformer


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Any]
    apply: Callable[..., Any]
    init_cache: Callable[..., Any]
    decode: Callable[..., Any]


def build_model(cfg: ModelConfig, impl: str = "auto",
                remat: bool = True) -> Model:
    if cfg.is_encdec:
        def init(key):
            return encdec.init_params(key, cfg)

        def apply(params, batch):
            return encdec.forward(params, batch["src_embeds"],
                                  batch["tokens"], cfg, impl, remat)

        def init_cache(batch_size, max_len, src_len=1024):
            return encdec.init_cache(cfg, batch_size, max_len, src_len)

        def decode(params, cache, token, pos):
            return encdec.decode_step(params, cache, token, pos, cfg)
    else:
        def init(key):
            return transformer.init_params(key, cfg)

        def apply(params, batch):
            inputs = batch.get("embeds", batch.get("tokens"))
            return transformer.forward(params, inputs, cfg, impl, remat)

        def init_cache(batch_size, max_len, src_len=None):
            return transformer.init_cache(cfg, batch_size, max_len)

        def decode(params, cache, token, pos):
            return transformer.decode_step(params, cache, token, pos, cfg)

    return Model(cfg, init, apply, init_cache, decode)


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def param_bytes(params) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
