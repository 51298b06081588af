"""Single-unit programs for the roofline's scan-body extrapolation.

`cost_analysis()` counts a scan body once (DESIGN.md S7), so per cell we
also lower the pattern unit alone — same shardings, same remat policy —
and extrapolate  total = full + sum_i multiplier_i * unit_i.

Multipliers per family:
- uniform decoder (dense/moe/ssm/vlm): (n_units - 1) x unit
- hybrid (zamba2): each run of plain mamba layers between hybrid layers
  is a scan (its body counted once); the hybrid layers are unrolled and
  counted in full => (n_plain_layers - n_runs) x mamba_unit
- enc-dec: (n_enc - 1) x enc_unit + (n_dec - 1) x dec_unit
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.models.attention import attention, decode_attention
from repro.models.layers import mlp, rmsnorm

UnitProgram = Tuple[str, Callable, Tuple, int]  # (name, fn, abstract_args, k)


def _abs_slice(tree, axes: int = 1):
    """Strip `axes` leading stacked dims from an abstract tree."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[axes:], s.dtype), tree)


def _x_abs(cfg: ModelConfig, batch: int, seq: int):
    return jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.bfloat16)


def _apply_unit(cfg: ModelConfig, unit_params, x, positions, impl):
    aux = jnp.zeros((), jnp.float32)
    for j, spec in enumerate(cfg.unit):
        x, aux = T._apply_block(unit_params[f"b{j}"], spec, x, cfg,
                                positions, impl, aux)
    return x, aux


def _scanned_extra(cfg: ModelConfig) -> int:
    """Units the full program's scans hold beyond the one body each counts:
    a hybrid model scans each run of plain layers and unrolls the rest."""
    if not cfg.hybrid_layer_ids:
        return cfg.n_units - 1
    runs = [stop - start for start, stop, _ in T._runs(cfg) if stop > start]
    return sum(runs) - len(runs)


def _train_wrap(fn):
    """grad-of-checkpointed-unit: matches the full program's remat'd scan
    body (fwd + replayed fwd + bwd)."""
    ck = jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)

    def loss(params, x, *rest):
        y, aux = ck(params, x, *rest)
        return (y.astype(jnp.float32).sum() + aux).astype(jnp.float32)

    return jax.grad(loss, argnums=(0, 1))


def _fwd_wrap(fn):
    def f(params, x, *rest):
        y, aux = fn(params, x, *rest)
        return y
    return f


def train_unit_programs(cfg: ModelConfig, abstract_state, batch: int,
                        seq: int, impl: str,
                        grad: bool = True) -> List[UnitProgram]:
    wrap = _train_wrap if grad else _fwd_wrap
    params = abstract_state["params"]
    positions = jnp.arange(seq, dtype=jnp.int32)
    x = _x_abs(cfg, batch, seq)
    out: List[UnitProgram] = []

    if cfg.is_encdec:
        enc_u = _abs_slice(params["enc_units"])
        dec_u = _abs_slice(params["dec_units"])

        def enc_fn(p, xx):
            h = rmsnorm(p["norm1"], xx, cfg.norm_eps)
            xx = xx + attention(p["attn"], h, cfg, positions, impl=impl,
                                causal=False)
            h = rmsnorm(p["norm2"], xx, cfg.norm_eps)
            return xx + mlp(p["mlp"], h, cfg.activation), 0.0

        enc_abs = _x_abs(cfg, batch, seq)

        def dec_fn(p, xx, enc):
            h = rmsnorm(p["norm1"], xx, cfg.norm_eps)
            xx = xx + attention(p["self_attn"], h, cfg, positions, impl=impl)
            h = rmsnorm(p["norm_x"], xx, cfg.norm_eps)
            from repro.models.encdec import _rope_kv_cross
            ck_, cv = _rope_kv_cross(p["cross_attn"], enc, cfg)
            xx = xx + attention(p["cross_attn"], h, cfg, positions,
                                impl=impl, causal=False,
                                kv_override=(ck_, cv, positions))
            h = rmsnorm(p["norm2"], xx, cfg.norm_eps)
            return xx + mlp(p["mlp"], h, cfg.activation), 0.0

        out.append(("enc_unit", wrap(enc_fn), (enc_u, x),
                    cfg.n_encoder_layers - 1))
        out.append(("dec_unit", wrap(dec_fn), (dec_u, x, enc_abs),
                    cfg.n_layers - 1))
        return out

    unit = _abs_slice(params["units"])

    def unit_fn(p, xx):
        return _apply_unit(cfg, p, xx, positions, impl)

    out.append(("unit", wrap(unit_fn), (unit, x), _scanned_extra(cfg)))
    return out


def decode_unit_programs(cfg: ModelConfig, abstract_params, abstract_cache,
                         batch: int) -> List[UnitProgram]:
    params = abstract_params
    x = _x_abs(cfg, batch, 1)
    pos = jnp.int32(7)
    out: List[UnitProgram] = []

    if cfg.is_encdec:
        dec_u = _abs_slice(params["dec_units"])
        self_c = _abs_slice(abstract_cache["self"])
        cross_c = _abs_slice(abstract_cache["cross"])

        def dec_fn(p, sc, cc, xx):
            h = rmsnorm(p["norm1"], xx, cfg.norm_eps)
            y, sc = decode_attention(p["self_attn"], h, sc, cfg, pos)
            xx = xx + y
            h = rmsnorm(p["norm_x"], xx, cfg.norm_eps)
            y, _ = decode_attention(p["cross_attn"], h, cc, cfg, pos,
                                    cross=True)
            xx = xx + y
            h = rmsnorm(p["norm2"], xx, cfg.norm_eps)
            return xx + mlp(p["mlp"], h, cfg.activation), sc

        out.append(("dec_unit", dec_fn, (dec_u, self_c, cross_c, x),
                    cfg.n_layers - 1))
        return out

    unit = _abs_slice(params["units"])
    cache_u = _abs_slice(abstract_cache["units"])

    def unit_fn(p, c, xx):
        new_c = {}
        for j, spec in enumerate(cfg.unit):
            cb = c.get(f"b{j}")
            xx, cb = T._decode_block(p[f"b{j}"], spec, cb, xx, cfg, pos)
            if f"b{j}" in c:
                new_c[f"b{j}"] = cb
        return xx, new_c

    out.append(("unit", unit_fn, (unit, cache_u, x), _scanned_extra(cfg)))
    return out
