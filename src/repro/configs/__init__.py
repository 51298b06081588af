"""Architecture registry: every assigned arch + the paper's platform."""

from .base import ModelConfig, ShapeConfig, SHAPES, BlockSpec
from .zamba2_7b import CONFIG as zamba2_7b
from .chatglm3_6b import CONFIG as chatglm3_6b
from .gemma2_2b import CONFIG as gemma2_2b
from .smollm_360m import CONFIG as smollm_360m
from .qwen2p5_32b import CONFIG as qwen2p5_32b
from .mamba2_130m import CONFIG as mamba2_130m
from .kimi_k2_1t import CONFIG as kimi_k2_1t
from .mixtral_8x22b import CONFIG as mixtral_8x22b
from .pixtral_12b import CONFIG as pixtral_12b
from .seamless_m4t_v2 import CONFIG as seamless_m4t_v2

ARCHS = {
    "zamba2-7b": zamba2_7b,
    "chatglm3-6b": chatglm3_6b,
    "gemma2-2b": gemma2_2b,
    "smollm-360m": smollm_360m,
    "qwen2.5-32b": qwen2p5_32b,
    "mamba2-130m": mamba2_130m,
    "kimi-k2-1t-a32b": kimi_k2_1t,
    "mixtral-8x22b": mixtral_8x22b,
    "pixtral-12b": pixtral_12b,
    "seamless-m4t-large-v2": seamless_m4t_v2,
}


def get_arch(name: str) -> ModelConfig:
    return ARCHS[name]


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Smoke-test variant: same family/pattern, tiny dimensions."""
    import dataclasses
    layers_per_unit = max(1, sum(1 for b in cfg.unit
                                 if b.kind in ("attn", "mamba")))
    small = dict(
        n_layers=2 * layers_per_unit,
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2),
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        n_experts=min(cfg.n_experts, 8) if cfg.n_experts else 0,
        experts_per_token=min(cfg.experts_per_token, 2)
        if cfg.n_experts else 0,
        moe_d_ff=32 if cfg.moe_d_ff else None,
        ssm_state=16 if cfg.ssm_state else 0,
        ssm_head_dim=16,
        ssm_chunk=16,
        sliding_window=32 if cfg.sliding_window else None,
        n_encoder_layers=2 if cfg.n_encoder_layers else 0,
        unit=(),  # rebuilt for the reduced dims
    )
    if cfg.attn_input_dim:        # the same multiple of d_model
        small["attn_input_dim"] = cfg.attn_input_dim * 64 // cfg.d_model
    if cfg.softmax_scale_dim:     # the same fraction of head_dim
        small["softmax_scale_dim"] = cfg.softmax_scale_dim * 16 // cfg.head_dim
    if cfg.hybrid_layer_ids:      # every shared block, once, after a mamba
        k = cfg.num_mem_blocks
        small.update(n_layers=2 * k,
                     hybrid_layer_ids=tuple(range(1, 2 * k, 2)),
                     adapter_rank=min(cfg.adapter_rank, 8))
    small.update(overrides)
    return dataclasses.replace(cfg, **small)


def cells(arch: str):
    """The (arch x shape) cells assigned to this arch (skips documented in
    DESIGN.md SArch-applicability: long_500k only for sub-quadratic archs)."""
    cfg = get_arch(arch)
    out = []
    for shape in SHAPES.values():
        if shape.name == "long_500k" and not cfg.subquadratic:
            continue
        out.append(shape)
    return out


ALL_CELLS = [(a, s.name) for a in ARCHS for s in cells(a)]
