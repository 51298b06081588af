"""Zamba2-7B: 81 Mamba2 layers (2 B/C groups); before each of the 13
hybrid layers one of two shared attention+MLP blocks, in turn, reads
[x, x0] (width 2d), with a per-layer LoRA adapter on the MLP's gate/up
and a per-layer d x d `linear` into that layer's Mamba input
[arXiv:2411.15242; Zyphra/Zamba2-7B-Instruct config.json]."""

from .base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    rope_theta=1e4,
    attn_input_dim=7168,
    softmax_scale_dim=112,
    tie_embeddings=True,
    scale_tied_embedding=False,
    activation="geglu_erf",
    ssm_state=64,
    d_conv=4,
    expand=2,
    ssm_head_dim=64,
    ssm_chunk=256,
    ssm_groups=2,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    norm_eps=1e-5,
)
