"""Config system: architecture + run configuration.

Every assigned architecture is a `ModelConfig` in its own module under
`repro.configs`, selectable by ``--arch <id>`` everywhere (launcher,
dry-run, benchmarks).  A config fully determines the model: the repeating
pattern unit (the `lax.scan` body), attention flavour, MoE/SSM settings,
and the modality frontend stub.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One sub-block inside the repeating pattern unit."""

    kind: str                    # "attn" | "mlp" | "moe" | "mamba"
    # attention options
    window: Optional[int] = None          # sliding-window size (None = full)
    is_global: bool = True                # False => local/sliding layer
    # mlp options — d_ff taken from the model config unless overridden
    d_ff: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None        # default d_model // n_heads

    # pattern unit: the scan body covers `unit` and repeats n_units times.
    # Built by `build_unit()` if left empty.
    unit: Tuple[BlockSpec, ...] = ()

    # attention variants
    rope_theta: float = 1e4
    rope_fraction: float = 1.0            # chatglm 2d-RoPE: 0.5
    qkv_bias: bool = False
    attn_softcap: Optional[float] = None  # gemma2: 50.0
    # attention's input width (default d_model; zamba2's shared block reads
    # [x, x0], 2 * d_model) and the width whose -1/2 power scales the
    # scores (default head_dim; zamba2: head_dim / 2)
    attn_input_dim: Optional[int] = None
    softmax_scale_dim: Optional[int] = None
    final_softcap: Optional[float] = None  # gemma2: 30.0
    sliding_window: Optional[int] = None  # mixtral SWA / gemma2 local
    tie_embeddings: bool = False
    # whether a tied embedding is multiplied by sqrt(d_model) on input
    scale_tied_embedding: bool = True
    activation: str = "silu"              # silu | geglu | geglu_erf | gelu

    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: Optional[int] = None        # per-expert hidden (kimi: 2048)

    # SSM (Mamba2/SSD)
    ssm_state: int = 0
    d_conv: int = 4
    expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    ssm_groups: int = 1                   # B/C groups; head h reads h // (H/G)

    # hybrid (zamba2): before each layer in `hybrid_layer_ids` the k-th
    # such layer applies shared attention+MLP block k % num_mem_blocks to
    # [x, x0] (x0 the embedding output), with its own rank-`adapter_rank`
    # adapter on the MLP's gate/up and its own d x d `linear`, whose
    # output is added to that layer's Mamba input
    hybrid_layer_ids: Tuple[int, ...] = ()
    num_mem_blocks: int = 0
    adapter_rank: int = 0

    # encoder-decoder (seamless)
    n_encoder_layers: int = 0

    # modality frontend stub: "none" => token ids in; "embed" => the
    # dry-run feeds precomputed frame/patch embeddings (B, S, d_model)
    frontend: str = "none"
    encoder_frontend: str = "none"

    norm_eps: float = 1e-6
    # whether this arch can run the 524k-token long-context decode shape
    subquadratic: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        object.__setattr__(self, "hybrid_layer_ids",
                           tuple(self.hybrid_layer_ids))
        ids = self.hybrid_layer_ids
        assert list(ids) == sorted(set(ids)) and all(
            0 <= i < self.n_layers for i in ids), (self.name, ids)
        assert not ids or self.num_mem_blocks > 0, self.name
        assert self.n_ssm_heads % self.ssm_groups == 0, self.name
        if not self.unit:
            object.__setattr__(self, "unit", self.build_unit())
        layers_per_unit = max(
            1, sum(1 for b in self.unit if b.kind in ("attn", "mamba")))
        assert self.n_layers % layers_per_unit == 0, (
            self.name, self.n_layers, layers_per_unit)

    def build_unit(self) -> Tuple[BlockSpec, ...]:
        if self.family == "ssm":
            return (BlockSpec("mamba"),)
        if self.family == "hybrid":
            # zamba-style: the shared blocks sit outside the unit list
            return (BlockSpec("mamba"),)
        if self.family == "moe":
            blocks = [BlockSpec("attn", window=self.sliding_window,
                                is_global=self.sliding_window is None),
                      BlockSpec("moe")]
            return tuple(blocks)
        return (BlockSpec("attn", window=self.sliding_window,
                          is_global=self.sliding_window is None),
                BlockSpec("mlp"))

    @property
    def n_units(self) -> int:
        """Scan trip count: layers grouped into identical pattern units."""
        layers_per_unit = max(
            1, sum(1 for b in self.unit if b.kind in ("attn", "mamba")))
        return self.n_layers // layers_per_unit

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def d_attn_in(self) -> int:
        return self.attn_input_dim or self.d_model

    @property
    def attn_scale(self) -> float:
        return (self.softmax_scale_dim or self.head_dim) ** -0.5

    @property
    def gated_mlp(self) -> bool:
        return self.activation in ("silu", "geglu", "geglu_erf")

    @property
    def is_encdec(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def kv_cache_dtype_bytes(self) -> int:
        return 2  # bf16

    def param_count(self) -> int:
        """Analytic parameter count (cross-checked against the real tree in
        tests); used for MODEL_FLOPS = 6*N*D."""
        d, h = self.d_model, self.head_dim
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        per_attn = self._attn_params(d)
        act_mult = 3 if self.gated_mlp else 2
        per_mlp = act_mult * d * self.d_ff
        per_moe = (self.n_experts * act_mult * d * (self.moe_d_ff or self.d_ff)
                   + d * self.n_experts)
        dssm = self.d_inner
        g_n = 2 * self.ssm_groups * self.ssm_state
        per_mamba = (d * (2 * dssm + g_n + self.n_ssm_heads)  # in_proj
                     + (self.d_conv + 1) * (dssm + g_n)       # conv, bias
                     + 3 * self.n_ssm_heads                   # A, D, dt_bias
                     + dssm                                   # gated norm
                     + dssm * d)                              # out_proj
        total = emb + d                                       # final norm
        norms = d
        n_dec = self.n_layers
        kinds = {"attn": per_attn + norms, "mlp": per_mlp + norms,
                 "moe": per_moe + norms, "mamba": per_mamba + norms}
        per_unit = sum(kinds[b.kind] for b in self.unit)
        total += self.n_units * per_unit
        if self.hybrid_layer_ids:
            d_in = self.d_attn_in
            per_block = self._attn_params(d_in) + per_mlp + d_in + d
            per_hybrid = d * d + self.adapter_rank * (d + 2 * self.d_ff)
            total += (self.num_mem_blocks * per_block
                      + len(self.hybrid_layer_ids) * per_hybrid)
        if self.is_encdec:
            total += self.n_encoder_layers * (per_attn + per_mlp + 2 * norms)
            total += self.n_layers * (per_attn + norms)  # cross attention
        return int(total)

    def _attn_params(self, d_in: int) -> int:
        h = self.head_dim
        n = (d_in * (self.n_heads + 2 * self.n_kv_heads) * h
             + self.n_heads * h * self.d_model)
        if self.qkv_bias:
            n += (self.n_heads + 2 * self.n_kv_heads) * h
        return n

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: top-k of the expert pool)."""
        if not self.n_experts:
            return self.param_count()
        act_mult = 3 if self.gated_mlp else 2
        per_moe_total = self.n_experts * act_mult * self.d_model * \
            (self.moe_d_ff or self.d_ff)
        per_moe_active = self.experts_per_token * act_mult * self.d_model * \
            (self.moe_d_ff or self.d_ff)
        n_moe_layers = self.n_units * sum(1 for b in self.unit
                                          if b.kind == "moe")
        return self.param_count() - n_moe_layers * (per_moe_total -
                                                    per_moe_active)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    mode: str           # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
