"""The model's named scopes: found again in a compiled module's text, one
top-level scope an op at most, every scope of a model in its compiled
train step whichever attention or SSD implementation runs, and no
instruction added by them."""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.launch.mesh import compile_work
from repro.models import attention, scopes, ssm
from repro.optim.optimizers import OptimizerConfig
from repro.runtime.train import TrainConfig, make_train_step

HLO = '''HloModule jit_train_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_type="mul" op_name="jit(train_step)/jvp(loss_fn)/mlp/mul"}
}

%body.2 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.633 = f32[8,5]{1,0:T(8,128)} fusion(%p), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(loss_fn))/while/body/closed_call/checkpoint/rematted_computation/attn/sdpa/dot_general" source_file="/x/attention.py" source_line=95}
  %custom-call.7 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {"body": "a}b"}}, metadata={op_name="jit(train_step)/jvp(loss_fn)/while/body/attn/sdpa/pallas_call"}
  %copy-start = (f32[8,5]{1,0}, f32[8,5]{1,0:S(1)}, u32[]{:S(2)}) copy-start(%fusion.633)
  %copy-done = f32[8,5]{1,0:S(1)} copy-done(%copy-start)
  %broadcast_fusion.2 = f32[8]{0:T(128)} fusion(), kind=kLoop, calls=%fused_computation.1
}

ENTRY %main.3 (a: f32[8]) -> f32[8] {
  ROOT %while.19 = (s32[], f32[8]) while(%t), condition=%cond, body=%body.2, metadata={op_name="jit(train_step)/jvp(loss_fn)/while"}
}
'''


def test_program_ops_from_module_text():
    """A copy with no op_name takes its operand's, through copy-start."""
    got = scopes.program_ops(HLO)
    assert got["module"] == "jit_train_step"
    assert got["ops"] == {
        "multiply.3": "jit(train_step)/jvp(loss_fn)/mlp/mul",
        "fusion.633": "jit(train_step)/transpose(jvp(loss_fn))/while/body/"
                      "closed_call/checkpoint/rematted_computation/attn/"
                      "sdpa/dot_general",
        "custom-call.7": "jit(train_step)/jvp(loss_fn)/while/body/attn/sdpa/"
                         "pallas_call",
        "while.19": "jit(train_step)/jvp(loss_fn)/while",
        "param_0": "",
        "copy-start": "jit(train_step)/transpose(jvp(loss_fn))/while/body/"
                      "closed_call/checkpoint/rematted_computation/attn/"
                      "sdpa/dot_general",
        "copy-done": "jit(train_step)/transpose(jvp(loss_fn))/while/body/"
                     "closed_call/checkpoint/rematted_computation/attn/"
                     "sdpa/dot_general",
        "broadcast_fusion.2": "",
    }


@pytest.mark.parametrize("op_name,top,kernel", [
    ("jit(train_step)/jvp(loss_fn)/while/body/closed_call/attn/dot_general",
     "attn", None),
    ("jit(train_step)/transpose(jvp(loss_fn))/while/body/closed_call/"
     "checkpoint/rematted_computation/attn/sdpa/exp", "attn", "sdpa"),
    ("jit(train_step)/transpose(jvp(attn))/mul", "attn", None),
    ("jit(train_step)/jvp(loss_fn)/while/body/mamba/ssd/while/body/add",
     "mamba", "ssd"),
    ("jit(train_step)/transpose(jvp(loss_fn))/head/log", "head", None),
    ("jit(train_step)/optimizer/sqrt", "optimizer", None),
    ("jit(train_step)/jvp(loss_fn)/while/body/dynamic_slice", None, None),
    ("jit(train_step)/attn_like/mlps/mul", None, None),
    ("jit(train_step)/transpose(jvp(head))/mul;jit(train_step)/mlp/add",
     "head", None),
])
def test_scope_is_a_path_segment(op_name, top, kernel):
    assert scopes.top_scope(op_name) == top
    for k in scopes.KERNELS:
        assert scopes.in_scope(op_name, k) == (k == kernel)


def test_unknown_scope_is_refused():
    with pytest.raises(ValueError):
        scopes.scope("attention")


TINY = {
    "dense": dict(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=256, tie_embeddings=True),
    "ssm": dict(name="t", family="ssm", n_layers=2, d_model=64, n_heads=1,
                n_kv_heads=1, head_dim=16, d_ff=0, vocab_size=256,
                ssm_state=16, d_conv=4, expand=2, ssm_head_dim=16,
                ssm_chunk=16, tie_embeddings=True),
    "hybrid": dict(name="t", family="hybrid", n_layers=4, d_model=64,
                   n_heads=4, n_kv_heads=4, head_dim=32, d_ff=128,
                   vocab_size=256, attn_input_dim=128, softmax_scale_dim=16,
                   tie_embeddings=True, scale_tied_embedding=False,
                   activation="geglu_erf", ssm_state=16, d_conv=4, expand=2,
                   ssm_head_dim=16, ssm_chunk=16, ssm_groups=2,
                   hybrid_layer_ids=(1, 3), num_mem_blocks=2,
                   adapter_rank=8),
}
EXPECTED = {
    "dense": ({"embed", "norm", "attn", "mlp", "head", "optimizer"},
              {"sdpa"}),
    "ssm": ({"embed", "norm", "mamba", "head", "optimizer"}, {"ssd"}),
    "hybrid": ({"embed", "norm", "attn", "mlp", "mamba", "hybrid", "head",
                "optimizer"}, {"sdpa", "ssd"}),
}


def _train_step_text(family, attention_impl="auto"):
    cfg = ModelConfig(**TINY[family])
    step, init = make_train_step(cfg, TrainConfig(
        optimizer=OptimizerConfig(name="adamw"), remat=True,
        attention_impl=attention_impl))
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    return jax.jit(step).lower(state, batch).compile().as_text()


def _scopes_of(text):
    names = scopes.program_ops(text)["ops"].values()
    for n in names:
        assert sum(s in scopes.SCOPES for s in scopes.segments(n)) <= 1, n
    tops = {scopes.top_scope(n) for n in names} - {None}
    kernels = {k for k in scopes.KERNELS
               if any(scopes.in_scope(n, k) for n in names)}
    return tops, kernels


@pytest.mark.parametrize("family,impl", [
    ("dense", "naive"), ("dense", "chunked"), ("ssm", "auto"),
    ("hybrid", "auto")])
def test_compiled_train_step_carries_every_scope(family, impl):
    text = _train_step_text(family, impl)
    assert _scopes_of(text) == EXPECTED[family]
    # one device: no mesh splits the heads, so no `in_proj` part
    assert not any(scopes.in_scope(n, "in_proj")
                   for n in scopes.program_ops(text)["ops"].values())


def test_adapter_is_a_part_of_the_shared_blocks_mlp():
    names = scopes.program_ops(_train_step_text("hybrid"))["ops"].values()
    inside = [n for n in names if scopes.in_scope(n, "adapter")]
    assert inside
    assert all(scopes.top_scope(n) == "mlp" for n in inside)


def test_in_proj_is_a_part_of_mamba_taken_on_a_mesh_only():
    """`in_proj` names the head-aligned input projection inside `mamba`:
    present in the tiny hybrid's Mamba block on the (1, 4) mesh of four
    CPU devices, absent from its one-device program.  The scalar adds of
    an all-reduce's reduction body carry the body's own relative name,
    outside `mamba`; no device op is one of them."""
    import mesh_block
    assert "in_proj" in scopes.PARTS
    out = mesh_block.run(TINY["hybrid"])
    inside = [n for n in out["ops_mesh"] if scopes.in_scope(n, "in_proj")]
    assert {scopes.top_scope(n) for n in inside} - {None} == {"mamba"}
    assert not any(scopes.in_scope(n, "in_proj") for n in out["ops_one"])


def _instructions(text):
    """The module's instructions with their metadata and numbering off."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return sorted(re.sub(r"\.\d+", ".N", line.strip())
                  for line in text.splitlines()
                  if re.match(r"\s*(ROOT\s+)?%", line))


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
def test_scopes_add_no_instruction(family, monkeypatch):
    with_scopes = _instructions(_train_step_text(family))
    monkeypatch.setattr(scopes.jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _instructions(_train_step_text(family))
    assert with_scopes == without


def _op_names(fn, *args):
    return scopes.program_ops(
        jax.jit(fn).lower(*args).compile().as_text())["ops"].values()


@pytest.mark.parametrize("impl", ["naive", "chunked", "pallas"])
def test_sdpa_scope_under_every_implementation(impl, monkeypatch):
    from repro.kernels.flash_attention import ops
    monkeypatch.setattr(ops, "flash_attention",
                        functools.partial(ops.flash_attention,
                                          interpret=True))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    pos = jnp.arange(128, dtype=jnp.int32)

    def fn(q, k):
        with scopes.scope("attn"):
            return attention.sdpa(q, k, k, pos, pos, None, None, 0.125,
                                  impl=impl)
    names = _op_names(jax.grad(lambda q, k: fn(q, k).sum()), q, k)
    assert any(scopes.in_scope(n, "sdpa") for n in names)
    assert all(scopes.top_scope(n) == "attn"
               for n in names if scopes.in_scope(n, "sdpa"))


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_ssd_scope_under_both_implementations(impl, monkeypatch):
    from repro.kernels.ssd import ops
    monkeypatch.setattr(ops, "ssd", functools.partial(ops.ssd,
                                                      interpret=True))
    cfg = ModelConfig(**TINY["ssm"])
    params = ssm.mamba_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64), jnp.bfloat16)
    names = _op_names(lambda x: ssm.mamba_block(params, x, cfg, impl), x)
    assert any(scopes.in_scope(n, "ssd") for n in names)
    assert all(scopes.top_scope(n) == "mamba"
               for n in names if scopes.in_scope(n, "ssd"))


def test_compile_work_counts_compiles_while_inside():
    with compile_work() as work:
        jax.jit(lambda x: jnp.sin(x) * 3).lower(jnp.ones(7)).compile()
    assert work["compile_s"] > 0 and work["cache_misses"] >= 0
    before = dict(work)
    jax.jit(lambda x: jnp.cos(x) * 5).lower(jnp.ones(7)).compile()
    assert work == before
