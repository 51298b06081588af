"""Zamba2's hybrid layers against the benchmark's plain reference.

The reference, `chipbench/reference/zamba2.py`, is imported as the
benchmark's own tests import it: with `chipbench/` on the path.  Its
weights, in the program's layout, feed both sides.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ModelConfig
from repro.models import build_model, param_count, scopes, transformer
from repro.models.ssm import ssd_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
sys.path[:0] = [BENCH, os.path.join(BENCH, "tests")]

import mesh_block  # noqa: E402
from reference import ssm as ref_ssm  # noqa: E402
from reference import zamba2 as ref  # noqa: E402
from reference.common import quantizer, to_f32  # noqa: E402

# every mechanism at a tiny size: 2 groups, both shared blocks, heads of
# 2d / n_heads as transformers' Zamba2Config makes them
TINY = {"name": "tiny-zamba2", "family": "hybrid", "n_layers": 4,
        "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 32,
        "d_ff": 128, "vocab_size": 256, "rope_theta": 10000.0,
        "attn_input_dim": 128, "softmax_scale_dim": 16,
        "tie_embeddings": True, "scale_tied_embedding": False,
        "activation": "geglu_erf", "ssm_state": 16, "d_conv": 4,
        "expand": 2, "ssm_head_dim": 16, "ssm_chunk": 16, "ssm_groups": 2,
        "hybrid_layer_ids": [1, 3], "num_mem_blocks": 2, "adapter_rank": 8,
        "norm_eps": 1e-05}


def _config(name="zamba2-7b"):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _tokens(B=2, S=64, seed=0):
    t = np.random.default_rng(seed).integers(0, 256, (B, S + 1))
    return jnp.asarray(t[:, :-1], jnp.int32), jnp.asarray(t[:, 1:], jnp.int32)


def _ce(logits, labels):
    lse = jax.nn.logsumexp(logits, -1)
    return (lse - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            ).mean()


def _by_path(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.fixture(scope="module")
def tiny():
    params = ref.weights(TINY, jax.random.PRNGKey(3), {})
    model = build_model(ModelConfig(**TINY), impl="naive", remat=False)
    return params, model


def test_program_matches_reference_logits_and_gradient(tiny):
    """Run in float32 the program computes the reference's function: both
    take the same float32 weights at the highest matmul precision, and
    differ in the order of their sums alone (the chunked SSD against the
    token-by-token recurrence, blocked attention against the whole
    softmax), which moves float32 results by a few 2^-24 units through
    four layers (1.3e-6 of the logits, 4.2e-6 of a gradient leaf read on
    the CPU), so 1e-4 is that rounding and no more.  Left in bfloat16 the
    program rounds every activation to 8 bits: its logits lie 3.5% off
    (rel. L2; float8 e4m3 products put them 48% off), under 10%."""
    params, model = tiny
    x, y = _tokens()
    p32 = to_f32(params)

    def ref_logits(p):
        return ref.forward(p, x, TINY, quantizer("f32"))

    def prog_logits(p):
        return model.apply(p, {"tokens": x})[0]

    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref_logits)(p32)
        got = jax.jit(prog_logits)(p32)
        g_ref = _by_path(jax.jit(jax.grad(lambda p: _ce(ref_logits(p), y)))(
            p32))
        g_prog = _by_path(jax.jit(jax.grad(lambda p: _ce(prog_logits(p), y)))(
            p32))
    assert _rel(got, want) < 1e-4
    assert g_prog.keys() == g_ref.keys()
    for k, g in g_ref.items():
        assert _rel(g_prog[k], g) < 1e-4, k
        # both shared blocks and every adapter and `linear` reach the loss
        assert float(jnp.abs(g).max()) > 0, k
    bf16 = jax.jit(prog_logits)(params)
    assert _rel(bf16, want) < 0.1


def test_grouped_ssd_scan_matches_recurrence_per_group():
    """Head h reads group h // (H/G): the chunked scan against the plain
    recurrence run once per group over its heads, in float32 (differences
    are the order of float32 sums: 1e-5 of the output's scale)."""
    b, L, H, P, N, G = 2, 64, 8, 16, 16, 2
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (b, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, L, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(k[3], (b, L, G, N))
    Cm = jax.random.normal(k[4], (b, L, G, N))
    with jax.default_matmul_precision("highest"):
        got, _ = ssd_scan(x, dt, A, Bm, Cm, chunk=16)
        Hg = H // G
        want = jnp.concatenate([ref_ssm.recurrence(
            x[:, :, g * Hg:(g + 1) * Hg], dt[..., g * Hg:(g + 1) * Hg],
            A[g * Hg:(g + 1) * Hg], Bm[:, :, g], Cm[:, :, g])
            for g in range(G)], axis=2)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    # one group given as a group axis takes the same arithmetic, bit for
    # bit, as the single-group form that mamba2-130m runs
    one, st1 = ssd_scan(x, dt, A, Bm[:, :, 0], Cm[:, :, 0], chunk=16)
    grp, stg = ssd_scan(x, dt, A, Bm[:, :, :1], Cm[:, :, :1], chunk=16)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(grp))
    np.testing.assert_array_equal(np.asarray(st1), np.asarray(stg))


def test_hybrid_placement(monkeypatch):
    """The k-th hybrid layer runs shared block k % num_mem_blocks with the
    k-th adapter and `linear`; plain layers run no MLP."""
    m = dict(TINY, n_layers=6, hybrid_layer_ids=[1, 3, 5])
    params = ref.weights(m, jax.random.PRNGKey(1), {})
    model = build_model(ModelConfig(**m), impl="naive", remat=False)
    seen, mlp = [], transformer.mlp

    def recording(p, x, activation, adapter=None):
        seen.append((p, adapter))
        return mlp(p, x, activation, adapter=adapter)

    monkeypatch.setattr(transformer, "mlp", recording)
    model.apply(params, {"tokens": _tokens(1, 16)[0]})
    blocks = [s["mlp"] for s in params["shared"]]
    adapters = [h["adapter"] for h in params["hybrid"]]
    def which(trees, t):
        return next(i for i, u in enumerate(trees) if u is t)
    assert [(which(blocks, p), which(adapters, a)) for p, a in seen] == [
        (0, 0), (1, 1), (0, 2)]


def test_decode_matches_forward():
    """Token by token through the caches, one KV cache per application of
    a shared block, against the forward pass: the bf16 bound of
    test_models.py's hybrid case (S * n_layers * 2^-6)."""
    m = dict(TINY, n_layers=6, hybrid_layer_ids=[1, 3, 5])
    params = ref.weights(m, jax.random.PRNGKey(2), {})
    model = build_model(ModelConfig(**m), impl="naive", remat=False)
    S = 12
    toks = _tokens(1, S)[0]
    full, _ = model.apply(params, {"tokens": toks})
    cache = model.init_cache(1, S + 1)
    assert len(cache["hybrid"]) == 3
    dec = jax.jit(model.decode)
    errs = []
    for t in range(S):
        lg, cache = dec(params, cache, toks[:, t:t + 1], jnp.int32(t))
        errs.append(float(jnp.abs(lg[:, 0] - full[:, t]).max()))
    assert max(errs) < S * m["n_layers"] * 2.0 ** -6, errs
    # each application wrote its own positions
    for kv in cache["hybrid"]:
        assert float(jnp.abs(kv["k"][0, :S].astype(jnp.float32)).min()) > 0
    a, b = cache["hybrid"][0]["k"], cache["hybrid"][2]["k"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_param_count_at_published_widths():
    """The analytic count equals the leaves of the program's tree, for the
    81 layers and for the benchmark's 12."""
    full = ARCHS["zamba2-7b"]
    cut = ModelConfig(**_config()["model"])
    for cfg, about in ((full, 7.357e9), (cut, 1.758e9)):
        tree = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        assert param_count(tree) == cfg.param_count()
        assert cfg.param_count() == pytest.approx(about, rel=1e-3)
    assert reduced(full).hybrid_layer_ids == (1, 3)


def test_flops_per_step_of_the_cell():
    """3 x the forward count at batch 4 x 4096, expanded by hand: per
    token, 12 grouped Mamba2 layers (projections, conv, SSD at chunk 256),
    two shared-block applications (q/k/v from 7168 wide, o, the causal
    scores and values over half of 4096 positions, gate/up/down, the
    adapter, `linear`) and the tied head."""
    from harness.flops import train_step_flops
    d, di, H, P, N, G, Q, F = 3584, 7168, 112, 64, 64, 2, 256, 14336
    mamba = (2 * d * (2 * di + 2 * G * N + H) + 2 * di * d
             + 2 * 4 * (di + 2 * G * N)
             + G * Q * N + Q * H * P + 4 * H * P * N)
    hybrid = (2 * 7168 * 3 * 7168 + 2 * 7168 * d + 2 * 4096 * 7168
              + 6 * d * F + 2 * 128 * (d + 2 * F) + 2 * d * d)
    per_token = 12 * mamba + 2 * hybrid + 2 * 32000 * d
    got = train_step_flops(_config(), 4, 4096)
    assert got == 3 * 4 * 4096 * per_token == 180_736_116_129_792


def test_mamba_block_on_four_devices_matches_one_device():
    """On the (1, 4) mesh `model` cuts a tiny Zamba2's in_proj (64 x 328:
    z 128 | x 128 | B 32 | C 32 | dt 8) into four blocks of 82 columns
    that cut across its parts.  The block then moves in_proj's columns to
    each device's heads (the `in_proj` scope, which the one-device program
    lacks) and computes in float32 what one device computes: the output
    and the gradients of in_proj, conv_w, conv_b and the input agree to
    1e-5 of their norms, the order of the sums aside."""
    out = mesh_block.run(TINY)
    for name in ("y", "in_proj", "conv_w", "conv_b", "x"):
        assert out[name] < 1e-5, (name, out[name])
    assert any(scopes.in_scope(n, "in_proj") for n in out["ops_mesh"])
    assert not any(scopes.in_scope(n, "in_proj") for n in out["ops_one"])


def test_remat_keeps_the_moved_columns():
    """The tiny Zamba2's gradient on the (1, 4) mesh moves in_proj's columns
    as often under the train step's remat as without it: the remat keeps
    the moved columns (`ssm.MOVED`), so its forward pass moves none."""
    n = mesh_block.run(TINY)["moves"]
    assert n["remat"] == n["plain"] > 0, n


def test_every_large_matrix_is_split_over_four_chips():
    """On the cell's (data=1, model=4) mesh every matrix of more than 1M
    parameters a layer is split over `model`, so the fullest chip holds
    about a quarter of the state.  The rules read only the mesh's axis
    sizes, so an abstract mesh stands for four chips."""
    from repro.runtime.sharding import param_spec
    mesh = jax.sharding.AbstractMesh((1, 4), ("data", "model"))
    cfg = ModelConfig(**_config()["model"])
    tree = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    total = held = 0
    for k, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in k)
        spec = param_spec(mesh, name, leaf.shape)
        split = "model" in tuple(spec)
        if leaf.ndim >= 2 and np.prod(leaf.shape[-2:]) > 1e6:
            assert split, (name, leaf.shape, spec)
        total += leaf.size
        held += leaf.size // 4 if split else leaf.size
    assert held < 0.251 * total


def test_reference_matches_transformers():
    """The plain reference against transformers' Zamba2ForCausalLM (its
    torch path) at a tiny size with the same float32 weights.
    transformers rotates halves of each head where the reference rotates
    adjacent pairs, so its q and k columns are the reference's permuted;
    its slow path clamps dt at time_step_min, which each mixer is given as
    0 here, as the fused path leaves dt unclamped.  That path also sums
    the chunk states over the wrong axis of its chunk decays (`.sum(dim=2)`
    where transformers' Mamba2 sums over the source chunk), so it runs
    the 48 positions as one chunk of 64; the program's chunked scan meets
    the recurrence over many chunks in the test above.  Both compute in float32: 1e-4 of the logits'
    scale covers the order of their sums."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    m = TINY
    d, F, hd, H = m["d_model"], m["d_ff"], m["head_dim"], m["n_heads"]
    hf_cfg = transformers.Zamba2Config(
        vocab_size=m["vocab_size"], hidden_size=d, intermediate_size=F,
        num_hidden_layers=m["n_layers"],
        layers_block_type=["hybrid" if i in m["hybrid_layer_ids"] else
                           "mamba" for i in range(m["n_layers"])],
        mamba_d_state=m["ssm_state"], mamba_d_conv=m["d_conv"],
        mamba_expand=m["expand"], mamba_ngroups=m["ssm_groups"],
        mamba_headdim=m["ssm_head_dim"],
        n_mamba_heads=m["expand"] * d // m["ssm_head_dim"],
        chunk_size=64,
        num_attention_heads=H, num_key_value_heads=m["n_kv_heads"],
        num_mem_blocks=m["num_mem_blocks"], adapter_rank=m["adapter_rank"],
        use_shared_mlp_adapter=True, use_mem_rope=True,
        rope_theta=m["rope_theta"], rms_norm_eps=m["norm_eps"],
        hidden_act="gelu", tie_word_embeddings=True,
        attn_implementation="eager")
    assert hf_cfg.attention_head_dim == hd
    hf = transformers.Zamba2ForCausalLM(hf_cfg).eval()
    p = jax.tree.map(lambda a: np.asarray(a, np.float32),
                     ref.weights(m, jax.random.PRNGKey(4), {}))

    def t(a):
        return torch.tensor(np.ascontiguousarray(a))

    pairs = np.concatenate([np.arange(0, hd, 2), np.arange(1, hd, 2)])
    perm = (np.arange(H)[:, None] * hd + pairs[None]).ravel()
    hf.model.embed_tokens.weight.data = t(p["embed"]["table"])
    hf.model.final_layernorm.weight.data = t(p["final_norm"]["scale"])
    u = p["units"]["b0"]
    hybrid_k = {layer: k for k, layer in enumerate(m["hybrid_layer_ids"])}
    for i, layer in enumerate(hf.model.layers):
        k = hybrid_k.get(i)
        dec = layer if k is None else layer.mamba_decoder
        mx, um = dec.mamba, u["mamba"]
        mx.time_step_min = 0.0
        dec.input_layernorm.weight.data = t(u["norm"]["scale"][i])
        mx.in_proj.weight.data = t(um["in_proj"][i].T)
        mx.conv1d.weight.data = t(um["conv_w"][i].T[:, None, :])
        mx.conv1d.bias.data = t(um["conv_b"][i])
        for name in ("dt_bias", "A_log", "D"):
            getattr(mx, name).data = t(um[name][i])
        mx.norm.weight.data = t(um["gate_norm"]["scale"][i])
        mx.out_proj.weight.data = t(um["out_proj"][i].T)
        if k is None:
            continue
        b, hy = k % m["num_mem_blocks"], p["hybrid"][k]
        blk, sp = layer.shared_transformer, p["shared"][b]
        layer.linear.weight.data = t(hy["linear"].T)
        blk.input_layernorm.weight.data = t(sp["norm_in"]["scale"])
        blk.pre_ff_layernorm.weight.data = t(sp["norm_ff"]["scale"])
        at = blk.self_attn
        at.q_proj.weight.data = t(sp["attn"]["wq"][:, perm].T)
        at.k_proj.weight.data = t(sp["attn"]["wk"][:, perm].T)
        at.v_proj.weight.data = t(sp["attn"]["wv"].T)
        at.o_proj.weight.data = t(sp["attn"]["wo"].T)
        ff, ad = blk.feed_forward, hy["adapter"]
        ff.gate_up_proj.weight.data = t(np.concatenate(
            [sp["mlp"]["w_gate"], sp["mlp"]["w_up"]], 1).T)
        ff.down_proj.weight.data = t(sp["mlp"]["w_down"].T)
        lora = ff.gate_up_proj_adapter_list[k]
        lora[0].weight.data = t(ad["adapter_in"].T)
        lora[1].weight.data = t(np.concatenate(
            [ad["adapter_gate"], ad["adapter_up"]], 1).T)
    x, _ = _tokens(2, 48, seed=1)
    with torch.no_grad():
        want = hf(input_ids=torch.tensor(np.asarray(x, np.int64)),
                  use_cache=False, logits_to_keep=0).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: ref.forward(p, x, m, quantizer("f32")))(
            p, x)
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()


# limits of the tiny cell, set as the cells' limits are, from readings on
# the CPU over 5 seeds: sound loss_gap up to 0.0064, grad_gap 0.023,
# change_gap 0.0105; the float8 control's least 0.036 / 0.087 / 0.019
TINY_LIMITS = {"check_steps": 3, "reference_rows": 2,
               "limits": {"loss_gap": 0.015, "grad_gap": 0.05,
                          "change_gap": 0.015}}


def test_tiny_cell_on_four_devices(tmp_path):
    """The benchmark's training cell of a tiny Zamba2 on 4 CPU devices,
    as new files and entries alone, in a process of its own: the harness
    places the program's state by the program's rules over the (1, 4) mesh,
    and the step's first readings meet the reference's."""
    import tiny
    root = tiny.make(str(tmp_path))
    bench = os.path.join(root, "chipbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    tiny._add_config(spec, bench, {"name": TINY["name"], "family": "hybrid",
                                   "reference": "zamba2", "model": TINY})
    workload = TINY["name"] + ".train.tiny"
    tiny._add_cell(spec, bench, workload, TINY["name"], "train.tiny",
                   TINY_LIMITS)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    tiny.set_chips(root, workload, 4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "cpu_run.py"),
         "--root", root, "--workload", workload, "--seed", str(2**33 + 7),
         "--seconds", "0.5"],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    r = out["result"]
    assert r["device"]["count"] == 4
    assert r["correct"], r["checks"]
    # the largest leaf, the layers' stacked (4, 64, 328) in_proj, split
    # four ways along its output
    assert out["largest_param"] == {"shape": [4, 64, 328],
                                    "shards": [[4, 64, 82]] * 4}
    assert out["flops_per_step"] == 3 * ref.forward_flops(TINY, 4, 32)
