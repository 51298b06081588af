"""Bring-up smoke test of the JAX LM path on a TPU, run from the repo root.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --phases sdpa,split   # attention measurements
    python chip_smoke.py --chips 4   # four chips: trainer on the (1, 4)
                                     # host mesh against one device

One chip, the phases named by --phases, all in this process (a chip
belongs to one process at a time):

- kernels: the three Pallas kernels compiled for the chip at smollm-360m /
  mamba2-130m widths, each against its ref.py oracle, and the flash
  attention output and gradients against the oracle's, at smollm-360m's
  training shape and for each feature of the other configs (FLASH_CASES);
- train:   `repro.launch.train --full`, smollm-360m at its published
  widths, 20 steps of 8 x 1024 tokens of synthetic data;
- serve:   `repro.launch.serve --full`, 12 requests on 4 slots with
  refills, every request's logits against a teacher-forced forward;
- sdpa:    `sdpa` forward + backward alone at smollm-360m's widths, 16,384
  tokens at each key length of SDPA_T, for each implementation: the
  measurement behind `repro.models.attention.PALLAS_MIN_T`;
- split:   each training cell of BENCHMARK.json once, traced, through
  chipbench/tests/layers.py: device ms a step per scope, and the `sdpa`
  scope split by flash kernel (the pallas_call names) and the rest; also
  whether the compiled step holds an S x S buffer.

Each phase prints one JSON line of its numbers; the last line is
{"ok": true, "device": {...}}.  Any failed check exits non-zero, and no
TPU means exit 1 before any phase runs.
"""

import argparse
import json
import math
import os
import statistics
import sys
import tempfile
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

TRAIN_ARGV = ["--arch", "smollm-360m", "--full", "--steps", "20",
              "--batch", "8", "--seq", "1024", "--ckpt-every", "1000",
              "--log-every", "5"]
# ce at init is near ln(vocab): the logits of a random model are ~uniform
CE0_BAND = 0.5
# logits have std ~1; decode and the full forward round bf16 at different
# places.  A row that still saw its previous request is off by > 4.
SERVE_MAX_ABS, SERVE_MEAN_ABS = 0.25, 0.03
# the sharded and the one-device run differ in reduction order and in
# attention: the (1, 4) mesh keeps the plain path (a Mosaic kernel
# cannot be partitioned), one device takes the flash kernels
CE_CURVE_RTOL = 2.0 ** -7                 # one bf16 ulp of the loss
# gradients of bf16 attention against the float32 oracle: two bf16 ulps,
# plus a floor of 1/4 of the reference's rms, since each entry sums 2048
# products of bf16-rounded factors (the plain XLA path that the kernels
# replace needs the same floor)
GRAD_RTOL, GRAD_ATOL_RMS = 2.0 ** -6, 2.0 ** -2
# (B, S, T, H, K, D, causal, window, softcap): smollm-360m's training
# shape, then what `auto` also routes to the kernels for other configs:
# softcap + window at D 256 (gemma2), padding with a window under one
# block, non-causal T != S (cross-attention), MQA with offset queries, a
# group of 16 query heads (chatglm3)
FLASH_CASES = ((8, 2048, 2048, 15, 5, 64, True, None, None),
               (2, 2048, 2048, 8, 4, 256, True, 1024, 50.0),
               (2, 1000, 1000, 8, 4, 256, True, 300, 50.0),
               (2, 700, 1500, 16, 16, 64, False, None, None),
               (2, 640, 2048, 4, 1, 128, True, None, None),
               (1, 4096, 4096, 32, 2, 128, True, None, None))
SDPA_T = (512, 1024, 2048, 4096)
SDPA_TOKENS = 16384                       # a step of the training cells
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
SPLIT_CELLS = ("smollm-360m.train.s2048", "mamba2-130m.train.s2048")
SPLIT_SEED, SPLIT_SECONDS = 9140000001, 12
PHASES = ("kernels", "train", "serve", "sdpa", "split")


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def _max_err(out, ref, atol, rtol):
    """Max |out - ref| and the worst ratio to atol + rtol * |ref|."""
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = np.abs(out - ref)
    return float(err.max()), float((err / (atol + rtol * np.abs(ref))).max())


def _rel_err(out, ref):
    """Worst |out - ref| / (GRAD_ATOL_RMS * rms(ref) + GRAD_RTOL * |ref|)."""
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    atol = GRAD_ATOL_RMS * float(np.sqrt(np.mean(ref ** 2)))
    return float((np.abs(out - ref) / (atol + GRAD_RTOL * np.abs(ref))).max())


def flash_grads(case) -> dict:
    """Output and dq, dk, dv of the flash kernels for one case of
    FLASH_CASES, and of the plain XLA path they replace, against the
    oracle's at highest precision in float32: worst error over tolerance."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.models.attention import sdpa_naive

    B, S, T, H, K, D, causal, window, softcap = case
    ks = jax.random.split(jax.random.PRNGKey(S + T), 4)
    bf16, f32 = jnp.bfloat16, jnp.float32
    q = jax.random.normal(ks[0], (B, S, H, D), bf16)
    k = jax.random.normal(ks[1], (B, T, K, D), bf16)
    v = jax.random.normal(ks[2], (B, T, K, D), bf16)
    do = jax.random.normal(ks[3], (B, S, H, D), bf16)
    qp = jnp.arange(T - S if causal else 0, T if causal else S,
                    dtype=jnp.int32)
    kp = jnp.arange(T, dtype=jnp.int32)
    t = (0, 2, 1, 3)

    def run(fn, do):
        def fwd_bwd(q, k, v, do):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out, *vjp(do))
        return jax.jit(fwd_bwd)(q, k, v, do)

    with jax.default_matmul_precision("highest"):
        want = run(lambda q, k, v: attention_ref(
            q.astype(f32).transpose(t), k.astype(f32).transpose(t),
            v.astype(f32).transpose(t), qp, kp, scale=D ** -0.5,
            causal=causal, window=window, softcap=softcap).transpose(t),
            do.astype(f32))
    got = run(lambda q, k, v: flash_attention(
        q, k, v, qp, kp, window=window, softcap=softcap, causal=causal), do)
    plain = run(lambda q, k, v: sdpa_naive(q, k, v, qp, kp, window, softcap,
                                           D ** -0.5, causal=causal), do)
    out = {"case": list(case)}
    for name, a, b, w in zip(("o", "dq", "dk", "dv"), got, plain, want):
        out[name] = {"worst_vs_tol": _rel_err(a, w),
                     "naive_worst_vs_tol": _rel_err(b, w)}
        check(out[name]["worst_vs_tol"] <= 1.0,
              f"flash attention {case} {name} outside tolerance")
    return out


def phase_kernels() -> dict:
    """Each kernel compiled (interpret=False) and run once on the chip at
    model widths, against its oracle computed at highest matmul precision.
    Tolerances (atol, rtol): bf16 output rounding is rtol 2^-7; flash
    attention and SSD accumulate in f32 over 1024-long rows."""
    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_ref

    sm, mb = ARCHS["smollm-360m"], ARCHS["mamba2-130m"]
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    bf16 = jnp.bfloat16
    B, S = 8, 1024

    q = jax.random.normal(ks[0], (B, S, sm.n_heads, sm.head_dim), bf16)
    k = jax.random.normal(ks[1], (B, S, sm.n_kv_heads, sm.head_dim), bf16)
    v = jax.random.normal(ks[2], (B, S, sm.n_kv_heads, sm.head_dim), bf16)
    pos = jnp.arange(S, dtype=jnp.int32)

    def fa_ref(q, k, v, pos):
        t = (0, 2, 1, 3)
        return attention_ref(q.transpose(t), k.transpose(t), v.transpose(t),
                             pos, pos, scale=sm.head_dim ** -0.5
                             ).transpose(t)

    H, P, N = mb.n_ssm_heads, mb.ssm_head_dim, mb.ssm_state
    b = 2
    x = (jax.random.normal(ks[3], (b, S, H, P)) * 0.5).astype(bf16)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (b, S, H)))
    A = -jnp.exp(jax.random.normal(ks[5], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[6], (b, S, N)) * 0.5).astype(bf16)
    Cm = (jax.random.normal(ks[7], (b, S, N)) * 0.5).astype(bf16)

    xr = jax.random.normal(ks[0], (B * S, sm.d_model), bf16)
    scale = jnp.linspace(0.5, 1.5, sm.d_model).astype(bf16)

    cases = {
        "flash_attention": (
            lambda q, k, v, p: flash_attention(q, k, v, p, p,
                                               interpret=False),
            fa_ref, (q, k, v, pos), 2e-2, 2.0 ** -7),
        "ssd": (
            lambda x, dt, A, B, C: ssd(x, dt, A, B, C, chunk=mb.ssm_chunk,
                                       interpret=False)[0],
            lambda x, dt, A, B, C: ssd_ref(
                x.astype(jnp.float32), dt, A, B.astype(jnp.float32),
                C.astype(jnp.float32))[0],
            (x, dt, A, Bm, Cm), 1e-1, 2.0 ** -7),
        "rmsnorm": (
            lambda x, s: rmsnorm(x, s, interpret=False),
            rmsnorm_ref, (xr, scale), 1e-2, 2.0 ** -7),
    }
    out = {"phase": "kernels"}
    for name, (fn, ref_fn, args, atol, rtol) in cases.items():
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Pallas kernel in the compiled program")
        got = jax.block_until_ready(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        max_abs, worst = _max_err(got, want, atol, rtol)
        out[name] = {"compile_s": compile_s, "max_abs_err": max_abs,
                     "atol": atol, "rtol": rtol, "worst_vs_tol": worst}
        check(worst <= 1.0, f"{name}: error {max_abs} outside tolerance")
    out["flash_attention_grad"] = {
        "rtol": GRAD_RTOL, "atol_of_rms": GRAD_ATOL_RMS,
        "cases": [flash_grads(case) for case in FLASH_CASES]}
    return out


def phase_sdpa() -> dict:
    """Median ms of `sdpa` forward + backward (its gradient for a fixed
    output cotangent) at smollm-360m's widths, SDPA_TOKENS tokens a call,
    for each implementation that `auto` may pick at that key length
    (naive's scores outgrow memory above 2048 keys, where auto never picks
    it), and the shortest key length from which pallas is the fastest."""
    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS
    from repro.models.attention import PALLAS_MIN_T, sdpa

    sm = ARCHS["smollm-360m"]
    H, K, D = sm.n_heads, sm.n_kv_heads, sm.head_dim
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    out = {"phase": "sdpa", "tokens": SDPA_TOKENS, "ms": {}}
    fastest = {}
    for T in SDPA_T:
        B = SDPA_TOKENS // T
        q = jax.random.normal(ks[0], (B, T, H, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, T, K, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, T, K, D), jnp.bfloat16)
        do = jax.random.normal(ks[3], (B, T, H, D), jnp.float32)
        pos = jnp.arange(T, dtype=jnp.int32)
        ms = {}
        for impl in ("naive", "chunked", "pallas"):
            if impl == "naive" and T > 2048:
                continue

            def loss(q, k, v, impl=impl):
                o = sdpa(q, k, v, pos, pos, None, None, D ** -0.5, impl)
                return jnp.sum(o.astype(jnp.float32) * do)

            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            try:
                jax.block_until_ready(step(q, k, v))
            except Exception as e:  # noqa: BLE001 — e.g. out of memory
                out.setdefault("failed", {})[f"{T}.{impl}"] = str(e)[:200]
                continue
            jax.block_until_ready(step(q, k, v))
            times = []
            for _ in range(10):
                t0 = time.perf_counter()
                jax.block_until_ready(step(q, k, v))
                times.append(1e3 * (time.perf_counter() - t0))
            ms[impl] = statistics.median(times)
        out["ms"][str(T)] = ms
        fastest[T] = min(ms, key=ms.get)
    from_t = [T for T in SDPA_T
              if all(fastest[u] == "pallas" for u in SDPA_T if u >= T)]
    out["pallas_fastest_from_t"] = from_t[0] if from_t else None
    out["PALLAS_MIN_T"] = PALLAS_MIN_T
    return out


def phase_split() -> dict:
    """Each cell of SPLIT_CELLS traced through chipbench/tests/layers.py,
    whose op times per op_name are kept: device ms a step per scope and
    kernel scope, the `sdpa` scope by flash kernel (a pallas_call's
    op_name holds `<its name>/pallas_call`) and the rest (its largest ops
    by name), and the S x S buffers the compiled step holds (shapes
    ending in seq_len, seq_len)."""
    import re
    bench = os.path.join(os.path.dirname(SRC), "chipbench")
    sys.path[:0] = [os.path.join(bench, "tests"), bench]
    import layers
    from harness import scopes, spec
    from repro.models.scopes import in_scope

    seen, op_times = [], scopes.op_times

    def kept_op_times(path, program):
        seen.append(op_times(path, program))
        return seen[-1]

    scopes.op_times = kept_op_times
    kept = layers.keep_compiled()
    out = {"phase": "split", "seed": SPLIT_SEED, "cells": {}}
    for cell in SPLIT_CELLS:
        r = layers.layers(os.path.dirname(SRC), cell, SPLIT_SEED,
                          SPLIT_SECONDS, kept)
        n = r["steps_traced"]
        sdpa = {k: 1e3 * s / n for k, s in seen[-1].items()
                if in_scope(k, "sdpa")}
        kernel_of = {k: next((kern for kern in FLASH_KERNELS
                              if f"/{kern}/pallas_call" in k), None)
                     for k in sdpa}
        split = {kern: sum(s for k, s in sdpa.items() if kernel_of[k] == kern)
                 for kern in FLASH_KERNELS}
        rest = sorted(((s, k) for k, s in sdpa.items() if not kernel_of[k]),
                      reverse=True)
        total = sum(sdpa.values())
        text = max((c.as_text() for c in kept), key=len)
        bench_spec = spec.Bench(os.path.dirname(SRC))
        seq = bench_spec.traffic(bench_spec.workload(cell)["traffic"])[
            "seq_len"]
        r.update({
            "sdpa_ms": total, "sdpa_kernel_ms": split,
            "sdpa_kernel_share": (sum(split.values()) / total
                                  if total else None),
            "sdpa_rest_ms": [[k, s] for s, k in rest[:8]],
            "sxs_buffers": sorted(set(re.findall(
                rf"\w+\[[\d,]*{seq},{seq}\]", text)))})
        out["cells"][cell] = r
    scopes.op_times = op_times
    return out


def _train(extra, ckpt_root) -> dict:
    from repro.launch import train
    with tempfile.TemporaryDirectory(dir=ckpt_root) as ckpt:
        return train.run(TRAIN_ARGV + ["--ckpt-dir", ckpt] + extra)


def phase_train(ckpt_root) -> dict:
    out = _train([], ckpt_root)
    ce, loss = out["ce"], out["loss"]
    ln_v = math.log(out["vocab_size"])
    res = {"phase": "train", "arch": out["arch"], "params": out["params"],
           "tokens_per_step": out["tokens_per_step"],
           "compile_s": out["compile_s"],
           "first_step_s": out["step_s"][0],
           "median_step_s": statistics.median(out["step_s"][1:]),
           "ce_first": ce[0], "ce_last": ce[-1], "ln_vocab": ln_v,
           "restores": out["restores"],
           "peak_bytes_in_use": out["peak_bytes_in_use"]}
    check(out["restores"] == 0, "train: a step failed and was restored")
    check(len(ce) == 20, f"train: {len(ce)} of 20 steps")
    check(all(math.isfinite(x) for x in loss), "train: non-finite loss")
    check(abs(ce[0] - ln_v) <= CE0_BAND,
          f"train: first ce {ce[0]} not within {CE0_BAND} of ln V {ln_v}")
    check(ce[-1] < ce[0], f"train: ce did not fall ({ce[0]} -> {ce[-1]})")
    return res


def phase_serve() -> dict:
    import numpy as np
    from repro.launch import serve
    out = serve.run(["--arch", "smollm-360m", "--full", "--requests", "12",
                     "--slots", "4", "--max-new", "12"])
    reqs = out["requests"]
    ref = serve.reference_logits(out["cfg"], out["params"], reqs)
    worst_max, worst_mean = 0.0, 0.0
    for r, f in zip(reqs, ref):
        check(r["logits"].shape == f.shape, "serve: logits shape")
        err = np.abs(r["logits"] - f)
        worst_max = max(worst_max, float(err.max()))
        worst_mean = max(worst_mean, float(err.mean()))
    res = {"phase": "serve", "requests": len(reqs), "slots": 4,
           "decode_steps": out["decode_steps"],
           "compile_s": out["compile_s"], "wall_s": out["wall_s"],
           "max_abs_logit_err": worst_max, "mean_abs_logit_err": worst_mean,
           "tol_max": SERVE_MAX_ABS, "tol_mean": SERVE_MEAN_ABS}
    check(len(reqs) == 12 and all(len(r["tokens"]) == 12 for r in reqs),
          "serve: not every request got 12 tokens")
    check(all(np.isfinite(r["logits"]).all() for r in reqs),
          "serve: non-finite logits")
    check(worst_max <= SERVE_MAX_ABS and worst_mean <= SERVE_MEAN_ABS,
          f"serve: logits off the teacher-forced forward by {worst_max}")
    return res


def phase_four_chips(ckpt_root) -> dict:
    """The same 20 steps on the (1, 4) host mesh and on one device."""
    sharded = _train(["--mesh", "host"], ckpt_root)
    single = _train(["--mesh", "one"], ckpt_root)
    diffs = [abs(a - b) for a, b in zip(sharded["ce"], single["ce"])]
    rel = max(d / b for d, b in zip(diffs, single["ce"]))
    res = {"phase": "four_chips", "mesh": sharded["mesh"],
           "ce_sharded": sharded["ce"], "ce_one_device": single["ce"],
           "max_abs_ce_diff": max(diffs), "max_rel_ce_diff": rel,
           "rtol": CE_CURVE_RTOL,
           "median_step_s_sharded": statistics.median(sharded["step_s"]),
           "median_step_s_one_device": statistics.median(single["step_s"]),
           "restores": sharded["restores"] + single["restores"]}
    check(res["restores"] == 0, "four_chips: a step was restored")
    check(len(diffs) == 20, "four_chips: runs of unequal length")
    check(rel <= CE_CURVE_RTOL,
          f"four_chips: loss curves differ by {rel} relative")
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--phases", default="kernels,train,serve",
                    help=f"one-chip phases, of {','.join(PHASES)}")
    args = ap.parse_args()
    names = args.phases.split(",")
    if not set(names) <= set(PHASES):
        ap.error(f"--phases takes names of {PHASES}")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    import jax
    from repro.launch.mesh import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 1
    use_compile_cache()
    # checkpoints go to the temp dir, never to copied-back output
    ckpt_root = tempfile.gettempdir()
    one_chip = {"kernels": phase_kernels,
                "train": lambda: phase_train(ckpt_root),
                "serve": phase_serve, "sdpa": phase_sdpa,
                "split": phase_split}
    phases = ([lambda: phase_four_chips(ckpt_root)] if args.chips == 4
              else [one_chip[n] for n in names])
    for phase in phases:
        try:
            res = phase()
        except CheckFailed as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
        print(json.dumps(res), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
