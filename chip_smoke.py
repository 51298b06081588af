"""Bring-up smoke test of the JAX LM path on a TPU, run from the repo root.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: trainer on the (1, 4)
                                     # host mesh against one device

One chip, three phases, all in this process (a chip belongs to one
process at a time):

- kernels: the three Pallas kernels compiled for the chip at smollm-360m /
  mamba2-130m widths, each against its ref.py oracle;
- train:   `repro.launch.train --full`, smollm-360m at its published
  widths, 20 steps of 8 x 1024 tokens of synthetic data;
- serve:   `repro.launch.serve --full`, 12 requests on 4 slots with
  refills, every request's logits against a teacher-forced forward.

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
# the sharded and the one-device run differ only in reduction order
CE_CURVE_RTOL = 2.0 ** -7                 # one bf16 ulp of the loss


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
    args = ap.parse_args()
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
    phases = ([lambda: phase_four_chips(ckpt_root)] if args.chips == 4
              else [phase_kernels, lambda: phase_train(ckpt_root),
                    phase_serve])
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
