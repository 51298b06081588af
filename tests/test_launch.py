"""The launchers' in-process entry points at reduced width on the CPU:
serving with slot refills against a teacher-forced forward, the
trainer's bounded crash recovery, and where the compile cache goes."""

import os

import numpy as np
import pytest

from repro.launch import serve, train

# Logits have std ~1.  Decode and the full forward round bf16 at
# different places (a recurrence against a chunked scan for SSM blocks):
# measured max |diff| <= 0.1 on CPU.  A refilled row that still sees its
# predecessor's KV entries or SSM state is off by > 4.
SERVE_MAX_ABS = 0.25
SERVE_MEAN_ABS = 0.03


@pytest.mark.parametrize("arch", ["smollm-360m", "mamba2-130m"])
def test_refilled_slots_match_teacher_forced_forward(arch):
    out = serve.run(["--arch", arch, "--requests", "5", "--slots", "2",
                     "--max-new", "4"])
    reqs = out["requests"]
    assert len(reqs) == 5 and all(len(r["tokens"]) == 4 for r in reqs)
    ref = serve.reference_logits(out["cfg"], out["params"], reqs)
    for r, f in zip(reqs, ref):
        assert r["logits"].shape == f.shape
        err = np.abs(r["logits"] - f)
        assert err.max() <= SERVE_MAX_ABS and err.mean() <= SERVE_MEAN_ABS


def _train_argv(tmp_path, steps):
    return ["--steps", str(steps), "--batch", "2", "--seq", "32",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
            "--lr", "1e-2", "--log-every", "100"]


def test_train_run_learns_and_reports(tmp_path):
    out = train.run(_train_argv(tmp_path, 6))
    assert out["restores"] == 0
    assert len(out["ce"]) == len(out["step_s"]) == 6
    assert np.all(np.isfinite(out["loss"]))
    assert out["ce"][-1] < out["ce"][0]
    assert out["compile_s"] > 0


def _failing_batches(monkeypatch, fail_at, times):
    """Make the data pipeline raise at step `fail_at`, `times` times."""
    real = train.batch_for_model
    left = [times]

    def batch_for_model(cfg, dcfg, step):
        if step == fail_at and left[0] > 0:
            left[0] -= 1
            raise RuntimeError(f"injected failure at step {step}")
        return real(cfg, dcfg, step)

    monkeypatch.setattr(train, "batch_for_model", batch_for_model)


def test_train_restores_a_failed_step(tmp_path, monkeypatch):
    _failing_batches(monkeypatch, fail_at=3, times=1)
    out = train.run(_train_argv(tmp_path, 5))
    assert out["restores"] == 1
    assert len(out["ce"]) == len(out["step_s"]) == 5


def test_train_fails_after_max_restarts(tmp_path, monkeypatch):
    _failing_batches(monkeypatch, fail_at=3, times=100)
    with pytest.raises(RuntimeError, match="max_restarts"):
        train.run(_train_argv(tmp_path, 5))


def test_compile_cache_dir_placed_from_outside(monkeypatch):
    import jax
    from repro.launch import mesh
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/caller")
        mesh.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        mesh.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == mesh.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert mesh.CACHE_DIR == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_train_profile_writes_one_trace_with_spans(tmp_path):
    import glob
    import json

    from jax.profiler import ProfileData

    from repro.models.scopes import top_scope
    prof = tmp_path / "profile"
    train.run(_train_argv(tmp_path / "ckpt", 6)
              + ["--profile-dir", str(prof), "--profile-steps", "1:5"])
    paths = glob.glob(str(prof / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    names = [e.name for p in ProfileData.from_file(paths[0]).planes
             if p.name.startswith("/host") for line in p.lines
             for e in line.events]
    # steps 1-4 traced; saves after steps 2 and 4, none restored
    assert names.count("train") == 4
    assert names.count("train.checkpoint") == 2
    assert "train.restore" not in names
    with open(prof / "program_ops.json") as f:
        program = json.load(f)
    assert program["module"] == "jit_train_step"
    assert {top_scope(n) for n in program["ops"].values()} >= {
        "embed", "norm", "attn", "mlp", "head", "optimizer"}
