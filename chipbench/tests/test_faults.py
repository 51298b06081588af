"""Runs of tiny cells added from new files and entries alone, with the
timed path sound and with each fault planted under it."""

import time

import pytest

import tiny
from harness import cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("bench")))


def _run(root, workload, fault=None, trace=False, control=False):
    return cell.run(root, workload, 2**33 + 5, 0.5, trace,
                    time.perf_counter(), allow_cpu=True, fault=fault,
                    control=control)


@pytest.mark.parametrize("workload", sorted(tiny.CELLS))
def test_sound_run_is_correct(root, workload):
    r = _run(root, workload)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert "setup_s" in r["metrics"] and len(r["metrics"]) >= 2
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("workload,fault", [
    ("tiny-dense.train.tiny", "unchanged"),
    ("tiny-dense.train.tiny", "half_batch"),
    ("tiny-ssm.train.tiny", "unchanged"),
    ("tiny-ssm.train.tiny", "half_batch"),
    ("tiny-dense.serve.tiny", "unchanged"),
    ("tiny-dense.serve.tiny", "token"),
])
def test_fault_is_not_correct(root, workload, fault):
    assert not _run(root, workload, fault)["correct"]


@pytest.mark.parametrize("workload", sorted(tiny.CELLS))
def test_control_in_the_programs_place_is_not_correct(root, workload):
    r = _run(root, workload, control=True)
    assert not r["correct"], r["checks"]


def test_traced_run_reports_per_layer_only(root):
    r = _run(root, "tiny-dense.train.tiny", trace=True)
    assert r["correct"]
    assert "train_tokens_per_s" not in r["metrics"]
    assert set(r["device"]) >= {"busy_s", "window_s"}
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_window_counts_every_step_it_sent(root, monkeypatch):
    """Steps are dispatched ahead in the window; when it closes, every
    step sent has been waited for and counted."""
    from harness import train
    made = []
    real = train.gen.train_batch
    monkeypatch.setattr(train.gen, "train_batch",
                        lambda *a: made.append(a[-1]) or real(*a))
    r = _run(root, "tiny-dense.train.tiny")
    assert r["correct"], r["checks"]
    window = made[made.index(2) + 1:]      # after the checked steps 0-2
    window = window[:window.index(0)]      # before the reference's batches
    assert window == list(range(3, 3 + len(window)))
    assert r["attempted"] == len(window) == r["step_s"]["n"]
