"""What a later PR adds as new files and entries alone, run on the CPU in a
process of its own: a cell of a new family, and a cell on four devices."""

import filecmp
import json
import os
import subprocess
import sys

import pytest

import tiny
from tiny import BENCH

SEED = 2**33 + 5


def _run(root, workload, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "cpu_run.py"),
         "--root", root, "--workload", workload, "--seed", str(SEED),
         "--seconds", "0.5"],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("bench")))


def test_new_family_is_new_files_alone(root):
    from reference import dense
    workload = tiny.add_family(root)
    assert not os.path.exists(os.path.join(BENCH, "reference",
                                           tiny.NEW_FAMILY + ".py"))
    harness = filecmp.dircmp(os.path.join(BENCH, "harness"),
                             os.path.join(root, "chipbench", "harness"),
                             ignore=["__pycache__"])
    assert not (harness.left_only or harness.right_only
                or harness.diff_files)
    out = _run(root, workload)
    assert out["result"]["correct"], out["result"]["checks"]
    m, tr = tiny.TINY_NEW["model"], tiny.TRAIN
    assert out["flops_per_step"] == 3 * dense.forward_flops(
        m, tr["batch"], tr["seq_len"])
    assert out["largest_param"]["shape"] == [m["vocab_size"], m["d_model"]]


def test_four_devices(root):
    workload = "tiny-dense.train.tiny"
    tiny.set_chips(root, workload, 4)
    out = _run(root, workload, devices=4)
    r = out["result"]
    assert r["device"]["count"] == 4
    # the largest leaf, the embedding (256, 64), split four ways along its
    # rows: in the program by its own sharding rules over the model axis,
    # in the reference by the reference's rule
    for big in out["largest_param"], out["reference_largest_grad"]:
        assert big["shape"] == [256, 64]
        assert big["shards"] == [[64, 64]] * 4
    assert r["correct"], r["checks"]
    # the reference over four devices against the same on one: GSPMD sums
    # each loss and norm in four partial sums, in another order than one
    # device does; that moves a float32 sum by a few units of its 2^-24
    # rounding (3.5e-7 read on the CPU), so 1e-5 is rounding and no more
    four, one = out["reference"], out["reference_one_device"]
    for a, b in zip(four["loss"], one["loss"]):
        assert a == pytest.approx(b, rel=1e-5)
    assert four["grad"].keys() == one["grad"].keys()
    for k, v in one["grad"].items():
        assert four["grad"][k] == pytest.approx(v, rel=1e-5, abs=1e-12), k
