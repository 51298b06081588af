"""The control, and in training the half-batch fault, read as control.py
reads them on the chip, at the tiny sizes on the CPU: each reads more than
three times the program's own readings in at least one number, and comes
out as not correct against the cell's limits."""

import pytest

import tiny
from control import readings


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("workload", ["tiny-dense.train.tiny",
                                      "tiny-dense.serve.tiny"])
def test_control_fails_where_the_program_passes(root, workload):
    rows = readings(root, workload, [11, 12], [11], 0.5, allow_cpu=True)
    assert all(r["correct"] for r in rows)
    worst = {k: max(r["program"][k] for r in rows) for k in rows[0]["program"]}
    for fault in ("control", "half_batch") if "train" in workload else (
            "control",):
        got = rows[0][fault]
        assert any(got[k] > 3 * worst[k] for k in worst), (fault, got, worst)
        assert rows[0][fault + "_correct"] is False, (fault, got)
