"""Device time per model scope (harness/scopes.py): on train traces
recorded on one TPU v5e with the compiled step's op names, the scopes, the
unscoped rest and the unmatched ops add up to the busy time that
`trace.reduce` reads from the same file."""

import gzip
import json
import os

import pytest

from harness import scopes, trace
from repro.models.scopes import KERNELS, SCOPES

DATA = os.path.join(os.path.dirname(__file__), "data")
# self times against the union of intervals: the trace's nanosecond
# rounding leaves a few ns of overlap between neighbouring ops a step
EXACT = 1e-5
MODEL_SCOPES = {
    "dense": {"embed", "norm", "attn", "mlp", "head", "optimizer"},
    "ssm": {"embed", "norm", "mamba", "head", "optimizer"},
}
MODEL_KERNEL = {"dense": "sdpa", "ssm": "ssd"}


def _fixture(family):
    stem = os.path.join(DATA, f"train.{family}")
    with gzip.open(stem + ".program.json.gz", "rt") as f:
        return stem + ".xplane.pb.gz", json.load(f)


def test_keys_by_module_and_instruction():
    program = {"module": "jit_train_step",
               "ops": {"fusion.1": "jit(train_step)/attn/dot_general",
                       "constant.3": ""}}
    modules = [(0, 100, "jit_train_step"), (200, 300, "jit_other")]
    ops = [(10, 20, "%fusion.1 = f32[8]{0} fusion(%p)"),
           (30, 40, "%copy.2 = f32[8]{0} copy(%q)"),
           (50, 60, "%constant.3 = f32[8]{0} constant({...})"),
           (210, 220, "%fusion.1 = f32[8]{0} fusion(%r)"),
           (400, 410, "%fusion.1 = f32[8]{0} fusion(%s)")]
    got = [k for _, _, k in scopes._keys(ops, modules, program)]
    assert got == ["jit(train_step)/attn/dot_general", scopes.UNMATCHED, "",
                   scopes.UNMATCHED, scopes.UNMATCHED]


def test_by_scope_and_by_kernel_fold_every_second():
    times = {"jit(s)/jvp(f)/while/body/attn/sdpa/exp": 3.0,
             "jit(s)/transpose(jvp(f))/attn/dot_general": 2.0,
             "jit(s)/optimizer/sqrt": 1.0,
             "jit(s)/jvp(f)/while/body/dynamic_slice": 0.5,
             scopes.UNMATCHED: 0.25}
    got = scopes.by_scope(times)
    assert set(got) == set(SCOPES) | {scopes.UNSCOPED, scopes.UNMATCHED}
    assert got["attn"] == 5.0 and got["optimizer"] == 1.0
    assert got[scopes.UNSCOPED] == 0.5 and got[scopes.UNMATCHED] == 0.25
    assert sum(got.values()) == pytest.approx(sum(times.values()))
    assert scopes.by_kernel(times) == {"sdpa": 3.0, "ssd": 0.0}


def test_serve_trace_with_no_program_is_all_unmatched():
    path = os.path.join(DATA, "serve.xplane.pb.gz")
    got = scopes.op_times(path, {"module": "jit_decode_step", "ops": {}})
    assert list(got) == [scopes.UNMATCHED]
    assert got[scopes.UNMATCHED] == pytest.approx(trace.reduce(path).busy_s,
                                                  rel=1e-9)


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_recorded_train_trace_adds_up(family):
    path, program = _fixture(family)
    busy = trace.reduce(path).busy_s
    times = scopes.op_times(path, program)
    per_scope = scopes.by_scope(times)
    assert sum(times.values()) == pytest.approx(busy, rel=EXACT)
    assert sum(per_scope.values()) == pytest.approx(busy, rel=EXACT)
    assert per_scope[scopes.UNMATCHED] <= 0.01 * busy
    assert {s for s in SCOPES if per_scope[s] > 0} == MODEL_SCOPES[family]
    kernels = scopes.by_kernel(times)
    kernel = MODEL_KERNEL[family]
    assert {k for k in KERNELS if kernels[k] > 0} == {kernel}
    top = "attn" if kernel == "sdpa" else "mamba"
    assert 0 < kernels[kernel] <= per_scope[top]


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_other_module_leaves_every_op_unmatched(family):
    path, program = _fixture(family)
    times = scopes.op_times(path, dict(program, module="jit_other"))
    assert list(times) == [scopes.UNMATCHED]
    assert times[scopes.UNMATCHED] == pytest.approx(
        trace.reduce(path).busy_s, rel=EXACT)
