"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files that the harness finds by it."""

import json
import os
import re
import subprocess
import sys

import pytest

from tiny import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["chipbench"]
    assert 1 <= spec["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for x in spec["configs"] + spec["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_name_has_its_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    for c in configs.values():
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        for sub, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.isfile(os.path.join(BENCH, sub, name + ".json"))
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_every_cell_reports_enough(spec):
    from harness.spec import Bench
    bench = Bench(ROOT)
    for w in spec["workloads"]:
        e2e = {m["name"] for m in bench.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = bench.per_layer(w["name"])
        assert layer and all(m["moves"] in e2e for m in layer)


def test_every_reference_is_a_whole_family(spec):
    """A family is one module, reference/<config["reference"]>.py: its
    forward pass, its weights and its FLOPs."""
    from reference.train import family
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            mod = family(json.load(f))
        for fn in ("forward", "weights", "forward_flops"):
            assert callable(getattr(mod, fn, None)), (c["name"], fn)


def test_exits_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "smollm-360m.train.s2048",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
