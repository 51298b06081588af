"""Roofline extraction: HLO collective parser, scan-counted-once
verification, term arithmetic.  These tests pin the methodology DESIGN.md
S7 relies on."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_auto_mesh
from repro.launch.roofline import (Roofline, collective_bytes, peaks,
                                   _type_bytes)


def test_type_bytes():
    assert _type_bytes("bf16[128,256]{1,0}") == 128 * 256 * 2
    assert _type_bytes("f32[8]") == 32
    assert _type_bytes("(bf16[4,4]{1,0}, f32[2])") == 32 + 8
    assert _type_bytes("pred[]") == 0 or _type_bytes("pred[]") >= 0


def _mesh2():
    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")
    return make_auto_mesh((1, 2), ("data", "model"))


def test_collective_parser_finds_allreduce():
    mesh = _mesh2()
    sh = NamedSharding(mesh, P(None, "model"))

    def f(x):
        return jnp.sum(x @ x.T)  # contraction over the sharded dim -> AR

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    with jax.set_mesh(mesh):
        compiled = jax.jit(f, in_shardings=sh).lower(x).compile()
    stats = collective_bytes(compiled.as_text())
    assert stats.payload_bytes > 0
    assert any(op.startswith("all-reduce") for op in stats.per_op)


def test_scan_body_counted_once():
    """The methodology's load-bearing assumption: cost_analysis() counts a
    scan body once, independent of trip count."""
    def make(n):
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, None, length=n)
            return y
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        return jax.jit(f).lower(x, w).compile().cost_analysis()["flops"]

    assert make(2) == make(8)


def test_extrapolation_math():
    full = Roofline(100.0, 1000.0, 10.0, {"all-reduce": 10.0})
    # fabricate a unit result and extrapolate manually like dryrun does
    unit = Roofline(7.0, 70.0, 1.0, {"all-gather": 1.0})
    k = 9
    total = Roofline(full.flops + k * unit.flops,
                     full.hbm_bytes + k * unit.hbm_bytes,
                     full.coll_link_bytes + k * unit.coll_link_bytes, {})
    assert total.flops == 163.0
    assert total.hbm_bytes == 1630.0
    assert total.t_compute < total.t_memory  # sanity on constants


def test_dominant_term():
    r = Roofline(flops=197e12, hbm_bytes=1.0, coll_link_bytes=1.0,
                 coll_per_op={})
    assert r.dominant == "compute" and r.step_time == pytest.approx(1.0)
    r2 = Roofline(flops=1.0, hbm_bytes=819e9 * 2, coll_link_bytes=1.0,
                  coll_per_op={})
    assert r2.dominant == "memory" and r2.step_time == pytest.approx(2.0)


def test_peaks_keyed_by_device_kind():
    assert peaks("TPU v5 lite").flops == 197e12
    assert peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")
