"""Ambient parallel context: which mesh axes exist for explicit
(shard_map) parallel blocks.

jit+GSPMD handles most of the model automatically, but the MoE dispatch
needs *explicit* expert parallelism (a data-dependent global argsort is
opaque to GSPMD — it replicates the full expanded token set; see
EXPERIMENTS.md SPerf H-kimi).  The launcher sets this context; model code
reads it.  When unset, the GSPMD (replicated-sort) path is used — fine
for CPU smoke tests.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple

_state = threading.local()


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    expert_axis: str = "model"          # mesh axis carrying experts
    data_axes: Tuple[str, ...] = ("data",)
    capacity_factor: float = 1.25       # per-destination-shard row budget


def get_context() -> Optional[ParallelContext]:
    return getattr(_state, "ctx", None)


def shard_batch(x):
    """Constrain an activation tensor to batch-sharded over the data axes.

    Pinning activations batch-sharded resolves GSPMD's FSDP-weight vs
    batch-sharding ambiguity toward ZeRO-3 semantics (gather the small
    weight shard, never replicate the big batch) — EXPERIMENTS.md SPerf
    H-gemma iteration 3."""
    ctx = get_context()
    if ctx is None:
        return x
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if not getattr(mesh, "shape", None):
        return x
    axes = tuple(a for a in ("pod", *ctx.data_axes) if a in mesh.shape)
    if not axes or x.ndim < 2:
        return x
    if x.shape[0] % _prod(mesh.shape[a] for a in axes) != 0:
        return x
    spec = P(axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def _prod(it):
    out = 1
    for v in it:
        out *= v
    return out


@contextlib.contextmanager
def parallel_context(ctx: ParallelContext):
    prev = get_context()
    _state.ctx = ctx
    try:
        yield
    finally:
        _state.ctx = prev
