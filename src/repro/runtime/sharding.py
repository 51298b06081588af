"""Divisibility-aware partition rules: param path -> PartitionSpec.

Strategy (megatron-style TP x FSDP x DP, on mesh axes
("pod",) "data", "model"):

- weight matrices: tensor-parallel on the dimension that maps to heads /
  d_ff / experts ('model'), FSDP on the complementary dimension ('data');
- a dimension is only assigned to a mesh axis when the axis size divides
  it — otherwise the rule falls back down a preference list and finally to
  replication (GSPMD would pad uneven shardings, but staying divisible
  keeps collective volumes exact);
- activations: batch on ("pod","data"); long-context (batch=1) shapes
  shard the sequence axis instead (context parallelism);
- KV caches: batch on ("pod","data"), kv-heads on 'model' when divisible,
  else sequence on 'model'.

Where a tensor is cut decides which collectives (multicast-shaped
all-gathers vs reduction traffic) the compiled step emits.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


def _fits(mesh: Mesh, dim: int, axis) -> bool:
    return dim % _axis_size(mesh, axis) == 0


def _choose(mesh: Mesh, shape: Tuple[int, ...], prefs) -> P:
    """prefs: per-dim list of candidate axes in preference order."""
    taken = set()
    spec: list = []
    for dim, cands in zip(shape, prefs):
        chosen = None
        for ax in cands:
            if ax is None:
                break
            flat = ax if isinstance(ax, tuple) else (ax,)
            if any(a in taken for a in flat):
                continue
            if _fits(mesh, dim, ax):
                chosen = ax
                taken.update(flat)
                break
        spec.append(chosen)
    return P(*spec)


DATA_AXES = ("pod", "data")


def _data(mesh: Mesh):
    """The (possibly pod-extended) FSDP/data axis present in this mesh."""
    return tuple(a for a in DATA_AXES if a in mesh.shape) or (None,)


def param_spec(mesh: Mesh, path: str, shape: Tuple[int, ...]) -> P:
    """Sharding rule for one parameter tensor, by name and rank."""
    fsdp = _data(mesh)
    if fsdp == (None,):
        fsdp = None
    last = path.split("/")[-1]

    def choose(*prefs):
        # strip leading stacked-unit axes (scan axes stay unsharded)
        extra = len(shape) - len(prefs)
        return _choose(mesh, shape,
                       [[None]] * extra + [list(p) for p in prefs])

    if last in ("table",):            # (V, d): vocab-parallel embedding.
        # d stays replicated: sharding d on the batch ('data') axis makes
        # the unembed contraction compete with batch sharding and GSPMD
        # replicates the full-batch logits.
        return choose(["model", None], [None])
    if last == "unembed":             # (d, V)
        return choose([None], ["model", None])
    if last in ("wq", "wk", "wv"):    # (d, H*hd): TP on the fused head dim
        return choose([fsdp, None], ["model", None])
    if last == "wo":                  # (H*hd, d)
        return choose(["model", None], [fsdp, None])
    if last in ("w_up", "w_gate"):    # (d, ff) or (E, d, ff)
        if len(shape) >= 3:           # expert-parallel; else TP on ff
            return choose(["model", None], [fsdp, None], ["model", None])
        return choose([fsdp, None], ["model", None])
    if last == "w_down":              # (ff, d) or (E, ff, d)
        if len(shape) >= 3:
            return choose(["model", None], ["model", None], [fsdp, None])
        return choose(["model", None], [fsdp, None])
    if last == "linear":              # hybrid layer's (d, d)
        return choose([fsdp, None], ["model", None])
    if last in ("adapter_gate", "adapter_up"):    # (rank, ff): TP on ff
        return choose([None], ["model", None])
    if last == "router":              # (d, E)
        return choose([fsdp, None], [None])
    if last in ("in_proj", "out_proj"):   # mamba: TP on d_inner side
        if last == "in_proj":
            return choose([fsdp, None], ["model", None])
        return choose(["model", None], [fsdp, None])
    if last in ("conv_w", "conv_b"):
        return choose(*[[None]] * len(shape))
    # norms, biases, scalars and an adapter's (d, rank) adapter_in: replicated
    return P(*([None] * len(shape)))


def params_shardings(mesh: Mesh, params_tree: Any):
    """Tree of NamedShardings matching a params (or abstract params) tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_tree)

    def name(kp):
        return "/".join(str(getattr(k, "key", k)) for k in kp)

    specs = [NamedSharding(mesh, param_spec(mesh, name(kp), x.shape))
             for kp, x in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def opt_shardings(mesh: Mesh, params_tree: Any, opt_name: str):
    """Optimizer-state shardings mirroring optimizers.init's structure.

    AdamW mu/nu inherit the parameter spec (ZeRO-for-free under FSDP);
    Adafactor's factored vr/vc take the parameter spec minus the reduced
    dimension."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_tree)

    def name(kp):
        return "/".join(str(getattr(k, "key", k)) for k in kp)

    def per_param(kp, x):
        spec = param_spec(mesh, name(kp), x.shape)
        ns = NamedSharding(mesh, spec)
        if opt_name == "adamw":
            return ns
        # adafactor
        parts = list(spec) + [None] * (len(x.shape) - len(spec))
        if x.ndim >= 2 and x.shape[-1] >= 128 and x.shape[-2] >= 128:
            return {
                "vr": NamedSharding(mesh, P(*parts[:-1])),
                "vc": NamedSharding(mesh, P(*(parts[:-2] + parts[-1:]))),
            }
        return {"v": ns}

    leaves = [per_param(kp, x) for kp, x in flat]
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    if opt_name == "adamw":
        return {"mu": tree, "nu": tree}
    return {"v": tree}


def state_shardings(mesh: Mesh, abstract_state: Any, opt_name: str):
    """Shardings for the full train state {params, opt, step}."""
    pshard = params_shardings(mesh, abstract_state["params"])
    return {
        "params": pshard,
        "opt": opt_shardings(mesh, abstract_state["params"], opt_name),
        "step": NamedSharding(mesh, P()),
    }


def batch_spec(mesh: Mesh, shape: Tuple[int, ...],
               kind: str = "tokens") -> P:
    """Activation/batch sharding: batch over ("pod","data"); batch=1
    long-context shapes shard the sequence axis (context parallel)."""
    fsdp = _data(mesh)
    batch = shape[0]
    if batch % _axis_size(mesh, fsdp) == 0:
        rest = [None] * (len(shape) - 1)
        return P(fsdp, *rest)
    if len(shape) >= 2 and shape[1] % _axis_size(mesh, fsdp) == 0:
        return P(None, fsdp, *([None] * (len(shape) - 2)))
    return P(*([None] * len(shape)))


def cache_spec(mesh: Mesh, shape: Tuple[int, ...]) -> P:
    """KV / SSM cache sharding (leading stacked-unit axes unsharded).

    KV caches arrive as (units..., B, L, kv_heads, hd) and SSM states as
    (units..., B, H, P, N)."""
    fsdp = _data(mesh)
    n_extra = max(0, len(shape) - 4)
    body = shape[n_extra:]
    spec: list = [None] * n_extra
    # batch axis
    if body and body[0] % _axis_size(mesh, fsdp) == 0:
        spec.append(fsdp)
        used_data = True
    else:
        spec.append(None)
        used_data = False
    rest = list(body[1:])
    # shard heads (axis -2) on model if divisible, else the seq axis
    model_done = False
    for i, dim in enumerate(rest):
        axis = None
        if not model_done and i == 1 and dim % _axis_size(mesh, "model") == 0:
            axis = "model"
            model_done = True
        spec.append(axis)
    if not model_done:
        # fall back: sequence (first body-rest axis) on model when divisible
        if rest and rest[0] % _axis_size(mesh, "model") == 0:
            spec[n_extra + 1] = "model"
        elif not used_data and rest and \
                rest[0] % _axis_size(mesh, fsdp) == 0:
            spec[n_extra + 1] = fsdp
    return P(*spec)


def cache_shardings(mesh: Mesh, cache_tree: Any):
    return jax.tree.map(
        lambda x: NamedSharding(mesh, cache_spec(mesh, x.shape)), cache_tree)


def logical_batch_shardings(mesh: Mesh, batch_tree: Any):
    return jax.tree.map(
        lambda x: NamedSharding(mesh, batch_spec(mesh, x.shape)), batch_tree)
