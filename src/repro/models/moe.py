"""Mixture-of-Experts block: top-k routing + sorted ragged_dot compute.

Dropless MoE in the TPU-idiomatic formulation: tokens are sorted by their
assigned expert and the expert matmuls run as `jax.lax.ragged_dot`
(group-wise GEMM), so compiled FLOPs equal the *active* FLOPs
(6 * N_active * D) — no dense-all-experts waste, which matters for the
roofline accounting of the 384-expert kimi config.

Expert weights are stacked (E, d, ff): the expert axis shards on the
'model' mesh axis (expert parallelism).  The token shuffle this induces is
the all-to-all-shaped multicast traffic that the paper's wireless plane
targets (see core/hybrid_schedule.py).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

from .layers import _dense_init
from .scopes import scoped

Params = Dict[str, jnp.ndarray]


def moe_init(key, cfg: ModelConfig) -> Params:
    d, ff, E = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": _dense_init(k1, (d, E)),
        "w_gate": _dense_init(k2, (E, d, ff)),
        "w_up": _dense_init(k3, (E, d, ff)),
        "w_down": _dense_init(k4, (E, ff, d)),
    }


def route(params: Params, x2d: jnp.ndarray, cfg: ModelConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing. x2d: (T, d) -> (weights (T,K), experts (T,K), aux)."""
    logits = jnp.einsum("td,de->te", x2d, params["router"]
                        ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch-style)
    E = cfg.n_experts
    me = probs.mean(0)
    ce = jnp.zeros((E,)).at[idx.reshape(-1)].add(
        jnp.ones_like(idx.reshape(-1), jnp.float32)) / idx.size
    aux = E * jnp.sum(me * ce)
    return w.astype(x2d.dtype), idx, aux


@scoped("moe")
def moe_block(params: Params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss).

    Two execution paths:
    - explicit expert parallelism (shard_map + all_to_all dispatch) when a
      ParallelContext is active — the production path: each device holds
      E/n_shards experts, token-rows travel to their expert's shard and
      back (this all-to-all is the multicast-shaped traffic the paper's
      hybrid plane offloads);
    - a GSPMD path otherwise (global sort + ragged_dot) — numerically
      identical (modulo capacity drops) and used as the test oracle.
    """
    from repro.runtime.parallel import get_context
    ctx = get_context()
    if ctx is not None:
        mesh = jax.sharding.get_abstract_mesh()
        if ctx.expert_axis in getattr(mesh, "shape", {}):
            n_e = mesh.shape[ctx.expert_axis]
            n_d = 1
            for a in ctx.data_axes:
                if a in mesh.shape:
                    n_d *= mesh.shape[a]
            T = x.shape[0] * x.shape[1]
            if cfg.n_experts % n_e == 0 and T % (n_d * n_e) == 0:
                return moe_block_expert_parallel(params, x, cfg, ctx)
            if cfg.n_experts <= n_e and \
                    (cfg.moe_d_ff or cfg.d_ff) % n_e == 0 and \
                    T % max(1, n_d) == 0:
                return moe_block_tp_ff(params, x, cfg, ctx)
    return moe_block_gspmd(params, x, cfg)


def moe_block_gspmd(params: Params, x: jnp.ndarray, cfg: ModelConfig
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, d = x.shape
    K, E = cfg.experts_per_token, cfg.n_experts
    x2d = x.reshape(B * S, d)
    w, idx, aux = route(params, x2d, cfg)

    # expand each token K times, sort by expert id
    flat_e = idx.reshape(-1)                       # (T*K,)
    order = jnp.argsort(flat_e)
    inv = jnp.argsort(order)
    xs = jnp.repeat(x2d, K, axis=0)[order]         # (T*K, d)
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)

    gate = jax.lax.ragged_dot(xs, params["w_gate"], group_sizes)
    up = jax.lax.ragged_dot(xs, params["w_up"], group_sizes)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    out = jax.lax.ragged_dot(h, params["w_down"], group_sizes)

    out = out[inv].reshape(B * S, K, d)            # unsort, fold K copies
    y = jnp.einsum("tkd,tk->td", out, w)
    return y.reshape(B, S, d), aux


# --------------------------------------------------------------------------
# explicit parallel paths (shard_map): see EXPERIMENTS.md SPerf H-kimi.
# GSPMD cannot partition the data-dependent global sort, so the jit path
# replicates every expanded token row; these paths keep rows sharded and
# move them explicitly.
# --------------------------------------------------------------------------

def _local_route(router, x2, cfg):
    logits = jnp.einsum("td,de->te", x2, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)
    ce = jnp.zeros((cfg.n_experts,)).at[idx.reshape(-1)].add(
        jnp.ones_like(idx.reshape(-1), jnp.float32)) / idx.size
    aux = cfg.n_experts * jnp.sum(me * ce)
    return w.astype(x2.dtype), idx, aux


def _expert_ffn(xs, group_sizes, wg, wu, wd):
    gate = jax.lax.ragged_dot(xs, wg, group_sizes)
    up = jax.lax.ragged_dot(xs, wu, group_sizes)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(xs.dtype) * up
    return jax.lax.ragged_dot(h, wd, group_sizes)


def _grouped_ffn(rows, expert_ids, n_experts, cap, wg, wu, wd):
    """Capacity-based grouped GEMM (the TPU 'dropping' formulation).

    rows: (N, d); expert_ids: (N,) in [0, n_experts] (n_experts = padding).
    Buckets rows per expert with capacity `cap`, runs batched einsum
    (e, cap, d) x (e, d, f) — true grouped-GEMM FLOPs on every backend
    (jax.lax.ragged_dot decomposes to masked dense-over-groups on the CPU
    backend, inflating compiled FLOPs n_experts-fold; see EXPERIMENTS.md
    SPerf H-kimi iteration 2) — and scatters results back to row order.
    Overflow rows are dropped (zero output), standard MoE behaviour.
    """
    N, d = rows.shape
    onehot = expert_ids[:, None] == jnp.arange(n_experts)[None, :]
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos_of = jnp.where(expert_ids < n_experts,
                       jnp.take_along_axis(
                           pos, jnp.minimum(expert_ids, n_experts - 1)[:, None],
                           axis=1)[:, 0],
                       cap)
    valid = pos_of < cap
    slot = jnp.where(valid, pos_of, cap)
    e_c = jnp.minimum(expert_ids, n_experts - 1)
    buck = jnp.zeros((n_experts, cap + 1, d), rows.dtype
                     ).at[e_c, slot].set(rows)[:, :cap]
    gate = jnp.einsum("ecd,edf->ecf", buck, wg)
    up = jnp.einsum("ecd,edf->ecf", buck, wu)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(rows.dtype) * up
    out = jnp.einsum("ecf,efd->ecd", h, wd)
    flat = out.reshape(n_experts * (cap), d)
    got = flat[e_c * cap + jnp.minimum(pos_of, cap - 1)]
    return jnp.where(valid[:, None], got, 0.0)


def moe_block_expert_parallel(params, x, cfg: ModelConfig, ctx):
    """Expert parallelism: E/n experts per model shard; token rows travel
    to their expert's shard over an explicit all_to_all and return — the
    multicast-shaped traffic the paper's hybrid plane offloads."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    ax = ctx.expert_axis
    n_e = mesh.shape[ax]
    data_axes = tuple(a for a in ("pod",) + tuple(ctx.data_axes)
                      if a in mesh.shape)
    n_d = 1
    for a in data_axes:
        n_d *= mesh.shape[a]
    B, S, d = x.shape
    T = B * S
    K, E = cfg.experts_per_token, cfg.n_experts
    E_local = E // n_e
    T_loc = T // (n_d * n_e)
    N = T_loc * K                                   # local expanded rows
    C = max(1, int(-(-N // n_e) * ctx.capacity_factor))  # per-dest budget

    tok_spec = P((*data_axes, ax), None)

    def run(wg, wu, wd, router, x2):
        idx_names = (*data_axes, ax)
        w, idx, aux = _local_route(router, x2, cfg)
        flat_e = idx.reshape(-1)                     # (N,)
        dest = flat_e // E_local
        # position of each row within its destination bucket
        onehot = dest[:, None] == jnp.arange(n_e)[None, :]
        pos = jnp.cumsum(onehot, axis=0) - 1
        pos_of = jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]
        valid = pos_of < C
        slot = jnp.where(valid, pos_of, C)           # overflow -> dropped
        rows = jnp.repeat(x2, K, axis=0)
        send = jnp.zeros((n_e, C + 1, d), x2.dtype).at[dest, slot].set(rows)
        meta = jnp.full((n_e, C + 1), E_local, jnp.int32).at[dest, slot].set(
            flat_e % E_local)
        send, meta = send[:, :C], meta[:, :C]
        recv = jax.lax.all_to_all(send, ax, 0, 0, tiled=False)
        rmeta = jax.lax.all_to_all(meta, ax, 0, 0, tiled=False)
        rrows = recv.reshape(n_e * C, d)
        re = rmeta.reshape(n_e * C)                  # E_local == padding
        cap_e = max(1, int(-(-T_loc * K // E_local) * ctx.capacity_factor))
        out = _grouped_ffn(rrows, re, E_local, cap_e, wg, wu,
                           wd).reshape(n_e, C, d)
        back = jax.lax.all_to_all(out, ax, 0, 0, tiled=False)
        flat_back = back.reshape(n_e * C, d)
        gathered = flat_back[dest * C + jnp.minimum(pos_of, C - 1)]
        gathered = jnp.where(valid[:, None], gathered, 0.0)
        y = jnp.einsum("tkd,tk->td", gathered.reshape(T_loc, K, d), w)
        aux = jax.lax.pmean(aux, (*data_axes, ax))
        return y, aux

    shard = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(ax, None, None), P(ax, None, None), P(ax, None, None),
                  P(None, None), tok_spec),
        out_specs=(tok_spec, P()),
        check_vma=False)
    y, aux = shard(params["w_gate"], params["w_up"], params["w_down"],
                   params["router"], x.reshape(T, d))
    return y.reshape(B, S, d), aux


def moe_block_tp_ff(params, x, cfg: ModelConfig, ctx):
    """Tensor parallelism over the expert hidden dim (few-expert MoE like
    mixtral where E < n_shards): rows stay put, every model shard computes
    its ff-slice for every row, partial results psum over the model axis."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    ax = ctx.expert_axis
    data_axes = tuple(a for a in ("pod",) + tuple(ctx.data_axes)
                      if a in mesh.shape)
    n_d = 1
    for a in data_axes:
        n_d *= mesh.shape[a]
    B, S, d = x.shape
    T = B * S
    K, E = cfg.experts_per_token, cfg.n_experts
    T_loc = T // n_d

    def run(wg, wu, wd, router, x2):
        w, idx, aux = _local_route(router, x2, cfg)
        flat_e = idx.reshape(-1)
        rows = jnp.repeat(x2, K, axis=0)
        cap = max(1, int(-(-T_loc * K // E) * ctx.capacity_factor))
        part = _grouped_ffn(rows, flat_e, E, cap, wg, wu, wd)
        out = jax.lax.psum(part, ax)                 # partial over ff slice
        y = jnp.einsum("tkd,tk->td", out.reshape(T_loc, K, d), w)
        aux = jax.lax.pmean(aux, (*data_axes, ax))
        return y, aux

    shard = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(None, None, ax), P(None, None, ax), P(None, ax, None),
                  P(None, None), P(data_axes, None)),
        out_specs=(P(data_axes, None), P()),
        check_vma=False)
    y, aux = shard(params["w_gate"], params["w_up"], params["w_down"],
                   params["router"], x.reshape(T, d))
    return y.reshape(B, S, d), aux
