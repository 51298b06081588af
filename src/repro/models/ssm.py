"""Mamba2 (SSD — state-space duality) block, chunked-scan formulation.

Follows the SSD algorithm of Dao & Gu (arXiv:2405.21060): the sequence is
split into chunks of Q tokens; within a chunk the recurrence is evaluated
as a (masked) quadratic attention-like product, across chunks a linear
recurrence carries the (H, P, N) state.  This is exactly the structure the
Pallas kernel in repro.kernels/ssd tiles for VMEM; this module is the
lowerable-everywhere jnp implementation (and the kernel's oracle lives in
kernels/ssd/ref.py, mirroring this math).

B and C come in `cfg.ssm_groups` groups: head h reads group h // (H/G), as
Mamba2's reference code repeats each group over its heads, and the gated
RMSNorm normalises each group's d_inner/G channels on their own.  One
group (mamba2-130m) takes the single-group path; the Pallas kernel
(kernels/ssd) takes one group only.

On a mesh whose `model` axis cuts `in_proj`'s columns into n blocks, those
blocks cut across [z | x | B | C | dt], so the block moves `in_proj`'s
columns instead of its output: each chip gets its own heads' z, x and dt
columns and every B and C column (`_mix_by_heads`), and the projection's
outputs come out split by heads, B and C whole on every chip.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from .layers import _dense_init, rmsnorm, rmsnorm_init
from .scopes import scope, scoped

Params = Dict[str, jnp.ndarray]

# the name of in_proj's columns moved to each chip's heads
# (`_mix_by_heads`): the one residual that transformer's remat keeps
MOVED = "in_proj_by_heads"


def mamba_init(key, cfg: ModelConfig) -> Params:
    d, dssm, H = cfg.d_model, cfg.d_inner, cfg.n_ssm_heads
    GN = cfg.ssm_groups * cfg.ssm_state
    conv_dim = dssm + 2 * GN
    k1, k2, k3 = jax.random.split(key, 3)
    # in_proj emits [z, x, B, C, dt]
    return {
        "in_proj": _dense_init(k1, (d, 2 * dssm + 2 * GN + H)),
        "conv_w": _dense_init(k2, (cfg.d_conv, conv_dim), 0),
        "conv_b": jnp.zeros((conv_dim,), jnp.bfloat16),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "gate_norm": rmsnorm_init(dssm),
        "out_proj": _dense_init(k3, (dssm, d)),
    }


def _split_proj(cfg: ModelConfig, zxbcdt: jnp.ndarray):
    dssm, GN = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    z, xBC, dt = jnp.split(zxbcdt, [dssm, 2 * dssm + 2 * GN], axis=-1)
    return z, xBC, dt


def _by_group(cfg: ModelConfig, a: jnp.ndarray) -> jnp.ndarray:
    """B or C (..., G*N) as (..., N) for one group, else (..., G, N)."""
    G = cfg.ssm_groups
    return a if G == 1 else a.reshape(a.shape[:-1] + (G, cfg.ssm_state))


def _split_xbc(cfg: ModelConfig, xBC: jnp.ndarray):
    """[x, B, C] -> x, B, C; B and C by group (`_by_group`)."""
    dssm, GN = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    xs, Bv, Cv = jnp.split(xBC, [dssm, dssm + GN], axis=-1)
    return xs, _by_group(cfg, Bv), _by_group(cfg, Cv)


def _model_axis() -> int:
    """The size of the abstract mesh's `model` axis; 1 off a mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    return 1 if mesh.empty else dict(mesh.shape).get("model", 1)


def _column_moves(cfg: ModelConfig, n: int):
    """Where `in_proj`'s columns, stored in n blocks over `model`, go on the
    head-aligned path.  Chip j needs its heads' columns [z_j | x_j | dt_j]
    and every B and C column.  Returns {(src, dst): [(src column, count,
    dst column)]}, the contiguous runs of chip src's block that make up
    chip dst's [z_j | x_j | dt_j], and [(src, src column, count, column of
    [B | C])], the runs that make up B and C."""
    dssm, GN, H = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state, cfg.n_ssm_heads
    C = (2 * dssm + 2 * GN + H) // n

    def runs(lo, hi):               # [lo, hi) of the fused columns by chip
        for i in range(lo // C, (hi - 1) // C + 1):
            a, b = max(lo, i * C), min(hi, (i + 1) * C)
            yield i, a - i * C, b - a, a - lo

    moves, at = {}, 0
    for start, width in ((0, dssm // n), (dssm, dssm // n),
                         (2 * dssm + 2 * GN, H // n)):
        for dst in range(n):
            lo = start + dst * width
            for src, a, m, o in runs(lo, lo + width):
                moves.setdefault((src, dst), []).append((a, m, at + o))
        at += width
    return moves, list(runs(2 * dssm, 2 * dssm + 2 * GN))


def _mix_by_heads(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                  n: int):
    """in_proj and the causal conv where in_proj's columns lie in n blocks
    over `model`: z, x and dt split by heads over `model`, B and C whole on
    every chip.  Each chip receives its heads' columns of in_proj, each run
    from the chip that holds it, and every B and C column, summed over the
    chips that hold them; then it multiplies and convolves its own
    channels.  The gradient goes back the same way.  Both shard_maps take
    every axis manual: XLA's CPU compiler cannot promote a bfloat16
    all-reduce whose reducer carries the sharding of an automatic axis."""
    from jax.sharding import PartitionSpec as P
    moves, bc_runs = _column_moves(cfg, n)
    dssm, H = cfg.d_inner, cfg.n_ssm_heads
    zc, GN2 = dssm // n, 2 * cfg.ssm_groups * cfg.ssm_state

    def place(cols, at, width):
        return jnp.pad(cols, ((0, 0), (at, width - at - cols.shape[1])))

    def move(w):
        me = jax.lax.axis_index("model")
        heads = []
        for (src, dst), runs in moves.items():
            cols = [w[:, a:a + m] for a, m, _ in runs]
            if src == dst:
                got = [jnp.where(me == src, c, 0) for c in cols]
            else:
                buf = jax.lax.ppermute(jnp.concatenate(cols, axis=1),
                                       "model", [(src, dst)])
                got = jnp.split(buf, np.cumsum([m for _, m, _ in runs])
                                [:-1], axis=1)
            heads += [place(c, o, 2 * zc + H // n)
                      for (_, _, o), c in zip(runs, got)]
        bc = jax.lax.psum(sum(
            jnp.where(me == src, place(w[:, a:a + m], o, GN2), 0)
            for src, a, m, o in bc_runs), "model")
        return sum(heads), bc

    def local(w, bc, x, conv_w, conv_b):
        with scope("in_proj"):
            z, xs, dt = jnp.split(jnp.einsum("bld,de->ble", x, w),
                                  [zc, 2 * zc], axis=-1)
            BC = jnp.einsum("bld,de->ble", x, bc)
        mine = jax.lax.axis_index("model") * zc
        xs = _causal_conv(xs,
                          jax.lax.dynamic_slice_in_dim(conv_w, mine, zc, 1),
                          jax.lax.dynamic_slice_in_dim(conv_b, mine, zc))
        Bv, Cv = jnp.split(_causal_conv(BC, conv_w[:, dssm:], conv_b[dssm:]),
                           2, axis=-1)
        return z, xs, dt, _by_group(cfg, Bv), _by_group(cfg, Cv)

    with scope("in_proj"):
        w, bc = jax.shard_map(move, in_specs=P(None, "model"),
                              out_specs=(P(None, "model"), P()))(
            params["in_proj"])
    # kept by transformer's remat, whose forward pass then moves no column
    w, bc = checkpoint_name((w, bc), MOVED)
    mesh = jax.sharding.get_abstract_mesh()
    data = tuple(a for a in mesh.axis_names if a != "model")
    rows = (data if data and x.shape[0] % math.prod(
        mesh.shape[a] for a in data) == 0 else None)
    split = P(rows, None, "model")
    return jax.shard_map(
        local, in_specs=(P(None, "model"), P(), P(rows), P(), P()),
        out_specs=(split, split, split, P(rows), P(rows)))(
        w, bc, x, params["conv_w"], params["conv_b"])


def _gated_norm(params: Params, y: jnp.ndarray, z: jnp.ndarray,
                cfg: ModelConfig) -> jnp.ndarray:
    """RMSNorm of y * silu(z), over each group's d_inner/G channels."""
    g = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    G = cfg.ssm_groups
    if G == 1:
        return rmsnorm(params["gate_norm"], g, cfg.norm_eps)
    scale = params["gate_norm"]["scale"]
    gg = g.reshape(g.shape[:-1] + (G, -1))
    out = rmsnorm({"scale": scale.reshape(G, -1)}, gg, cfg.norm_eps)
    return out.reshape(g.shape)


def _causal_conv(xBC: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray
                 ) -> jnp.ndarray:
    """Depthwise causal conv1d. xBC: (B, L, C); w: (K, C)."""
    K = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i] for i in range(K))
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(xBC.dtype)


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """Stable segment-sum: out[..., i, j] = sum_{j<k<=i} x[..., k]."""
    T = x.shape[-1]
    c = jnp.cumsum(x, axis=-1)
    seg = c[..., :, None] - c[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, chunk: int,
             init_state: jnp.ndarray | None = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD chunked scan.

    x: (b, L, H, P); dt: (b, L, H) (post-softplus); A: (H,) negative;
    B, C: (b, L, N) single group, or (b, L, G, N), whose group g serves
    heads g*H/G .. (g+1)*H/G - 1.  Returns (y (b,L,H,P), state (b,H,P,N)).
    """
    if B.ndim == 4:                       # groups: one scan per group
        b, L, H, P = x.shape
        G = B.shape[2]
        heads = (b, L, G, H // G)
        st0 = (None if init_state is None
               else init_state.reshape((b, G, H // G) + init_state.shape[2:]))
        y, st = jax.vmap(
            lambda x, dt, A, B, C, st0: ssd_scan(x, dt, A, B, C, chunk, st0),
            in_axes=(2, 2, 0, 2, 2, None if st0 is None else 1),
            out_axes=(2, 1))(x.reshape(heads + (P,)), dt.reshape(heads),
                             A.reshape(G, H // G), B, C, st0)
        return y.reshape(b, L, H, P), st.reshape((b, H) + st.shape[3:])
    b, L, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q

    xc = x.reshape(b, nc, Q, H, P)
    dtc = dt.reshape(b, nc, Q, H)
    Bc = B.reshape(b, nc, Q, N)
    Cc = C.reshape(b, nc, Q, N)
    dA = dtc * A  # (b, nc, Q, H)
    dA_cum = jnp.cumsum(dA, axis=2)

    # intra-chunk (quadratic within the chunk)
    Lmat = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))       # (b,nc,H,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)          # (b,nc,Q,Q)
    gate = (scores[:, :, None] * Lmat).astype(x.dtype)      # (b,nc,H,Q,Q)
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(x.dtype)
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", gate, xdt)

    # chunk states
    decay_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)      # (b,nc,Q,H)
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", Bc,
                        decay_end.astype(x.dtype) * dtc.astype(x.dtype), xc)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])              # (b,nc,H)

    def body(carry, xs):
        st_c, dec = xs
        new = carry * dec[:, :, None, None].astype(carry.dtype) + st_c
        return new, carry  # emit state BEFORE this chunk

    init = (jnp.zeros((b, H, P, N), jnp.float32) if init_state is None
            else init_state.astype(jnp.float32))
    final, prev_states = jax.lax.scan(
        body, init,
        (states.transpose(1, 0, 2, 3, 4).astype(jnp.float32),
         chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)      # (b,nc,H,P,N)

    # inter-chunk output
    state_decay = jnp.exp(dA_cum)                            # (b,nc,Q,H)
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cc,
                       prev_states.astype(x.dtype),
                       state_decay.astype(x.dtype))
    y = (y_diag + y_off).reshape(b, L, H, P)
    return y, final.astype(x.dtype)


@scoped("mamba")
def mamba_block(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                impl: str = "auto") -> jnp.ndarray:
    """Full-sequence Mamba2 block. x: (B, L, d) -> (B, L, d)."""
    B_, L, _ = x.shape
    dssm, H, P = cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_head_dim
    n = _model_axis()
    # runtime/sharding.py splits in_proj's columns over `model` where n
    # divides them; n dividing H keeps each chip's heads whole
    if n > 1 and H % n == 0 and params["in_proj"].shape[-1] % n == 0:
        z, xs, dt, Bv, Cv = _mix_by_heads(params, x, cfg, n)
    else:
        z, xBC, dt = _split_proj(cfg, jnp.einsum("bld,de->ble", x,
                                                 params["in_proj"]))
        xBC = _causal_conv(xBC, params["conv_w"], params["conv_b"])
        xs, Bv, Cv = _split_xbc(cfg, xBC)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    xh = xs.reshape(B_, L, H, P)
    with scope("ssd"):
        if impl == "pallas":
            if cfg.ssm_groups > 1:
                raise NotImplementedError("the SSD kernel takes one group")
            from repro.kernels.ssd.ops import ssd
            y, _ = ssd(xh, dt, A, Bv, Cv, chunk=cfg.ssm_chunk)
        else:
            # pad L to a chunk multiple for the scan
            Q = min(cfg.ssm_chunk, max(16, L))
            pad = (-L) % Q
            if pad:
                xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
                dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
                grp = ((0, 0),) * (Bv.ndim - 2)
                Bv = jnp.pad(Bv, ((0, 0), (0, pad)) + grp)
                Cv = jnp.pad(Cv, ((0, 0), (0, pad)) + grp)
            y, _ = ssd_scan(xh, dt, A, Bv, Cv, Q)
            y = y[:, :L]
    y = y + params["D"].astype(y.dtype)[:, None] * xs.reshape(B_, L, H, P)
    y = y.reshape(B_, L, dssm)
    y = _gated_norm(params, y, z, cfg)
    return jnp.einsum("ble,ed->bld", y, params["out_proj"])


# --------------------------------------------------------------------------
# decode: O(1) recurrent state per block
# --------------------------------------------------------------------------

def init_ssm_cache(cfg: ModelConfig, batch: int) -> Dict[str, jnp.ndarray]:
    conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, conv_dim), jnp.bfloat16),
        "state": jnp.zeros((batch, cfg.n_ssm_heads, cfg.ssm_head_dim,
                            cfg.ssm_state), jnp.bfloat16),
    }


@scoped("mamba")
def decode_mamba(params: Params, x: jnp.ndarray, cache: Dict,
                 cfg: ModelConfig, pos: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, Dict]:
    """Single-token step. x: (B, 1, d); pos: (B,) int32 (or a shared
    scalar).  A row at position 0 starts a new sequence from zero state,
    whatever the previous occupant of that row left in the cache."""
    B_ = x.shape[0]
    fresh = jnp.broadcast_to(jnp.asarray(pos) == 0, (B_,))
    dssm, G, N, H, P = (cfg.d_inner, cfg.ssm_groups, cfg.ssm_state,
                        cfg.n_ssm_heads, cfg.ssm_head_dim)
    z, xBC, dt = _split_proj(cfg, jnp.einsum("bld,de->ble", x,
                                             params["in_proj"]))
    xBC = xBC[:, 0]
    conv_prev = jnp.where(fresh[:, None, None], 0, cache["conv"])
    window = jnp.concatenate([conv_prev, xBC[:, None]], axis=1)
    conv = (window * params["conv_w"]).sum(axis=1) + params["conv_b"]
    xBC = jax.nn.silu(conv.astype(jnp.float32)).astype(x.dtype)
    new_conv = window[:, 1:]
    xs, Bv, Cv = jnp.split(xBC, [dssm, dssm + G * N], axis=-1)
    # each head's group: (B, G*N) -> (B, H, N)
    Bv = jnp.repeat(Bv.reshape(B_, G, N), H // G, axis=1)
    Cv = jnp.repeat(Cv.reshape(B_, G, N), H // G, axis=1)
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    dA = jnp.exp(dtv * A)                                    # (B, H)
    xh = xs.reshape(B_, H, P)
    st = jnp.where(fresh[:, None, None, None], 0.0,
                   cache["state"].astype(jnp.float32))
    st = st * dA[:, :, None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dtv, xh.astype(jnp.float32),
        Bv.astype(jnp.float32))
    y = jnp.einsum("bhpn,bhn->bhp", st, Cv.astype(jnp.float32))
    y = y + params["D"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(B_, 1, dssm).astype(x.dtype)
    y = _gated_norm(params, y, z, cfg)
    out = jnp.einsum("ble,ed->bld", y, params["out_proj"])
    return out, {"conv": new_conv.astype(jnp.bfloat16),
                 "state": st.astype(jnp.bfloat16)}
