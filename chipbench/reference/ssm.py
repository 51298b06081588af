"""Plain reference of the Mamba2 decoder (arXiv:2405.21060): in_proj to
[z, x, B, C, dt], depthwise causal conv with SiLU over [x, B, C], the
selective state-space recurrence run token by token, the skip D, the
gated RMSNorm, out_proj; pre-RMSNorm residual blocks, tied embeddings.

The recurrence is the plain one, h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
y_t = h_t C_t, not the chunked dual form the program uses.  As in the
program, the embedding is scaled by sqrt(d_model) (a departure from the
published model that the configuration file names).

Beside the forward pass: `weights`, the tree the benchmark makes from the
seed in the program's layout, and `forward_flops`, the forward pass's
operations in closed form (conventions in harness/flops.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

from .common import BF16, mat, matmul, rmsnorm

INNER = 64          # tokens per rematerialised block of the recurrence


def weights(m: dict, key, init: dict) -> dict:
    """Mamba2's published initialisation for A, dt and D (arXiv:2405.21060,
    reference code): A ~ U[1, 16], dt log-uniform in [1e-3, 1e-1] held as
    the inverse softplus in dt_bias, D = 1."""
    L, d, V, N, K = (m["n_layers"], m["d_model"], m["vocab_size"],
                     m["ssm_state"], m["d_conv"])
    di = m["expand"] * d
    H = di // m["ssm_head_dim"]
    conv_dim = di + 2 * N
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[4], (L, H), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "embed": {"table": mat(ks[0], (V, d), d)},
        "final_norm": {"scale": jnp.ones((d,), BF16)},
        "units": {"b0": {
            "norm": {"scale": jnp.ones((L, d), BF16)},
            "mamba": {
                "in_proj": mat(ks[1], (L, d, 2 * di + 2 * N + H), d),
                "conv_w": mat(ks[2], (L, K, conv_dim), K),
                "conv_b": jnp.zeros((L, conv_dim), BF16),
                "A_log": jnp.log(jax.random.uniform(ks[3], (L, H),
                                                    jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((L, H), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "gate_norm": {"scale": jnp.ones((L, di), BF16)},
                "out_proj": mat(ks[5], (L, di, d), di),
            }}},
    }


def dims(m: dict):
    di = m["expand"] * m["d_model"]
    return di, di // m["ssm_head_dim"], m["ssm_head_dim"], m["ssm_state"]


def forward_flops(m: dict, batch: int, seq: int) -> int:
    d, N, K, V, Q = (m["d_model"], m["ssm_state"], m["d_conv"],
                     m["vocab_size"], m["ssm_chunk"])
    di, H, P, _ = dims(m)
    tokens = batch * seq
    proj = 2 * d * (2 * di + 2 * N + H) + 2 * di * d
    conv = 2 * K * (di + 2 * N)
    # per token in a chunk of Q: C.B over the causal half, the gated
    # product with x over the causal half, the chunk state and its output
    ssd = Q * N + Q * H * P + 2 * H * P * N + 2 * H * P * N
    return tokens * (m["n_layers"] * (proj + conv + ssd) + 2 * V * d)


def recurrence(x, dt, A, Bm, Cm):
    """x (b, S, H, P), dt (b, S, H), A (H,), Bm/Cm (b, S, N) ->
    y (b, S, H, P), with zero initial state."""
    b, S, H, P = x.shape
    N = Bm.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (h * jnp.exp(dtt * A)[:, :, None, None]
             + (dtt[:, :, None, None] * xt[..., None]) * bt[:, None, None, :])
        return h, (h * ct[:, None, None, :]).sum(-1)

    def block(h, inp):
        return jax.lax.scan(step, h, inp, unroll=8)

    inner = min(INNER, S)

    def tm(a):      # time-major, in blocks of `inner`
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((S // inner, inner) + a.shape[1:])

    h0 = jnp.zeros((b, H, P, N), x.dtype)
    _, y = jax.lax.scan(jax.checkpoint(block), h0,
                        (tm(x), tm(dt), tm(Bm), tm(Cm)))
    return jnp.moveaxis(y.reshape((S,) + y.shape[2:]), 0, 1)


def forward(p, tokens, m, q):
    mm = matmul(q)
    B, S = tokens.shape
    d, N, K = m["d_model"], m["ssm_state"], m["d_conv"]
    di = m["expand"] * d
    P = m["ssm_head_dim"]
    H = di // P
    eps = m["norm_eps"]
    x = p["embed"]["table"][tokens] * jnp.sqrt(float(d))

    def layer(x, lp):
        mp = lp["b0"]["mamba"]
        h = rmsnorm(x, lp["b0"]["norm"]["scale"], eps)
        zxbcdt = mm("bsd,de->bse", h, mp["in_proj"])
        z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], -1)
        pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * mp["conv_w"][i] for i in range(K))
        xbc = jax.nn.silu(conv + mp["conv_b"])
        xs, Bm, Cm = jnp.split(xbc, [di, di + N], -1)
        dt = jax.nn.softplus(dt + mp["dt_bias"])
        xh = xs.reshape(B, S, H, P)
        y = recurrence(xh, dt, -jnp.exp(mp["A_log"]), Bm, Cm)
        y = (y + mp["D"][:, None] * xh).reshape(B, S, di)
        y = rmsnorm(y * jax.nn.silu(z), mp["gate_norm"]["scale"], eps)
        return x + mm("bse,ed->bsd", y, mp["out_proj"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, p["units"])
    x = rmsnorm(x, p["final_norm"]["scale"], eps)
    return mm("bsd,vd->bsv", x, p["embed"]["table"])
