"""Plain reference of the dense decoder: GQA with RoPE, SwiGLU MLP,
pre-RMSNorm, tied embeddings (Llama architecture, as SmolLM publishes it).

Written from the published description in float32 with no cache, no
kernels and full softmax.  Where the program departs from the published
architecture the reference follows the program, so that the comparison
is of the arithmetic, and the configuration file names the departure:
the embedding is scaled by sqrt(d_model).  RoPE rotates adjacent pairs of
dimensions, as Meta's original Llama code does (Hugging Face's
rotate-half form is the same up to a fixed permutation of q/k columns).

Beside the forward pass: `weights`, the tree the benchmark makes from the
seed in the program's layout, and `forward_flops`, the forward pass's
operations in closed form (conventions in harness/flops.py).
"""

import jax
import jax.numpy as jnp

from .common import BF16, mat, matmul, rmsnorm


def weights(m: dict, key, init: dict) -> dict:
    """`init["out_gain"]` scales the blocks' output projections (wo,
    w_down), which write into the residual stream; see the configuration
    file for why."""
    L, d, H, K, hd, F, V = (m["n_layers"], m["d_model"], m["n_heads"],
                            m["n_kv_heads"], m["head_dim"], m["d_ff"],
                            m["vocab_size"])
    g = init.get("out_gain", 1.0)
    ks = jax.random.split(key, 8)
    return {
        "embed": {"table": mat(ks[0], (V, d), d)},
        "final_norm": {"scale": jnp.ones((d,), BF16)},
        "units": {
            "b0": {"norm": {"scale": jnp.ones((L, d), BF16)},
                   "attn": {"wq": mat(ks[1], (L, d, H * hd), d),
                            "wk": mat(ks[2], (L, d, K * hd), d),
                            "wv": mat(ks[3], (L, d, K * hd), d),
                            "wo": mat(ks[4], (L, H * hd, d), H * hd, g)}},
            "b1": {"norm": {"scale": jnp.ones((L, d), BF16)},
                   "mlp": {"w_gate": mat(ks[5], (L, d, F), d),
                           "w_up": mat(ks[6], (L, d, F), d),
                           "w_down": mat(ks[7], (L, F, d), F, g)}},
        },
    }


def matmul_params(m: dict) -> int:
    """Weights that take part in a matrix product for every token,
    including the tied unembedding."""
    d, H, K, hd, F, V = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"], m["vocab_size"])
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * F
    return m["n_layers"] * per_layer + V * d


def forward_flops(m: dict, batch: int, seq: int) -> int:
    tokens = batch * seq
    attn = 2 * batch * seq * seq * m["n_heads"] * m["head_dim"]  # causal
    return 2 * tokens * matmul_params(m) + m["n_layers"] * attn


def forward(p, tokens, m, q):
    """tokens (B, S) int -> logits (B, S, V) float32; p in float32."""
    mm = matmul(q)
    B, S = tokens.shape
    d, H, K, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    x = p["embed"]["table"][tokens] * jnp.sqrt(float(d))
    inv = 1.0 / m["rope_theta"] ** (jnp.arange(0, hd, 2) / hd)
    ang = jnp.arange(S)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def rope(t):
        t1, t2 = t[..., 0::2], t[..., 1::2]
        return jnp.stack([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                         -1).reshape(t.shape)

    def layer(x, lp):
        a, f = lp["b0"], lp["b1"]
        h = rmsnorm(x, a["norm"]["scale"], eps)
        qh = rope(mm("bsd,de->bse", h, a["attn"]["wq"]).reshape(B, S, H, hd))
        kh = rope(mm("bsd,de->bse", h, a["attn"]["wk"]).reshape(B, S, K, hd))
        vh = mm("bsd,de->bse", h, a["attn"]["wv"]).reshape(B, S, K, hd)
        kh, vh = jnp.repeat(kh, H // K, 2), jnp.repeat(vh, H // K, 2)
        s = mm("bshd,bthd->bhst", qh, kh) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = mm("bhst,bthd->bshd", w, vh).reshape(B, S, H * hd)
        x = x + mm("bse,ed->bsd", o, a["attn"]["wo"])
        h = rmsnorm(x, f["norm"]["scale"], eps)
        g = jax.nn.silu(mm("bsd,df->bsf", h, f["mlp"]["w_gate"]))
        u = mm("bsd,df->bsf", h, f["mlp"]["w_up"])
        return x + mm("bsf,fd->bsd", g * u, f["mlp"]["w_down"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, p["units"])
    x = rmsnorm(x, p["final_norm"]["scale"], eps)
    return mm("bsd,vd->bsv", x, p["embed"]["table"])
