"""Plain reference of the dense decoder: GQA with RoPE, SwiGLU MLP,
pre-RMSNorm, tied embeddings (Llama architecture, as SmolLM publishes it).

Written from the published description in float32 with no cache, no
kernels and full softmax.  Where the program departs from the published
architecture the reference follows the program, so that the comparison
is of the arithmetic, and the configuration file names the departure:
the embedding is scaled by sqrt(d_model).  RoPE rotates adjacent pairs of
dimensions, as Meta's original Llama code does (Hugging Face's
rotate-half form is the same up to a fixed permutation of q/k columns).
"""

import jax
import jax.numpy as jnp

from .common import matmul, rmsnorm


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
