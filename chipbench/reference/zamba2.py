"""Plain reference of the Zamba2 hybrid decoder (arXiv:2411.15242, as
Zyphra/Zamba2-7B-Instruct's config.json and transformers'
`Zamba2ForCausalLM` describe it).

Every layer l holds a Mamba2 mixer with G groups of B and C: in_proj to
[z, x, B, C, dt], a depthwise causal conv with bias and SiLU over
[x, B, C], dt = softplus(dt + dt_bias) with no clamp (the fused path's
`time_step_limit: null`; transformers' slow torch path clamps dt at
`time_step_min`), the selective recurrence run token by token for each
group over its own heads (`ssm.recurrence`, mapped over the groups by
`vmap`: head h reads group h // (H/G)), the skip D, an RMSNorm of
y * silu(z) over each group's d_inner/G channels, out_proj.  A plain
layer is x + Mamba_l(RMSNorm_l(x)).

Before each layer of `hybrid_layer_ids` the k-th such layer runs shared
block b = k mod num_mem_blocks on [x, x0], x0 the embedding output:

    h = RMSNorm_b,in([x, x0]);  a = o(Attn(q(h), k(h), v(h)))
    [g; v] = RMSNorm_b,ff(a) @ W_gu,b + (RMSNorm_b,ff(a) @ A_k) @ B_k
    t = (gelu_erf(g) * v) @ W_down,b
    x = x + Mamba_l(RMSNorm_l(x + t @ W_k))

with causal softmax scaled by softmax_scale_dim^-1/2 and RoPE over the
whole head; no residual inside the block.  The end is the final RMSNorm
and the tied unembedding; the embedding is not scaled.  RoPE rotates
adjacent pairs of dimensions as the program does (transformers' rotate-
half form is the same up to a fixed permutation of q/k columns).
Attention runs in blocks of query rows, so that 4096 positions fit.

Beside the forward pass: `weights`, the tree the benchmark makes from the
seed in the program's layout, and `forward_flops`, the forward pass's
operations in closed form (conventions in harness/flops.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

from .common import BF16, mat, matmul, rmsnorm
from .ssm import recurrence

Q_BLOCK = 512       # query rows per block of the attention


def dims(m: dict):
    """d_inner, SSM heads, head width, state, groups."""
    di = m["expand"] * m["d_model"]
    return (di, di // m["ssm_head_dim"], m["ssm_head_dim"], m["ssm_state"],
            m["ssm_groups"])


def weights(m: dict, key, init: dict) -> dict:
    """Every matrix normal / sqrt(fan_in), conv bias 0, norm scales 1;
    A, dt and D as Mamba2 initialises them (reference/ssm.py)."""
    L, d, V, K, F, r = (m["n_layers"], m["d_model"], m["vocab_size"],
                        m["d_conv"], m["d_ff"], m["adapter_rank"])
    di, H, _, N, G = dims(m)
    d_in, hq = m["attn_input_dim"], m["n_heads"] * m["head_dim"]
    hkv = m["n_kv_heads"] * m["head_dim"]
    conv_dim = di + 2 * G * N
    ks = jax.random.split(key, 8)
    dt = jnp.exp(jax.random.uniform(ks[4], (L, H), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)

    def block(k):
        kq, kk, kv, ko, kg, ku, kd = jax.random.split(k, 7)
        return {"norm_in": {"scale": jnp.ones((d_in,), BF16)},
                "attn": {"wq": mat(kq, (d_in, hq), d_in),
                         "wk": mat(kk, (d_in, hkv), d_in),
                         "wv": mat(kv, (d_in, hkv), d_in),
                         "wo": mat(ko, (hq, d), hq)},
                "norm_ff": {"scale": jnp.ones((d,), BF16)},
                "mlp": {"w_gate": mat(kg, (d, F), d),
                        "w_up": mat(ku, (d, F), d),
                        "w_down": mat(kd, (F, d), F)}}

    def hybrid(k):
        kl, ka, kg, ku = jax.random.split(k, 4)
        return {"linear": mat(kl, (d, d), d),
                "adapter": {"adapter_in": mat(ka, (d, r), d),
                            "adapter_gate": mat(kg, (r, F), r),
                            "adapter_up": mat(ku, (r, F), r)}}

    return {
        "embed": {"table": mat(ks[0], (V, d), d)},
        "final_norm": {"scale": jnp.ones((d,), BF16)},
        "units": {"b0": {
            "norm": {"scale": jnp.ones((L, d), BF16)},
            "mamba": {
                "in_proj": mat(ks[1], (L, d, 2 * di + 2 * G * N + H), d),
                "conv_w": mat(ks[2], (L, K, conv_dim), K),
                "conv_b": jnp.zeros((L, conv_dim), BF16),
                "A_log": jnp.log(jax.random.uniform(ks[3], (L, H),
                                                    jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((L, H), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "gate_norm": {"scale": jnp.ones((L, di), BF16)},
                "out_proj": mat(ks[5], (L, di, d), di),
            }}},
        "shared": [block(k) for k in
                   jax.random.split(ks[6], m["num_mem_blocks"])],
        "hybrid": [hybrid(k) for k in
                   jax.random.split(ks[7], len(m["hybrid_layer_ids"]))],
    }


def forward_flops(m: dict, batch: int, seq: int) -> int:
    d, K, V, Q, F, r = (m["d_model"], m["d_conv"], m["vocab_size"],
                        m["ssm_chunk"], m["d_ff"], m["adapter_rank"])
    di, H, P, N, G = dims(m)
    d_in, hq = m["attn_input_dim"], m["n_heads"] * m["head_dim"]
    hkv = m["n_kv_heads"] * m["head_dim"]
    tokens = batch * seq
    proj = 2 * d * (2 * di + 2 * G * N + H) + 2 * di * d
    conv = 2 * K * (di + 2 * G * N)
    # per token in a chunk of Q: each group's C.B over the causal half,
    # the gated product with x over the causal half, the chunk state and
    # its output
    ssd = G * Q * N + Q * H * P + 2 * H * P * N + 2 * H * P * N
    # per token: q, k, v and o; the causal scores and their product with v
    # over half the positions; gate, up and down; the adapter; `linear`
    attn = 2 * d_in * (hq + 2 * hkv) + 2 * hq * d + 2 * seq * hq
    ffn = 2 * 3 * d * F + 2 * r * (d + 2 * F)
    hybrid = attn + ffn + 2 * d * d
    return tokens * (m["n_layers"] * (proj + conv + ssd)
                     + len(m["hybrid_layer_ids"]) * hybrid + 2 * V * d)


def mixer(mp, h, m, mm):
    """The Mamba2 mixer of one layer: (B, S, d) -> (B, S, d)."""
    B, S, _ = h.shape
    di, H, P, N, G = dims(m)
    K, eps, Hg = m["d_conv"], m["norm_eps"], H // G
    zxbcdt = mm("bsd,de->bse", h, mp["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], -1)
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * mp["conv_w"][i] for i in range(K))
    xbc = jax.nn.silu(conv + mp["conv_b"])
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], -1)
    Bm, Cm = Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N)
    dt = jax.nn.softplus(dt + mp["dt_bias"])
    A = -jnp.exp(mp["A_log"])
    xh = xs.reshape(B, S, H, P)
    y = jax.vmap(recurrence, in_axes=(2, 2, 0, 2, 2), out_axes=2)(
        xh.reshape(B, S, G, Hg, P), dt.reshape(B, S, G, Hg),
        A.reshape(G, Hg), Bm, Cm).reshape(B, S, H, P)
    y = (y + mp["D"][:, None] * xh).reshape(B, S, G, di // G)
    z = z.reshape(B, S, G, di // G)
    scale = mp["gate_norm"]["scale"].reshape(G, di // G)
    y = rmsnorm(y * jax.nn.silu(z), scale, eps).reshape(B, S, di)
    return mm("bse,ed->bsd", y, mp["out_proj"])


def attention(ap, h, m, mm):
    """Causal attention over [x, x0]'s norm, in blocks of query rows."""
    B, S, _ = h.shape
    H, Kh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    scale = m["softmax_scale_dim"] ** -0.5
    inv = 1.0 / m["rope_theta"] ** (jnp.arange(0, hd, 2) / hd)
    ang = jnp.arange(S)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):
        t1, t2 = t[..., 0::2], t[..., 1::2]
        return jnp.stack([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                         -1).reshape(t.shape)

    qh = rope(mm("bsd,de->bse", h, ap["wq"]).reshape(B, S, H, hd))
    kh = rope(mm("bsd,de->bse", h, ap["wk"]).reshape(B, S, Kh, hd))
    vh = mm("bsd,de->bse", h, ap["wv"]).reshape(B, S, Kh, hd)
    kh, vh = jnp.repeat(kh, H // Kh, 2), jnp.repeat(vh, H // Kh, 2)
    blk = min(Q_BLOCK, S)

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(qh, i * blk, blk, 1)
        s = mm("bqhd,bkhd->bhqk", qb, kh) * scale
        seen = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(S)[None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm("bhqk,bkhd->bqhd", w, vh)

    o = jax.lax.map(jax.checkpoint(rows), jnp.arange(S // blk))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * hd)
    return mm("bse,ed->bsd", o, ap["wo"])


def forward(p, tokens, m, q):
    """tokens (B, S) int -> logits (B, S, V) float32; p in float32."""
    mm = matmul(q)
    eps, M = m["norm_eps"], m["num_mem_blocks"]
    x0 = p["embed"]["table"][tokens]

    def plain(x, lp):
        h = rmsnorm(x, lp["b0"]["norm"]["scale"], eps)
        return x + mixer(lp["b0"]["mamba"], h, m, mm), None

    def hybrid(x, x0, blk, hyb, lp):
        h = rmsnorm(jnp.concatenate([x, x0], -1), blk["norm_in"]["scale"],
                    eps)
        a = rmsnorm(attention(blk["attn"], h, m, mm),
                    blk["norm_ff"]["scale"], eps)
        ad, f = hyb["adapter"], blk["mlp"]
        r = mm("bsd,dr->bsr", a, ad["adapter_in"])
        g = (mm("bsd,df->bsf", a, f["w_gate"])
             + mm("bsr,rf->bsf", r, ad["adapter_gate"]))
        v = (mm("bsd,df->bsf", a, f["w_up"])
             + mm("bsr,rf->bsf", r, ad["adapter_up"]))
        t = mm("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * v,
               f["w_down"])
        xt = x + mm("bsd,de->bse", t, hyb["linear"])
        h = rmsnorm(xt, lp["b0"]["norm"]["scale"], eps)
        return x + mixer(lp["b0"]["mamba"], h, m, mm)

    x, start = x0, 0
    for k, layer in enumerate([*m["hybrid_layer_ids"], m["n_layers"]]):
        if layer > start:
            x, _ = jax.lax.scan(jax.checkpoint(plain), x, jax.tree.map(
                lambda a: a[start:layer], p["units"]))
        if layer < m["n_layers"]:
            lp = jax.tree.map(lambda a: a[layer], p["units"])
            x = jax.checkpoint(hybrid)(x, x0, p["shared"][k % M],
                                       p["hybrid"][k], lp)
        start = layer + 1
    x = rmsnorm(x, p["final_norm"]["scale"], eps)
    return mm("bsd,vd->bsv", x, p["embed"]["table"])
