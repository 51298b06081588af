"""The reference's first training steps and the numbers they are compared by.

Loss: mean cross-entropy plus z_loss_weight * mean(logsumexp^2), as the
mix states.  Optimizer: AdamW as the mix states it (global-norm clipping,
linear warm-up then cosine decay, bias correction, decoupled weight decay
on every stored tensor of rank 2 or more), moments in float32, and each
parameter stored back in the type it is held in (bfloat16 for matrices and
norm scales, float32 where the weights are made in float32).  Gradients are
computed in float32 at the highest matmul precision, in blocks of rows.

The reference runs over the cell's chips, placed by a rule of its own
(`shardings`), not the program's: each tensor it keeps split along its
largest axis that the number of chips divides, the batch on every chip;
jit and GSPMD place the rest.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .common import quantizer, store, to_f32


def family(config: dict):
    return importlib.import_module(__package__ + "." + config["reference"])


def leaf_norms(tree) -> dict:
    """Frobenius norm of each leaf, by path."""
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(norms(tree)))
    return {jax.tree_util.keystr(k): float(v) for k, v in flat[0]}


def shardings(tree, mesh: Mesh):
    """For each leaf of `tree` (arrays or shapes): split over the mesh's
    one axis along the leaf's largest axis that the axis's size divides
    (the first of equals), else held whole on every device."""
    n = mesh.devices.size

    def one(x):
        fits = [i for i, k in enumerate(x.shape) if k % n == 0]
        spec = [None] * len(x.shape)
        if fits:
            spec[max(fits, key=lambda i: x.shape[i])] = "x"
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(one, tree)


def lr_at(o: dict, step: int) -> float:
    warm = min((step + 1.0) / max(o["warmup_steps"], 1), 1.0)
    prog = min(max((step - o["warmup_steps"])
                   / max(1, o["total_steps"] - o["warmup_steps"]), 0.0), 1.0)
    return o["lr"] * warm * 0.5 * (1.0 + np.cos(np.pi * prog))


def make_grad_fn(config: dict, z_weight: float, rows: int, precision: str,
                 out_shardings):
    fwd, m, q = family(config).forward, config["model"], quantizer(precision)

    def block_loss(p, tok, lab):
        logits = fwd(p, tok, m, q)
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, lab[..., None], -1)[..., 0]
        return jnp.sum(lse - ll + z_weight * lse * lse)

    g = jax.value_and_grad(block_loss)

    def loss_and_grads(p, tokens, labels):
        B, S = tokens.shape
        r = min(rows, B)
        blocks = (tokens.reshape(B // r, r, S), labels.reshape(B // r, r, S))

        def body(acc, blk):
            v, gr = g(p, *blk)
            return (acc[0] + v, jax.tree.map(jnp.add, acc[1], gr)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (v, gr), _ = jax.lax.scan(body, zero, blocks)
        n = B * S
        return v / n, jax.tree.map(lambda x: x / n, gr)

    return jax.jit(loss_and_grads, out_shardings=out_shardings)


def adamw(o: dict, dtypes, p, g, mu, nu, lr, bc1, bc2):
    leaves = jax.tree.leaves(g)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, o["clip_norm"]
                                               / jnp.maximum(gn, 1e-12)), g)
    mu = jax.tree.map(lambda m_, x: o["b1"] * m_ + (1 - o["b1"]) * x, mu, g)
    nu = jax.tree.map(lambda v, x: o["b2"] * v + (1 - o["b2"]) * x * x, nu, g)

    def upd(x, m_, v, dt):
        u = (m_ / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
        if x.ndim >= 2:
            u = u + o["weight_decay"] * x
        return store(x - lr * u, dt)

    p = jax.tree.map(upd, p, mu, nu, dtypes)
    return p, g, mu, nu


def readings(config: dict, traffic: dict, seed: int, n_steps: int,
             devices: list, precision: str = "f32", rows: int = 2,
             half_batch=False) -> dict:
    """Losses of the first n_steps, per-leaf norms of the first (clipped)
    gradient and of the parameters' change over the n_steps, computed over
    `devices`."""
    from harness import traffic as gen, weights
    o = traffic["optimizer"]
    V = config["model"]["vocab_size"]
    mesh = Mesh(np.array(devices), ("x",))
    whole = NamedSharding(mesh, P())
    make, kd = weights.maker(config), weights.key_data(seed)
    sh = shardings(jax.eval_shape(make, kd), mesh)
    with jax.default_matmul_precision("highest"):
        made = jax.jit(make, out_shardings=sh)(kd)
        dtypes = jax.tree.map(lambda x: x.dtype, made)
        p0 = to_f32(made)
        del made
        grads = make_grad_fn(config, traffic["z_loss_weight"], rows,
                             precision, (whole, sh))
        step = jax.jit(lambda *a: adamw(o, dtypes, *a),
                       out_shardings=(sh,) * 4)
        p = p0
        mu = nu = jax.tree.map(jnp.zeros_like, p0)
        losses, first = [], None
        for s in range(n_steps):
            b = gen.train_batch(traffic, V, seed, s)
            tok, lab = b["tokens"], b["labels"]
            if half_batch:
                tok, lab = tok[:len(tok) // 2], lab[:len(lab) // 2]
            loss, g = grads(p, jax.device_put(tok, whole),
                            jax.device_put(lab, whole))
            p, g, mu, nu = step(p, g, mu, nu, lr_at(o, s),
                                1.0 - o["b1"] ** (s + 1.0),
                                1.0 - o["b2"] ** (s + 1.0))
            losses.append(float(loss))
            if s == 0:
                first = leaf_norms(g)
            del g
        change = leaf_norms(jax.tree.map(jnp.subtract, p, p0))
    return {"loss": losses, "grad": first, "change": change}
