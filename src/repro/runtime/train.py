"""Training step: CE loss, remat, gradient accumulation, optional gradient
compression, optimizer update.  All control flow is jax.lax; the whole
step jits to one XLA program.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.models.scopes import scope
from repro.optim.optimizers import OptimizerConfig, build_optimizer
from .compression import CompressionConfig, compress_decompress


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: OptimizerConfig = OptimizerConfig()
    microbatches: int = 1            # gradient accumulation
    aux_loss_weight: float = 0.01    # MoE load-balance loss
    z_loss_weight: float = 1e-4      # logit normalisation loss
    compression: Optional[CompressionConfig] = None
    attention_impl: str = "auto"
    remat: bool = True


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  z_loss_weight: float = 0.0
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean CE over tokens (+z-loss). logits fp32 (B,S,V), labels (B,S).

    The label pick is an iota-compare masked reduction, which stays local
    to each shard of vocab-sharded logits (+ a scalar psum);
    take_along_axis would make GSPMD all-gather them."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
           == labels[..., None])
    ll = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    ce = (lse - ll).mean()
    zl = (lse ** 2).mean()
    return ce + z_loss_weight * zl, ce


def make_loss_fn(cfg: ModelConfig, tcfg: TrainConfig):
    model = build_model(cfg, impl=tcfg.attention_impl, remat=tcfg.remat)

    def loss_fn(params, batch):
        logits, aux = model.apply(params, batch)
        with scope("head"):
            loss, ce = cross_entropy(logits, batch["labels"],
                                     tcfg.z_loss_weight)
        total = loss + tcfg.aux_loss_weight * aux
        return total, {"ce": ce, "aux": aux}

    return loss_fn


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {"params", "opt", "step"}.  With microbatches > 1 the batch's
    leading axis is split and gradients accumulate in a lax.scan (same
    math, 1/k activation memory).
    """
    loss_fn = make_loss_fn(cfg, tcfg)
    opt = build_optimizer(tcfg.optimizer)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if tcfg.microbatches <= 1:
            (loss, m), grads = grad_fn(params, batch)
            return loss, m, grads
        k = tcfg.microbatches

        def split(x):
            return x.reshape((k, x.shape[0] // k) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(carry, mb):
            acc, loss_a, ce_a, aux_a = carry
            (loss, m), g = grad_fn(params, mb)
            acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32) / k, acc, g)
            return (acc, loss_a + loss / k, ce_a + m["ce"] / k,
                    aux_a + m["aux"] / k), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (grads, loss, ce, aux), _ = jax.lax.scan(
            body, (zeros, 0.0, 0.0, 0.0), micro)
        return loss, {"ce": ce, "aux": aux}, grads

    def train_step(state, batch):
        params, opt_state, step = state["params"], state["opt"], state["step"]
        loss, metrics, grads = compute_grads(params, batch)
        with scope("optimizer"):
            if tcfg.compression is not None:
                grads = compress_decompress(grads, tcfg.compression)
            new_params, new_opt = opt.update(grads, opt_state, params, step)
        metrics = dict(metrics, loss=loss)
        return {"params": new_params, "opt": new_opt, "step": step + 1}, \
            metrics

    def init_state(key):
        model = build_model(cfg, impl=tcfg.attention_impl, remat=tcfg.remat)
        params = model.init(key)
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    return train_step, init_state
