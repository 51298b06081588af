"""The batch each architecture's model is fed in the tests: token ids,
frame/patch embeddings for `frontend="embed"`, source embeddings for an
encoder-decoder, and labels."""

import jax.numpy as jnp
import numpy as np


def arch_batch(cfg, B=2, S=32, key=0):
    rng = np.random.default_rng(key)
    batch = {}
    if cfg.is_encdec:
        batch["src_embeds"] = jnp.asarray(
            rng.standard_normal((B, 16, cfg.d_model)), jnp.bfloat16)
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    elif cfg.frontend == "embed":
        batch["embeds"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), jnp.bfloat16)
    else:
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    batch["labels"] = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    return batch
