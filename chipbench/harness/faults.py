"""Faults planted under the timed path, to show that `correct` fails.

Used by the tests and by the readings that set the limits; a benchmark
run plants none.
"""

import jax.numpy as jnp


def train(name: str, step_fn):
    if name == "unchanged":        # the step returns its state unchanged
        return lambda state, batch: (state, step_fn(state, batch)[1])
    if name == "half_batch":       # half the rows left out
        return lambda state, batch: step_fn(
            state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    raise ValueError(f"no training fault {name!r}")


def serve(name: str, decode_step, vocab: int):
    if name == "unchanged":        # the step returns its cache unchanged
        def stale(params, cache, token, pos):
            nxt, logits, _ = decode_step(params, cache, token, pos)
            return nxt, logits, cache
        return stale
    if name == "token":            # each token altered where it is produced
        def altered(params, cache, token, pos):
            nxt, logits, cache = decode_step(params, cache, token, pos)
            return (nxt + 1) % vocab, logits, cache
        return altered
    raise ValueError(f"no serving fault {name!r}")
