"""The reference's reading of served tokens.

For each sampled request the reference runs once over its prompt and its
served tokens and reads, at every position where a token was served, how
far that token's logit lies below the reference's best there.  Greedy
decoding serves the program's own best, so in exact arithmetic every gap
is 0; the widest gap is the number compared.
"""

import jax
import jax.numpy as jnp
import numpy as np

from .common import quantizer, to_f32
from .train import family


def _forward_rows(config, precision):
    fwd, m, q = family(config).forward, config["model"], quantizer(precision)

    def one(p, tok):
        return fwd(p, tok[None], m, q)[0]
    return one


def gaps(config: dict, seed: int, seqs: np.ndarray, targets: np.ndarray,
         control: bool = False) -> np.ndarray:
    """seqs, targets (R, T) int: the tokens fed and, at each position, the
    token served there.  Returns (R, T) gaps under the float32 reference
    of `targets`, or with control=True of the tokens that the control's
    precision puts first at the same positions."""
    from harness import weights
    f32 = _forward_rows(config, "f32")
    low = _forward_rows(config, "fp8")

    def row(p, tok, tgt):
        if control:
            tgt = jnp.argmax(low(p, tok), -1)
        lg = f32(p, tok)
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, tgt[:, None], -1)[:, 0]

    with jax.default_matmul_precision("highest"):
        p = to_f32(jax.jit(weights.maker(config))(weights.key_data(seed)))
        run = jax.jit(lambda p, s, t: jax.lax.map(lambda a: row(p, *a),
                                                  (s, t)))
        return np.asarray(run(p, jnp.asarray(seqs), jnp.asarray(targets)))
