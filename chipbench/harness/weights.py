"""Weights made from the seed, on the device, in the type they are used in.

The benchmark makes the weights itself, so that the plain reference can
be given the very same values without taking anything the program made.
Each family's `weights(m, key, init)` sits beside its reference,
`reference/<config["reference"]>.py`.  The tree has the program's layout
(per-layer tensors stacked on a leading axis); `check_layout` refuses a
program whose layout has moved.
"""

import jax
import numpy as np

from reference.train import family


def key_data(seed: int) -> np.ndarray:
    """The seed, which may exceed 32 bits, as threefry key data."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def maker(config: dict):
    """fn(key_data) -> params, to be jitted."""
    build = family(config).weights
    model, init = config["model"], config.get("init", {})

    def make(kd):
        return build(model, jax.random.wrap_key_data(kd), init)
    return make


def check_layout(ours, program) -> None:
    """Raise where the program's parameter tree differs from ours."""
    a = {jax.tree_util.keystr(k): (v.shape, v.dtype)
         for k, v in jax.tree_util.tree_flatten_with_path(ours)[0]}
    b = {jax.tree_util.keystr(k): (v.shape, v.dtype)
         for k, v in jax.tree_util.tree_flatten_with_path(program)[0]}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise ValueError(f"the program's parameter layout differs from the "
                         f"benchmark's: {diff[:6]}")
