"""Operations and bytes the algorithm needs, in closed form from the sizes.

Kept with the benchmark so that the yardstick does not move with the
program.  Matrix products count 2 operations per multiply-add; elementwise
work, norms and softmax are not counted.  A training step counts the
forward pass and twice it for the backward pass; recomputation under remat
is not counted.  Causal attention and the SSD chunk products count the
half of the square they need.

Each family's forward count, `forward_flops(m, batch, seq)`, sits beside
its plain reference, `reference/<config["reference"]>.py`.  The decode
counts below are the dense decoder's, the one family that has a serving
path.
"""

from reference.dense import matmul_params
from reference.train import family


def train_step_flops(config: dict, batch: int, seq: int) -> int:
    """The family's `forward_flops`, beside its reference, times 3."""
    return 3 * family(config).forward_flops(config["model"], batch, seq)


def dense_param_bytes(m: dict) -> int:
    """bf16 bytes of every weight a decode step reads."""
    norms = (2 * m["n_layers"] + 1) * m["d_model"]
    return 2 * (matmul_params(m) + norms)


def kv_bytes_per_token(m: dict) -> int:
    """bf16 K and V of every layer for one cached position."""
    return 2 * 2 * m["n_kv_heads"] * m["head_dim"] * m["n_layers"]


def dense_decode_step(m: dict, slots: int, live: int) -> tuple:
    """(FLOPs, bytes) of one decode step over `slots` sequences that hold
    `live` cached positions between them, the new tokens' included: each
    slot's matrix products and its attention over its own positions; the
    weights once and the live KV entries once."""
    flops = (2 * slots * matmul_params(m)
             + 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * live)
    nbytes = dense_param_bytes(m) + kv_bytes_per_token(m) * live
    return flops, nbytes
