"""Operations and bytes the algorithm needs, in closed form from the sizes.

Kept with the benchmark so that the yardstick does not move with the
program.  Matrix products count 2 operations per multiply-add; elementwise
work, norms and softmax are not counted.  A training step counts the
forward pass and twice it for the backward pass; recomputation under remat
is not counted.  Causal attention and the SSD chunk products count the
half of the square they need.
"""


def dense_matmul_params(m: dict) -> int:
    """Weights that take part in a matrix product for every token,
    including the tied unembedding."""
    d, H, K, hd, F, V = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"], m["vocab_size"])
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * F
    return m["n_layers"] * per_layer + V * d


def dense_forward_flops(m: dict, batch: int, seq: int) -> int:
    tokens = batch * seq
    attn = 2 * batch * seq * seq * m["n_heads"] * m["head_dim"]  # causal
    return 2 * tokens * dense_matmul_params(m) + m["n_layers"] * attn


def ssm_dims(m: dict):
    di = m["expand"] * m["d_model"]
    return di, di // m["ssm_head_dim"], m["ssm_head_dim"], m["ssm_state"]


def ssm_forward_flops(m: dict, batch: int, seq: int) -> int:
    d, N, K, V, Q = (m["d_model"], m["ssm_state"], m["d_conv"],
                     m["vocab_size"], m["ssm_chunk"])
    di, H, P, _ = ssm_dims(m)
    tokens = batch * seq
    proj = 2 * d * (2 * di + 2 * N + H) + 2 * di * d
    conv = 2 * K * (di + 2 * N)
    # per token in a chunk of Q: C.B over the causal half, the gated
    # product with x over the causal half, the chunk state and its output
    ssd = Q * N + Q * H * P + 2 * H * P * N + 2 * H * P * N
    return tokens * (m["n_layers"] * (proj + conv + ssd) + 2 * V * d)


FORWARD = {"dense": dense_forward_flops, "ssm": ssm_forward_flops}


def train_step_flops(config: dict, batch: int, seq: int) -> int:
    return 3 * FORWARD[config["family"]](config["model"], batch, seq)


def dense_param_bytes(m: dict) -> int:
    """bf16 bytes of every weight a decode step reads."""
    norms = (2 * m["n_layers"] + 1) * m["d_model"]
    return 2 * (dense_matmul_params(m) + norms)


def kv_bytes_per_token(m: dict) -> int:
    """bf16 K and V of every layer for one cached position."""
    return 2 * 2 * m["n_kv_heads"] * m["head_dim"] * m["n_layers"]


def dense_decode_step(m: dict, slots: int, live: int) -> tuple:
    """(FLOPs, bytes) of one decode step over `slots` sequences that hold
    `live` cached positions between them, the new tokens' included: each
    slot's matrix products and its attention over its own positions; the
    weights once and the live KV entries once."""
    flops = (2 * slots * dense_matmul_params(m)
             + 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * live)
    nbytes = dense_param_bytes(m) + kv_bytes_per_token(m) * live
    return flops, nbytes
