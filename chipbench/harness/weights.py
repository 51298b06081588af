"""Weights made from the seed, on the device, in the type they are used in.

The benchmark makes the weights itself, so that the plain reference can
be given the very same values without taking anything the program made.
The tree has the program's layout (per-layer tensors stacked on a leading
axis); `check_layout` refuses a program whose layout has moved.
"""

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16


def key_data(seed: int) -> np.ndarray:
    """The seed, which may exceed 32 bits, as threefry key data."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def _mat(key, shape, fan_in, gain=1.0):
    return (jax.random.normal(key, shape, jnp.float32)
            * (gain / np.sqrt(fan_in))).astype(BF16)


def _dense(m: dict, key, init: dict) -> dict:
    """`init["out_gain"]` scales the blocks' output projections (wo,
    w_down), which write into the residual stream; see the configuration
    file for why."""
    L, d, H, K, hd, F, V = (m["n_layers"], m["d_model"], m["n_heads"],
                            m["n_kv_heads"], m["head_dim"], m["d_ff"],
                            m["vocab_size"])
    g = init.get("out_gain", 1.0)
    ks = jax.random.split(key, 8)
    return {
        "embed": {"table": _mat(ks[0], (V, d), d)},
        "final_norm": {"scale": jnp.ones((d,), BF16)},
        "units": {
            "b0": {"norm": {"scale": jnp.ones((L, d), BF16)},
                   "attn": {"wq": _mat(ks[1], (L, d, H * hd), d),
                            "wk": _mat(ks[2], (L, d, K * hd), d),
                            "wv": _mat(ks[3], (L, d, K * hd), d),
                            "wo": _mat(ks[4], (L, H * hd, d), H * hd, g)}},
            "b1": {"norm": {"scale": jnp.ones((L, d), BF16)},
                   "mlp": {"w_gate": _mat(ks[5], (L, d, F), d),
                           "w_up": _mat(ks[6], (L, d, F), d),
                           "w_down": _mat(ks[7], (L, F, d), F, g)}},
        },
    }


def _ssm(m: dict, key, init: dict) -> dict:
    """Mamba2's published initialisation for A, dt and D (arXiv:2405.21060,
    reference code): A ~ U[1, 16], dt log-uniform in [1e-3, 1e-1] held as
    the inverse softplus in dt_bias, D = 1."""
    L, d, V, N, K = (m["n_layers"], m["d_model"], m["vocab_size"],
                     m["ssm_state"], m["d_conv"])
    di = m["expand"] * d
    H = di // m["ssm_head_dim"]
    conv_dim = di + 2 * N
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[4], (L, H), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "embed": {"table": _mat(ks[0], (V, d), d)},
        "final_norm": {"scale": jnp.ones((d,), BF16)},
        "units": {"b0": {
            "norm": {"scale": jnp.ones((L, d), BF16)},
            "mamba": {
                "in_proj": _mat(ks[1], (L, d, 2 * di + 2 * N + H), d),
                "conv_w": _mat(ks[2], (L, K, conv_dim), K),
                "conv_b": jnp.zeros((L, conv_dim), BF16),
                "A_log": jnp.log(jax.random.uniform(ks[3], (L, H),
                                                    jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((L, H), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "gate_norm": {"scale": jnp.ones((L, di), BF16)},
                "out_proj": _mat(ks[5], (L, di, d), di),
            }}},
    }


FAMILIES = {"dense": _dense, "ssm": _ssm}


def maker(config: dict):
    """fn(key_data) -> params, to be jitted."""
    build = FAMILIES[config["family"]]
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
