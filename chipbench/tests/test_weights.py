"""The weights the benchmark makes, held where they were before each
family's maker moved beside its reference: the bytes of the tiny
configurations from a fixed seed, and the layout of the committed
configurations, both read before the move."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

from harness import weights
from tiny import BENCH, TINY_DENSE, TINY_SSM

BF16, F32 = "bfloat16", "float32"
LAYOUT = {
    "smollm-360m": {
        "['embed']['table']": ((49152, 960), BF16),
        "['final_norm']['scale']": ((960,), BF16),
        "['units']['b0']['attn']['wk']": ((32, 960, 320), BF16),
        "['units']['b0']['attn']['wo']": ((32, 960, 960), BF16),
        "['units']['b0']['attn']['wq']": ((32, 960, 960), BF16),
        "['units']['b0']['attn']['wv']": ((32, 960, 320), BF16),
        "['units']['b0']['norm']['scale']": ((32, 960), BF16),
        "['units']['b1']['mlp']['w_down']": ((32, 2560, 960), BF16),
        "['units']['b1']['mlp']['w_gate']": ((32, 960, 2560), BF16),
        "['units']['b1']['mlp']['w_up']": ((32, 960, 2560), BF16),
        "['units']['b1']['norm']['scale']": ((32, 960), BF16),
    },
    "mamba2-130m": {
        "['embed']['table']": ((50288, 768), BF16),
        "['final_norm']['scale']": ((768,), BF16),
        "['units']['b0']['mamba']['A_log']": ((24, 24), F32),
        "['units']['b0']['mamba']['D']": ((24, 24), F32),
        "['units']['b0']['mamba']['conv_b']": ((24, 1792), BF16),
        "['units']['b0']['mamba']['conv_w']": ((24, 4, 1792), BF16),
        "['units']['b0']['mamba']['dt_bias']": ((24, 24), F32),
        "['units']['b0']['mamba']['gate_norm']['scale']": ((24, 1536), BF16),
        "['units']['b0']['mamba']['in_proj']": ((24, 768, 3352), BF16),
        "['units']['b0']['mamba']['out_proj']": ((24, 1536, 768), BF16),
        "['units']['b0']['norm']['scale']": ((24, 768), BF16),
    },
}


@pytest.mark.parametrize("config", sorted(LAYOUT))
def test_committed_layout(config):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        c = json.load(f)
    shapes = jax.eval_shape(weights.maker(c), weights.key_data(0))
    got = {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
           for k, v in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == LAYOUT[config]


@pytest.mark.parametrize("config,digest", [
    (TINY_DENSE,
     "3200823e88c9d4370e9d52dfd9d008a808d4d2e626d43f64097b13c6088e4d1c"),
    (TINY_SSM,
     "dd999b3970841d19337c2a3bc57a9bb5b56afbec8de24b662112bfe0a9f31521"),
], ids=["tiny-dense", "tiny-ssm"])
def test_weight_bytes(config, digest):
    """sha256 over each leaf's path and its bytes, in the tree's order."""
    p = jax.jit(weights.maker(config))(weights.key_data(2**40 + 3))
    h = hashlib.sha256()
    for k, v in jax.tree_util.tree_flatten_with_path(p)[0]:
        h.update(jax.tree_util.keystr(k).encode())
        h.update(np.asarray(v).tobytes())
    assert h.hexdigest() == digest
