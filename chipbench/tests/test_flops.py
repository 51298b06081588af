"""Closed-form counts against hand counts at the tiny sizes, and the
counts of the committed cells, read before the counts moved beside each
family's reference, held exactly."""

import json
import os

import pytest

from harness import flops
from reference import dense, ssm
from tiny import BENCH, TINY_DENSE, TINY_SSM


def test_dense_train_flops():
    # per layer: wq 64*64 + wk 64*32 + wv 64*32 + wo 64*64 + MLP 3*64*128
    # = 36,864; 2 layers + tied unembedding 256*64 = 90,112 weights
    assert dense.matmul_params(TINY_DENSE["model"]) == 90_112
    # forward at 4 x 32: 2 * 128 tokens * 90,112 = 23,068,672, plus causal
    # attention 2 layers * 2 * 4 * 32^2 * 4 heads * 16 = 1,048,576
    assert dense.forward_flops(TINY_DENSE["model"], 4, 32) == 24_117_248
    assert flops.train_step_flops(TINY_DENSE, 4, 32) == 3 * 24_117_248


def test_ssm_train_flops():
    # per token and layer: in_proj 2*64*296 + out_proj 2*128*64 = 54,272;
    # conv 2*4*160 = 1,280; SSD 16*16 + 16*8*16 + 4*8*16*16 = 10,496;
    # 2 layers + unembedding 2*256*64 = 164,864 per token; 128 tokens
    assert ssm.forward_flops(TINY_SSM["model"], 4, 32) == 21_102_592
    assert flops.train_step_flops(TINY_SSM, 4, 32) == 3 * 21_102_592


@pytest.mark.parametrize("config,want", [
    ("smollm-360m", 41_747_082_117_120),
    ("mamba2-130m", 14_100_511_850_496),
])
def test_committed_cells_train_step_flops(config, want):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        c = json.load(f)
    assert flops.train_step_flops(c, 8, 2048) == want


def test_dense_decode_step():
    # 4 slots * 2 * 90,112 + 4 * 2 layers * 4 heads * 16 * 100 live
    # positions; bytes: 2 * (90,112 + 5 norms * 64) + 100 * 2*2*2*16*2
    assert flops.dense_decode_step(TINY_DENSE["model"], 4, 100) == (
        772_096, 206_464)
