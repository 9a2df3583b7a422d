"""The FLOP and byte counts equal numbers worked by hand for both configurations."""

import json
import os

import pytest
from benchmark_testlib import REPO

from benchmarks import counts


def config(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as fh:
        return json.load(fh)


# by hand, gpt2-medium: a block multiplies by 4 x 1024^2 + 2 x 1024 x 4096 = 12,582,912 weights;
# 24 blocks = 301,989,888; the head 1024 x 50304 = 51,511,296; together 353,501,184.
# gpt2-large: 4 x 1280^2 + 2 x 1280 x 5120 = 19,660,800; x 36 = 707,788,800; head 64,389,120.
@pytest.mark.parametrize("name,matmul,total", [
    ("gpt2-medium", 353_501_184, 406_284_288),
    ("gpt2-large", 772_177_920, 838_295_040),
])
def test_parameter_counts(name, matmul, total):
    cfg = config(name)
    assert counts.matmul_params(cfg) == matmul
    # + embedding (50304 x d), positions (1024 x d), LayerNorms (2 x 2d a block + 2d), biases (5d a block)
    assert counts.total_params(cfg) == total


def test_training_flops_of_gpt2_medium_at_8_by_1024():
    cfg = config("gpt2-medium")
    # attention: 6 matmuls x 2 FLOPs x 8 x 1024^2 x 1024 x 24 layers / 2 (causal) = 1.2369e12
    assert counts.attention_train_flops(cfg, 8, 1024) == 6 * 2 * 8 * 1024**2 * 1024 * 24 / 2
    step = counts.train_step_flops(cfg, 8, 1024)
    assert step == 6 * 353_501_184 * 8192 + 1_236_950_581_248
    assert abs(step / 8192 - 2.272e9) < 1e6  # the issue's 2.27 GFLOP a token


def test_serving_flops_and_bytes_of_gpt2_large():
    cfg = config("gpt2-large")
    # one decoded token over 300 cached positions: 2 x 772,177,920 + 4 x 300 x 1280 x 36
    assert counts.decode_flops(cfg, 300) == 2 * 772_177_920 + 4 * 300 * 1280 * 36
    # a 128-token prompt: blocks over every token, attention 2 x 128^2 x 1280 x 36, head once
    assert counts.prefill_flops(cfg, 128) == (2 * 707_788_800 * 128 + 2 * 128**2 * 1280 * 36
                                              + 2 * 1280 * 50304)
    # a cached position: K and V, 1280 wide, 36 layers, bf16 = 184,320 bytes (the issue's 184 KB)
    assert counts.kv_row_bytes(cfg, 2) == 184_320
    # weights streamed once a step: matmul weights + biases and LayerNorms (36 x 11,520 + 2,560), bf16
    assert counts.weight_stream_bytes(cfg, 2) == 2 * (772_177_920 + 36 * 11_520 + 2_560)
    assert counts.decode_step_bytes(cfg, 1000) == counts.weight_stream_bytes(cfg, 2) + 184_320_000
