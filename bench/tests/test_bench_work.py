"""Operation and byte counts from shapes, against hand counts at the two
configurations' published widths."""

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the import path)

import json
import os

import pytest

from benchlib import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dims(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as fh:
        return work.Dims.of(json.load(fh)["model"])


@pytest.mark.parametrize("name,layer,kv_token,weights", [
    # q 576x576, k and v 576x192, o 576x576, SwiGLU 3 x 576x1536
    ("smollm-135m", 576 * 576 * 2 + 2 * 576 * 192 + 3 * 576 * 1536,
     30 * 3 * (2 * 64 + 8), 2 * (30 * (3538944 + 2 * 576) + 576 * 49152 + 576)),
    # MHA: q, k, v, o all 2304x2304; SwiGLU 3 x 2304x5760
    ("minicpm-2b", 4 * 2304 * 2304 + 3 * 2304 * 5760,
     40 * 36 * (2 * 64 + 8), 2 * (40 * (61046784 + 2 * 2304) + 2304 * 122753 + 2304)),
])
def test_counts_at_published_widths(name, layer, kv_token, weights):
    m = dims(name)
    assert work.layer_params(m) == layer
    assert work.kv_bytes_per_token(m) == kv_token
    assert work.weight_bytes(m) == weights


def test_minicpm_numbers_the_design_quotes():
    m = dims("minicpm-2b")
    assert work.kv_bytes_per_token(m) == 195840           # 195.8 KB a token
    assert abs(work.weight_bytes(m) / 1e9 - 5.45) < 0.01  # 5.45 GB bf16


def test_decode_attention_counts_by_hand():
    m = dims("minicpm-2b")
    # 1000 live tokens x 36 KV heads x (64 K + 64 V codes + 4 + 4 scale bytes),
    # bf16 q (36x64x2), f32 out (36x64x4), 16-entry block table, position
    assert work.decode_attention_bytes(m, 1000, 16) == (
        1000 * 36 * 136 + 36 * 64 * 2 + 36 * 64 * 4 + 16 * 4 + 4)
    assert work.decode_attention_flops(m, 1000) == 4 * 1000 * 36 * 64
    s = dims("smollm-135m")
    assert work.attention_flops(s, 10) == 4 * 10 * 9 * 64 * 30


def test_token_flops_count_the_head_only_when_asked():
    m = dims("smollm-135m")
    body = 2 * 30 * work.layer_params(m)
    assert work.token_matmul_flops(m, head=False) == body
    assert work.token_matmul_flops(m, head=True) == body + 2 * 576 * 49152
