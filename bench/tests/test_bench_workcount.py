"""Work counts of both configurations against sums written out by hand
from the published sizes (batch 2, prompt 128, 16 new tokens)."""
import json
import os

import pytest

import reference

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def load(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_qwen3_1_7b():
    w = reference.family("qwen3").work(load("qwen3-1.7b"), 2, 128, 16)
    # per layer: q and o 2048x2048, k and v 2048x1024, MLP 3 x 2048x6144
    layers = 28 * (2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144)
    head = 2048 * 151936
    kv_row = 28 * 2 * 8 * 128 * 2          # K and V of one position, bf16
    attn = 2 * 2 * 28 * 16 * 128           # QK^T and PV per key and query
    assert w["prefill_flops"] == (2 * layers * 256 + attn * (128 * 129 // 2) * 2
                                  + 2 * head * 2)
    assert w["prefill_bytes"] == (layers + head) * 2 + 256 * 2048 * 2 \
        + 256 * kv_row
    ctx = 128 + 17 / 2
    assert w["token_flops"] == pytest.approx(2 * layers + attn * ctx + 2 * head)
    assert w["token_bytes"] == pytest.approx(
        (layers + head) * 2 / 2 + 2048 * 2 + ctx * kv_row + kv_row)
    # one decoded token's share of the weights is most of its bytes
    assert 1.7e9 < w["token_bytes"] < 1.8e9


def test_xlstm_350m():
    w = reference.family("xlstm").work(load("xlstm-350m"), 2, 128, 16)
    d, dm, H, dh, dsf = 1024, 2048, 4, 512, 1365
    pair = (d * 2 * dm + 3 * dm * dm + 2 * dm * H + dm * d     # mLSTM
            + 2 * d * 4 * d + 2 * d * dsf + dsf * d)           # sLSTM
    head = d * 50304
    state = 12 * 4 * (H * dh * dh + H * dh + H + 4 * d)
    assert w["prefill_flops"] == (2 * 12 * pair * 256
                                  + 2 * 2 * 12 * H * dh * (128 * 129 // 2) * 2
                                  + 2 * head * 2)
    assert w["prefill_bytes"] == (12 * pair + head) * 2 + 256 * d * 2 \
        + 2 * state
    assert w["token_flops"] == 2 * 12 * pair + 4 * 12 * H * dh * dh + 2 * head
    assert w["token_bytes"] == (12 * pair + head) * 2 / 2 + d * 2 + 2 * state
    # the matrix memory read and written is a fifth of a token's bytes
    assert 0.15 < 2 * state / w["token_bytes"] < 0.25
