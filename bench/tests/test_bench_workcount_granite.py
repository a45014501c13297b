"""Work counts of granite-4.0-h-micro against sums written out by hand
from the published sizes (batch 2, prompt 512, 16 new tokens)."""
import json
import os

import pytest

import reference

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def load(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


# 4 attention layers: q and o 2048x2048, k and v 2048x512 (8 heads of 64)
ATTN = 4 * (2 * 2048 * 2048 + 2 * 2048 * 512)
# 36 Mamba-2 layers: in_proj 2048 x (z 4096 + xBC 4352 + dt 64), out_proj
MAMBA = 36 * (2048 * (4096 + 4352 + 64) + 4096 * 2048)
MLP = 40 * 3 * 2048 * 8192
HEAD = 2048 * 100352                     # tied: the embedding
# conv weights and bias over 4352 channels, dt_bias, A_log, D, the gated
# norm; two norms a layer and the final one
SMALL = 36 * (4352 * 4 + 4352 + 3 * 64 + 4096) + 2 * 40 * 2048 + 2048
KV_ROW = 4 * 2 * 8 * 64 * 2              # K and V of one position, bf16
# a row's state: SSM 64 heads x 64 x 128 in f32, conv 3 positions x 4352
STATE = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
SSM_FLOPS = 36 * (2 * 4352 * 4 + 4 * 64 * 64 * 128 + 2 * 64 * 64 * 128)
ATTN_FLOPS = 2 * 2 * 4 * 32 * 64         # QK^T and PV per key and query


@pytest.fixture(scope="module")
def w():
    return reference.family("granite_hybrid").work(
        load("granite-4.0-h-micro"), 2, 512, 16)


def test_every_weight_is_counted_once():
    # 3,191,396,096 parameters: the published model's count
    assert ATTN + MAMBA + MLP + HEAD + SMALL == 3_191_396_096


def test_prefill(w):
    dense = ATTN + MAMBA + MLP
    assert w["prefill_flops"] == (2 * dense * 1024 + SSM_FLOPS * 1024
                                  + ATTN_FLOPS * (512 * 513 // 2) * 2
                                  + 2 * HEAD * 2)
    assert w["prefill_bytes"] == (3_191_396_096 * 2 + 1024 * 2048 * 2
                                  + 1024 * KV_ROW + 2 * STATE)


def test_decoded_token(w):
    ctx = 512 + 17 / 2
    assert w["token_flops"] == pytest.approx(
        2 * (ATTN + MAMBA + MLP) + SSM_FLOPS + ATTN_FLOPS * ctx + 2 * HEAD)
    assert w["token_bytes"] == pytest.approx(
        3_191_396_096 * 2 / 2 + 2048 * 2 + ctx * KV_ROW + KV_ROW + 2 * STATE)


def test_the_state_outweighs_the_kv_cache(w):
    # batch 2 carries 152.9 MB of state and 8.65 MB of KV at 528 positions
    assert 2 * STATE == 152_875_008 and 2 * 528 * KV_ROW == 8_650_752
    # a decoded token's state traffic is a twentieth of its bytes
    assert 0.04 < 2 * STATE / w["token_bytes"] < 0.06
