"""`flops_sambay` against a hand count at the cell's shapes: hidden 2560, 40
query heads over 20 KV heads of 64, window 512, MLP width 10,240, Mamba-1
over 5,120 channels with 16 states, 25,008 rows of the tied embedding, the
stage of layers 14-19 (MWMFGX), one sequence of 8,192 tokens."""
import json
import os

import pytest

from benchmark import flops_sambay, harness
from benchmark.reference.sambay_ref import layer_kinds


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(harness.BENCH_DIR, "configs", "phi4-mini-flash-stage6vp8.json")) as fh:
        return json.load(fh)["model"]


def test_the_per_token_products_by_hand(model):
    assert flops_sambay.mlp_flops_per_token(model) == 2 * 2560 * 20480 + 2 * 10240 * 2560
    mamba = 2 * 2560 * 10240 + 2 * 5120 * 192 + 2 * 160 * 5120 + 2 * 5120 * 2560
    assert flops_sambay.mamba_flops_per_token(model) == mamba
    assert flops_sambay.gmu_flops_per_token(model) == 2 * 2560 * 5120 + 2 * 5120 * 2560
    assert flops_sambay.head_flops_per_token(model) == 2 * 2560 * 25008
    # a parameter in a product is 2 FLOPs a token: the counts are the layers' matrices
    assert flops_sambay.mlp_flops_per_token(model) / 2 == 78_643_200
    assert mamba / 2 == 26_214_400 + 983_040 + 819_200 + 13_107_200


def test_the_keys_a_mask_lets_a_query_see():
    assert flops_sambay.keys_seen(8192) == 8192 * 8193 / 2
    # queries 0..511 see 1..512 keys, the 7,680 after them 512 each
    assert flops_sambay.keys_seen(8192, 512) == 512 * 513 / 2 + 7680 * 512
    assert flops_sambay.keys_seen(8192, 512) / flops_sambay.keys_seen(8192) == pytest.approx(
        0.1211, abs=1e-4)                                     # the issue's 12% by the masks alone
    assert flops_sambay.keys_seen(300, 512) == flops_sambay.keys_seen(300)
    assert flops_sambay.keys_seen(8192, 8192) == flops_sambay.keys_seen(8192)


def test_the_attention_layers_by_hand(model):
    own = 2 * 2560 * (2560 + 1280 + 1280) + 2 * 2560 * 2560
    cross = 2 * 2560 * 2560 + 2 * 2560 * 2560
    per_pair = 40 * (2 * 64 + 2 * 128)          # every query head: scores over 64, values over 128
    full, window = 8192 * 8193 / 2, 512 * 513 / 2 + 7680 * 512
    assert flops_sambay.attention_flops_per_sequence(model, 8192, "F") == pytest.approx(
        8192 * own + per_pair * full)
    assert flops_sambay.attention_flops_per_sequence(model, 8192, "W") == pytest.approx(
        8192 * own + per_pair * window)
    assert flops_sambay.attention_flops_per_sequence(model, 8192, "X") == pytest.approx(
        8192 * cross + per_pair * full)
    # the score work of a full-length layer is 8 times the window layer's
    assert full / window == pytest.approx(8.26, abs=0.01)


def test_the_step_by_hand(model):
    assert layer_kinds(model) == "MWMFGX"
    fwd = (8192 * (6 * flops_sambay.mlp_flops_per_token(model)
                   + 2 * flops_sambay.mamba_flops_per_token(model)
                   + flops_sambay.gmu_flops_per_token(model))
           + sum(flops_sambay.attention_flops_per_sequence(model, 8192, k) for k in "WFX")
           + 8191 * 2 * 2560 * 25008)
    assert flops_sambay.forward_flops_per_step(model, 1, 8192) == pytest.approx(fwd)
    step = flops_sambay.train_flops_per_step(model, 1, 8192)
    assert step == pytest.approx(3 * fwd) and step / 1e12 == pytest.approx(37.53, abs=0.01)
    # 6 FLOPs a parameter a token for the 633M parameters of the layers' matrices and
    # the 64M of the head (the tied embedding counts once, as the head), and the
    # attention's score work on top: 9% of the step
    dense = 6 * 8192 * 697_094_272
    scores = 3 * 40 * (2 * 64 + 2 * 128) * (2 * 8192 * 8193 / 2 + 512 * 513 / 2 + 7680 * 512)
    assert step == pytest.approx(dense + scores, rel=2e-3)
    assert scores / step == pytest.approx(0.087, abs=0.002)
    assert flops_sambay.train_flops_per_step(model, 2, 8192) == pytest.approx(2 * step)


def test_the_scans_operations_and_bytes(model):
    scan = flops_sambay.scan_ops_and_bytes(model, 1, 8192)
    assert scan["ops"] == 7 * 8192 * 5120 * 16
    assert scan["bytes"] == 8192 * (8 * 5120 + 4 * 16)
    # 14 operations a byte: an order under the chip's 240 FLOPs a byte, so a
    # kernel that keeps the states on the chip is bound by the vector units'
    # rate, not by memory
    assert scan["ops"] / scan["bytes"] == pytest.approx(14.0, abs=0.1)
