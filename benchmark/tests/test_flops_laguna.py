"""`flops_laguna` against a hand count at the cell's shapes: hidden 2,048,
48 or 64 query heads over 8 KV heads of 128, window 512, dense MLP 8,192,
32 of 256 experts of width 512 held and 8 a token, a shared expert of 512,
12,544 rows of embedding and head, layers 0-4 (FD SE SE SE FE), sequences of
8,192 tokens."""
import json
import os

import pytest

from benchmark import flops_laguna as fl
from benchmark import harness
from benchmark.flops_sambay import keys_seen
from benchmark.reference.laguna_ref import layer_kinds


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(harness.BENCH_DIR, "configs", "laguna-xs2-ep8vp8.json")) as fh:
        return json.load(fh)["model"]


def test_the_per_token_products_by_hand(model):
    # a parameter in a product is 2 FLOPs a token: the counts are the layers' matrices
    assert fl.attention_projection_flops_per_token(model, "F") / 2 == (
        2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48 + 6144 * 2048) == 29_458_432
    assert fl.attention_projection_flops_per_token(model, "S") / 2 == (
        2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64 + 8192 * 2048) == 37_879_808
    assert fl.dense_mlp_flops_per_token(model) / 2 == 3 * 2048 * 8192
    assert fl.head_flops_per_token(model) == 2 * 2048 * 12544
    # an expert layer: router and shared expert for every token, a SwiGLU of 512 a pair
    assert fl.expert_layer_flops(model, 100, 0.0) == 100 * 2 * (2048 * 256 + 3 * 2048 * 512)
    assert fl.expert_layer_flops(model, 0, 7.0) == 7 * 2 * 3 * 2048 * 512


def test_the_scores_follow_the_masks(model):
    full, window = 8192 * 8193 / 2, 512 * 513 / 2 + 7680 * 512
    assert (keys_seen(8192), keys_seen(8192, 512)) == (full, window)
    assert fl.attention_score_flops_per_sequence(model, 8192, "F") == 48 * full * 4 * 128
    assert fl.attention_score_flops_per_sequence(model, 8192, "S") == 64 * window * 4 * 128
    # the issue's count: a full layer's scores 0.82 TFLOP, a sliding layer's 0.14
    assert fl.attention_score_flops_per_sequence(model, 8192, "F") / 1e12 == pytest.approx(
        0.825, abs=0.002)
    assert fl.attention_score_flops_per_sequence(model, 8192, "S") / 1e12 == pytest.approx(
        0.133, abs=0.002)


def test_the_step_by_hand(model):
    assert layer_kinds(model) == [("F", "D"), ("S", "E"), ("S", "E"), ("S", "E"), ("F", "E")]
    parts = fl.forward_flops_by_part(model, 1, 8192, 8192.0)
    assert parts["full_attention"] == pytest.approx(
        2 * (8192 * 2 * 29_458_432 + 48 * keys_seen(8192) * 512))
    assert parts["window_attention"] == pytest.approx(
        3 * (8192 * 2 * 37_879_808 + 64 * keys_seen(8192, 512) * 512))
    assert parts["dense_mlp"] == 8192 * 2 * 3 * 2048 * 8192
    assert parts["experts"] == 4 * fl.expert_layer_flops(model, 8192, 8192.0)
    assert parts["head"] == 8191 * 2 * 2048 * 12544
    # one sequence's forward: 6.5 TFLOP, of which the attentions 4.9
    assert sum(parts.values()) / 1e12 == pytest.approx(6.57, abs=0.01)
    assert (parts["full_attention"] + parts["window_attention"]) / 1e12 == pytest.approx(
        4.88, abs=0.01)
    two = fl.train_flops_per_step(model, 2, 8192, 16384.0)
    assert two == pytest.approx(3 * 2 * sum(parts.values()))
    assert two / 1e12 == pytest.approx(39.4, abs=0.1)
    # the pairs are the step's own counter: more of them, more FLOPs, nothing else moves
    more = fl.train_flops_per_step(model, 2, 8192, 20000.0)
    assert more - two == pytest.approx(3 * 4 * 3616 * 2 * 3 * 2048 * 512)


def test_the_attention_kernels_operations_and_bytes(model):
    need = fl.attention_kernel_ops_and_bytes(model, 2, 8192)
    pairs = 2 * 48 * keys_seen(8192) + 3 * 64 * keys_seen(8192, 512)
    assert need["ops"] == pytest.approx(2 * pairs * 12 * 128)
    # three times the forward's scores and values (the kernels' share of the needed FLOPs)
    scores = sum(fl.attention_score_flops_per_sequence(model, 8192, a)
                 for a, _ in layer_kinds(model))
    assert need["ops"] == pytest.approx(3 * 2 * scores)
    q_f, q_s, kv = (2 * 8192 * h * 128 * 2 for h in (48, 64, 8))
    assert need["bytes"] == pytest.approx(2 * (6 * q_f + 6 * kv) + 3 * (6 * q_s + 6 * kv))
    # compute-bound on a v5e: 62 ms of operations against 10 ms of bytes a step
    assert need["ops"] / 197e12 > 5 * need["bytes"] / 819e9
