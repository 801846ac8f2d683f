"""`flops_kimi` against a hand count at the cell's shapes: hidden 2,304, 32
KDA heads of 128 behind low-rank maps of 128, 32 latent-attention heads of 128
+ 64 key and 128 value dimensions over a latent of 512, dense MLP 9,216, 8 of
256 experts of width 1,024 held and 8 a token, a shared expert of 1,024,
20,480 rows of embedding and head, layers 1-5 (KD KE KE AE KE), one row of
16,384 tokens."""
import json
import os

import pytest

from benchmark import flops_kimi as fk
from benchmark import harness
from benchmark.flops_sambay import keys_seen
from benchmark.reference.kimi_linear_ref import layer_kinds


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(harness.BENCH_DIR, "configs", "kimi-linear-ep32vp8.json")) as fh:
        return json.load(fh)["model"]


def test_the_per_token_products_by_hand(model):
    # a parameter in a product is 2 FLOPs a token: the counts are the layers' matrices
    assert fk.kda_projection_flops_per_token(model) / 2 == (
        3 * 2304 * 4096 + 2 * 2304 * 128 + 2 * 128 * 4096 + 2304 * 32 + 4096 * 2304) == 39_460_864
    assert fk.latent_projection_flops_per_token(model) / 2 == (
        2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304) == 29_114_368
    # the recurrence: S^T k, the rank-one update and S^T q of a 128 x 128 state, 32 heads
    assert fk.kda_state_flops_per_token(model) == 32 * 3 * 2 * 128 * 128
    assert fk.dense_mlp_flops_per_token(model) / 2 == 3 * 2304 * 9216
    assert fk.head_flops_per_token(model) == 2 * 2304 * 20480
    # an expert layer: router and shared expert for every token, a SwiGLU of 1,024 a pair
    assert fk.expert_layer_flops(model, 100, 0.0) == 100 * 2 * (2304 * 256 + 3 * 2304 * 1024)
    assert fk.expert_layer_flops(model, 0, 7.0) == 7 * 2 * 3 * 2304 * 1024


def test_the_step_by_hand(model):
    assert layer_kinds(model) == [("K", "D"), ("K", "E"), ("K", "E"), ("A", "E"), ("K", "E")]
    t = 16384
    parts = fk.forward_flops_by_part(model, 1, t, 4096.0)
    assert parts["kda_projections"] == 4 * t * 2 * 39_460_864
    assert parts["kda_state"] == 4 * t * 32 * 6 * 128 * 128
    scores = 32 * keys_seen(t) * 2 * (192 + 128)
    assert fk.latent_score_flops_per_sequence(model, t) == scores
    assert parts["latent_attention"] == pytest.approx(t * 2 * 29_114_368 + scores)
    assert parts["dense_mlp"] == t * 2 * 3 * 2304 * 9216
    assert parts["experts"] == 4 * fk.expert_layer_flops(model, t, 4096.0)
    assert parts["head"] == (t - 1) * 2 * 2304 * 20480
    # the issue's count: the latent layer's scores 2.7 TFLOP forward; one row's forward 14.0
    # TFLOP, of which the five mixers 9.1 (three fifths of it) and the delta rule's own
    # products 0.2 (1.5%); the held experts under a balanced router 0.23 (2%)
    assert scores / 1e12 == pytest.approx(2.75, abs=0.01)
    total = sum(parts.values())
    assert total / 1e12 == pytest.approx(13.95, abs=0.01)
    mixers = parts["kda_projections"] + parts["kda_state"] + parts["latent_attention"]
    assert mixers / total == pytest.approx(0.651, abs=0.002)
    assert parts["kda_state"] / total == pytest.approx(0.0148, abs=0.0005)
    assert 4 * 4096 * 2 * 3 * 2304 * 1024 / total == pytest.approx(0.0166, abs=0.0005)
    step = fk.step_flops(model, 1, t, 4096.0)
    assert step == pytest.approx(3 * total) and step / 1e12 == pytest.approx(41.86, abs=0.02)
    # the pairs are the step's own counter: more of them, more FLOPs, nothing else moves
    more = fk.step_flops(model, 1, t, 16384.0)
    assert more - step == pytest.approx(3 * 4 * 12288 * 2 * 3 * 2304 * 1024)


def test_the_attention_kernels_operations_and_bytes(model):
    need = fk.attention_kernel_ops_and_bytes(model, 1, 16384)
    pairs = 32 * keys_seen(16384)
    assert need["ops"] == pytest.approx(pairs * 6 * (192 + 128))
    # three times the forward's scores and values (the kernels' share of the needed FLOPs)
    assert need["ops"] == pytest.approx(3 * fk.latent_score_flops_per_sequence(model, 16384))
    q, v = (16384 * 32 * width * 2 for width in (192, 128))   # bytes of a q- or k-like, v- or o-like
    assert need["bytes"] == 3 * (2 * q + 2 * v)
    # compute-bound on a v5e: 42 ms of operations against 2.5 ms of bytes a step
    assert need["ops"] / 197e12 > 10 * need["bytes"] / 819e9
    assert need["ops"] / 197e12 == pytest.approx(0.0419, abs=0.0002)
    two = fk.attention_kernel_ops_and_bytes(model, 2, 16384)
    assert two["ops"] == 2 * need["ops"] and two["bytes"] == 2 * need["bytes"]
