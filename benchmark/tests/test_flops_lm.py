"""`flops_lm` against a hand count at the cell's shapes: hidden 4096, the
chip's share of NVIDIA-Nemotron-3-Super-120B-A12B (8 of 512 experts, 16
Mamba-2 heads in one group, 4 query heads over one KV head, 16,384 rows of
the vocabulary), layers EMEMEMEMEM*, one sequence of 8,192 tokens."""
import json
import os

import pytest

from benchmark import flops_lm, harness


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(harness.BENCH_DIR, "configs", "nemotron3-super-ep64tp8.json")) as fh:
        return json.load(fh)["model"]


def test_an_expert_layer_by_hand(model):
    router = 2 * 4096 * 512
    latent = 2 * 4096 * 1024 + 2 * 1024 * 4096
    shared = 2 * 4096 * 5376 + 2 * 5376 * 4096
    assert flops_lm.moe_dense_flops_per_token(model) == router + latent + shared == 109_051_904
    assert flops_lm.expert_flops_per_pair(model) == 2 * 1024 * 2688 + 2 * 2688 * 1024
    # the issue's count: 22 choices of 512 land on the 8 held with chance 8/512
    routed = 22 * 8 / 512 * flops_lm.expert_flops_per_pair(model)
    assert routed / 1e6 == pytest.approx(3.78, abs=0.01)
    assert (109_051_904 + routed) / 1e6 == pytest.approx(112.8, abs=0.1)


def test_a_mamba_layer_by_hand(model):
    in_width = 1024 + 1024 + 128 + 128 + 16
    proj = 2 * 4096 * in_width + 2 * 1024 * 4096
    scan = 2 * 128 * 64.5 * 1 + 2 * 64 * 64.5 * 16 + 2 * (2 * 64 * 128 * 16)
    assert flops_lm.mamba_flops_per_token(model) == pytest.approx(proj + scan)
    assert scan / (proj + scan) < 0.03


def test_the_attention_layer_by_hand(model):
    proj = 2 * 4096 * (512 + 128 + 128) + 2 * 512 * 4096
    pairs = 2 * (2 * 128 * 4) * (8192 + 1) / 2
    assert flops_lm.attention_flops_per_token(model, 8192) == pytest.approx(proj + pairs)


def test_the_step_by_hand(model):
    assert flops_lm.layer_kinds(model) == "EMEMEMEMEM*"
    pairs = 8192 * 22 * 8 / 512  # 2,816 a layer when the routing is even
    fwd = (8192 * (5 * flops_lm.moe_dense_flops_per_token(model)
                   + 5 * flops_lm.mamba_flops_per_token(model)
                   + flops_lm.attention_flops_per_token(model, 8192))
           + 8191 * 2 * 4096 * 16384
           + 5 * pairs * flops_lm.expert_flops_per_pair(model))
    assert flops_lm.forward_flops_per_step(model, 1, 8192, pairs) == pytest.approx(fwd)
    step = flops_lm.train_flops_per_step(model, 1, 8192, pairs)
    assert step == pytest.approx(3 * fwd) and step / 1e12 == pytest.approx(21.08, abs=0.01)
    # the routed experts are 3% of it; no pair at all takes exactly that off
    none = flops_lm.train_flops_per_step(model, 1, 8192, 0.0)
    assert (step - none) / step == pytest.approx(0.022, abs=0.002)
    assert flops_lm.train_flops_per_step(model, 2, 8192, pairs) > 1.9 * none
