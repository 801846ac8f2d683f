"""`flops_ouro.py`: the counts against a hand count at the cell's size
(ISSUE 46's arithmetic) and against brute force over the causal mask."""
import json
import os

import numpy as np

from benchmark import flops_ouro as fl
from benchmark import harness


def _model():
    with open(os.path.join(harness.BENCH_DIR, "configs", "ouro-2.6b-stage8.json")) as fh:
        return json.load(fh)["model"]


def test_the_pairs_are_the_causal_masks_counted():
    for t in (1, 7, 128, 300):
        assert fl.pairs_seen(t) == int(np.tril(np.ones((t, t), bool)).sum())


def test_the_cells_counts_are_the_hand_count():
    model = _model()
    assert fl.applications(model) == 32
    tokens = 2 * 4096
    # a token an application: q, k, v, o 4 x 2 x 2,048 x 2,048; the MLP 3 x 2 x 2,048 x 5,632
    projections, mlp = 4 * 2 * 2048 * 2048, 3 * 2 * 2048 * 5632
    assert (projections, mlp) == (33_554_432, 69_206_016)
    # a row an application: 16 heads x 4,096 x 4,097 / 2 pairs x 2 products x 2 x 128
    scores = 16 * (4096 * 4097 // 2) * 2 * 2 * 128
    head = 2 * 2048 * 49152                                  # a token a pass
    forward = 32 * (tokens * (projections + mlp) + 2 * scores) + 4 * tokens * head
    parts = fl.forward_flops_by_part(model, 2, 4096)
    assert sum(parts.values()) == forward
    assert round(forward / 1e12, 1) == 37.9
    assert round(fl.step_flops(model, 2, 4096) / 1e12, 1) == 113.8          # ISSUE 46
    assert round(100 * 32 * 2 * scores / forward, 1) == 11.6                # the scores alone
    assert round(100 * parts["head"] / forward, 1) == 17.4                  # the four heads
    # the whole 48-layer model: the head is 3.4% of it (the depth cut overstates it five times)
    whole = dict(model, num_hidden_layers=48)
    full = fl.forward_flops_by_part(whole, 2, 4096)
    assert round(100 * full["head"] / sum(full.values()), 1) == 3.4
    # one pass of the same layers is a quarter of the layers' work and of the heads'
    once = fl.forward_flops_by_part(dict(model, total_ut_steps=1), 2, 4096)
    assert all(parts[k] == 4 * once[k] for k in parts)


def test_the_kernels_operations_bound_them_on_a_v5e():
    model = _model()
    kernels = fl.attention_kernel_ops_and_bytes(model, 2, 4096)
    assert kernels["ops"] == 32 * 2 * 16 * (4096 * 4097 // 2) * 12 * 128
    assert round(kernels["ops"] / 1e12, 1) == 13.2 and round(kernels["bytes"] / 1e9, 1) == 12.9
    assert 0.11 < kernels["ops"] / fl.step_flops(model, 2, 4096) < 0.12
    assert kernels["ops"] / 197e12 > 4 * kernels["bytes"] / 819e9
