"""`flops_evabyte.py`: the counts against brute force over the mask the
reference writes out, and against ISSUE.md's arithmetic at the cell's size."""
import json
import os

import numpy as np

from benchmark import flops_evabyte as fl
from benchmark import harness
from benchmark.reference import evabyte_ref


def _model():
    with open(os.path.join(harness.BENCH_DIR, "configs", "evabyte-stage4tp4.json")) as fh:
        return json.load(fh)["model"]


def test_the_pairs_and_the_blocks_are_the_references_mask_counted():
    import jax.numpy as jnp

    model = dict(_model(), window_size=256, chunk_size=8)
    for t in (1024, 1000, 300):
        seen = np.asarray(evabyte_ref.visible(jnp.arange(t)[:, None], t, model))
        own, summary = seen[:, :t], seen[:, t:]
        assert fl.pairs_seen(model, t) == (int(own.sum()), int(summary.sum()))
        tiles = [slice(i, min(t, i + 128)) for i in range(0, t, 128)]
        blocks = lambda part: sum(int(part[rows][:, j:j + 128].any())
                                  for rows in tiles for j in range(0, part.shape[1], 128))
        assert fl.needed_key_blocks(model, t, query_tile=128) == (blocks(own), blocks(summary))


def test_the_cells_counts_are_the_issues():
    model = _model()
    own, summary = fl.pairs_seen(model, 16384)
    assert (round(own / 1e6, 1), round(summary / 1e6, 1)) == (16.8, 7.3)    # 24.1M pairs a head
    assert 16384 * 16385 // 2 == 134_225_920                                 # full causal: 134M
    assert fl.needed_key_blocks(model, 16384) == (320, 112)
    parts = fl.forward_flops_by_part(model, 1, 16384)
    total = sum(parts.values())
    assert 0.10 < parts["attention"] / total < 0.15         # projections and scores of 8 heads
    kernels = fl.attention_kernel_ops_and_bytes(model, 1, 16384)
    assert 0.015 < kernels["ops"] / fl.step_flops(model, 1, 16384) < 0.025   # "about 2%"
    assert round(fl.step_flops(model, 1, 16384) / 1e12, 1) == 62.0
    assert round(kernels["ops"] / 1e12, 2) == 1.19 and round(kernels["bytes"] / 1e9, 2) == 1.66
    # operations bound the kernels on a v5e: 1.19 TFLOP / 197 against 1.66 GB / 819
    assert kernels["ops"] / 197e12 > kernels["bytes"] / 819e9
