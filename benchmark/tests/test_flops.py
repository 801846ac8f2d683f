import numpy as np

from benchmark import flops


def _model(side_px, patch, radius):
    return {"dim": 512, "levels": 6, "image_size": side_px, "patch_size": patch,
            "mult": 4, "channels": 3, "local_consensus_radius": radius}


def test_radius0_equals_the_programs_dense_count():
    from glom_tpu.utils.config import GlomConfig
    from glom_tpu.utils.metrics import flops_per_column_iter

    for px, p in ((224, 14), (256, 8), (64, 8)):
        cfg = GlomConfig(dim=512, levels=6, image_size=px, patch_size=p)
        assert flops.loop_flops_per_col_iter(_model(px, p, 0)) == flops_per_column_iter(cfg)


def test_radius7_counts_the_unmasked_pairs_of_build_local_mask():
    from glom_tpu.ops.consensus import build_local_mask

    for side in (8, 32):
        mask = build_local_mask(side, 7.0)
        assert flops.unmasked_pairs(side, 7.0) == int((~mask).sum())
    m = _model(256, 8, 7)
    pairs = int((~build_local_mask(32, 7.0)).sum())
    assert flops.consensus_flops_per_col_iter(m) == 2 * 2 * 6 * pairs * 512
    dense = flops.consensus_flops_per_col_iter(_model(256, 8, 0))
    assert flops.consensus_flops_per_col_iter(m) < 0.2 * dense


def test_training_step_counts_forward_plus_backward_over_executed_iterations():
    m = _model(224, 14, 0)
    assert flops.train_loop_iters(m) == 7
    assert flops.train_loop_flops_per_step(m, 64, 7) == 3 * 64 * 7 * flops.loop_flops_per_col_iter(m)
