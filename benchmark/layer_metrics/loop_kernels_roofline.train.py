"""Roofline share of the loop's kernels in a training step.

Numerator: the FLOPs the loop of one step needs on one chip (benchmark/flops.py:
both FFWs and consensus over unmasked pairs only, forward + backward = 3x).
Denominator: the device time of the ops that execute those FLOPs in one step
(the Mosaic custom calls, plus every convolution, dot or matmul-output fusion
outside them, which the run names on an earlier line), times the chip's peak
bf16 FLOP/s. The loop is compute-bound at these widths (arithmetic intensity
of a d x 4d MLP over thousands of rows is far above the chip's 240 FLOP/B),
so the FLOP bound is the roofline."""

from benchmark import flops
from benchmark.peaks import peaks_for


def read(ctx):
    t = ctx.get("trace")
    if not t or t.get("main_module_mosaic_median_s") is None:
        return None
    kernel_s = (t["main_module_mosaic_median_s"]
                + (t.get("main_module_matmul_outside_median_s") or 0.0))
    if kernel_s <= 0:
        return None
    per_chip = flops.train_loop_flops_per_step(
        ctx["model"], ctx["batch"] // ctx["chips"], ctx["loop_iters"])
    peak = peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_chip / kernel_s / peak
