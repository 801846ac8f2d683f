"""Roofline share of the Ouro step's matrix products.

Numerator: the FLOPs the step needs (benchmark/flops_ouro.py, by layer
application: the projections, the causal attention, the dense MLP at 2,048 x
5,632, all 32 times, and the head's 2,048 x 49,152 four times; forward +
backward = 3x, recomputation not counted). Denominator: the device time of
the ops that execute products in one step: dots, matmul-output fusions AND
the attention kernels' calls (the scores' products run inside them: PERF.md
trap 14); recomputed ones and the masked part of a key tile included, which
is what keeps the share under what the products alone reach. At these widths
the products are compute-bound, so the FLOP bound is the roofline."""
from benchmark import flops_ouro
from benchmark.peaks import peaks_for
from benchmark.reduce_ouro import for_run


def read(ctx):
    r = for_run(ctx)
    if not r or r["product_s"] <= 0:
        return None
    need = flops_ouro.step_flops(ctx["model"], ctx["batch"] // ctx["chips"], ctx["seq_len"])
    return 100.0 * need / r["product_s"] / peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
