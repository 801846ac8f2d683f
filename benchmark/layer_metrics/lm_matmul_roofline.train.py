"""Roofline share of the language-model step's matrix products.

Numerator: the FLOPs the step needs (benchmark/flops_lm.py: every
projection, the experts' products for the pairs the step's own counter says
were routed here, attention over the causal half, the chunked recurrence's
products, the head; forward + backward = 3x, recomputation not counted).
Denominator: the device time of the ops that execute products in one step
(dots, convolutions, matmul-output fusions, the compiler's ragged-dot custom
calls; recomputed ones included, which is what keeps the share under what
the products alone reach), times the chip's peak bf16 FLOP/s. At these
widths (4096 x 5376 over 8192 rows) the products are compute-bound, so the
FLOP bound is the roofline."""
from benchmark import flops_lm
from benchmark.peaks import peaks_for
from benchmark.reduce_lm import counter_mean, for_run


def read(ctx):
    r = for_run(ctx)
    pairs = counter_mean(ctx, "moe_pairs_here")
    if not r or pairs is None or r["product_s"] <= 0:
        return None
    need = flops_lm.train_flops_per_step(ctx["model"], ctx["batch"] // ctx["chips"],
                                         ctx["seq_len"], pairs)
    return 100.0 * need / r["product_s"] / peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
