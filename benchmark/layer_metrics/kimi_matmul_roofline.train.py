"""Roofline share of the Kimi Linear step's matrix products.

Numerator: the FLOPs the step needs (benchmark/flops_kimi.py: every
projection, the delta rule's three state products a position, the latent
attention over the keys at or before each query, the dense MLP, the router,
the shared expert, the experts' SwiGLU for the pairs the step's own counter
says were routed here, the head; forward + backward = 3x, recomputation not
counted). Denominator: the device time of the ops that execute products in
one step: dots, convolutions, matmul-output fusions, the compiler's
ragged-dot custom calls AND the attention kernels' calls (the scores'
products run inside them: PERF.md trap 14); recomputed ones, the chunked
form's in-chunk products and solve at float32 precision, the masked part of a
key tile and the rows of room included, which is what keeps the share under
what the products alone reach. At these widths the products are
compute-bound, so the FLOP bound is the roofline."""
from benchmark import flops_kimi
from benchmark.peaks import peaks_for
from benchmark.reduce_kimi import for_run
from benchmark.reduce_lm import counter_mean


def read(ctx):
    r = for_run(ctx)
    pairs = counter_mean(ctx, "moe_pairs_here")
    if not r or pairs is None or r["product_s"] <= 0:
        return None
    need = flops_kimi.step_flops(ctx["model"], ctx["batch"] // ctx["chips"],
                                 ctx["seq_len"], pairs)
    return 100.0 * need / r["product_s"] / peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
