"""Roofline share of the SambaY step's matrix products.

Numerator: the FLOPs the step needs (benchmark/flops_sambay.py: every
projection and MLP, attention over the keys each mask lets a query see, the
tied head; forward + backward = 3x, recomputation not counted). Denominator:
the device time of the ops that execute products in one step (dots,
convolutions, matmul-output fusions; recomputed ones and the masked part of
a key block included, which is what keeps the share under what the products
alone reach), times the chip's peak bf16 FLOP/s. At these widths (2,560 x
20,480 over 8,192 rows) the products are compute-bound, so the FLOP bound is
the roofline."""
from benchmark import flops_sambay
from benchmark.peaks import peaks_for
from benchmark.reduce_sambay import for_run


def read(ctx):
    r = for_run(ctx)
    if not r or r["product_s"] <= 0:
        return None
    need = flops_sambay.train_flops_per_step(ctx["model"], ctx["batch"] // ctx["chips"],
                                             ctx["seq_len"])
    return 100.0 * need / r["product_s"] / peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
