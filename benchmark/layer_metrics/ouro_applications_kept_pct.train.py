"""Layer applications whose recomputation read the attention forward kernel's
kept output and log-sum-exp, over the layer applications of the step: the
step's own counters `attn_forward_kept` and `layer_applications`
(`hybrid_lm.forward_kept`, `ouro.lm_loss`), from the window's logging records.
100 where all 32 recomputations read what the first forward pass kept (the
kernels ran and the loop's residuals carry the kept arrays a pass); 0 where
the XLA loop ran."""
from benchmark.reduce_lm import counter_mean


def read(ctx):
    kept = counter_mean(ctx, "attn_forward_kept")
    applications = counter_mean(ctx, "layer_applications")
    if kept is None or not applications:
        return None
    return 100.0 * kept / applications
