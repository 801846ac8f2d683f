"""Key blocks of 128 keys EVA attention multiplied over the blocks its mask
needs: the step's own counters `attn_key_blocks_local` (own keys) and
`attn_key_blocks_summary` (chunk summaries), summed over the query tiles
and the layers by the function that builds the kernels' grid (once a grid
row: the heads and the rows of a batch share it), from the window's logging
records, over benchmark/flops_evabyte.py:needed_key_blocks (for every tile of
512 queries the blocks that hold a key of L(t) or a summary of C(t) for some
query of the tile) times the layers. 100 is a schedule that
visits nothing the mask empties; a full causal schedule over both segments
would read 548 at 16,384 bytes."""
from benchmark import flops_evabyte
from benchmark.reduce_lm import counter_mean


def read(ctx):
    own = counter_mean(ctx, "attn_key_blocks_local")
    summary = counter_mean(ctx, "attn_key_blocks_summary")
    if own is None or summary is None:
        return None
    need = sum(flops_evabyte.needed_key_blocks(ctx["model"], ctx["seq_len"]))
    # the counters count a key block once a query tile, whatever heads share the grid
    return 100.0 * (own + summary) / (need * ctx["model"]["num_hidden_layers"])
