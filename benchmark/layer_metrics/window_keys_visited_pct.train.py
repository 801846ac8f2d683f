"""Key blocks a window layer multiplies over those a full-length layer
multiplies: the step's own counters `attn_key_blocks_window` and
`attn_key_blocks_full` (summed over the layers of each kind by the loop that
slices the keys), each over the number of such layers, from the window's
logging records. By the masks alone it would read 12% at 8,192 tokens; a
window that is masked and not skipped reads 100%."""
from benchmark.reduce_lm import counter_mean
from benchmark.reference.sambay_ref import layer_kinds


def read(ctx):
    window = counter_mean(ctx, "attn_key_blocks_window")
    full = counter_mean(ctx, "attn_key_blocks_full")
    if window is None or not full:
        return None
    kinds = layer_kinds(ctx["model"])
    n_window, n_full = kinds.count("W"), kinds.count("F") + kinds.count("X")
    if not n_window or not n_full:
        return None
    return 100.0 * (window / n_window) / (full / n_full)
