"""The attention kernels' share of their roofline in the Ouro step: plain
causal attention at one query head a KV head (R 1, G 16, D 128, T 4,096), 32
forward and 32 backward calls a step.

Numerator: the least time the chip could take for what the algorithm needs
of `attn_flash_fwd` and `attn_flash_bwd_onesweep` in one step
(benchmark/flops_ouro.py:attention_kernel_ops_and_bytes: the six products of
the seen (query, key) pairs, 12 D operations a pair a head, and the arrays
each pass has to read and write once): the larger of operations over the bf16
peak and bytes over the HBM peak. Denominator: the device time of the kernels'
calls in one step: the backward's rebuilt scores and the masked half of the
tiles the diagonal crosses included, which is what keeps the share under what
the needed products alone reach."""
from benchmark import flops_ouro
from benchmark.peaks import peaks_for
from benchmark.reduce_ouro import for_run


def read(ctx):
    r = for_run(ctx)
    if not r or r["kernel_s"] <= 0:
        return None
    need = flops_ouro.attention_kernel_ops_and_bytes(
        ctx["model"], ctx["batch"] // ctx["chips"], ctx["seq_len"])
    peaks = peaks_for(ctx["device_kind"])
    least = max(need["ops"] / peaks["bf16_flops_per_s"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / r["kernel_s"]
