"""The attention kernels' share of their roofline in the EvaByte step, under
the `Aligned` mask (D 128, one query head a KV head, keys of T + T / 16 rows).

Numerator: the least time the chip could take for what the algorithm needs
of `attn_flash_fwd` and `attn_flash_bwd_onesweep` in one step
(benchmark/flops_evabyte.py:attention_kernel_ops_and_bytes: the six products
of the seen (query, key) and (query, summary) pairs, 12 D operations a pair a
head, and the arrays each pass has to read and write once): the larger of
operations over the bf16 peak and bytes over the HBM peak. Denominator: the
device time of the kernels' calls in one step: the backward's rebuilt scores
and the masked part of the tiles an edge crosses included, which is what
keeps the share under what the needed products alone reach."""
from benchmark import flops_evabyte
from benchmark.peaks import peaks_for
from benchmark.reduce_evabyte import for_run


def read(ctx):
    r = for_run(ctx)
    if not r or r["kernel_s"] <= 0:
        return None
    need = flops_evabyte.attention_kernel_ops_and_bytes(
        ctx["model"], ctx["batch"] // ctx["chips"], ctx["seq_len"])
    peaks = peaks_for(ctx["device_kind"])
    least = max(need["ops"] / peaks["bf16_flops_per_s"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / r["kernel_s"]
