"""The Ouro looped language-model step by the program's own scopes: which
scope of `glom_tpu.tracing.spans.OURO_DEVICE_PHASES` (or of the step builder's
`optimizer` and `step_metrics`) each device op of the traced step belongs to,
the device time of the ops that execute its matrix products, and that of the
attention kernels' calls.

The reduction is `reduce_laguna.by_scopes`, given this family's tuple; the
vocabulary is copied here so that this file reads a checkout without it and
finds nothing rather than failing (`tests/test_reduce_ouro.py` holds the copy
to the original). A step counts as this family's when it opens the scope that
closes a pass and the exit gate's. The passes are the trips of one loop: an
instruction of its body is an event a trip (trap 19), and `by_scopes` sums
them.
"""

from __future__ import annotations

import functools

from benchmark import reduce_laguna as rg
from benchmark import reduce_lm as rl
from benchmark import reduce_phases as rp

OURO_DEVICE_PHASES = ("embed", "ouro_in", "full_attention", "ouro_out", "sandwich_norm",
                      "dense_mlp", "ut_close", "exit_gate", "lm_head_loss")
UNATTRIBUTED = rl.UNATTRIBUTED


def reduce(devices: list):
    """Means over the chips; None where no chip shows a step, or the step is
    not this family's."""
    phases = OURO_DEVICE_PHASES + rl.STEP_BUILDER_PHASES
    steps = [s for s in (rg.by_scopes(d["ops"], d["modules"], phases) for d in devices) if s]
    opens = lambda name: any(s["by_phase"].get(name) for s in steps)
    if not steps or not (opens("ut_close") and opens("exit_gate")):
        return None
    mean = lambda key: sum(s[key] for s in steps) / len(steps)
    return {"runs": steps[0]["runs"], "step_s": mean("step_s"), "product_s": mean("product_s"),
            "kernel_s": mean("kernel_s"),
            "by_phase": rp._mean_dicts([s["by_phase"] for s in steps]),
            "by_kernel": rp._mean_dicts([s["by_kernel"] for s in steps])}


def tables(r: dict) -> list:
    total = r["step_s"]
    lines = [f"step device time {1e3 * total:.3f} ms a run over {r['runs']} runs, by the "
             f"Ouro model's scopes (product ops with the attention kernels: "
             f"{1e3 * r['product_s']:.3f} ms; under no scope: "
             f"{100 * r['by_phase'].get(UNATTRIBUTED, 0.0) / total:.2f}% of the step):"]
    for k, v in sorted(r["by_phase"].items(), key=lambda kv: -kv[1]):
        lines.append(f"  scope {k:<16} {1e3 * v:9.3f} ms {100 * v / total:6.2f}%")
    for k, v in sorted(r["by_kernel"].items()):
        lines.append(f"  kernel {k:<24} {1e3 * v:9.3f} ms {100 * v / total:6.2f}%")
    return lines


@functools.lru_cache(maxsize=2)
def load(path: str, n_devices: int):
    from benchmark.harness import log

    devices, _ = rp.read_xplane(path, n_devices)
    result = reduce(devices)
    for line in tables(result) if result else ():
        log("ouro scopes: " + line)
    return result


def for_run(ctx: dict):
    """The reduction of this run's trace, or None where the run made none or
    its step is not this family's."""
    from benchmark import harness

    if not ctx.get("trace") or not ctx.get("steps_traced"):
        return None
    path = rp.newest_trace(harness.OUT_DIR)
    return load(path, int(ctx.get("chips", 1))) if path else None


def phase_pct(ctx: dict, phases):
    r = for_run(ctx)
    if not r:
        return None
    return 100.0 * sum(r["by_phase"].get(p, 0.0) for p in phases) / r["step_s"]
