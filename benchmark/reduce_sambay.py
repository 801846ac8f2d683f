"""The SambaY language-model step by the program's own scopes: which scope of
`glom_tpu.tracing.spans.SAMBAY_DEVICE_PHASES` (or of the step builder's
`optimizer` and `step_metrics`) each device op of the traced step belongs
to, and the device time of the ops that execute its matrix products.

Built on `reduce_phases.read_xplane` and `reduce_phases.step_ops`, as
`reduce_lm.py` is for the other language model; the vocabulary is copied
here so that this file reads a checkout without it and finds nothing rather
than failing (`tests/test_reduce_sambay.py` holds the copy to the original).
A step counts as this family's when it opens a scope only this family has.
The product ops are the dots and convolutions and the fusions whose output
is one.
"""

from __future__ import annotations

import functools
import re
from collections import defaultdict

from benchmark import reduce_phases as rp
from benchmark import reduce_trace as rt

SAMBAY_DEVICE_PHASES = ("embed", "mamba_in", "selective_scan", "mamba_out",
                        "window_attention", "full_attention", "cross_attention", "gmu",
                        "mlp", "lm_head_loss")
STEP_BUILDER_PHASES = ("optimizer", "step_metrics")
ATTENTION_PHASES = ("window_attention", "full_attention", "cross_attention")
OWN_PHASES = ("selective_scan",) + ATTENTION_PHASES + ("gmu",)  # no other family opens these
UNATTRIBUTED = "(no phase)"
_PHASES = frozenset(SAMBAY_DEVICE_PHASES + STEP_BUILDER_PHASES)
_WORD = re.compile(r"[A-Za-z0-9_]+")


@functools.lru_cache(maxsize=None)
def phase_of(op_name: str) -> str:
    """The innermost scope of the vocabulary in the op's `op_name`."""
    for w in reversed(_WORD.findall(op_name or "")):
        if w in _PHASES:
            return w
    return UNATTRIBUTED


def reduce_step(ops, modules):
    """One chip's step: seconds a run by scope, in the product ops, and by
    scope within the product ops. `ops`: (name, start_ns, duration_ns,
    op_name)."""
    leaf, n_runs = rp.step_ops(ops, modules)
    if not leaf:
        return None
    by_phase, product_by_phase = defaultdict(float), defaultdict(float)
    for name, _, d, op_name in leaf:
        phase = phase_of(op_name)
        by_phase[phase] += d
        if rt.has_matmul(name):
            product_by_phase[phase] += d
    scale = 1e-9 / n_runs
    return {"runs": n_runs,
            "step_s": sum(by_phase.values()) * scale,
            "product_s": sum(product_by_phase.values()) * scale,
            "by_phase": {k: v * scale for k, v in by_phase.items()},
            "product_by_phase": {k: v * scale for k, v in product_by_phase.items()}}


def reduce(devices: list):
    """Means over the chips; None where no chip shows a step, or the step
    opens none of the scopes that are this family's alone."""
    steps = [s for s in (reduce_step(d["ops"], d["modules"]) for d in devices) if s]
    if not steps or not any(s["by_phase"].get(p) for s in steps for p in OWN_PHASES):
        return None
    mean = lambda key: sum(s[key] for s in steps) / len(steps)
    return {"runs": steps[0]["runs"], "step_s": mean("step_s"), "product_s": mean("product_s"),
            "by_phase": rp._mean_dicts([s["by_phase"] for s in steps]),
            "product_by_phase": rp._mean_dicts([s["product_by_phase"] for s in steps])}


def tables(r: dict) -> list:
    total = r["step_s"]
    lines = [f"step device time {1e3 * total:.3f} ms a run over {r['runs']} runs, by the "
             f"SambaY model's scopes (product ops: {1e3 * r['product_s']:.3f} ms; under no "
             f"scope: {100 * r['by_phase'].get(UNATTRIBUTED, 0.0) / total:.2f}% of the step, "
             "the true share where step_unattributed_pct.train reads GLOM's vocabulary):"]
    for k, v in sorted(r["by_phase"].items(), key=lambda kv: -kv[1]):
        lines.append(f"  scope {k:<16} {1e3 * v:9.3f} ms {100 * v / total:6.2f}%   products "
                     f"{1e3 * r['product_by_phase'].get(k, 0.0):9.3f} ms")
    return lines


@functools.lru_cache(maxsize=2)
def load(path: str, n_devices: int):
    from benchmark.harness import log

    devices, _ = rp.read_xplane(path, n_devices)
    result = reduce(devices)
    for line in tables(result) if result else ():
        log("sambay scopes: " + line)
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
