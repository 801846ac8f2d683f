"""The Laguna language-model step by the program's own scopes: which scope of
`glom_tpu.tracing.spans.LAGUNA_DEVICE_PHASES` (or of the step builder's
`optimizer` and `step_metrics`) each device op of the traced step belongs
to, the device time of the ops that execute its matrix products, and that of
the attention kernels' calls.

`reduce_lm.py`'s reduction reads its own family's tuple from a module
constant, so this is the smallest file that reads another: `by_scopes` takes
the tuple (a later family can pass its own), and everything that knows no
family is `reduce_lm`'s and `reduce_phases`'. The vocabulary is copied here so
that this file reads a checkout without it and finds nothing rather than
failing (`tests/test_reduce_laguna.py` holds the copy to the original). A
step counts as this family's when it opens an attention scope of the two
kinds and a routed part's. The product ops are the dots and convolutions, the
fusions whose output is one, the compiler's `ragged-dot-*` calls AND the
`attn_flash_*` kernels (PERF.md trap 14: the scores' products run inside them).
"""

from __future__ import annotations

import functools
from collections import defaultdict

from benchmark import reduce_lm as rl
from benchmark import reduce_phases as rp
from benchmark import reduce_trace as rt

LAGUNA_DEVICE_PHASES = ("embed", "window_attention", "full_attention", "dense_mlp",
                        "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
                        "moe_shared", "lm_head_loss")
ATTENTION_PHASES = ("window_attention", "full_attention")
MOE_ROUTED_PHASES = rl.MOE_ROUTED_PHASES
ATTENTION_KERNELS = "attn_flash"   # the prefix of the attention kernels' names
UNATTRIBUTED = rl.UNATTRIBUTED


def is_attention_kernel(name: str) -> bool:
    return rt.MOSAIC in name and rp.instruction(name).startswith(ATTENTION_KERNELS)


def by_scopes(ops, modules, phases):
    """One chip's step: seconds a run by the innermost scope of `phases` in
    each op's `op_name` (the compiler's ragged-dot calls, which carry none,
    are the experts'), in the product ops, and in the attention kernels by
    their names. `ops`: (name, start_ns, duration_ns, op_name)."""
    leaf, n_runs = rp.step_ops(ops, modules)
    if not leaf:
        return None
    known = frozenset(phases)
    by_phase, by_kernel = defaultdict(float), defaultdict(float)
    product = 0.0
    for name, _, d, op_name in leaf:
        phase = next((w for w in reversed(rl._WORD.findall(op_name or "")) if w in known),
                     "moe_experts" if rl.is_ragged_dot(name) else UNATTRIBUTED)
        by_phase[phase] += d
        kernel = is_attention_kernel(name)
        if kernel:
            by_kernel[rp.instruction(name)] += d
        if kernel or rl.is_product(name):
            product += d
    scale = 1e-9 / n_runs
    return {"runs": n_runs, "step_s": sum(by_phase.values()) * scale,
            "product_s": product * scale, "kernel_s": sum(by_kernel.values()) * scale,
            "by_phase": {k: v * scale for k, v in by_phase.items()},
            "by_kernel": {k: v * scale for k, v in by_kernel.items()}}


def reduce(devices: list):
    """Means over the chips; None where no chip shows a step, or the step is
    not this family's."""
    phases = LAGUNA_DEVICE_PHASES + rl.STEP_BUILDER_PHASES
    steps = [s for s in (by_scopes(d["ops"], d["modules"], phases) for d in devices) if s]
    opens = lambda names: any(s["by_phase"].get(p) for s in steps for p in names)
    if not steps or not (opens(ATTENTION_PHASES) and opens(MOE_ROUTED_PHASES)):
        return None
    mean = lambda key: sum(s[key] for s in steps) / len(steps)
    return {"runs": steps[0]["runs"], "step_s": mean("step_s"), "product_s": mean("product_s"),
            "kernel_s": mean("kernel_s"),
            "by_phase": rp._mean_dicts([s["by_phase"] for s in steps]),
            "by_kernel": rp._mean_dicts([s["by_kernel"] for s in steps])}


def tables(r: dict) -> list:
    total = r["step_s"]
    lines = [f"step device time {1e3 * total:.3f} ms a run over {r['runs']} runs, by the "
             f"Laguna model's scopes (product ops with the attention kernels: "
             f"{1e3 * r['product_s']:.3f} ms; under no scope: "
             f"{100 * r['by_phase'].get(UNATTRIBUTED, 0.0) / total:.2f}% of the step, the true "
             "share where step_unattributed_pct.train reads GLOM's vocabulary):"]
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
        log("laguna scopes: " + line)
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
