"""The comparisons that decide `correct`, each number beside its limit.

Limits come from the traffic file (`limits`), where PERF.md records the
readings they were set from. A number is a relative gap; a run is correct
when every number is at or under its limit and every required fact holds.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import log


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def worst_leaf_gap(got: dict, want: dict):
    """The largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    med = float(np.median(list(want.values())))
    worst, where = 0.0, None
    for k, w in want.items():
        gap = abs(got[k] - w) / max(w, med, 1e-30)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def worst_leaf_diff(got: dict, want: dict):
    """The largest norm of a leaf's difference, program minus reference,
    against the reference's norm of that leaf or of the median leaf. First
    order in the rounding error, where a gap between norms is second order:
    this is the number that tells bfloat16 from a precision below it."""
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    med = float(np.median(list(norms.values())))
    worst, where = 0.0, None
    for k, w in want.items():
        d = float(np.linalg.norm(np.asarray(got[k], np.float32) - w)) / max(norms[k], med, 1e-30)
        if d > worst:
            worst, where = d, k
    return worst, where


def compare_train(program: dict, reference: dict, limits: dict) -> dict:
    """`program` and `reference`: {"losses": [...], "first_grad_norms":
    {leaf: norm}, "delta_norms": {leaf: norm}}. The program reports the
    losses of the steps it logs (`loss_steps`, indices into the
    reference's)."""
    numbers = {}
    steps = program["loss_steps"]
    numbers["loss_gap"] = max(
        _rel(pl, reference["losses"][s])
        for pl, s in zip(program["losses"], steps))
    numbers["first_grad_norm_gap"], g_leaf = worst_leaf_gap(
        program["first_grad_norms"], reference["first_grad_norms"])
    numbers["first_grad_diff"], f_leaf = worst_leaf_diff(
        program["first_grad"], reference["first_grad"])
    numbers["param_delta_norm_gap"], d_leaf = worst_leaf_gap(
        program["delta_norms"], reference["delta_norms"])
    log(f"correct: losses program {program['losses']} reference "
        f"{[reference['losses'][s] for s in steps]} (steps {steps}); worst "
        f"gradient leaf {g_leaf} (norm) {f_leaf} (difference), worst delta leaf {d_leaf}")
    return judge(numbers, limits)


def compare_serve(samples: list, limits: dict) -> dict:
    """`samples`: [(served [n, L, d] float32, reference [n, L, d] float32)].
    The number compared is each request's relative RMS error over all its
    columns; the worst request decides."""
    errs = []
    for got, want in samples:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        errs.append(float(np.sqrt(np.mean((got - want) ** 2)
                                  / max(float(np.mean(want ** 2)), 1e-30))))
    numbers = {"columns_rel_rms_worst": max(errs) if errs else float("inf")}
    log(f"correct: {len(errs)} requests compared, rel rms median "
        f"{float(np.median(errs)) if errs else float('nan'):.6g}")
    return judge(numbers, limits)


def judge(numbers: dict, limits: dict) -> dict:
    ok = True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the traffic file")
        passed = bool(np.isfinite(value) and value <= limits[name])
        ok = ok and passed
        log(f"correct: {name} = {value:.6g}  limit {limits[name]:.6g}  "
            f"{'ok' if passed else 'OVER'}")
    return {"ok": ok, "numbers": numbers}


def require(fact: str, holds: bool) -> bool:
    log(f"correct: {fact}: {'ok' if holds else 'FAILED'}")
    return bool(holds)
