"""What decides `correct`: the numbers compared, each beside its limit, and
the facts required, each beside what was seen.

Limits come from `limits/<cell>.json`, where PERF.md records the readings
they were set from. A number is a relative gap; a run is correct when every
number is at or under its limit and every required fact holds. A `Verdict`
collects both as they are decided, logs each, and hands them to the result
line (`compared`, its last key) and to the run's last lines on standard
error.

The route requirement lives here too. A training run reports the route it
took through the program (`vjp_path`); `routes/<route>.json` says which
Mosaic kernels that route has to show in the step's trace and which it may
not, as shell patterns over the kernels' names (`pallas_call(name=)`, the
vocabulary of PERF.md section 3). A route the program gains later brings a
file of its own.
"""

from __future__ import annotations

import fnmatch
import json
import os

import numpy as np

from benchmark.harness import BENCH_DIR, log


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


def train_numbers(program: dict, reference: dict) -> dict:
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
    return numbers


def serve_numbers(samples: list) -> dict:
    """`samples`: [(served [n, L, d] float32, reference [n, L, d] float32)].
    The number compared is each request's relative RMS error over all its
    columns; the worst request decides."""
    errs = []
    for got, want in samples:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        errs.append(float(np.sqrt(np.mean((got - want) ** 2)
                                  / max(float(np.mean(want ** 2)), 1e-30))))
    log(f"correct: {len(errs)} requests compared, rel rms median "
        f"{float(np.median(errs)) if errs else float('nan'):.6g}")
    return {"columns_rel_rms_worst": max(errs) if errs else float("inf")}


class Verdict:
    """One run's `correct`, with everything it was decided from."""

    def __init__(self):
        self.ok = True
        self.compared = {}  # name -> {"value", "limit", "ok"}, in the order decided
        self.lines = []  # the same, as the lines the log and standard error get

    def number(self, name: str, value: float, limit: float) -> None:
        passed = bool(np.isfinite(value) and value <= limit)
        # a reading that is not finite goes into the line as text: json has no NaN
        self._add(name, float(value) if np.isfinite(value) else str(value), float(limit),
                  passed, f"{value:.6g}  limit {limit:.6g}  {'ok' if passed else 'OVER'}")

    def numbers(self, numbers: dict, limits: dict) -> None:
        for name, value in numbers.items():
            if name not in limits:
                raise KeyError(f"no limit for {name!r} in the cell's limits file")
            self.number(name, value, limits[name])

    def fact(self, name: str, saw, required, holds: bool) -> None:
        """A requirement that is no number: what the run showed (`saw`)
        beside what it has to show (`required`)."""
        self._add(name, saw, required, bool(holds),
                  f"{saw}  required: {required}  {'ok' if holds else 'FAILED'}")

    def _add(self, name, value, limit, passed, text) -> None:
        self.ok = self.ok and passed
        self.compared[name] = {"value": value, "limit": limit, "ok": passed}
        self.lines.append(f"correct: {name} = {text}")
        log(self.lines[-1])


def judge(numbers: dict, limits: dict) -> dict:
    v = Verdict()
    v.numbers(numbers, limits)
    return {"ok": v.ok, "numbers": numbers}


# ------------------------------------------------------------------ the route


def listed(expect) -> list:
    """`bench.expect_vjp_path` of a configuration file: absent (any route),
    one route, or a list of routes."""
    if expect is None:
        return []
    return [expect] if isinstance(expect, str) else list(expect)


def route_table(route: str):
    """`routes/<route>.json`: {"required": [patterns], "forbidden":
    [patterns]}, or None for a route the benchmark has no file of."""
    path = os.path.join(BENCH_DIR, "routes", f"{route}.json")
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        return json.load(fh)


def kernels_fit(names, table: dict):
    """(holds, what is wrong) for the kernel names of a traced step against
    one route's table: every required pattern matches some name, and no
    forbidden pattern matches any."""
    names = sorted(names)
    missing = [p for p in table["required"] if not fnmatch.filter(names, p)]
    found = sorted({n for p in table["forbidden"] for n in fnmatch.filter(names, p)})
    wrong = ([f"no kernel named {p}" for p in missing]
             + [f"kernel {n} does not belong to the route" for n in found])
    return not wrong, "; ".join(wrong)


def hold_route(verdict: Verdict, route: str, record_paths, expect, kernels=None) -> None:
    """The route requirement of a training run. Untraced (`kernels` None):
    every record of the window carries the route the trainer resolved when it
    was built, and the route is one the configuration admits. Traced: also,
    the step's device time by Mosaic kernel name shows the kernels of that
    route and none of another's, so that the name reported is what the
    device ran."""
    admits = listed(expect)
    verdict.fact("route", route, " or ".join(admits) if admits else "any",
                 not admits or route in admits)
    verdict.fact("records_vjp_path", " ".join(sorted(map(str, record_paths))),
                 f"only {route}", set(record_paths) == {route})
    if kernels is None:
        return
    table = route_table(route)
    saw = " ".join(sorted(kernels)) or "(no Mosaic kernel in the traced step)"
    if table is None:
        verdict.fact("route_kernels", saw, f"a file benchmark/routes/{route}.json", False)
        return
    fits, wrong = kernels_fit(kernels, table)
    need = "some " + ", ".join(table["required"])
    if table["forbidden"]:
        need += "; none of " + ", ".join(table["forbidden"])
    verdict.fact("route_kernels", saw + (f" ({wrong})" if wrong else ""), need, fits)
