"""One reduction from a profiler trace (`.xplane.pb`) to numbers.

What a v5e trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<i>`, with the lines `XLA Modules` (one event per program
run), `XLA Ops` (one event per HLO instruction run, named by the whole
instruction text; a `while` holds its body's events inside its own span)
and `Steps`; and `/host:CPU` with a line per host thread (Python frames as
`$file:line fn`, the profiler's own `TraceAnnotation`s by name).

From them, per chip and then averaged over the chips used:

* busy: the union of the `XLA Ops` intervals; the window runs from the
  first op's start to the last op's end; idle is the rest.
* Mosaic time: instructions whose text says
  `custom_call_target="tpu_custom_call"` (every Pallas kernel).
* collective time: all-reduce, all-gather, reduce-scatter, all-to-all and
  collective-permute instructions (sync or the `-start`/`-done` pair's
  span on the `Async XLA Ops` line), and the part of it with no other op
  running.
* matmuls outside Mosaic: `convolution`/`dot` instructions and fusions
  that contain one are reported by name, so a roofline numerator's FLOPs
  are set against every op that executes them.
* per-program time: median duration of the runs of the program that took
  most of the time.
* the longest idle gaps, each labelled by the innermost host event open
  at its midpoint.

Pure functions over lists of (name, start_ns, duration_ns), so the tests
run them on a small recorded trace.
"""

from __future__ import annotations

import re
import statistics
from collections import defaultdict

MOSAIC = 'custom_call_target="tpu_custom_call"'
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")
CONTAINER_OPS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_MIN_GAP_NS = 20_000


def opcode(name: str) -> str:
    """The HLO opcode of an `XLA Ops` event name
    (`%x.1 = f32[..]{..} fusion(...)` -> `fusion`)."""
    _, _, rhs = name.partition(" = ")
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else ""


def short_name(name: str) -> str:
    """`%transpose_jvp___.37 = ... custom-call(...tpu_custom_call...)` ->
    `transpose_jvp___ custom-call:tpu_custom_call`."""
    inst = name.partition(" = ")[0].lstrip("%")
    inst = re.sub(r"\.\d+$", "", inst)
    op = opcode(name)
    if op == "custom-call":
        m = re.search(r'custom_call_target="([^"]+)"', name)
        op += ":" + (m.group(1) if m else "?")
    return f"{inst} {op}".strip()


def is_collective(name: str) -> bool:
    op = opcode(name)
    return any(op == c or op == c + "-start" or op == c + "-done"
               for c in COLLECTIVE_OPS)


def has_matmul(name: str) -> bool:
    op = opcode(name)
    if op in ("convolution", "dot"):
        return True
    return op == "fusion" and ("convolution" in name or "kind=kOutput" in name)


def union(intervals):
    """Merged, sorted [(start, end)] of possibly nested or overlapping
    intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Parts of the merged intervals `a` not covered by the merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def reduce_device(ops, async_ops, modules):
    """One chip's lines -> its numbers. Each argument is a list of
    (name, start_ns, duration_ns)."""
    iv = [(s, s + d) for _, s, d in ops if d > 0]
    busy = union(iv)
    if not busy:
        return None
    start, end = busy[0][0], busy[-1][1]
    leaf = [(n, s, d) for n, s, d in ops if opcode(n) not in CONTAINER_OPS]
    mosaic = [(s, s + d) for n, s, d in leaf if MOSAIC in n]
    coll = [(s, s + d) for n, s, d in list(leaf) + list(async_ops) if is_collective(n)]
    coll_u = union(coll)
    other = union([(s, s + d) for n, s, d in leaf if not is_collective(n)])
    by_name = defaultdict(float)
    for n, s, d in leaf:
        by_name[short_name(n)] += d
    matmul_outside = defaultdict(float)
    for n, s, d in leaf:
        if MOSAIC not in n and has_matmul(n):
            matmul_outside[short_name(n)] += d
    by_module = defaultdict(list)
    for n, s, d in modules:
        by_module[n.partition("(")[0]].append(d)
    main = max(by_module.items(), key=lambda kv: sum(kv[1]), default=(None, []))
    per_run = _per_run(modules, main[0], leaf) if main[1] else {}
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])
            if s1 - e0 >= _MIN_GAP_NS]
    return {
        "window_s": (end - start) / 1e9,
        "busy_s": total(busy) / 1e9,
        "mosaic_s": total(union(mosaic)) / 1e9,
        "collective_s": total(coll_u) / 1e9,
        "collective_exposed_s": total(subtract(coll_u, other)) / 1e9,
        "matmul_outside_mosaic_s": sum(matmul_outside.values()) / 1e9,
        "matmul_outside_mosaic": sorted(((k, v / 1e9) for k, v in matmul_outside.items()),
                                        key=lambda kv: -kv[1])[:10],
        "main_module": main[0],
        "main_module_runs": len(main[1]),
        "main_module_median_s": (statistics.median(main[1]) / 1e9 if main[1] else None),
        "main_module_mosaic_median_s": per_run.get("mosaic"),
        "main_module_matmul_outside_median_s": per_run.get("matmul_outside"),
        "main_module_collective_median_s": per_run.get("collective"),
        "top_ops": sorted(((k, v / 1e9) for k, v in by_name.items()),
                          key=lambda kv: -kv[1])[:10],
        "gaps": sorted(gaps, key=lambda g: g[0] - g[1])[:10],
    }


def _per_run(modules, main_name, leaf) -> dict:
    """Median over the complete runs of the main program of the time its
    Mosaic calls, its other matmuls and its collectives took. A run cut by
    the trace's edge (shorter than 0.9 of the median) is left out."""
    runs = [(s, d) for n, s, d in modules if n.partition("(")[0] == main_name]
    med = statistics.median(d for _, d in runs)
    runs = [(s, d) for s, d in runs if d >= 0.9 * med]
    sums = {"mosaic": [], "matmul_outside": [], "collective": []}
    for s, d in runs:
        acc = dict.fromkeys(sums, 0.0)
        for n, os_, od in leaf:
            if s <= os_ < s + d:
                if MOSAIC in n:
                    acc["mosaic"] += od
                elif is_collective(n):
                    acc["collective"] += od
                elif has_matmul(n):
                    acc["matmul_outside"] += od
        for k in sums:
            sums[k].append(acc[k])
    return {k: statistics.median(v) / 1e9 for k, v in sums.items() if v}


def label_gap(gap, host_events) -> str:
    """The innermost host event (shortest one) open at the gap's midpoint;
    `host_events` is a list of (name, start_ns, duration_ns)."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for n, s, d in host_events:
        if s <= mid <= s + d and (best is None or d < best[1]):
            best = (n, d)
    return best[0] if best else "(no host event open)"


def reduce_lines(devices: list, host_events: list) -> dict:
    """`devices`: per chip {"ops": [...], "async": [...], "modules": [...]}."""
    per = [r for r in (reduce_device(d["ops"], d.get("async", []), d.get("modules", []))
                       for d in devices) if r is not None]
    if not per:
        return None
    mean = lambda key: sum(r[key] for r in per) / len(per)
    fullest = max(per, key=lambda r: r["busy_s"])
    top = defaultdict(float)
    for r in per:
        for k, v in r["top_ops"]:
            top[k] += v / len(per)
    gaps = [[label_gap(g, host_events), (g[1] - g[0]) / 1e9] for g in fullest["gaps"]]
    out = {
        "n_devices": len(per),
        "busy_s": mean("busy_s"), "window_s": mean("window_s"),
        "mosaic_s": mean("mosaic_s"), "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "matmul_outside_mosaic_s": mean("matmul_outside_mosaic_s"),
        "matmul_outside_mosaic": fullest["matmul_outside_mosaic"],
        "main_module": fullest["main_module"],
        "main_module_runs": fullest["main_module_runs"],
        "main_module_median_s": fullest["main_module_median_s"],
        "main_module_mosaic_median_s": fullest["main_module_mosaic_median_s"],
        "main_module_matmul_outside_median_s": fullest["main_module_matmul_outside_median_s"],
        "main_module_collective_median_s": fullest["main_module_collective_median_s"],
        "top_ops": [[k, v] for k, v in sorted(top.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": gaps,
    }
    out["summary"] = {k: out[k] for k in (
        "n_devices", "busy_s", "window_s", "mosaic_s", "collective_s",
        "collective_exposed_s", "matmul_outside_mosaic_s", "main_module",
        "main_module_runs", "main_module_median_s", "main_module_mosaic_median_s",
        "main_module_matmul_outside_median_s", "main_module_collective_median_s",
        "matmul_outside_mosaic")}
    return out


def read_xplane(path: str, n_devices: int):
    """(devices, host_events) from an .xplane.pb, with JAX alone."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "Async XLA Ops": "async",
                       "XLA Modules": "modules"}.get(line.name)
                if key:
                    lines[key] = [(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events]
            devices[int(m.group(1))] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    used = [devices[i] for i in sorted(devices)][:n_devices]
    return used, host


def reduce_xplane(path: str, n_devices: int = 1):
    devices, host = read_xplane(path, n_devices)
    # Only host events long enough to explain a gap are worth the search.
    host = [h for h in host if h[2] >= _MIN_GAP_NS]
    return reduce_lines(devices, host)
