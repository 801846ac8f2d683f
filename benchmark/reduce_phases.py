"""A traced run by the program's own phases: which phase of the vocabulary
each device op of the training step belongs to, and which of the program's
host spans was open while the device sat idle.

What a v5e trace of the program holds beyond what `reduce_trace.py` reads
(looked at by hand, PR 24):

* Every `XLA Ops` event's *metadata* (not the event, so `ProfileData` does
  not show it) has a `tf_op` stat: the instruction's `op_name`, the path of
  JAX transforms and `jax.named_scope`s it was traced under, e.g.
  `jit(train_step)/transpose(jvp(loop))/while/body/closed_call/`
  `consensus_update/blij,bjld->bild/dot_general:`. 99.7% of the step's
  device time is in events that carry it (the rest: async copy and slice
  halves the compiler made). A Mosaic call's instruction is named by the
  kernel's `name=`.
* `/host:CPU` has one line per host thread; the program's spans are there as
  `TraceAnnotation`s under their own names (`tracing/spans.py:span`), with
  the loop's step index as the `step` stat.

The vocabulary is the program's (`glom_tpu.tracing.spans.PHASES`), copied
here so that this file reads a checkout without it (the parent of PR 24)
and finds nothing rather than failing; `tests/test_reduce_phases.py` holds
the copy to the original.

Pure functions over lists of tuples, plus a loader that is cached so that
the ten readers of `layer_metrics/` parse the file once.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from collections import defaultdict

from benchmark import reduce_trace as rt

HOST_PHASES = ("host_data_next", "host_step_dispatch", "host_log_fetch",
               "host_prefetch_next", "host_prefetch_stage")
DEVICE_PHASES = ("noise", "image_to_tokens", "loop", "bottom_up", "top_down", "ffw",
                 "consensus", "mean_update", "consensus_update", "reconstruction",
                 "grad_reduce", "optimizer", "step_metrics")
# A program that opens none of these scopes does not speak the vocabulary
# (the parent of PR 24 has only the forward's), and the readers of device
# phases then have nothing to read.
STEP_BUILDER_PHASES = ("noise", "optimizer", "step_metrics", "grad_reduce")
COLLECTIVE = "(collective)"
UNATTRIBUTED = "(no phase)"
NO_SPAN = "(no program span open)"
_WORD = re.compile(r"[A-Za-z0-9_]+")
_LONGEST_FIRST = sorted(DEVICE_PHASES, key=len, reverse=True)  # consensus_update before consensus


# ------------------------------------------------------------- device phases


def instruction(name: str) -> str:
    """`%loop_ffw_fwd.3 = bf16[...] custom-call(...)` -> `loop_ffw_fwd`."""
    return re.sub(r"\.\d+$", "", name.partition(" = ")[0].strip().lstrip("%"))


@functools.lru_cache(maxsize=None)  # a step's ops repeat run after run
def phase_of(op_name: str, name: str) -> str:
    """The innermost phase of the vocabulary in the op's `op_name`; failing
    that the phase a Mosaic kernel's own name starts with; failing that a
    collective (found by opcode) is the distributed layer's; the rest
    belongs to no phase."""
    words = _WORD.findall(op_name or "")
    for w in reversed(words):
        if w in DEVICE_PHASES:
            return w
    if rt.MOSAIC in name:
        inst = instruction(name)
        for p in _LONGEST_FIRST:
            if inst == p or inst.startswith(p + "_"):
                return p
    if rt.is_collective(name):
        return COLLECTIVE
    return UNATTRIBUTED


def primitive(op_name: str) -> str:
    """Direction and last component of an `op_name`:
    `jit(f)/transpose(jvp())/consensus_update/blij,bjld->bild/dot_general:` ->
    `bwd blij,bjld->bild/dot_general`."""
    parts = [p for p in (op_name or "").rstrip(":").split("/") if p]
    last = "/".join(parts[-2:]) if len(parts) > 1 and "->" in parts[-2] else (
        parts[-1] if parts else "?")
    return ("bwd " if "transpose(" in (op_name or "") else "fwd ") + last


def step_ops(ops, modules):
    """Leaf ops of the complete runs of the step program (the module that
    took most of the traced time; a run cut by the trace's edge, shorter
    than 0.9 of the median, is left out). Returns (ops, number of runs)."""
    by_module = defaultdict(list)
    for n, s, d in modules:
        by_module[n.partition("(")[0]].append((s, d))
    if not by_module:
        return [], 0
    runs = max(by_module.values(), key=lambda r: sum(d for _, d in r))
    med = sorted(d for _, d in runs)[len(runs) // 2]
    runs = sorted((s, s + d) for s, d in runs if d >= 0.9 * med)
    out, j = [], 0
    for op in sorted(ops, key=lambda o: o[1]):
        while j < len(runs) and runs[j][1] <= op[1]:
            j += 1
        if j < len(runs) and runs[j][0] <= op[1] and rt.opcode(op[0]) not in rt.CONTAINER_OPS:
            out.append(op)
    return out, len(runs)


def reduce_step(ops, modules) -> dict:
    """One chip's step by phase and by kernel name. `ops` is a list of
    (name, start_ns, duration_ns, op_name); times come out in seconds per
    run of the step program."""
    leaf, n_runs = step_ops(ops, modules)
    if not leaf:
        return None
    by_phase, by_kernel, detail = defaultdict(float), defaultdict(float), defaultdict(float)
    for name, _, d, op_name in leaf:
        phase = phase_of(op_name, name)
        by_phase[phase] += d
        if rt.MOSAIC in name:
            by_kernel[instruction(name)] += d
        detail[(phase, primitive(op_name))] += d
    scale = 1e-9 / n_runs
    return {
        "runs": n_runs,
        "step_s": sum(by_phase.values()) * scale,
        "by_phase": {k: v * scale for k, v in by_phase.items()},
        "by_kernel": {k: v * scale for k, v in by_kernel.items()},
        "detail": {k: v * scale for k, v in detail.items()},
    }


# ---------------------------------------------------------------- idle time


def idle_gaps(ops):
    """The device's idle intervals of at least 20 us (as `reduce_trace`)
    between its first op's start and its last op's end, and that window."""
    busy = rt.union([(s, s + d) for _, s, d, *_ in ops if d > 0])
    if not busy:
        return [], 0.0
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])
            if s1 - e0 >= rt._MIN_GAP_NS]
    return gaps, float(busy[-1][1] - busy[0][0])


def attribute_idle(gaps, window_ns: float, spans) -> dict:
    """Each idle gap goes to the program span open at its midpoint: the fit
    loop's (it is the loop that feeds the device), failing that the prefetch
    worker's, the shortest where several are open; `spans` is a list of
    (name, start_ns, duration_ns, ...). The time the loop waited for data is
    measured exactly: the overlap of the gaps with `host_data_next`.
    `idle_s` is the idle time from the loop's first span in the trace on."""
    rank = lambda sp: (sp[0].startswith("host_prefetch_"), sp[2])
    # A trace opens in the middle of the loop: what idles before the loop's
    # first span in it is the profiler's own start, not the program's to name.
    first = min((sp[1] for sp in spans if not rank(sp)[0]), default=None)
    by_span = defaultdict(float)
    for g0, g1 in gaps:
        if first is not None and g1 <= first:
            continue
        mid = (g0 + g1) / 2
        open_ = [sp for sp in spans if sp[1] <= mid <= sp[1] + sp[2]]
        by_span[min(open_, key=rank)[0] if open_ else NO_SPAN] += g1 - g0
    idle = sum(by_span.values())
    waits = rt.union([(s, s + d) for n, s, d, *_ in spans if n == "host_data_next"])
    overlap = rt.total(gaps) - rt.total(rt.subtract(gaps, waits)) if gaps else 0.0
    return {
        "window_s": window_ns / 1e9,
        "idle_s": idle / 1e9,
        "attributed_s": (idle - by_span.get(NO_SPAN, 0.0)) / 1e9,
        "data_wait_s": overlap / 1e9,
        "by_span": {k: v / 1e9 for k, v in by_span.items()},
    }


# ------------------------------------------------------------ the reduction


def _mean_dicts(dicts):
    keys = {k for d in dicts for k in d}
    return {k: sum(d.get(k, 0.0) for d in dicts) / len(dicts) for k in keys}


def reduce_phases(devices: list, spans: list) -> dict:
    """`devices`: per chip {"ops": [(name, start, dur, op_name)], "modules":
    [(name, start, dur)]}; `spans`: the program's host spans. Means over the
    chips."""
    steps = [s for s in (reduce_step(d["ops"], d["modules"]) for d in devices) if s]
    idles = [attribute_idle(*idle_gaps(d["ops"]), spans) for d in devices if d["ops"]]
    out = {"step": None, "idle": None, "n_spans": len(spans)}
    if steps:
        out["step"] = {
            "runs": steps[0]["runs"],
            "step_s": sum(s["step_s"] for s in steps) / len(steps),
            "by_phase": _mean_dicts([s["by_phase"] for s in steps]),
            "by_kernel": _mean_dicts([s["by_kernel"] for s in steps]),
            "detail": _mean_dicts([s["detail"] for s in steps]),
        }
        out["speaks_vocabulary"] = any(
            out["step"]["by_phase"].get(p) for p in STEP_BUILDER_PHASES)
    if idles:
        out["idle"] = {k: sum(i[k] for i in idles) / len(idles)
                       for k in ("window_s", "idle_s", "attributed_s", "data_wait_s")}
        out["idle"]["by_span"] = _mean_dicts([i["by_span"] for i in idles])
    return out


def tables(r: dict) -> list:
    """The reduction as lines for the run's log: step device time by phase,
    by kernel name, within the larger phases by direction and primitive, and
    idle time by the program's spans. Whole tables, in ms a step."""
    lines = []
    step = r.get("step")
    if step:
        total = step["step_s"]
        lines.append(f"step device time {1e3 * total:.3f} ms a run over {step['runs']} "
                     f"runs, by phase (sums to the step):")
        for k, v in sorted(step["by_phase"].items(), key=lambda kv: -kv[1]):
            lines.append(f"  phase {k:<18} {1e3 * v:9.3f} ms {100 * v / total:6.2f}%")
        lines.append("step device time by kernel name (Mosaic calls):")
        for k, v in sorted(step["by_kernel"].items(), key=lambda kv: -kv[1]):
            lines.append(f"  kernel {k:<30} {1e3 * v:9.3f} ms {100 * v / total:6.2f}%")
        lines.append("within each phase of 1% or more, by direction and primitive:")
        for (ph, prim), v in sorted(step["detail"].items(), key=lambda kv: -kv[1]):
            if step["by_phase"][ph] >= 0.01 * total and v >= 0.001 * total:
                lines.append(f"  {ph:<18} {prim:<44} {1e3 * v:9.3f} ms "
                             f"{100 * v / total:6.2f}%")
    idle = r.get("idle")
    if idle:
        lines.append(f"device idle {1e3 * idle['idle_s']:.3f} ms of a {idle['window_s']:.3f} s "
                     f"window, by the program span open at each gap's midpoint "
                     f"(waiting in host_data_next: {1e3 * idle['data_wait_s']:.3f} ms):")
        for k, v in sorted(idle["by_span"].items(), key=lambda kv: -kv[1]):
            lines.append(f"  idle {k:<24} {1e3 * v:9.3f} ms")
    return lines


# ------------------------------------------------------------------- loader


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message on the wire: an int for
    a varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an XSpace")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def op_names(path: str) -> dict:
    """{device plane name: {instruction text: op_name}} from the raw XSpace.
    `jax.profiler.ProfileData` gives an event's own stats, not its
    metadata's, and `tf_op` is a metadata stat; so the file is walked on the
    wire by the field numbers of tsl's xplane.proto (XSpace.planes=1;
    XPlane.name=2, event_metadata=4, stat_metadata=5; map entry value=2;
    XEventMetadata.name=2, stats=5; XStat.metadata_id=1, str_value=5,
    ref_value=7; XStatMetadata.id=1, name=2). Lines and events are skipped,
    not parsed."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        plane_name, events, stats = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                plane_name = bytes(v).decode()
            elif pf == 4:
                events.extend(ev for ef, ev in _fields(v) if ef == 2)
            elif pf == 5:
                for ef, sm in _fields(v):
                    if ef == 2:
                        d = dict(_fields(sm))
                        stats[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        if not plane_name.startswith("/device:"):
            continue
        tf_op = next((k for k, n in stats.items() if n == "tf_op"), None)
        names = {}
        for ev in events:
            text, path_ = "", None
            for ef, v in _fields(ev):
                if ef == 2:
                    text = bytes(v).decode()
                elif ef == 5:
                    st = dict(_fields(v))
                    if st.get(1) == tf_op:
                        path_ = (bytes(st[5]).decode() if 5 in st
                                 else stats.get(st.get(7), ""))
            if path_ is not None:
                names[text] = path_
        out[plane_name] = names
    return out


def read_xplane(path: str, n_devices: int):
    """(devices, spans): per chip the `XLA Ops` with their `op_name` and the
    `XLA Modules`; from the host's threads the program's spans as (name,
    start_ns, duration_ns, thread, step)."""
    from jax.profiler import ProfileData

    paths = op_names(path)
    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            names = paths.get(plane.name, {})
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [(e.name, e.start_ns, e.duration_ns, names.get(e.name, ""))
                                  for e in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_PHASES:
                        step = dict(e.stats).get("step")
                        spans.append((e.name, e.start_ns, e.duration_ns, line.name,
                                      None if step is None else int(step)))
    return [devices[i] for i in sorted(devices)][:n_devices], spans


def newest_trace(out_dir: str):
    """The run's trace: `ctx` carries no path, the harness writes each cell's
    trace under `out/trace/<cell>/`, and a traced run wrote its own last."""
    found = glob.glob(os.path.join(out_dir, "trace", "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def load(path: str, n_devices: int) -> dict:
    from benchmark.harness import log

    result = reduce_phases(*read_xplane(path, n_devices))
    for line in tables(result):
        log("phases: " + line)
    return result


def for_run(ctx: dict):
    """The phases of this run's trace, or None where the run made none."""
    from benchmark import harness

    if not ctx.get("trace") or not ctx.get("steps_traced"):
        return None
    path = newest_trace(harness.OUT_DIR)
    return load(path, int(ctx.get("chips", 1))) if path else None


def idle_of(ctx: dict):
    """The run's idle-time attribution, or None where there is no trace or
    the trace holds none of the program's spans."""
    r = for_run(ctx)
    return r["idle"] if r and r.get("idle") and r["n_spans"] else None


def phase_pct(ctx: dict, phases) -> float:
    """Share of the step program's device time under `phases`, in %; None
    where there is no trace or the program opens no step-builder scope."""
    r = for_run(ctx)
    if not r or not r.get("step") or not r.get("speaks_vocabulary"):
        return None
    step = r["step"]
    return 100.0 * sum(step["by_phase"].get(p, 0.0) for p in phases) / step["step_s"]


def span_ms(ctx: dict, name: str, per: str):
    """Milliseconds in the program's span `name` over the window, from its
    rollup records: per step of the loop, or per occurrence (`per="count"`)."""
    recs = [r for r in ctx.get("records", ())
            if r.get("kind") == "span" and r.get("name") == name]
    n = ctx.get("steps") if per == "step" else sum(r.get("count", 0) for r in recs)
    if not recs or not n:
        return None
    return 1e3 * sum(r["dur_s"] for r in recs) / n
