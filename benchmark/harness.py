"""What every cell shares: finding a cell's files by name, the device
check, the compile counter, the profiler window, peak memory, the
per-layer readers and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own (configs/, traffic/,
layer_metrics/), found by the name `BENCHMARK.json` gives. A later PR adds
files and entries and edits nothing here.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")


class Refused(SystemExit):
    """The run cannot be a measurement (no chip, too few chips, an unknown
    device): exit non-zero, print no result line."""

    def __init__(self, why: str):
        print(f"benchmark: refused: {why}", file=sys.stderr)
        super().__init__(3)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    """Earlier lines of a run: free text on stdout (the last line is the
    contract's JSON object and nothing else), stamped with the seconds since
    the harness was imported."""
    print(f"[bench {time.perf_counter() - _T0:7.2f}] {msg}", flush=True)


# ----------------------------------------------------------------- manifest


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_cell(name: str) -> dict:
    """The cell's entry with its configuration and traffic files read in,
    and the names of the metrics it reports. A cell that is not (yet) in
    `BENCHMARK.json` may wait in `parked/<name>.json`, which holds the
    entries a benchmark PR would move there: `workload`, and the
    `end_to_end` and `per_layer` metrics the manifest lacks (or, for a
    metric that is there, the `workloads` it gains)."""
    man = load_manifest()
    parked = os.path.join(BENCH_DIR, "parked", name + ".json")
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells and os.path.exists(parked):
        with open(parked) as fh:
            extra = json.load(fh)
        cells[name] = extra["workload"]
        for group in ("end_to_end", "per_layer"):
            have = {m["name"]: m for m in man[group]}
            for m in extra.get(group, []):
                if m["name"] not in have:
                    man[group].append(m)
                elif "workloads" in have[m["name"]]:  # a metric that is there: join it
                    have[m["name"]]["workloads"] += m.get("workloads", [])
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"or benchmark/parked/ (have: {sorted(cells)})")
    cell = dict(cells[name])
    cfg_entry = {c["name"]: c for c in man["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as fh:
        cell["config_file"] = json.load(fh)
    with open(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")) as fh:
        cell["traffic_file"] = json.load(fh)
    with open(os.path.join(BENCH_DIR, "limits", name + ".json")) as fh:
        cell["limits"] = json.load(fh)["limits"]

    def wanted(metric):
        return "workloads" not in metric or name in metric["workloads"]

    cell["end_to_end"] = [m for m in man["end_to_end"] if wanted(m)]
    e2e_names = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in man["per_layer"]
                         if wanted(m) and m["moves"] in e2e_names]
    return cell


# ------------------------------------------------------------------- device


def check_device(dev: dict, chips: int) -> None:
    """A run is a measurement only on a TPU the table of peaks knows, with
    the chips the cell asks for. (The benchmark's own tests replace this one
    function to drive the rest of a run on the CPU.)"""
    from benchmark.peaks import peaks_for

    if dev["platform"] != "tpu":
        raise Refused(f"needs a TPU, jax.devices()[0].platform is "
                      f"{dev['platform']!r}")
    if dev["count"] < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX reports "
                      f"{dev['count']}")
    peaks_for(dev["kind"])  # an unknown device is an error


def start_jax(chips: int) -> dict:
    """Import JAX, place the compile cache the way the program's entry
    points do, and check the device. Returns the device record."""
    import jax

    from glom_tpu.utils.startup import enable_compile_cache

    cache_dir = enable_compile_cache()
    # Cache every program, also the ones that compile in under a second, so
    # that only a checkout's first run compiles anything.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device {dev} compile cache {cache_dir}")
    check_device(dev, chips)
    dev["count"] = min(dev["count"], chips)
    return dev


def memory_peak_bytes(n_devices: int) -> int:
    """Peak bytes on the fullest chip. The TPU allocator counts a running
    program's scratch as *reserved*, not *in use* (a 7.9 GB training step
    leaves peak_bytes_in_use at 0.5 GB and peak_bytes_reserved at 7.3 GB),
    so the peak is the sum of the two."""
    import jax

    peak = 0
    for d in jax.local_devices()[:n_devices]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


# ----------------------------------------------------------------- compiles


class CompileCounter:
    """Counts programs JAX had to build or load: a jit-cache miss either
    compiles (backend_compile_duration) or reads the persistent cache
    (cache_hits). Inside the measured window both must stay at 0."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


# ------------------------------------------------------------------ tracing


class StepWindow:
    """The profiler around units [first, last] of a train loop, each unit a
    `StepTraceAnnotation`. Same interface as the program's
    `tracing.capture.TraceCapture` (fit's `trace_capture=`), which starts
    the trace without options; this one is the benchmark's so that the trace
    directory and its clean-up are too."""

    def __init__(self, first: int, last: int, trace_dir: str):
        self.first, self.last, self.trace_dir = first, last, trace_dir
        self._count = 0
        self._active = False
        self.steps_traced = 0

    @contextlib.contextmanager
    def unit(self):
        import jax

        i = self._count
        if not self._active and i == self.first:
            start_trace(self.trace_dir)
            self._active = True
        ann = (jax.profiler.StepTraceAnnotation("step", step_num=i)
               if self._active else contextlib.nullcontext())
        try:
            with ann:
                yield i
        finally:
            self._count += 1
            if self._active:
                self.steps_traced += 1

    def stop_if_due(self) -> bool:
        """Close the window once unit `last` is past. Called between spans,
        after the span's loss fetch, so the device has finished the traced
        steps."""
        import jax

        if self._active and self._count > self.last:
            jax.profiler.stop_trace()
            self._active = False
            return True
        return False

    def close(self):
        import jax

        if self._active:
            jax.profiler.stop_trace()
            self._active = False


def start_trace(trace_dir: str) -> None:
    """Open the profiler without the Python tracer: with it, opening the
    trace in a process that has served traffic stalls every Python thread
    for seconds (2.4 s at 400 requests/s, PR 23), and the traced window
    would measure the profiler. Host threads still carry the runtime's own
    events and every `TraceAnnotation`."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def fresh_trace_dir(cell_name: str) -> str:
    d = os.path.join(OUT_DIR, "trace", cell_name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d, exist_ok=True)
    return d


def find_xplane(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return paths[-1] if paths else None


# --------------------------------------------------------- per-layer readers


def read_layer_metrics(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell is read by its own file,
    layer_metrics/<name>.py, whose `read(ctx)` returns a number or None. A
    reader that finds nothing to read returns nothing, and the metric is
    left out of the line."""
    out = {}
    for m in cell["per_layer"]:
        path = os.path.join(BENCH_DIR, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + m["name"].replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is None or not math.isfinite(value):
            log(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -------------------------------------------------------------- result line


def quantile(sorted_values, q: float) -> float:
    """Nearest-rank quantile of an ascending list."""
    if not sorted_values:
        return float("nan")
    k = min(len(sorted_values) - 1, max(0, math.ceil(q * len(sorted_values)) - 1))
    return sorted_values[k]


def print_result(*, verdict, attempted: int, failed: int, metrics: dict,
                 device: dict, breakdown=None) -> None:
    """The run's last lines: on standard error everything `correct` was
    decided from, each number beside its limit (`correct.Verdict`); on
    standard output the result object, with the same under `compared`, its
    last key."""
    line = {"correct": bool(verdict.ok), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = verdict.compared
    sys.stdout.flush()
    print("\n".join(verdict.lines + [f"correct: {bool(verdict.ok)}"]),
          file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


class Collector:
    """Stands where the CLIs put their MetricsWriter (which writes to a file
    and forgets): keeps the records the benchmark reads. With `keep`, only
    serve events of those kinds are kept, so that the several records a
    request leaves do not grow the heap the server's collector walks."""

    def __init__(self, keep=None):
        self.records, self.keep = [], keep

    def write(self, rec):
        if self.keep is None or rec.get("event") in self.keep:
            self.records.append(rec)


def report(cell: dict, args, *, verdict, attempted: int, failed: int,
           end_to_end: dict, device: dict, ctx: dict, trace_dir) -> int:
    """The run's last line. Untraced: the end-to-end metrics. Traced: the
    trace is reduced, every per-layer reader of the cell reads `ctx`, and the
    device record gains busy_s/window_s and the breakdown."""
    if not args.trace:
        print_result(verdict=verdict, attempted=attempted, failed=failed,
                     metrics=end_to_end, device=device)
        return 0
    from benchmark.reduce_trace import reduce_xplane

    xplane = find_xplane(trace_dir)
    trace = reduce_xplane(xplane, n_devices=cell["chips"]) if xplane else None
    metrics = read_layer_metrics(cell, dict(ctx, trace=trace))
    breakdown = None
    if trace is not None:
        device = dict(device, busy_s=trace["busy_s"], window_s=trace["window_s"])
        breakdown = {"device_ops": trace["top_ops"][:10],
                     "idle_gaps": trace["idle_gaps"][:10]}
        log(f"trace: {trace['summary']}")
    print_result(verdict=verdict, attempted=attempted, failed=failed,
                 metrics=metrics, device=device, breakdown=breakdown)
    return 0


class Clock:
    """Process start, as near as a Python program can read it: run.py takes
    the time on its first line, before any import."""

    def __init__(self, t_start: float):
        self.t_start = t_start

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start
