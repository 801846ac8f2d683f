"""Host-side software spans: where the WALL TIME went, on any backend.

XProf answers "where did device time go" — but only when a profiler backend
exists, which is exactly what rounds 4-5 did not have. These spans are the
host-side complement: a `span()` context manager that times a named block
with `time.perf_counter`, tracks nesting on a thread-local stack, and emits
versioned "span" JSONL events into the same stream every other telemetry
record rides, so a run with no profiler backend (the CPU functional
drives, a postmortem) still attributes time per phase.

Naming: one vocabulary, PHASES, for host spans and in-graph scopes alike.
In-graph phases are the `jax.named_scope` names of the step builders and
models/core.py, and the prefixes of the Pallas kernels' `name=`; host
phases the fit loop and the prefetch worker time are prefixed `host_`.
Every span also enters a `jax.profiler.TraceAnnotation` of its own name
(with the span's extra fields, e.g. the loop's `step=`), so an open
profiler window shows the block on the device trace's clock; with no
window open the annotation is a flag test.

Cost: a span with an aggregator and no writer is two perf_counter calls,
the annotation's enter and exit, and dict arithmetic — single-digit
microseconds. The fit loop therefore aggregates per-name between logging
steps (SpanAggregator) and emits one rollup span event per phase per
logging record instead of two JSONL lines per step. Pure stdlib but for
the annotation, which is resolved on the first span and left out where
jax is broken or absent.

What a `fit` call cannot know, an IntervalAccount keeps: the interval from
one logging boundary to the next is the unit a stall costs in (each
boundary syncs with the device), so the account holds the last boundary's
time and host counters and the last intervals' walls, and gives every
logging record its interval (wall, time outside the loop's spans, GC
pauses, CPU time, scheduler delay, faults) and, against the median of the
earlier intervals, a stall with its phase and cause (`judge_interval`).
"""

from __future__ import annotations

import gc
import resource
import statistics
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Optional

# The phases of a training step, host and device: the names of the fit
# loop's and the prefetch worker's spans, of every `jax.named_scope` in the
# step builders (train/trainer.py, parallel/manual.py) and the forward
# (models/core.py, train/objectives.py), and the first word of every Pallas
# GLOM kernel's `name=` (`loop_*`: kernels/fused_loop.py, `ffw_*`:
# kernels/grouped_mlp.py, `consensus_*`: the consensus kernels). A device op
# of the step belongs to the innermost of these in its `op_name`.
HOST_PHASES = (
    "host_data_next",
    "host_step_dispatch",
    "host_log_fetch",
    "host_prefetch_next",
    "host_prefetch_stage",
)
DEVICE_PHASES = (
    "noise",
    "image_to_tokens",
    "loop",
    "bottom_up",
    "top_down",
    "ffw",
    "consensus",
    "mean_update",
    "consensus_update",
    "reconstruction",
    "grad_reduce",
    "optimizer",
    "step_metrics",
)
PHASES = HOST_PHASES + DEVICE_PHASES

# The device scopes of the hybrid language model's step (models/hybrid_lm.py),
# in a tuple of their own: the trainer's `optimizer` and `step_metrics` above
# are shared, the rest of DEVICE_PHASES is GLOM's. `embed`; a Mamba-2 layer
# is `mamba_in` (in-projection, conv, gates), `ssd_scan` (the chunked
# recurrence), `mamba_out` (gated norm, out-projection); `attention`; an
# expert layer is `moe_router` (scores, top-k, weights), `moe_dispatch`
# (latent down-projection, sort by expert, gather), `moe_experts` (the
# grouped products), `moe_combine` (weights, scatter back, latent
# up-projection), `moe_shared`; `lm_head_loss` (final norm, head,
# cross-entropy). Each layer's input norm belongs to no scope.
LM_DEVICE_PHASES = (
    "embed",
    "mamba_in",
    "ssd_scan",
    "mamba_out",
    "attention",
    "moe_router",
    "moe_dispatch",
    "moe_experts",
    "moe_combine",
    "moe_shared",
    "lm_head_loss",
)

# The device scopes of the SambaY language model's step (models/sambay.py).
# `embed`, `mamba_in`, `mamba_out` and `lm_head_loss` mean what they mean
# above (in-projection, conv and step; skip, gate and out-projection);
# `selective_scan` is the Mamba-1 recurrence alone; the three differential
# attentions, each with its input norm, projections, softmaxes and
# out-projection: `window_attention`, `full_attention` (which makes the
# shared keys and values), `cross_attention` (which reads them); `gmu`;
# `mlp` (every layer's second half: norm, SwiGLU).
SAMBAY_DEVICE_PHASES = (
    "embed",
    "mamba_in",
    "selective_scan",
    "mamba_out",
    "window_attention",
    "full_attention",
    "cross_attention",
    "gmu",
    "mlp",
    "lm_head_loss",
)

# The device scopes of the Laguna language model's step (models/laguna.py).
# `embed`, the four `moe_*` scopes of the routed part, `moe_shared` and
# `lm_head_loss` mean what they mean in LM_DEVICE_PHASES (the routed part is
# the same code; it has no latent step here, and `moe_router` holds the
# layer's second norm); `window_attention` and `full_attention` are a layer's
# whole first half by its kind: input norm, projections, the rotation, the
# scores (the kernels' calls), the gate, the out-projection; `dense_mlp` is
# the leading layers' SwiGLU with its norm.
LAGUNA_DEVICE_PHASES = (
    "embed",
    "window_attention",
    "full_attention",
    "dense_mlp",
    "moe_router",
    "moe_dispatch",
    "moe_experts",
    "moe_combine",
    "moe_shared",
    "lm_head_loss",
)

# Scopes opened inside an attention scope of LAGUNA_DEVICE_PHASES: an op
# under one of them belongs to the attention phase round it, and a reader that
# wants the part alone finds it by this name in the op's scope path.
LAGUNA_INNER_SCOPES = (
    # the rotary positions on q and k (float32 cos and sin, YaRN or default): one
    # pass a tensor, x * cos + (x @ P) * sin over whole heads with P the signed
    # permutation of a head's halves; the pass's transpose opens the scope too
    "rope",
    # the sigmoid gate a query head on the attention's output, with its product
    "attn_gate",
)

# The device scopes of the Kimi Linear language model's step
# (models/kimi_linear.py). `embed`, the four `moe_*` scopes of the routed
# part, `moe_shared`, `dense_mlp` and `lm_head_loss` mean what they mean in
# LAGUNA_DEVICE_PHASES (the routed part is the same code). A Kimi Delta
# Attention layer's first half is `kda_in` (input norm, the three projections
# with their convolutions, the decay's, beta's and the gate's projections),
# `kda_scan` (`kda_chunked` alone: the delta rule in chunks) and `kda_out`
# (head norm, gate, out-projection); `latent_attention` is a latent layer's
# whole first half: input norm, query and latent projections, the latent's
# norm and expansion, the scores (the kernels' calls), the out-projection.
KIMI_DEVICE_PHASES = (
    "embed",
    "kda_in",
    "kda_scan",
    "kda_out",
    "latent_attention",
    "dense_mlp",
    "moe_router",
    "moe_dispatch",
    "moe_experts",
    "moe_combine",
    "moe_shared",
    "lm_head_loss",
)

# The device scopes of the EvaByte language model's step (models/evabyte.py).
# `embed`, `dense_mlp` (the layer's second norm and its SwiGLU, with the
# float32 add) and `lm_head_loss` (final norm, the eight heads, the loss) mean
# what they mean above. A layer's first half is `eva_in` (input norm, the
# three projections, the rotation of q and k), `eva_summary` (the chunk
# summariser: one softmax of 16 a chunk a head and two weighted sums, forward
# and backward), `eva_attention` (EVA attention alone: the two key segments
# laid end to end, the kernels' calls or the XLA loop, and what joins them)
# and `eva_out` (the out-projection and the float32 add into the stream).
EVABYTE_DEVICE_PHASES = (
    "embed",
    "eva_in",
    "eva_summary",
    "eva_attention",
    "eva_out",
    "dense_mlp",
    "lm_head_loss",
)

# The device scopes of the Ouro looped language model's step (models/ouro.py).
# `embed`, `full_attention` (here the causal scores alone: the kernels' calls
# or the XLA loop), `dense_mlp` (the layer's third norm and its SwiGLU) and
# `lm_head_loss` (the head's product and the weighed cross-entropy of every
# pass's state) mean what they mean above. A layer's first half is `ouro_in`
# (first norm, the three projections, the rotation of q and k) and `ouro_out`
# (the out-projection); `sandwich_norm` is the two norms on the branches' outputs
# with their adds into the stream; `ut_close` the norm that closes a pass and
# the loop's own ops (the copies that stack a pass's kept arrays for the
# backward pass); `exit_gate` the gate's sigmoids, the exit distribution and
# its entropy.
OURO_DEVICE_PHASES = (
    "embed",
    "ouro_in",
    "full_attention",
    "ouro_out",
    "sandwich_norm",
    "dense_mlp",
    "ut_close",
    "exit_gate",
    "lm_head_loss",
)

# The language models' Pallas kernels, by their `name=`
# (kernels/flash_attention.py). They open no scope of their own: a call runs
# inside the model's attention scope (`attention`; `window_attention`,
# `full_attention`, `cross_attention`; Laguna's two; `latent_attention`; `eva_attention`; Ouro's `full_attention`), and its device time
# belongs to that scope. All begin with `attn_`; none matches `loop_*`, `ffw_*`,
# `consensus_*` or `ragged-dot*`, the names by which the benchmark tells the
# routes apart.
LM_KERNELS = (
    # causal (windowed) grouped-query attention forward: online softmax over the scheduled key tiles
    "attn_flash_fwd",
    # its backward: one sweep over key tiles, scores rebuilt from the log-sum-exp, dq resident
    "attn_flash_bwd_onesweep",
)

# SambaY's Mamba-1 recurrence as Pallas kernels, by their `name=`
# (kernels/selective_scan.py). A call runs inside the mixer's `selective_scan`
# scope, whose name each begins with; none matches a route's pattern above, and
# none begins with `attn_flash`, whose calls the attention readers sum.
SCAN_KERNELS = (
    # the recurrence a position at a time, the state in VMEM; keeps the state entering each block
    "selective_scan_fwd",
    # its backward: a block's states made again in VMEM, then walked in reverse carrying ds
    "selective_scan_bwd",
)

# The serving stack's host phases (glom_tpu/serve): one request's path is
# enqueue -> (gathered into a) batch -> dispatch (the compiled forward) ->
# fetch (device->host of the valid rows). The batcher aggregates these the
# same way fit_loop aggregates its host_ phases.
SERVE_PHASES = (
    "serve_enqueue",
    "serve_batch",
    "serve_dispatch",
    "serve_fetch",
)

_local = threading.local()

# The calling thread's own usage where the platform has it (Linux).
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)


def _no_annotation(name, **fields):
    """Stands in for jax.profiler.TraceAnnotation where jax is broken or
    absent: the span itself must work in exactly that environment."""
    return nullcontext()


_annotation_cls = None  # resolved by the first span


def _resolve_annotation():
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # jax absent, or broken at import: spans still work
        return _no_annotation
    return TraceAnnotation


def _trace_annotation():
    global _annotation_cls
    if _annotation_cls is None:
        _annotation_cls = _resolve_annotation()
    return _annotation_cls


def _stack() -> list:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


def current_span() -> Optional[str]:
    """Name of the innermost open span on this thread, or None."""
    stack = _stack()
    return stack[-1] if stack else None


class SpanAggregator:
    """Per-name rollup of closed spans (count / total / max), drained into
    stamped "span" records at each logging boundary — the <1%-overhead form
    of per-step span events. Thread-safe: the prefetch thread's spans can
    land in the same aggregator as the fit loop's."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: dict = {}  # name -> [count, total_s, max_s]

    def observe(self, name: str, dur_s: float) -> None:
        with self._lock:
            st = self._stats.get(name)
            if st is None:
                self._stats[name] = [1, dur_s, dur_s]
            else:
                st[0] += 1
                st[1] += dur_s
                if dur_s > st[2]:
                    st[2] = dur_s

    def records(self, *, reset: bool = True, extra: Optional[dict] = None):
        """One stamped span record per name seen since the last drain:
        dur_s is the TOTAL seconds in that phase (the attribution number);
        count/mean_ms/max_ms unpack it."""
        from glom_tpu.telemetry import schema

        with self._lock:
            stats = self._stats
            if reset:
                self._stats = {}
            else:
                stats = dict(stats)
        out = []
        for name in sorted(stats):
            count, total, mx = stats[name]
            rec = {
                "name": name,
                "dur_s": round(total, 6),
                "count": count,
                "mean_ms": round(1e3 * total / count, 4),
                "max_ms": round(1e3 * mx, 4),
            }
            if extra:
                rec.update(extra)
            out.append(schema.stamp(rec, kind="span"))
        return out


# ------------------------------------------------------ the interval's account

# The fit loop's own spans; the rest of an interval's wall (fit's own work,
# the caller's loop, the writer, the memory probe, opening and closing a
# profiler) lies between them and is called "other".
LOOP_PHASES = HOST_PHASES[:3]
STALL_PHASES = LOOP_PHASES + ("other",)

# The constants of the stall rule (judge_interval) and of the account's
# memory. The two thresholds were corrected once from the chip's readings
# (CHANGES.md, PR 38): over 32 untraced runs on a TPU v5e an ordinary interval
# of 850-980 ms lay within -4 .. +13 ms of its run's median and the six
# stalls found were 51-117 ms over, so the line is drawn at half the smallest
# stall and twice the largest ordinary excess (the issue's 50 ms and 5% would
# have caught the smallest by 1 ms).
HISTORY_INTERVALS = 32  # intervals an account remembers
REFERENCE_MIN_INTERVALS = 4  # earlier intervals of a step count before it has a reference
STALL_MIN_MS = 25.0  # an interval is stalled at this much over its reference ...
STALL_MIN_SHARE = 0.025  # ... or at this share of the reference, whichever is more
CAUSE_SHARE = 0.5  # a counter explains a stall where it covers this share of the excess

_CAUSE_BY_PHASE = {
    "host_data_next": "data_wait",
    "host_step_dispatch": "dispatch",
    "other": "between_spans",
    # Nothing on the host moved and the loop waited for the device longer:
    # the device took longer (a routed layer on its full row count does
    # that), or the wait has a cause no counter here sees.
    "host_log_fetch": "device_or_unknown",
}


def judge_interval(interval: dict, reference: Optional[dict]) -> dict:
    """The stall rule: a pure function of one interval and its reference.

    `interval`: `wall_ms`; `phases`, the milliseconds in each of
    STALL_PHASES; `gc_ms`, every thread's collection pauses; `run_delay_ms`,
    the loop thread runnable and not running (None where unread); `majflt`,
    its major page faults. `reference`: the medians `wall_ms` and `phases`
    of the earlier intervals of the same step count, or None while there
    are too few.

    An interval over its reference by max(STALL_MIN_MS, STALL_MIN_SHARE of
    the reference) is stalled by the excess, in the phase furthest over its
    own median, by the first cause that holds: `gc` (the pauses cover
    CAUSE_SHARE of the excess), `descheduled` (the run delay does),
    `page_fault` (major faults, outside `host_log_fetch`), else what the
    phase says (_CAUSE_BY_PHASE)."""
    if reference is None:
        return {"stall_ms": 0.0}
    excess = interval["wall_ms"] - reference["wall_ms"]
    if excess < max(STALL_MIN_MS, STALL_MIN_SHARE * reference["wall_ms"]):
        return {"stall_ms": 0.0}
    phase = max(
        STALL_PHASES,
        key=lambda p: interval["phases"][p] - reference["phases"][p],
    )
    if interval["gc_ms"] >= CAUSE_SHARE * excess:
        cause = "gc"
    elif (interval["run_delay_ms"] or 0.0) >= CAUSE_SHARE * excess:
        cause = "descheduled"
    elif interval["majflt"] > 0 and phase != "host_log_fetch":
        cause = "page_fault"
    else:
        cause = _CAUSE_BY_PHASE[phase]
    return {"stall_ms": round(excess, 3), "stall_phase": phase, "stall_cause": cause}


class _GcWatch:
    """Every collection of the process, timed by one `gc.callbacks` hook
    (collections do not overlap: the interpreter runs one at a time). The
    totals only grow; an account reads them at its boundaries. A collection
    is also a `TraceAnnotation("host_gc", generation=g)` on the thread it
    ran on, from its start to its stop, and its pause is charged to the span
    open on that thread (`other` outside any)."""

    def __init__(self):
        self.pause_s = 0.0
        self.collections = 0
        self.gen2 = 0
        self.by_span: dict = {}  # span name -> seconds of pauses under it
        self.annotation = _resolve_annotation()
        self._open = None  # (start, annotation) of the collection in progress

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            annotation = self.annotation("host_gc", generation=info["generation"])
            annotation.__enter__()
            self._open = (time.perf_counter(), annotation)
        elif self._open is not None:  # a stop whose start this hook saw
            t0, annotation = self._open
            self._open = None
            dur = time.perf_counter() - t0
            annotation.__exit__(None, None, None)
            self.pause_s += dur
            self.collections += 1
            self.gen2 += info["generation"] == 2
            name = current_span() or "other"
            self.by_span[name] = self.by_span.get(name, 0.0) + dur

    def totals(self) -> dict:
        return {"pause_s": self.pause_s, "collections": self.collections,
                "gen2": self.gen2, "by_span": dict(self.by_span)}


_gc_watch: Optional[_GcWatch] = None
_gc_watch_lock = threading.Lock()


def gc_watch() -> _GcWatch:
    """The process's one watch, hooked into `gc.callbacks` by its first
    caller (an import hooks nothing)."""
    global _gc_watch
    with _gc_watch_lock:
        if _gc_watch is None:
            _gc_watch = _GcWatch()
            gc.callbacks.append(_gc_watch)
    return _gc_watch


def _thread_counters(run_delay: bool) -> dict:
    """What the kernel has counted for the calling thread so far: CPU
    seconds, involuntary context switches, major page faults and, if
    `run_delay` is asked for and /proc has it, the nanoseconds it was
    runnable and not running."""
    ru = resource.getrusage(_RUSAGE_THREAD)
    out = {"ident": threading.get_ident(), "cpu_s": time.thread_time(),
           "nivcsw": ru.ru_nivcsw, "majflt": ru.ru_majflt}
    if run_delay:
        try:
            with open("/proc/thread-self/schedstat") as fh:
                out["run_delay_ns"] = int(fh.read().split()[1])
        except (OSError, IndexError, ValueError):
            pass
    return out


class IntervalAccount:
    """What one trainer's loop remembers from one `fit` call to the next:
    the time of its last logging boundary, the host counters read there, and
    the last HISTORY_INTERVALS intervals' walls and per-phase totals with
    their step counts. `fit_loop` reports to it (`enter`, `step`, `close`)
    and puts what `close` returns on the boundary's logging record.
    Counters are read once a boundary, never once a span. One account
    belongs to one loop on one thread."""

    def __init__(self):
        self._gc = gc_watch()
        self._t_last: Optional[float] = None  # the last boundary, or the first entry
        self._thread: dict = {}
        self._schedstat = True  # until a read of /proc/thread-self/schedstat fails
        self._gc_last: dict = {}
        self._steps = 0
        self._compiled = False  # a jit variant's first call lies in this interval
        self._history: deque = deque(maxlen=HISTORY_INTERVALS)  # (steps, wall_ms, phases)

    def _mark(self, t: float) -> None:
        self._t_last = t
        # A machine without the file is not asked again: a failing open costs
        # a sandboxed boundary a tenth of a millisecond, with the chip idle.
        self._thread = _thread_counters(self._schedstat)
        self._schedstat = "run_delay_ns" in self._thread
        self._gc_last = self._gc.totals()
        self._steps = 0
        self._compiled = False

    def enter(self) -> None:
        """A `fit_loop` call begins: the first one opens the first interval,
        a later one finds its interval open since the last boundary."""
        if self._t_last is None:
            self._mark(time.perf_counter())

    def step(self, first_call: bool) -> None:
        """One step was dispatched (`first_call`: of its jit variant)."""
        self._steps += 1
        self._compiled = self._compiled or first_call

    def reference(self, steps: int) -> Optional[dict]:
        """Medians over the remembered intervals of `steps` steps, once
        there are REFERENCE_MIN_INTERVALS."""
        same = [(wall, phases) for n, wall, phases in self._history if n == steps]
        if len(same) < REFERENCE_MIN_INTERVALS:
            return None
        return {
            "wall_ms": statistics.median(wall for wall, _ in same),
            "phases": {p: statistics.median(phases[p] for _, phases in same)
                       for p in STALL_PHASES},
        }

    def close(self, t: float, step, phase_ms: dict) -> tuple:
        """The boundary at time `t` (the end of its `host_log_fetch`), at
        training step `step`, with the loop spans' milliseconds since the
        last boundary. Returns the logging record's fields and, for a
        stalled interval, its line for standard error (else None)."""
        steps, compiled = self._steps, self._compiled
        wall_ms = 1e3 * (t - self._t_last)
        thread0, gc0 = self._thread, self._gc_last
        self._mark(t)
        thread1, gc1 = self._thread, self._gc_last
        if thread0["ident"] != thread1["ident"]:
            thread0 = thread1  # another thread's counters: nothing to subtract
        phases = {p: phase_ms.get(p, 0.0) for p in LOOP_PHASES}
        phases["other"] = wall_ms - sum(phases.values())
        fields = {
            "interval_steps": steps,
            "interval_ms": round(wall_ms, 3),
            "interval_other_ms": round(phases["other"], 3),
            "host_gc_ms": round(1e3 * (gc1["pause_s"] - gc0["pause_s"]), 3),
            "host_gc_collections": gc1["collections"] - gc0["collections"],
            "host_gc_gen2": gc1["gen2"] - gc0["gen2"],
            "host_cpu_ms": round(1e3 * (thread1["cpu_s"] - thread0["cpu_s"]), 3),
            "host_nivcsw": thread1["nivcsw"] - thread0["nivcsw"],
            "host_majflt": thread1["majflt"] - thread0["majflt"],
        }
        if "run_delay_ns" in thread0 and "run_delay_ns" in thread1:
            fields["host_run_delay_ms"] = round(
                1e-6 * (thread1["run_delay_ns"] - thread0["run_delay_ns"]), 3)
        # An interval that compiled is neither judged nor remembered.
        reference = None if compiled else self.reference(steps)
        verdict = judge_interval(
            {"wall_ms": wall_ms, "phases": phases, "gc_ms": fields["host_gc_ms"],
             "run_delay_ms": fields.get("host_run_delay_ms"),
             "majflt": fields["host_majflt"]},
            reference)
        fields.update(verdict)
        if not compiled:
            self._history.append((steps, wall_ms, phases))
        if not verdict["stall_ms"]:
            return fields, None
        gc_by_span = {
            name: 1e3 * (s - gc0["by_span"].get(name, 0.0))
            for name, s in gc1["by_span"].items() if s > gc0["by_span"].get(name, 0.0)}
        return fields, stall_line(step, fields, phases, reference, gc_by_span)


def stall_line(step, fields: dict, phases: dict, reference: dict, gc_by_span: dict) -> str:
    """A stalled interval in one line: what an operator of a long job reads
    on standard error."""
    ms = " ".join(f"{p} {phases[p]:.1f} ({reference['phases'][p]:.1f})" for p in STALL_PHASES)
    gc_where = "".join(f", {v:.1f} under {k}" for k, v in sorted(gc_by_span.items()))
    run_delay = fields.get("host_run_delay_ms")
    return (
        f"glom_tpu stall: step {step:g}: {fields['interval_steps']} steps took "
        f"{fields['interval_ms']:.1f} ms against a median of {reference['wall_ms']:.1f}, "
        f"{fields['stall_ms']:.1f} over, in {fields['stall_phase']}, cause "
        f"{fields['stall_cause']} | ms (median): {ms} | gc {fields['host_gc_ms']:.1f} ms in "
        f"{fields['host_gc_collections']} collections, {fields['host_gc_gen2']} of gen 2"
        f"{gc_where} | cpu {fields['host_cpu_ms']:.1f} ms, run delay "
        f"{'unread' if run_delay is None else format(run_delay, '.1f') + ' ms'}, "
        f"{fields['host_nivcsw']} involuntary switches, {fields['host_majflt']} major faults"
    )


class _Timing:
    """What `span` yields: the block's duration, set when it is left."""

    __slots__ = ("dur_s",)

    def __init__(self):
        self.dur_s = 0.0


@contextmanager
def span(
    name: str,
    *,
    writer=None,
    aggregator: Optional[SpanAggregator] = None,
    **fields,
):
    """Time the enclosed block as a named span.

    `writer` (anything with .write(dict), e.g. MetricsWriter) receives one
    stamped "span" event per close — start wall time, duration, nesting
    depth, and the enclosing span's name. `aggregator` rolls the duration
    into a SpanAggregator instead (the cheap fit-loop form; both may be
    given). The block also runs inside a jax.profiler.TraceAnnotation of
    the same name, so an open profiler window shows it on the device
    trace's clock. Extra keyword `fields` (numbers or strings, e.g. the
    loop's `step=`) ride both the emitted event and the annotation.

    Yields the span's own timing: its `dur_s` is the duration once the
    block is left (a caller that wants the number times nothing twice)."""
    stack = _stack()
    parent = stack[-1] if stack else None
    stack.append(name)
    timing = _Timing()
    t_wall = time.time() if writer is not None else 0.0
    t0 = time.perf_counter()
    try:
        with _trace_annotation()(name, **fields):
            yield timing
    finally:
        dur = timing.dur_s = time.perf_counter() - t0
        stack.pop()
        if aggregator is not None:
            aggregator.observe(name, dur)
        if writer is not None:
            from glom_tpu.telemetry import schema, tracectx

            rec = {
                "name": name,
                "dur_s": round(dur, 6),
                "t_start": round(t_wall, 3),
                "depth": len(stack),
            }
            if parent is not None:
                rec["parent"] = parent
            rec.update(fields)
            # A span closed under a serve dispatch scope carries that
            # dispatch's trace context — host time joins the request's
            # causal tree like every other stamped record.
            if not any(k in rec for k in ("trace_id", "trace_ids")):
                rec.update(tracectx.current_fields())
            writer.write(schema.stamp(rec, kind="span"))


def spanned(name: str, **span_kw):
    """Decorator form: time every call of `fn` as a span."""

    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name, **span_kw):
                return fn(*args, **kwargs)

        return wrapper

    return deco
