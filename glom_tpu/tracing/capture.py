"""Programmatic XLA trace capture: step-windowed XProf traces on demand.

`--profile-dir` (train/cli.py) wraps a WHOLE run in one trace — unusable
past a few hundred steps (multi-GB trace, compile noise swamping steady
state). TraceCapture is the step-windowed form every serious harness ends
up with: `--trace-steps A:B` opens `jax.profiler.start_trace` right before
step A and closes it after step B, stamps the window's metadata (trace
dir, first/last step) into the telemetry event stream as "note" records,
and marks each captured step with `jax.profiler.StepTraceAnnotation` so
XProf's step view lines up with the trainer's step numbers.

The step counter lives on the TraceCapture object itself, so a window can
span checkpoint-span boundaries (the CLI calls fit() once per span over
one shared capture). jax is imported lazily inside methods: constructing
and parsing never touches a backend, and tests monkeypatch `jax.profiler`
to run without one.

View captures with: tensorboard --logdir <trace_dir>  (or xprof).
"""

from __future__ import annotations

import contextlib
from typing import Tuple


def start_trace(trace_dir: str) -> None:
    """Open the profiler with the Python tracer off. With it on, opening a
    trace stalls every Python thread for 1-2.4 s (PERF.md section 6) and
    the window measures the profiler; host threads still carry the
    runtime's own events and every TraceAnnotation, the program's spans
    among them."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def parse_trace_steps(spec: str) -> Tuple[int, int]:
    """'A:B' -> (first, last) inclusive; a bare 'A' captures one step."""
    parts = spec.split(":")
    try:
        if len(parts) == 1:
            first = last = int(parts[0])
        elif len(parts) == 2:
            first, last = int(parts[0]), int(parts[1])
        else:
            raise ValueError
    except ValueError:
        raise ValueError(
            f"--trace-steps {spec!r}: expected 'A:B' (or a bare step 'A')"
        ) from None
    if first < 0 or last < first:
        raise ValueError(
            f"--trace-steps {spec!r}: need 0 <= first <= last"
        )
    return first, last


class TraceCapture:
    """A [first, last]-inclusive step window around jax.profiler traces.

    Wrap each training step (or bench measurement unit) in `unit()`; the
    capture opens the trace when its internal counter hits `first` and
    closes it after `last`. `writer` (anything with .write(dict)) receives
    the stamped start/stop metadata events; without one they fall through
    to telemetry.sinks.emit (stdout), so bench logs carry them too.

    NOTE on async dispatch: the window bounds step DISPATCH; device
    execution of the last steps may spill slightly past stop_trace. The
    profiler still attributes whatever executed inside the window — for
    exact per-step walls read the StepTraceAnnotation markers, not the
    window edges.
    """

    def __init__(self, first: int, last: int, trace_dir: str, *, writer=None):
        if first < 0 or last < first:
            raise ValueError(f"need 0 <= first <= last, got {first}:{last}")
        self.first = first
        self.last = last
        self.trace_dir = trace_dir
        self.writer = writer
        self._count = 0  # units seen (monotonic across fit() spans)
        self._active = False
        self._captured = 0
        self._closed = False

    @classmethod
    def parse(cls, spec: str, trace_dir: str, *, writer=None) -> "TraceCapture":
        first, last = parse_trace_steps(spec)
        return cls(first, last, trace_dir, writer=writer)

    # -- event plumbing ----------------------------------------------------

    def _emit(self, rec: dict) -> None:
        from glom_tpu.telemetry import schema

        rec = schema.stamp(rec, kind="note")
        if self.writer is not None:
            self.writer.write(rec)
        else:
            from glom_tpu.telemetry.sinks import emit

            emit(rec, kind="note")

    # -- the window --------------------------------------------------------

    def _start(self) -> None:
        start_trace(self.trace_dir)
        self._active = True
        self._emit(
            {
                "note": "xla-trace-start",
                "trace_dir": self.trace_dir,
                "first_step": self._count,
                "trace_steps": f"{self.first}:{self.last}",
            }
        )

    def _stop(self, *, reason: str = "window-complete") -> None:
        import jax

        try:
            jax.profiler.stop_trace()
        finally:
            self._active = False
        self._emit(
            {
                "note": "xla-trace-stop",
                "trace_dir": self.trace_dir,
                "last_step": self._count - 1 if self._captured else None,
                "steps_captured": self._captured,
                "reason": reason,
            }
        )

    @contextlib.contextmanager
    def unit(self):
        """Wrap ONE step/measurement unit; yields the unit's index."""
        i = self._count
        if not self._closed and not self._active and i == self.first:
            self._start()
        ann = None
        if self._active:
            try:
                import jax

                ann = jax.profiler.StepTraceAnnotation("step", step_num=i)
                ann.__enter__()
            except Exception:
                ann = None
        try:
            yield i
        finally:
            if ann is not None:
                try:
                    ann.__exit__(None, None, None)
                except Exception:
                    pass
            self._count += 1
            if self._active:
                self._captured += 1
                if i >= self.last:
                    self._stop()

    def close(self) -> None:
        """Idempotent teardown: stops a still-open window (a run that ended
        before reaching step B must not leak a profiler session) and stamps
        the truncation in the event stream."""
        if self._closed:
            return
        self._closed = True
        if self._active:
            self._stop(reason="truncated-by-close")


# -- whole-block capture (the original profiling.py surface) ---------------


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/glom_tpu_trace"):
    """Capture a profiler trace of the enclosed block.

    View with: tensorboard --logdir <log_dir>  (or xprof).
    """
    import jax

    start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def start_server(port: int = 9999):
    """On-demand profiling: connect TensorBoard's profile tab to this port
    while training runs (the 'attach to a live job' workflow)."""
    import jax

    return jax.profiler.start_server(port)


def annotate(name: str):
    """Trace annotation decorator for host-side phases (data loading, eval)."""

    def deco(fn):
        import jax

        return jax.profiler.annotate_function(fn, name=name)

    return deco
