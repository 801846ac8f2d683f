"""Tracing subsystem tests: spans, step-windowed XLA capture, HBM
accounting, the crash flight recorder (including the induced-crash
acceptance path: dump -> schema lint -> event ordering), and the
utils/profiling.py compat shim.

Deliberately host-side: every test here uses fake step functions / fake
devices / a monkeypatched jax.profiler, so the module adds no jit compiles
to the tier-1 budget and runs without a profiler backend — which is the
spans' and flight recorder's own contract.
"""

import gc
import importlib.util
import json
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from glom_tpu.telemetry import schema
from glom_tpu.tracing.capture import TraceCapture, parse_trace_steps
from glom_tpu.tracing.flight import (
    FlightRecorder,
    dump_flight_recorder,
    observe_event,
    set_global_flight_recorder,
)
from glom_tpu.tracing.memory import (
    hbm_watermarks,
    memory_record,
    model_live_bytes_total,
)
from glom_tpu.tracing import spans as spans_mod
from glom_tpu.tracing.spans import (
    IntervalAccount,
    SpanAggregator,
    current_span,
    judge_interval,
    span,
)


class ListWriter:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)


@pytest.fixture(autouse=True)
def _clean_global_recorder():
    """No test may leak a global flight recorder into the rest of the
    suite (every sink in the process feeds it)."""
    yield
    set_global_flight_recorder(None)


class TestSpans:
    def test_span_emits_stamped_event(self):
        w = ListWriter()
        with span("host_data_next", writer=w, step=3):
            pass
        (rec,) = w.records
        assert rec["kind"] == "span"
        assert rec["name"] == "host_data_next"
        assert rec["dur_s"] >= 0
        assert rec["depth"] == 0
        assert rec["step"] == 3
        assert schema.validate_record(rec) == [], rec

    def test_span_nesting_tracks_parent_and_depth(self):
        w = ListWriter()
        with span("outer", writer=w):
            assert current_span() == "outer"
            with span("inner", writer=w):
                assert current_span() == "inner"
        assert current_span() is None
        inner, outer = w.records  # inner closes first
        assert inner["name"] == "inner"
        assert inner["parent"] == "outer"
        assert inner["depth"] == 1
        assert outer["depth"] == 0
        assert "parent" not in outer

    def test_span_reraises_and_still_records(self):
        agg = SpanAggregator()
        with pytest.raises(RuntimeError):
            with span("x", aggregator=agg):
                raise RuntimeError("boom")
        assert current_span() is None
        (rec,) = agg.records()
        assert rec["count"] == 1

    def test_aggregator_rollup_and_reset(self):
        agg = SpanAggregator()
        for dur in (0.01, 0.02, 0.03):
            agg.observe("host_step_dispatch", dur)
        agg.observe("host_data_next", 0.5)
        recs = agg.records(extra={"step": 7.0})
        by_name = {r["name"]: r for r in recs}
        d = by_name["host_step_dispatch"]
        assert d["count"] == 3
        assert d["dur_s"] == pytest.approx(0.06, abs=1e-6)
        assert d["max_ms"] == pytest.approx(30.0, abs=0.01)
        assert d["mean_ms"] == pytest.approx(20.0, abs=0.01)
        assert d["step"] == 7.0
        for r in recs:
            assert schema.validate_record(r) == [], r
        # drained: the next logging boundary starts fresh
        assert agg.records() == []


class FakeProfiler:
    """Stand-in for jax.profiler: records start/stop calls, no backend."""

    def __init__(self):
        self.calls = []
        self.options = []  # the profiler_options of each start_trace

    class ProfileOptions:
        python_tracer_level = 1  # the real default: Python tracer on

    def start_trace(self, log_dir, profiler_options=None):
        self.calls.append(("start", log_dir))
        self.options.append(profiler_options)

    def stop_trace(self):
        self.calls.append(("stop", None))

    class StepTraceAnnotation:
        def __init__(self, name, **kw):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False


@pytest.fixture
def fake_profiler(monkeypatch):
    import jax

    prof = FakeProfiler()
    monkeypatch.setattr(jax, "profiler", prof)
    return prof


class TestTraceCapture:
    def test_parse_specs(self):
        assert parse_trace_steps("3:5") == (3, 5)
        assert parse_trace_steps("7") == (7, 7)
        for bad in ("5:3", "-1:2", "a:b", "1:2:3", ""):
            with pytest.raises(ValueError):
                parse_trace_steps(bad)

    def test_window_opens_and_closes_at_bounds(self, fake_profiler):
        w = ListWriter()
        cap = TraceCapture.parse("2:4", "/tmp/tr", writer=w)
        seen = []
        for _ in range(7):
            with cap.unit() as i:
                seen.append((i, cap._active))
        assert seen == [
            (0, False), (1, False), (2, True), (3, True), (4, True),
            (5, False), (6, False),
        ]
        assert fake_profiler.calls == [("start", "/tmp/tr"), ("stop", None)]
        start, stop = w.records
        assert start["note"] == "xla-trace-start"
        assert start["first_step"] == 2
        assert stop["note"] == "xla-trace-stop"
        assert stop["steps_captured"] == 3
        assert stop["last_step"] == 4
        for r in w.records:
            assert schema.validate_record(r) == [], r

    def test_counter_spans_multiple_fit_calls(self, fake_profiler):
        # The CLI's checkpoint-span pattern: one capture across fit calls.
        cap = TraceCapture.parse("3:4", "/tmp/tr", writer=ListWriter())
        for _ in range(2):  # span 1: units 0,1
            with cap.unit():
                pass
        assert not fake_profiler.calls
        for _ in range(3):  # span 2: units 2,3,4 — window 3:4 inside it
            with cap.unit():
                pass
        assert fake_profiler.calls == [("start", "/tmp/tr"), ("stop", None)]

    def test_close_truncates_open_window(self, fake_profiler):
        w = ListWriter()
        cap = TraceCapture.parse("1:100", "/tmp/tr", writer=w)
        for _ in range(3):
            with cap.unit():
                pass
        assert cap._active
        cap.close()
        cap.close()  # idempotent
        assert fake_profiler.calls == [("start", "/tmp/tr"), ("stop", None)]
        assert w.records[-1]["reason"] == "truncated-by-close"
        # a closed capture never reopens
        with cap.unit():
            pass
        assert len(fake_profiler.calls) == 2

    def test_window_opens_with_python_tracer_off(self, fake_profiler):
        # With the Python tracer on, opening a trace stalls every Python
        # thread for seconds and the window measures the profiler.
        cap = TraceCapture.parse("0:0", "/tmp/tr", writer=ListWriter())
        with cap.unit():
            pass
        (opts,) = fake_profiler.options
        assert opts.python_tracer_level == 0

    def test_whole_block_trace_opens_with_python_tracer_off(self, fake_profiler):
        from glom_tpu.tracing.capture import trace

        with trace("/tmp/tr"):
            pass
        (opts,) = fake_profiler.options
        assert opts.python_tracer_level == 0


class FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


class TestMemory:
    STATS = {
        "bytes_in_use": 1100,
        "peak_bytes_in_use": 2000,
        "bytes_limit": 4000,
    }

    def test_watermarks_from_device_stats(self):
        wm = hbm_watermarks(FakeDevice(self.STATS))
        assert wm == {
            "hbm_bytes_in_use": 1100,
            "hbm_peak_bytes": 2000,
            "hbm_bytes_limit": 4000,
        }

    def test_no_stats_degrades_to_empty(self):
        assert hbm_watermarks(FakeDevice(None)) == {}
        assert memory_record(1000, device=FakeDevice(None)) == {}
        # CPU backend (the test platform) has no allocator stats either:
        # the probe the trainers install must stay a silent no-op there.
        assert memory_record(1000) == {}

    def test_drift_reconciles_against_model(self):
        rec = memory_record(1000, device=FakeDevice(self.STATS))
        assert rec["hbm_model_live_bytes"] == 1000
        assert rec["hbm_model_drift"] == pytest.approx(0.1)
        # no model -> watermarks only
        rec = memory_record(None, device=FakeDevice(self.STATS))
        assert "hbm_model_drift" not in rec
        assert rec["hbm_bytes_in_use"] == 1100

    def test_model_total_from_static_record(self):
        static = {
            "params_bytes_per_replica": 10,
            "grads_bytes_per_replica": 20,
            "opt_bytes_per_replica": 30,
            "comm_bytes_per_step": 999,  # not a tenant
        }
        assert model_live_bytes_total(static) == 60

    def test_raising_device_never_raises(self):
        class Broken:
            def memory_stats(self):
                raise RuntimeError("plugin wedged")

        assert memory_record(100, device=Broken()) == {}


def _step_rec(i):
    return schema.stamp({"step": float(i), "loss": 1.0 / (i + 1)},
                        kind="train_step")


class TestFlightRecorder:
    def test_ring_keeps_last_n_in_order(self, tmp_path):
        fr = FlightRecorder(tmp_path, capacity=5)
        for i in range(12):
            fr.observe(_step_rec(i))
        path = fr.dump("manual")
        lines = [json.loads(l) for l in open(path)]
        header, events = lines[0], lines[1:]
        assert header["kind"] == "note"
        assert header["trigger"] == "manual"
        assert header["n_events"] == 5
        assert [e["step"] for e in events] == [7.0, 8.0, 9.0, 10.0, 11.0]
        seqs = [e["flight_seq"] for e in events]
        assert seqs == sorted(seqs)
        assert schema.lint_stream(open(path)) == []

    def test_dump_skips_when_nothing_new(self, tmp_path):
        fr = FlightRecorder(tmp_path, capacity=4)
        fr.observe(_step_rec(0))
        assert fr.dump("one") is not None
        assert fr.dump("atexit") is None  # no new events since
        fr.observe(_step_rec(1))
        assert fr.dump("two") is not None
        assert len(fr.dumps) == 2

    def test_watchdog_down_triggers_dump(self, tmp_path):
        """The acceptance path: steps flow, the backend watchdog forces a
        'down' transition through the shared writer, and the dump holds
        the last N step + watchdog events in arrival order and passes the
        schema linter."""
        from glom_tpu.telemetry.watchdog import BackendWatchdog
        from glom_tpu.utils.metrics import MetricsWriter

        fr = FlightRecorder(tmp_path / "flight", capacity=8)
        set_global_flight_recorder(fr)
        writer = MetricsWriter(str(tmp_path / "m.jsonl"), echo=False)
        for i in range(4):
            writer.write({"step": float(i), "loss": 0.5})
        probes = iter([8, None])
        wd = BackendWatchdog(probe=lambda t: next(probes), writer=writer)
        assert wd.probe_once() == "up"
        assert wd.probe_once() == "down"
        assert len(fr.dumps) == 1, "down transition must dump exactly once"
        lines = [json.loads(l) for l in open(fr.dumps[0])]
        assert lines[0]["trigger"] == "backend-down"
        kinds = [l["kind"] for l in lines[1:]]
        assert kinds == ["train_step"] * 4 + ["watchdog"] * 2
        assert [l["backend_state"] for l in lines[-2:]] == ["up", "down"]
        seqs = [l["flight_seq"] for l in lines[1:]]
        assert seqs == sorted(seqs)
        assert schema.lint_stream(open(fr.dumps[0])) == []

    def test_writerless_watchdog_feeds_global_recorder(self, tmp_path):
        from glom_tpu.telemetry.watchdog import BackendWatchdog

        fr = FlightRecorder(tmp_path, capacity=8)
        set_global_flight_recorder(fr)
        wd = BackendWatchdog(probe=lambda t: None)  # no writer
        wd.probe_once()
        assert len(fr.dumps) == 1  # unknown -> down dumps immediately

    def test_anomaly_storm_triggers_dump(self, tmp_path):
        t = [0.0]
        fr = FlightRecorder(
            tmp_path, capacity=16, storm_threshold=3, storm_window_s=60.0,
            clock=lambda: t[0],
        )
        anomaly = schema.stamp({"step": 1.0, "reason": "nonfinite"},
                               kind="anomaly")
        fr.observe(anomaly)
        t[0] += 100.0  # outside the window: the counter must have aged out
        fr.observe(anomaly)
        assert fr.dumps == []
        fr.observe(anomaly)
        fr.observe(anomaly)  # 3 inside one window -> storm
        assert len(fr.dumps) == 1
        header = json.loads(open(fr.dumps[0]).readline())
        assert header["trigger"] == "anomaly-storm"

    def test_observe_never_raises(self, tmp_path, monkeypatch):
        fr = FlightRecorder(tmp_path, capacity=2)
        monkeypatch.setattr(
            FlightRecorder, "dump",
            lambda self, *a, **k: (_ for _ in ()).throw(OSError("disk full")),
        )
        # trigger event with a broken dump: swallowed, run survives
        fr.observe(schema.stamp(
            {"backend_state": "down", "t": 1.0}, kind="watchdog"
        ))

    def test_global_helpers_are_noops_without_recorder(self):
        observe_event({"kind": "note", "note": "x"})
        assert dump_flight_recorder("whatever") is None

    def test_metrics_writer_and_emit_feed_global_recorder(self, tmp_path, capsys):
        from glom_tpu.telemetry.sinks import emit
        from glom_tpu.utils.metrics import MetricsWriter

        fr = FlightRecorder(tmp_path, capacity=8)
        set_global_flight_recorder(fr)
        w = MetricsWriter(str(tmp_path / "m.jsonl"), echo=False)
        w.write({"step": 0, "loss": 1.0})
        emit({"metric": "m", "value": 1.0, "unit": "u"})
        capsys.readouterr()
        path = fr.dump("check")
        kinds = [json.loads(l)["kind"] for l in open(path)][1:]
        assert kinds == ["train_step", "bench"]

    def test_fit_loop_exception_dumps_postmortem(self, tmp_path):
        """Induced crash inside fit_loop (acceptance criterion): the dump
        exists, names the exception, holds the preceding step records in
        order, and passes the schema linter; the exception re-raises."""
        from glom_tpu.train.trainer import fit_loop
        from glom_tpu.utils.metrics import MetricsWriter

        fr = FlightRecorder(tmp_path / "flight", capacity=16)
        set_global_flight_recorder(fr)
        writer = MetricsWriter(str(tmp_path / "m.jsonl"), echo=False)
        calls = [0]

        def fake_step(batch):
            calls[0] += 1
            if calls[0] == 4:
                raise RuntimeError("induced crash")
            return {"loss": 0.5, "step": float(calls[0] - 1)}

        def data():
            while True:
                yield None

        with pytest.raises(RuntimeError, match="induced crash"):
            fit_loop(fake_step, data(), 10, log_every=1,
                     metrics_writer=writer)
        assert len(fr.dumps) == 1
        lines = [json.loads(l) for l in open(fr.dumps[0])]
        header = lines[0]
        assert header["trigger"] == "fit-loop-exception"
        assert "RuntimeError: induced crash" in header["exception"]
        assert header["at_iteration"] == 3
        steps = [l["step"] for l in lines[1:] if l["kind"] == "train_step"]
        assert steps == [0.0, 1.0, 2.0]
        assert schema.lint_stream(open(fr.dumps[0])) == []

    def test_fit_loop_writerless_still_feeds_recorder(self, tmp_path):
        from glom_tpu.train.trainer import fit_loop

        fr = FlightRecorder(tmp_path, capacity=16)
        set_global_flight_recorder(fr)

        def fake_step(batch):
            return {"loss": 0.5, "step": 0.0}

        fit_loop(fake_step, iter(lambda: None, 1), 2, log_every=1)
        path = fr.dump("check")
        kinds = [json.loads(l)["kind"] for l in open(path)][1:]
        assert "train_step" in kinds and "span" in kinds

    def test_sigterm_hook_dumps(self, tmp_path):
        import os
        import signal

        fr = FlightRecorder(tmp_path, capacity=4)
        fr.observe(_step_rec(0))
        prev = signal.getsignal(signal.SIGTERM)
        try:
            fr.install_process_hooks(on_exit=False)
            with pytest.raises(SystemExit):
                os.kill(os.getpid(), signal.SIGTERM)
            assert len(fr.dumps) == 1
            assert json.loads(open(fr.dumps[0]).readline())["trigger"] == "sigterm"
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_sigterm_hook_preserves_ignored_disposition(self, tmp_path):
        # A host that set SIG_IGN must stay alive through SIGTERM — the
        # hook dumps and returns instead of converting ignore into exit.
        import os
        import signal

        fr = FlightRecorder(tmp_path, capacity=4)
        fr.observe(_step_rec(0))
        prev = signal.getsignal(signal.SIGTERM)
        try:
            signal.signal(signal.SIGTERM, signal.SIG_IGN)
            fr.install_process_hooks(on_exit=False)
            os.kill(os.getpid(), signal.SIGTERM)  # must NOT raise
            assert len(fr.dumps) == 1
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_capacity_validation(self, tmp_path):
        with pytest.raises(ValueError):
            FlightRecorder(tmp_path, capacity=0)
        with pytest.raises(ValueError):
            FlightRecorder(tmp_path, storm_threshold=0)


class TestFitLoopTracingHooks:
    """fit_loop's span/memory/trace plumbing on a fake step — no compiles."""

    def _data(self):
        while True:
            yield None

    def test_logging_records_carry_spans_and_memory(self, tmp_path):
        from glom_tpu.train.trainer import fit_loop
        from glom_tpu.utils.metrics import MetricsWriter

        path = tmp_path / "m.jsonl"
        writer = MetricsWriter(str(path), echo=False)
        n = [0]

        def fake_step(batch):
            n[0] += 1
            return {"loss": 1.0, "step": float(n[0] - 1)}

        probe = lambda: {"hbm_bytes_in_use": 123, "hbm_model_drift": 0.01}
        history = fit_loop(
            fake_step, self._data(), 4, log_every=2,
            metrics_writer=writer, memory_probe=probe,
        )
        assert all(r["hbm_bytes_in_use"] == 123 for r in history)
        recs = [json.loads(l) for l in path.read_text().splitlines()]
        span_recs = [r for r in recs if r["kind"] == "span"]
        names = {r["name"] for r in span_recs}
        assert {"host_data_next", "host_step_dispatch", "host_log_fetch"} <= names
        # two logging boundaries -> each phase drained twice
        assert sum(r["name"] == "host_data_next" for r in span_recs) == 2
        # the rollup covers every step since the previous boundary
        first = next(r for r in span_recs if r["name"] == "host_data_next")
        assert first["count"] == 2
        for r in recs:
            assert schema.validate_record(r) == [], r
        # history itself stays homogeneous train_step records
        assert all(r["kind"] == "train_step" for r in history)

    def test_trace_capture_advances_per_step(self, fake_profiler, tmp_path):
        from glom_tpu.train.trainer import fit_loop

        cap = TraceCapture.parse("1:2", "/tmp/tr", writer=ListWriter())
        fit_loop(lambda b: {"loss": 1.0, "step": 0.0}, self._data(), 4,
                 log_every=4, trace_capture=cap)
        assert fake_profiler.calls == [("start", "/tmp/tr"), ("stop", None)]
        assert cap._count == 4


class RecordingAnnotation:
    """Stands where spans.py puts jax.profiler.TraceAnnotation: records
    (thread, event, name, fields) of every enter and exit."""

    log = None  # a list, set by the fixture

    def __init__(self, name, **fields):
        self.name, self.fields = name, fields

    def __enter__(self):
        self.log.append((threading.get_ident(), "enter", self.name, self.fields))
        return self

    def __exit__(self, *exc):
        self.log.append((threading.get_ident(), "exit", self.name, self.fields))
        return False


@pytest.fixture
def annotations(monkeypatch):
    from glom_tpu.tracing import spans

    log = []
    monkeypatch.setattr(RecordingAnnotation, "log", log)
    monkeypatch.setattr(spans, "_annotation_cls", RecordingAnnotation)
    return log


def _endless(shape=(2, 3)):
    while True:
        yield np.ones(shape, np.float32)


class TestSpansInTheProfilerTrace:
    """The program's host spans are TraceAnnotations of the same name, on
    the thread that runs them, carrying the step they belong to."""

    def test_fit_loop_spans_annotate_with_the_step_index(self, annotations):
        from glom_tpu.train.trainer import fit_loop

        fit_loop(lambda b: {"loss": 1.0, "step": 0.0}, _endless(), 3,
                 log_every=2, metrics_writer=ListWriter())
        me = threading.get_ident()
        assert {t for t, *_ in annotations} == {me}
        entered = [(n, f) for _, ev, n, f in annotations if ev == "enter"]
        assert entered == [
            ("host_data_next", {"step": 0}), ("host_step_dispatch", {"step": 0}),
            ("host_data_next", {"step": 1}), ("host_step_dispatch", {"step": 1}),
            ("host_log_fetch", {"step": 1}),
            ("host_data_next", {"step": 2}), ("host_step_dispatch", {"step": 2}),
            ("host_log_fetch", {"step": 2}),
        ]
        # every span is left before the next is entered: no nesting
        events = [ev for _, ev, _, _ in annotations]
        assert events == ["enter", "exit"] * len(entered)

    def test_prefetch_worker_annotates_on_its_own_thread(self, annotations):
        from glom_tpu.data.prefetch import prefetch_to_device
        from glom_tpu.train.trainer import fit_loop

        data = prefetch_to_device(_endless(), size=2)
        fit_loop(lambda b: {"loss": 1.0, "step": 0.0}, data, 3, log_every=3)
        data.close()
        me = threading.get_ident()
        worker = [(n, f["step"]) for t, ev, n, f in annotations
                  if t != me and ev == "enter"]
        assert len({t for t, *_ in annotations}) == 2
        assert {n for n, _ in worker} == {"host_prefetch_next", "host_prefetch_stage"}
        staged = [k for n, k in worker if n == "host_prefetch_stage"]
        assert staged == list(range(len(staged))) and len(staged) >= 3
        loop = {n for t, ev, n, f in annotations if t == me}
        assert loop == {"host_data_next", "host_step_dispatch", "host_log_fetch"}

    def test_span_fields_ride_event_and_annotation(self, annotations):
        w = ListWriter()
        with span("phase_a", writer=w, step=7):
            pass
        assert annotations[0][2:] == ("phase_a", {"step": 7})
        assert w.records[0]["step"] == 7

    def test_span_works_with_jax_unimportable(self, monkeypatch):
        import sys

        from glom_tpu.tracing import spans

        monkeypatch.setattr(spans, "_annotation_cls", None)  # not yet resolved
        monkeypatch.setitem(sys.modules, "jax", None)         # import jax -> ImportError
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        agg = SpanAggregator()
        with span("phase_a", aggregator=agg, step=1):
            with span("phase_b", aggregator=agg):
                assert current_span() == "phase_b"
        assert spans._annotation_cls is spans._no_annotation
        assert [r["name"] for r in agg.records()] == ["phase_a", "phase_b"]

    def test_spans_module_imports_without_jax(self):
        import subprocess
        import sys

        code = (
            "import sys; sys.modules['jax'] = None\n"
            "from glom_tpu.tracing.spans import PHASES, span, SpanAggregator\n"
            "agg = SpanAggregator()\n"
            "with span(PHASES[0], aggregator=agg, step=0): pass\n"
            "assert agg._stats[PHASES[0]][0] == 1\n"
            "assert not [m for m in sys.modules if m.startswith('jax.')]\n"
        )
        subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


class TestPrefetchRollupsAtLogBoundaries:
    """A training stream does not end inside a run: the worker's rollups
    reach the metrics stream with the loop's, at every log boundary."""

    def test_endless_stream_reports_at_each_boundary_once(self):
        from glom_tpu.data.prefetch import prefetch_to_device
        from glom_tpu.train.trainer import fit_loop

        # The worker pulls a batch when the loop's step lets it, and the
        # step returns once the worker is back at the source for the next:
        # by then the batch it let through was pulled, staged and queued, so
        # every interval between boundaries holds both worker spans whatever
        # the machine's other work does to the threads.
        pulled, entered, gate = [], [], threading.Semaphore(1)

        def source():
            while True:
                entered.append(1)
                gate.acquire()
                pulled.append(1)
                yield np.ones((2, 3), np.float32)

        def step(batch):
            n = len(entered)
            gate.release()
            deadline = time.monotonic() + 60.0
            while len(entered) == n:
                assert time.monotonic() < deadline, "the worker never came back"
                time.sleep(0.001)
            return {"loss": 1.0, "step": 0.0}

        end_sink, w = ListWriter(), ListWriter()
        data = prefetch_to_device(source(), size=2, metrics_writer=end_sink)
        fit_loop(step, data, 6, log_every=2, metrics_writer=w)
        kinds = [(r["kind"], r.get("name")) for r in w.records]
        boundaries = [i for i, k in enumerate(kinds) if k[0] == "train_step"]
        assert len(boundaries) == 3
        for lo, hi in zip(boundaries, boundaries[1:] + [len(kinds)]):
            names = [n for k, n in kinds[lo:hi] if k == "span"]
            assert sorted(names) == sorted(
                ["host_data_next", "host_step_dispatch", "host_log_fetch",
                 "host_prefetch_next", "host_prefetch_stage"])
        for r in w.records:
            assert schema.validate_record(r) == [], r
            if r.get("name", "").startswith("host_prefetch_"):
                assert r["source"] == "prefetch_to_device" and "step" in r
        assert end_sink.records == []  # the stream has not ended
        gate.release(100)  # the worker runs free again, so that it can be stopped
        data.close()
        # The end of the stream reports what no boundary has: each batch the
        # worker pulled and staged is counted exactly once over both sinks.
        both = w.records + end_sink.records
        n_next = sum(r["count"] for r in both if r.get("name") == "host_prefetch_next")
        n_stage = sum(r["count"] for r in both if r.get("name") == "host_prefetch_stage")
        assert n_next == len(pulled)
        assert n_next - 1 <= n_stage <= n_next
        assert n_stage >= 6

    def test_plain_iterator_has_no_rollups_to_drain(self):
        from glom_tpu.train.trainer import fit_loop

        w = ListWriter()
        fit_loop(lambda b: {"loss": 1.0, "step": 0.0}, _endless(), 2, log_every=2,
                 metrics_writer=w)
        names = {r["name"] for r in w.records if r["kind"] == "span"}
        assert names == {"host_data_next", "host_step_dispatch", "host_log_fetch"}


def _made_up(wall, data=0.2, dispatch=3.0, fetch=296.0, gc_ms=0.0,
             run_delay=0.0, majflt=0):
    """An interval of three steps as `judge_interval` takes it; what the
    three spans do not cover is `other`."""
    return {"wall_ms": wall,
            "phases": {"host_data_next": data, "host_step_dispatch": dispatch,
                       "host_log_fetch": fetch,
                       "other": wall - data - dispatch - fetch},
            "gc_ms": gc_ms, "run_delay_ms": run_delay, "majflt": majflt}


USUAL = {"wall_ms": 300.0,
         "phases": {"host_data_next": 0.2, "host_step_dispatch": 3.0,
                    "host_log_fetch": 296.0, "other": 0.8}}


class TestStallRule:
    """`judge_interval` is a pure function of an interval and its reference:
    no clock, no counter is read here."""

    @pytest.mark.parametrize("interval,reference,expected", [
        pytest.param(_made_up(420.0, dispatch=123.0, gc_ms=110.0), USUAL,
                     (120.0, "host_step_dispatch", "gc"), id="gc"),
        pytest.param(_made_up(420.0, dispatch=123.0, gc_ms=59.0, run_delay=61.0), USUAL,
                     (120.0, "host_step_dispatch", "descheduled"), id="descheduled"),
        pytest.param(_made_up(420.0, dispatch=123.0, gc_ms=59.0, run_delay=None), USUAL,
                     (120.0, "host_step_dispatch", "dispatch"), id="run-delay-unread"),
        pytest.param(_made_up(420.0, data=120.2, majflt=3), USUAL,
                     (120.0, "host_data_next", "page_fault"), id="page_fault"),
        pytest.param(_made_up(420.0, fetch=416.0, majflt=3), USUAL,
                     (120.0, "host_log_fetch", "device_or_unknown"),
                     id="page-faults-while-waiting-for-the-device-cost-nothing"),
        pytest.param(_made_up(420.0, data=120.2), USUAL,
                     (120.0, "host_data_next", "data_wait"), id="data_wait"),
        pytest.param(_made_up(420.0, dispatch=123.0), USUAL,
                     (120.0, "host_step_dispatch", "dispatch"), id="dispatch"),
        pytest.param(_made_up(420.0), USUAL,
                     (120.0, "other", "between_spans"), id="between_spans"),
        pytest.param(_made_up(420.0, fetch=416.0), USUAL,
                     (120.0, "host_log_fetch", "device_or_unknown"), id="device_or_unknown"),
        pytest.param(_made_up(420.0, fetch=416.0, gc_ms=70.0), USUAL,
                     (120.0, "host_log_fetch", "gc"),
                     id="the-counters-come-before-the-phase"),
        pytest.param(_made_up(324.0, dispatch=27.0, gc_ms=24.0), USUAL,
                     (0.0, None, None), id="under-the-threshold"),
        pytest.param(_made_up(2045.0, fetch=2041.0),
                     {"wall_ms": 2000.0, "phases": dict(USUAL["phases"], host_log_fetch=1996.0)},
                     (0.0, None, None), id="under-its-share-of-a-long-interval"),
        pytest.param(_made_up(2055.0, fetch=2051.0),
                     {"wall_ms": 2000.0, "phases": dict(USUAL["phases"], host_log_fetch=1996.0)},
                     (55.0, "host_log_fetch", "device_or_unknown"),
                     id="over-its-share-of-a-long-interval"),
        pytest.param(_made_up(5000.0, dispatch=4700.0, gc_ms=4000.0), None,
                     (0.0, None, None), id="no-reference-yet"),
    ])
    def test_the_rule(self, interval, reference, expected):
        verdict = judge_interval(interval, reference)
        stall_ms, phase, cause = expected
        assert verdict["stall_ms"] == pytest.approx(stall_ms, abs=0.01)
        assert verdict.get("stall_phase") == phase
        assert verdict.get("stall_cause") == cause
        if not stall_ms:
            assert verdict == {"stall_ms": 0.0}

    def test_the_reference_is_the_median_of_four_or_more_of_the_same_step_count(self):
        acc = IntervalAccount()
        phases = dict(USUAL["phases"])
        for wall in (300.0, 310.0, 9000.0):  # one of them a stall: the median forgets it
            acc._history.append((3, wall, phases))
        acc._history.append((1, 100.0, phases))
        assert acc.reference(3) is None and acc.reference(1) is None
        acc._history.append((3, 320.0, phases))
        assert acc.reference(3)["wall_ms"] == 315.0
        assert acc.reference(3)["phases"] == phases
        for _ in range(100):
            acc._history.append((3, 1.0, phases))
        assert len(acc._history) == spans_mod.HISTORY_INTERVALS


INTERVAL_FIELDS = {
    "interval_steps", "interval_ms", "interval_other_ms", "host_gc_ms",
    "host_gc_collections", "host_gc_gen2", "host_cpu_ms", "host_run_delay_ms",
    "host_nivcsw", "host_majflt", "stall_ms",
}


class TestIntervalAccount:
    """Every logging record accounts for its interval, and a disturbed
    interval says where and why. Each test disturbs ONE interval by 0.3 s
    and asserts on that interval alone: what a busy machine does to the
    others does not matter."""

    def _run(self, n_calls, steps, *, step=None, data=None, between=None,
             log_every=None):
        """`n_calls` fit_loop calls of `steps` steps over one account and one
        compile tracker, as a trainer's fit makes them; returns the logging
        records and everything written."""
        from glom_tpu.train.trainer import fit_loop

        w, account, tracker, history = ListWriter(), IntervalAccount(), set(), []
        data = data if data is not None else _endless()
        for call in range(n_calls):
            if between is not None:
                between(call)
            history += fit_loop(
                step or (lambda b: {"loss": 1.0, "step": 0.0}), data, steps,
                log_every=log_every or steps, metrics_writer=w,
                compile_tracker=tracker, interval_account=account)
        return history, w.records

    def _check_stream(self, records):
        for r in records:
            assert schema.validate_record(r) == [], r
        steps = [r for r in records if r["kind"] == "train_step"]
        for r in steps:
            assert INTERVAL_FIELDS <= set(r), sorted(INTERVAL_FIELDS - set(r))
            if not r["stall_ms"]:
                assert "stall_phase" not in r and "stall_cause" not in r
        # the span records of a boundary are what they were
        names = {r["name"] for r in records if r["kind"] == "span"}
        assert names == {"host_data_next", "host_step_dispatch", "host_log_fetch"}
        assert spans_mod.HOST_PHASES == (
            "host_data_next", "host_step_dispatch", "host_log_fetch",
            "host_prefetch_next", "host_prefetch_stage")

    def test_a_slow_dispatch(self, capsys):
        calls = [0]

        def step(batch):
            calls[0] += 1
            if calls[0] == 8:
                time.sleep(0.3)
            return {"loss": 1.0, "step": float(calls[0] - 1)}

        history, records = self._run(1, 10, step=step, log_every=1)
        assert [r["interval_steps"] for r in history] == [1] * 10
        hit = history[7]
        assert hit["stall_ms"] >= 250.0
        assert (hit["stall_phase"], hit["stall_cause"]) == ("host_step_dispatch", "dispatch")
        assert hit["interval_ms"] >= 300.0
        # the first interval compiled (a variant's first call): never judged
        assert history[0]["stall_ms"] == 0.0
        self._check_stream(records)
        # one line on standard error for the stalled interval
        lines = [l for l in capsys.readouterr().err.splitlines()
                 if l.startswith("glom_tpu stall: step 7:")]
        assert len(lines) == 1
        assert "in host_step_dispatch, cause dispatch" in lines[0]
        for word in ("host_data_next", "host_log_fetch", "other", "gc ", "cpu ",
                     "run delay", "involuntary switches", "major faults"):
            assert word in lines[0]
        # the histogram is fed the span's own duration: one timing, two readers
        span_max = max(r["max_ms"] for r in records
                       if r.get("name") == "host_step_dispatch" and r["step"] == 7.0)
        assert history[-1]["step_time_max_ms"] == pytest.approx(span_max, abs=2e-3)

    def test_a_slow_source(self):
        def source():
            for n in range(100):
                if n == 7:
                    time.sleep(0.3)
                yield None

        history, records = self._run(1, 10, data=source(), log_every=1)
        hit = history[7]
        assert hit["stall_ms"] >= 250.0
        assert (hit["stall_phase"], hit["stall_cause"]) == ("host_data_next", "data_wait")
        self._check_stream(records)

    def test_a_pause_between_two_fit_calls_and_the_account_outlives_the_call(self):
        def between(call):
            if call == 7:
                time.sleep(0.3)

        history, records = self._run(10, 3, between=between)
        # ten calls of three steps: ten intervals, each from the last call's
        # boundary, and a reference no single call could have had
        assert [r["interval_steps"] for r in history] == [3] * 10
        hit = history[7]
        assert hit["stall_ms"] >= 250.0
        assert (hit["stall_phase"], hit["stall_cause"]) == ("other", "between_spans")
        assert hit["interval_other_ms"] >= 300.0
        self._check_stream(records)

    def test_a_fit_loop_without_an_account_makes_its_own(self):
        from glom_tpu.train.trainer import fit_loop

        history = fit_loop(lambda b: {"loss": 1.0, "step": 0.0}, _endless(), 4,
                           log_every=2)
        assert [r["interval_steps"] for r in history] == [2, 2]
        assert all(INTERVAL_FIELDS <= set(r) for r in history)

    def test_a_collection_is_counted_charged_and_annotated(self, monkeypatch, capsys):
        log = []
        monkeypatch.setattr(RecordingAnnotation, "log", log)
        monkeypatch.setattr(spans_mod.gc_watch(), "annotation", RecordingAnnotation)
        calls = [0]

        def step(batch):
            calls[0] += 1
            if calls[0] == 3:
                # a large cyclic heap that only a collection frees, made
                # with the collector off so that its pause is one pause
                gc.disable()
                for _ in range(500_000):
                    a = []
                    a.append([a])
            if calls[0] == 8:
                gc.collect()
                gc.enable()
            return {"loss": 1.0, "step": float(calls[0] - 1)}

        try:
            history, records = self._run(1, 10, step=step, log_every=1)
        finally:
            gc.enable()
        hit = history[7]
        assert hit["host_gc_ms"] >= 50.0
        assert hit["host_gc_collections"] >= 1 and hit["host_gc_gen2"] >= 1
        assert hit["stall_ms"] >= 50.0  # the pause itself, 200 ms here, is over the rule's 25
        assert (hit["stall_phase"], hit["stall_cause"]) == ("host_step_dispatch", "gc")
        self._check_stream(records)
        (line,) = [l for l in capsys.readouterr().err.splitlines()
                   if l.startswith("glom_tpu stall: step 7:")]
        assert "under host_step_dispatch" in line  # the pause is charged to the open span
        # the collection lies on this thread of a profiler trace, start to stop
        me = threading.get_ident()
        assert (me, "enter", "host_gc", {"generation": 2}) in log
        assert (me, "exit", "host_gc", {"generation": 2}) in log

    def test_one_hook_a_process(self):
        watch = spans_mod.gc_watch()
        IntervalAccount(), IntervalAccount()
        assert spans_mod.gc_watch() is watch
        assert gc.callbacks.count(watch) == 1


def _reader(name):
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _window(**over):
    """A window's records as the benchmark's collector holds them: the
    first interval began before the window and is left out by every reader;
    the counted three are 1,000 ms and 9 steps."""
    quiet = {"kind": "train_step", "interval_steps": 3, "interval_ms": 300.0,
             "interval_other_ms": 0.6, "host_gc_ms": 0.0, "host_nivcsw": 0,
             "stall_ms": 0.0}
    recs = [dict(quiet, interval_ms=9000.0, stall_ms=8700.0, stall_cause="between_spans",
                 host_gc_ms=500.0, host_nivcsw=40, interval_other_ms=8700.0),
            dict(quiet),
            dict(quiet, interval_ms=400.0, stall_ms=100.0, stall_cause="gc",
                 stall_phase="host_step_dispatch", host_gc_ms=90.0, host_nivcsw=7),
            dict(quiet, interval_ms=300.0, stall_ms=60.0, stall_cause="device_or_unknown",
                 stall_phase="host_log_fetch", interval_other_ms=3.9)]
    recs = [dict(r, **over) for r in recs]
    spans_between = [{"kind": "span", "name": "host_log_fetch", "dur_s": 0.3}]
    return {"kind": "train", "records": spans_between + recs, "steps": 12}


RATIO_READERS = ["window_stall_pct.train", "window_stall_unexplained_pct.train",
                 "host_gc_pause_pct.train", "host_preempted_per_step.train"]


class TestIntervalReaders:
    """The five per-layer metrics that read the records' intervals
    (benchmark/layer_metrics/): a number in every run of this program, 0
    where nothing happened, and nothing from a program without the fields."""

    @pytest.mark.parametrize("name,expected", [
        ("window_stall_pct.train", 16.0),
        ("window_stall_unexplained_pct.train", 6.0),
        ("host_gc_pause_pct.train", 9.0),
        ("host_preempted_per_step.train", 7 / 9),
        ("host_loop_other_ms.train", 5.1 / 9),
    ])
    def test_reads_the_windows_intervals_but_the_first(self, name, expected):
        assert _reader(name)(_window()) == pytest.approx(expected)

    @pytest.mark.parametrize("name", RATIO_READERS)
    def test_a_quiet_window_reads_zero_and_not_nothing(self, name):
        ctx = _window(stall_ms=0.0, stall_cause=None, host_gc_ms=0.0, host_nivcsw=0)
        assert _reader(name)(ctx) == 0.0

    @pytest.mark.parametrize("name", RATIO_READERS + ["host_loop_other_ms.train"])
    def test_a_program_without_the_account_gives_nothing_to_read(self, name):
        parent = {"kind": "train", "steps": 6, "records": [
            {"kind": "train_step", "step": 2.0, "loss": 1.0},
            {"kind": "span", "name": "host_log_fetch", "dur_s": 0.3},
            {"kind": "train_step", "step": 5.0, "loss": 1.0}]}
        assert _reader(name)(parent) is None
        assert _reader(name)({"kind": "train", "records": []}) is None


class TestProfilingShim:
    def test_reexports_are_the_tracing_objects(self):
        from glom_tpu import tracing
        from glom_tpu.utils import profiling

        assert profiling.trace is tracing.capture.trace
        assert profiling.start_server is tracing.capture.start_server
        assert profiling.annotate is tracing.capture.annotate

    def test_trace_context_manager_drives_profiler(self, fake_profiler):
        from glom_tpu.utils.profiling import trace

        with trace("/tmp/shimtrace") as d:
            assert d == "/tmp/shimtrace"
        assert fake_profiler.calls == [("start", "/tmp/shimtrace"),
                                       ("stop", None)]
        # stop must run on exception too (no leaked profiler session)
        with pytest.raises(RuntimeError):
            with trace("/tmp/shimtrace2"):
                raise RuntimeError("boom")
        assert fake_profiler.calls[-1] == ("stop", None)


class TestHostSpanCoverage:
    """The last unattributed host-time sinks the ROADMAP named: checkpoint
    save/wait and the prefetch worker are span-covered via spans.spanned."""

    class Sink:
        def __init__(self):
            self.records = []

        def write(self, rec):
            self.records.append(rec)

    def test_checkpoint_save_and_wait_emit_spans(self, tmp_path):
        from glom_tpu.telemetry import schema
        from glom_tpu.utils.checkpoint import CheckpointManager, abstract_like

        sink = self.Sink()
        mgr = CheckpointManager(
            str(tmp_path / "ckpt"), async_save=False, metrics_writer=sink
        )
        state = {"w": jnp.arange(4.0)}
        mgr.save(0, state)
        mgr.wait()
        names = [r.get("name") for r in sink.records]
        assert "host_checkpoint_save" in names
        assert "host_checkpoint_wait" in names
        for r in sink.records:
            assert r["kind"] == "span"
            assert schema.validate_record(r) == [], r
        # The spanned wrapper must not break the return contract.
        step, restored = mgr.restore(abstract_state=abstract_like(state))
        assert step == 0
        np.testing.assert_allclose(restored["w"], np.arange(4.0))

    def test_checkpoint_spans_feed_flight_ring_without_writer(self, tmp_path):
        from glom_tpu.tracing.flight import (
            FlightRecorder,
            set_global_flight_recorder,
        )
        from glom_tpu.utils.checkpoint import CheckpointManager

        fr = FlightRecorder(str(tmp_path / "fl"), capacity=16)
        set_global_flight_recorder(fr)
        try:
            mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
            mgr.save(0, {"w": jnp.zeros(2)})
            mgr.wait()
        finally:
            set_global_flight_recorder(None)
        names = [r.get("name") for r in fr._buf]
        assert "host_checkpoint_save" in names

    def test_prefetch_worker_emits_span_rollups(self):
        from glom_tpu.data.prefetch import prefetch_to_device
        from glom_tpu.telemetry import schema

        sink = self.Sink()
        data = iter(np.ones((2, 3), np.float32) for _ in range(4))
        out = list(prefetch_to_device(data, size=2, metrics_writer=sink))
        assert len(out) == 4
        spans = [r for r in sink.records if r.get("kind") == "span"]
        names = {r["name"] for r in spans}
        assert "host_prefetch_stage" in names
        assert "host_prefetch_next" in names
        for r in spans:
            assert r.get("source") == "prefetch_to_device"
            assert schema.validate_record(r) == [], r
        stage = next(r for r in spans if r["name"] == "host_prefetch_stage")
        assert stage["count"] == 4

    def test_prefetch_spans_drain_on_early_drop(self):
        from glom_tpu.data.prefetch import prefetch_to_device

        sink = self.Sink()
        data = iter(np.zeros(2) for _ in range(100))
        it = prefetch_to_device(data, size=2, metrics_writer=sink)
        next(it)
        it.close()  # consumer walks away mid-stream
        assert any(
            r.get("name") == "host_prefetch_stage" for r in sink.records
        )
