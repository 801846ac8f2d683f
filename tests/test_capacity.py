"""Capacity observatory (ISSUE 13, docs/OBSERVABILITY.md): per-collective
wall-time (α-β time model, sampled/full harnesses), the serve latency
decomposition's bit-exact conservation, and headroom accounting + the
watch --slo headroom lower-bound rule.

Host-side fakes wherever possible; the jitted pieces (the sampler's
re-dispatched sub-graphs, the manual-zero1 and serve-mesh acceptance
locks) ride the 8-device virtual CPU mesh the conftest pins.
"""

import math
import time
from pathlib import Path

import numpy as np
import pytest

from glom_tpu.serve.batcher import DynamicBatcher
from glom_tpu.serve.engine import ServeResult
from glom_tpu.telemetry import comm_time, schema, tracectx
from glom_tpu.telemetry.aggregate import SLOMonitor, watch_main
from glom_tpu.telemetry.counters import (
    CollectiveCounters,
    CollectiveTimeLog,
    recording,
    resolve_collective_timing,
    scaled,
    timed_collective,
    timing,
)
from glom_tpu.telemetry.tracectx import PHASE_KEYS
from glom_tpu.utils.config import ServeConfig

FIXTURES = Path(__file__).resolve().parent / "fixtures"


class Sink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(dict(rec))


IMG = np.zeros((3, 8, 8), np.float32)


# ---------------------------------------------------------------------------
# the α-β time model
# ---------------------------------------------------------------------------


class TestTimeModel:
    def test_fit_recovers_alpha_beta(self):
        alpha, beta = 0.5, 2e-6
        pts = [
            {"wire_bytes": x, "wall_ms": alpha + beta * x}
            for x in (1e5, 2e5, 4e5, 8e5)
        ]
        m = comm_time.fit_time_model(pts)
        assert m["alpha_ms"] == pytest.approx(alpha, rel=1e-6)
        assert m["beta_ms_per_byte"] == pytest.approx(beta, rel=1e-6)
        assert m["n_points"] == 4
        for p in pts:
            pred = comm_time.predict_ms(m, p["wire_bytes"])
            assert comm_time.time_model_drift(p["wall_ms"], pred) == (
                pytest.approx(0.0, abs=1e-6)
            )

    def test_degenerate_fits_stay_honest(self):
        # No points at all.
        m0 = comm_time.fit_time_model([])
        assert m0 == {
            "alpha_ms": 0.0, "beta_ms_per_byte": 0.0, "n_points": 0
        }
        # One point / all points at one byte size: alpha = mean, beta 0 —
        # a bandwidth term the data never measured must not be invented.
        m1 = comm_time.fit_time_model(
            [{"wire_bytes": 1024, "wall_ms": 3.0},
             {"wire_bytes": 1024, "wall_ms": 5.0}]
        )
        assert m1["alpha_ms"] == pytest.approx(4.0)
        assert m1["beta_ms_per_byte"] == 0.0

    def test_negative_slope_clamps_to_zero(self):
        # Noise giving smaller payloads LONGER times must not extrapolate
        # to negative predictions.
        m = comm_time.fit_time_model(
            [{"wire_bytes": 100, "wall_ms": 5.0},
             {"wire_bytes": 10000, "wall_ms": 1.0}]
        )
        assert m["beta_ms_per_byte"] == 0.0
        assert m["alpha_ms"] >= 0.0

    def test_drift_conventions_match_comm_model_drift(self):
        assert comm_time.time_model_drift(0.0, 0.0) == 0.0
        assert comm_time.time_model_drift(1.0, 0.0) == 1e9  # inf clamp
        assert comm_time.time_model_drift(3.0, 2.0) == pytest.approx(0.5)

    def test_records_carry_model_row_and_lint(self):
        samples = [
            {"site": "a", "axis": "data", "collective": "psum",
             "wire_bytes": 1000, "wall_ms": 1.0, "calls": 2},
            {"site": "b", "axis": "data", "collective": "all_gather",
             "wire_bytes": 4000, "wall_ms": 2.0},
        ]
        recs = comm_time.collective_time_records(
            samples, path="test", mode="sampled"
        )
        assert [r["site"] for r in recs] == ["a", "b", "comm_time_model"]
        for r in recs:
            assert schema.validate_record(r) == [], r
            assert r["kind"] == "collective_time"
            assert math.isfinite(r["comm_time_model_drift"])
        model = recs[-1]
        assert model["wall_ms"] == pytest.approx(3.0)
        assert {"alpha_ms", "beta_ms_per_byte", "n_points"} <= set(model)
        # bytes/s only where wall time exists.
        assert recs[0]["bytes_per_s"] == pytest.approx(1000 / 1e-3)
        assert comm_time.collective_time_records(
            [], path="test", mode="sampled"
        ) == []


# ---------------------------------------------------------------------------
# the shared timing wrapper + site registry
# ---------------------------------------------------------------------------


class TestTimedCollective:
    def test_registers_site_with_scaled_calls(self):
        c = CollectiveCounters()
        x = np.zeros((4, 8), np.float32)
        with recording(c), scaled(3):
            out = timed_collective(
                "site_a", "data", "reduce", 128,
                lambda v: v + 1, x, collective="psum",
            )
        np.testing.assert_array_equal(out, x + 1)
        # Bytes counted exactly as record_collective would (x scale).
        assert c.reduce_bytes == 128 * 3
        (site,) = c.sites
        assert site["site"] == "site_a" and site["calls"] == 3
        assert site["shape"] == (4, 8) and site["collective"] == "psum"

    def test_retrace_accumulates_calls_not_duplicates(self):
        c = CollectiveCounters()
        x = np.zeros((2,), np.float32)
        with recording(c):
            for _ in range(2):
                timed_collective(
                    "site_a", "data", "reduce", 8,
                    lambda v: v, x, collective="psum",
                )
        (site,) = c.sites
        assert site["calls"] == 2

    def test_resolve_vocabulary_and_degrade(self):
        with pytest.raises(ValueError, match="collective_timing"):
            resolve_collective_timing("bogus")
        assert resolve_collective_timing("off") == "off"
        assert resolve_collective_timing("full") == "full"
        with pytest.warns(UserWarning, match="sampled"):
            assert (
                resolve_collective_timing("full", supports_full=False)
                == "sampled"
            )

    def test_full_mode_brackets_inside_shard_map(self):
        """The full-mode io_callback brackets, traced INSIDE a shard_map:
        every shard's execution contributes one wall-clock sample to the
        log; off-mode traces of the same body contribute none."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from glom_tpu.parallel.mesh import make_mesh
        from jax import shard_map
        from glom_tpu.utils.config import MeshConfig

        mesh = make_mesh(MeshConfig(data=2), jax.devices()[:2])

        def body(x):
            return timed_collective(
                "bracket_psum", "data", "reduce", 64,
                lambda v: lax.psum(v, "data"), x, collective="psum",
            )

        fn = shard_map(
            body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False,
        )
        x = jnp.arange(8.0).reshape(2, 4)
        log = CollectiveTimeLog()
        with timing("full", log):
            compiled = jax.jit(fn).lower(x).compile()
        jax.block_until_ready(compiled(x))
        time.sleep(0.05)  # callbacks flush asynchronously
        rows = log.drain()
        assert rows, "full-mode brackets produced no samples"
        (row,) = rows
        assert row["site"] == "bracket_psum" and row["mode"] == "full"
        assert row["calls"] == 2  # one sample per shard
        assert row["wall_ms"] > 0
        # Off mode: same trace, no callbacks, no samples.
        log2 = CollectiveTimeLog()
        with timing("off", log2):
            compiled2 = jax.jit(fn).lower(x).compile()
        jax.block_until_ready(compiled2(x))
        time.sleep(0.05)
        assert log2.drain() == []


class TestSampler:
    def _mesh(self, k=2):
        import jax

        from glom_tpu.parallel.mesh import make_mesh
        from glom_tpu.utils.config import MeshConfig

        return make_mesh(MeshConfig(data=k), jax.devices()[:k])

    def test_sample_times_each_site(self):
        sites = [
            {"site": "s_psum", "axis": "data", "collective": "psum",
             "wire_bytes": 64, "calls": 1, "shape": (4, 4),
             "dtype": "float32", "dim": 0},
            {"site": "s_gather", "axis": "data",
             "collective": "all_gather", "wire_bytes": 64, "calls": 1,
             "shape": (2, 4), "dtype": "float32", "dim": 0},
        ]
        s = comm_time.CollectiveTimeSampler(
            self._mesh(), sites, interval=2, repeats=2
        )
        rows = s.sample()
        assert {r["site"] for r in rows} == {"s_psum", "s_gather"}
        assert all(r["wall_ms"] > 0 for r in rows)

    def test_maybe_sample_rate_limits(self):
        sites = [
            {"site": "s", "axis": "data", "collective": "psum",
             "wire_bytes": 16, "calls": 1, "shape": (2,),
             "dtype": "float32", "dim": 0},
        ]
        s = comm_time.CollectiveTimeSampler(
            self._mesh(), sites, interval=2, repeats=1
        )
        assert s.maybe_sample(path="t") == []
        recs = s.maybe_sample(path="t")
        assert recs and recs[-1]["site"] == "comm_time_model"
        for r in recs:
            assert schema.validate_record(r) == [], r
        assert s.maybe_sample(path="t") == []

    def test_dedupes_byte_identical_shapes(self):
        sites = [
            {"site": "s", "axis": "data", "collective": "psum",
             "wire_bytes": 64, "calls": 2, "shape": (4, 4),
             "dtype": "float32", "dim": 0},
            {"site": "s", "axis": "data", "collective": "psum",
             "wire_bytes": 64, "calls": 3, "shape": (16,),
             "dtype": "float32", "dim": 0},
            {"site": "s", "axis": "data", "collective": "psum",
             "wire_bytes": 0, "calls": 1, "shape": (1,),
             "dtype": "float32", "dim": 0},
        ]
        s = comm_time.CollectiveTimeSampler(self._mesh(), sites)
        # Two byte-identical entries merge (calls sum); the zero-byte
        # site is filtered entirely.
        assert len(s.sites) == 1
        assert s.sites[0]["calls"] == 5


# ---------------------------------------------------------------------------
# serve latency decomposition (host-side fakes)
# ---------------------------------------------------------------------------


class PhaseFakeEngine:
    """FakeEngine returning a fixed engine wall + engine-side phase
    split, so the batcher's derived device_ms is deterministic."""

    def __init__(self, buckets=(1, 2, 4), latency_s=0.01, phases=None):
        self.scfg = ServeConfig(
            buckets=buckets, max_batch=max(buckets), max_delay_ms=5.0,
            queue_depth=8,
        )
        self.latency_s = latency_s
        self.phases = (
            phases if phases is not None
            else {"h2d_ms": 0.5, "resolve_ms": 0.25}
        )
        self.calls = []

    def pick_bucket(self, n):
        for b in self.scfg.buckets:
            if n <= b:
                return b
        raise ValueError(n)

    def infer(self, imgs, n_valid=None):
        b = imgs.shape[0]
        self.calls.append((b, n_valid))
        return ServeResult(
            levels=np.zeros((b, 16, 3, 16), np.float32),
            iters_run=6,
            latency_s=self.latency_s,
            bucket=b,
            compiled=False,
            phases=dict(self.phases),
        )


class TieredPhaseEngine(PhaseFakeEngine):
    """Auto-route fake whose FIRST dispatch leaves one straggler (row 0
    unconverged), so the batcher opens a continuation hop; a permanent
    `fail` exception drives the failover path."""

    def __init__(self, name="engine0", fail=None, **kw):
        super().__init__(**kw)
        self.scfg = ServeConfig(
            buckets=(1, 2, 4), max_batch=4, max_delay_ms=5.0,
            queue_depth=8, iters="auto", max_auto_iters=12,
            max_continuations=2, exit_threshold=1e-3,
        )
        self.name = name
        self.iters_key = "auto"
        self.auto_budget = 12
        self.fail = fail
        self.dispatches = 0

    def cold_levels(self):
        return np.zeros((16, 3, 16), np.float32)

    def infer(self, imgs, n_valid=None, levels0=None, auto_budget=None,
              iters_override=None):
        if self.fail is not None:
            raise self.fail
        b = imgs.shape[0]
        self.dispatches += 1
        conv = np.ones((b,), bool)
        if self.dispatches == 1 and levels0 is None:
            conv[0] = False  # one straggler on the first cold dispatch
        iters = 4 if levels0 is None else 3
        return ServeResult(
            levels=np.zeros((b, 16, 3, 16), np.float32),
            iters_run=iters,
            latency_s=self.latency_s,
            bucket=b,
            compiled=False,
            row_converged=conv,
            row_iters=np.full((b,), iters, np.int32),
            phases=dict(self.phases),
        )


class TestPhaseSplit:
    def test_phases_sum_bit_exactly_to_latency_ms(self):
        eng = PhaseFakeEngine()
        sink = Sink()
        with DynamicBatcher(eng, max_batch=2, max_delay_ms=10.0,
                            writer=sink) as b:
            ts = [b.submit(IMG) for _ in range(2)]
            for t in ts:
                t.result(timeout=10.0)
        (d,) = [r for r in sink.records if r.get("event") == "dispatch"]
        s = 0.0
        for k in PHASE_KEYS:
            assert isinstance(d[k], float), (k, d[k])
            s = s + d[k]
        assert s == d["latency_ms"]  # BIT-exact, not approx
        # The engine split surfaces: h2d as reported, device = engine
        # wall minus the engine-side h2d + resolve.
        assert d["h2d_ms"] == 0.5
        assert d["device_ms"] == pytest.approx(10.0 - 0.5 - 0.25, abs=0.2)
        assert schema.validate_record(d) == []

    def test_phase_split_off_stamps_null_keys(self):
        eng = PhaseFakeEngine()
        sink = Sink()
        with DynamicBatcher(eng, max_batch=1, max_delay_ms=5.0,
                            writer=sink, phase_split=False) as b:
            b.submit(IMG).result(timeout=10.0)
        (d,) = [r for r in sink.records if r.get("event") == "dispatch"]
        for k in PHASE_KEYS:
            assert k in d and d[k] is None
        # latency_ms reverts to the bare engine wall (pre-v7 reading).
        assert d["latency_ms"] == pytest.approx(10.0, abs=0.01)
        (leaf,) = [r for r in sink.records if r.get("event") == "resolve"]
        assert leaf["phase_ms_total"] is None
        check = tracectx.conservation(sink.records, leaf["trace_id"])
        assert check["ok"], check

    def test_engine_without_phases_attributes_wall_to_device(self):
        class Bare(PhaseFakeEngine):
            def infer(self, imgs, n_valid=None):
                r = super().infer(imgs, n_valid=n_valid)
                return r._replace(phases=None)

        sink = Sink()
        with DynamicBatcher(Bare(), max_batch=1, max_delay_ms=5.0,
                            writer=sink) as b:
            b.submit(IMG).result(timeout=10.0)
        (d,) = [r for r in sink.records if r.get("event") == "dispatch"]
        assert d["h2d_ms"] == 0.0
        assert d["device_ms"] == pytest.approx(10.0, abs=0.01)
        s = 0.0
        for k in PHASE_KEYS:
            s = s + d[k]
        assert s == d["latency_ms"]

    def test_conservation_across_continuation_hops(self):
        """The extended parity lock: per-hop phase sums AND cross-hop
        per-phase totals conserve bit-exactly through a straggler
        continuation chain."""
        eng = TieredPhaseEngine()
        sink = Sink()
        with DynamicBatcher(eng, max_batch=2, max_delay_ms=10.0,
                            writer=sink) as b:
            ts = [b.submit(IMG) for _ in range(2)]
            for t in ts:
                t.result(timeout=10.0)
        recs = sink.records
        assert any(r.get("event") == "continuation" for r in recs)
        for t in ts:
            check = tracectx.conservation(recs, t.trace_id)
            assert check["ok"], check
        straggler = [t for t in ts if t.hops][0]
        check = tracectx.conservation(recs, straggler.trace_id)
        assert check["n_hops"] >= 2
        assert set(check["phase_ms_total"]) == set(PHASE_KEYS)

    def test_conservation_across_failover(self):
        bad = TieredPhaseEngine(name="bad", fail=RuntimeError("boom"))
        good = TieredPhaseEngine(name="good")
        sink = Sink()
        with DynamicBatcher(engines=[bad, good], max_batch=4,
                            max_delay_ms=10.0, writer=sink) as b:
            # PACED submissions until "bad" has demonstrably taken (and
            # failed) a batch — an all-at-once burst let one pickup race
            # decide whether the failover path ran at all (the
            # test_serve.py kill-path fix, same flake).
            ts = [b.submit(IMG)]
            deadline = time.monotonic() + 10.0
            while not any(
                r.get("event") == "engine_failover" for r in sink.records
            ):
                assert time.monotonic() < deadline, "bad never dispatched"
                time.sleep(0.02)
                ts.append(b.submit(IMG))
            ts += [b.submit(IMG) for _ in range(2)]
            for t in ts:
                t.result(timeout=10.0)
        recs = sink.records
        assert any(r.get("event") == "engine_failover" for r in recs)
        for t in ts:
            check = tracectx.conservation(recs, t.trace_id)
            assert check["ok"], check
        for r in recs:
            assert schema.validate_record(r) == [], r

    def test_tampered_phase_fails_conservation(self):
        eng = TieredPhaseEngine()
        sink = Sink()
        with DynamicBatcher(eng, max_batch=2, max_delay_ms=10.0,
                            writer=sink) as b:
            ts = [b.submit(IMG) for _ in range(2)]
            for t in ts:
                t.result(timeout=10.0)
        recs = [dict(r) for r in sink.records]
        straggler = [t for t in ts if t.hops][0]
        for r in recs:
            if r.get("event") == "dispatch":
                r["device_ms"] = r["device_ms"] + 0.001
                break
        check = tracectx.conservation(recs, straggler.trace_id)
        assert not check["ok"]
        assert "phase" in check["why"] or "conserve" in check["why"]

    def test_queue_wait_reflects_actual_waiting(self):
        eng = PhaseFakeEngine()
        sink = Sink()
        b = DynamicBatcher(eng, max_batch=1, max_delay_ms=5.0,
                           writer=sink)  # not started yet
        t = b.submit(IMG)
        time.sleep(0.05)  # the request ages in the queue
        b.start()
        t.result(timeout=10.0)
        b.stop()
        (d,) = [r for r in sink.records if r.get("event") == "dispatch"]
        assert d["queue_wait_ms"] >= 40.0


# ---------------------------------------------------------------------------
# headroom accounting
# ---------------------------------------------------------------------------


class StubPool:
    def __init__(self, used, total):
        self._used, self._total = used, total
        self.delta = False
        self.page_tokens = 16

    def record(self):
        return {"pages_total": self._total, "pages_used": self._used,
                "pages_free": self._total - self._used}


class TestCapacityRecords:
    def test_headroom_monotone_under_queue_load(self):
        eng = PhaseFakeEngine()
        b = DynamicBatcher(eng, queue_depth=8)  # NOT started: queue fills
        headrooms = []
        for _ in range(6):
            b.submit(IMG)
            (cap,) = b.capacity_records()
            headrooms.append(cap["headroom"])
            assert schema.validate_record(cap) == []
        assert headrooms == sorted(headrooms, reverse=True)
        assert headrooms[-1] < headrooms[0]
        b.stop(drain=False)

    def test_dead_engine_has_zero_headroom(self):
        eng = PhaseFakeEngine()
        b = DynamicBatcher(eng)
        with b._engine_lock:
            b._engine_state["engine0"]["alive"] = False
        (cap,) = b.capacity_records()
        assert cap["headroom"] == 0.0 and cap["alive"] is False
        b.stop(drain=False)

    def test_pool_fill_caps_headroom(self):
        eng = PhaseFakeEngine()
        eng.pool = StubPool(used=9, total=10)
        eng.name = "engine0"
        b = DynamicBatcher(eng)
        (cap,) = b.capacity_records()
        assert cap["pool_fill"] == pytest.approx(0.9)
        assert cap["utilization"] >= 0.9
        assert cap["headroom"] <= 0.1
        b.stop(drain=False)

    def test_service_rate_from_dispatch_evidence(self):
        eng = PhaseFakeEngine(latency_s=0.01)
        sink = Sink()
        with DynamicBatcher(eng, max_batch=2, max_delay_ms=10.0,
                            writer=sink) as b:
            ts = [b.submit(IMG) for _ in range(4)]
            for t in ts:
                t.result(timeout=10.0)
            (cap,) = b.capacity_records()
        assert cap["service_rate_rps"] is not None
        assert cap["service_rate_rps"] > 0
        assert cap["n_dispatches"] >= 1

    def test_summary_emits_capacity_records_and_nest(self):
        eng = PhaseFakeEngine()
        sink = Sink()
        with DynamicBatcher(eng, max_batch=1, max_delay_ms=5.0,
                            writer=sink) as b:
            b.submit(IMG).result(timeout=10.0)
            summary = b.summary_record()
        caps = [r for r in sink.records if r.get("kind") == "capacity"]
        assert caps and caps[0]["engine"] == "engine0"
        assert "capacity" in summary
        assert summary["capacity"]["engine0"]["headroom"] == (
            caps[0]["headroom"]
        )
        assert "latency_phases" in summary
        assert set(summary["latency_phases"]) == set(PHASE_KEYS)
        assert schema.validate_record(summary) == []


class TestHeadroomSLO:
    def test_headroom_is_a_lower_bound_rule(self):
        mon = SLOMonitor({"headroom": 0.2}, window_s=None)
        for h in (0.9, 0.5, 0.4):
            mon.observe(schema.stamp(
                {"engine": "e0", "headroom": h}, kind="capacity"
            ))
        assert mon.evaluate() == []  # min 0.4 >= 0.2: no breach
        mon.observe(schema.stamp(
            {"engine": "e1", "headroom": 0.05}, kind="capacity"
        ))
        (breach,) = mon.evaluate()
        assert breach["rule"] == "headroom"
        assert breach["observed"] == pytest.approx(0.05)
        assert breach["bound"] == "lower"
        assert schema.validate_record(breach) == []

    def test_min_across_engines_is_the_signal(self):
        # One exhausted engine among idle siblings IS the scale-out
        # signal.
        mon = SLOMonitor({"headroom": 0.2}, window_s=None)
        mon.observe(schema.stamp(
            {"engine": "idle", "headroom": 0.95}, kind="capacity"
        ))
        mon.observe(schema.stamp(
            {"engine": "hot", "headroom": 0.1}, kind="capacity"
        ))
        assert mon.observed()["headroom"] == pytest.approx(0.1)
        assert len(mon.evaluate()) == 1

    def test_upper_bound_rules_unchanged(self):
        mon = SLOMonitor({"p99_ms": 50.0}, window_s=None)
        mon.observe(schema.stamp(
            {"event": "resolve", "latency_ms": 100.0, "iters_total": 4,
             "trace_id": "t1"}, kind="serve",
        ))
        (breach,) = mon.evaluate()
        assert breach["rule"] == "p99_ms" and breach["bound"] == "upper"

    def test_watch_once_exits_nonzero_on_exhausted_stream(self, capsys):
        rc = watch_main(
            [str(FIXTURES / "capacity_exhausted.jsonl"),
             "--slo", "headroom=0.2", "--once"]
        )
        assert rc == 1
        out = capsys.readouterr()
        assert "headroom" in out.out

    def test_watch_once_exits_zero_on_idle_stream(self):
        rc = watch_main(
            [str(FIXTURES / "capacity_idle.jsonl"),
             "--slo", "headroom=0.2", "--once"]
        )
        assert rc == 0


# ---------------------------------------------------------------------------
# the acceptance locks on the CPU mesh (manual zero1 + serve-mesh witness)
# ---------------------------------------------------------------------------


class TestManualZero1Timing:
    def test_sampled_timing_produces_site_records(self):
        """ISSUE 13 acceptance: with timing enabled on the CPU mesh,
        every registered collective site on the manual zero1 path
        produces collective_time records — schema-clean, nonzero wall_ms,
        finite comm_time_model_drift."""
        import jax

        from glom_tpu.parallel.runtime import DistributedTrainer
        from glom_tpu.utils.config import (
            GlomConfig,
            MeshConfig,
            TrainConfig,
        )

        dp = min(8, len(jax.devices()))
        cfg = GlomConfig(dim=16, levels=2, image_size=8, patch_size=4)
        tcfg = TrainConfig(
            batch_size=dp, use_pallas=True, zero_stage=1,
            telemetry_level="scalars", collective_timing="sampled",
            collective_timing_interval=1,
        )
        tr = DistributedTrainer(cfg, tcfg, MeshConfig(data=dp))
        assert tr.collective_timing == "sampled"
        assert tr._static_record["collective_timing"] == "sampled"
        recs = tr.collective_time_records(force=True)
        sites = {r["site"] for r in recs}
        # The zero1 schedule's registered sites (seq=1: no seq psum).
        assert {"zero_psum_scatter", "zero_all_gather",
                "comm_time_model"} <= sites
        for r in recs:
            assert schema.validate_record(r) == [], r
            assert r["wall_ms"] > 0
            assert math.isfinite(r["comm_time_model_drift"])

    def test_full_degrades_to_sampled_loudly_and_off_is_silent(self):
        import jax

        from glom_tpu.parallel.runtime import DistributedTrainer
        from glom_tpu.utils.config import (
            GlomConfig,
            MeshConfig,
            TrainConfig,
        )

        dp = min(8, len(jax.devices()))
        cfg = GlomConfig(dim=16, levels=2, image_size=8, patch_size=4)
        with pytest.warns(UserWarning, match="sampled"):
            tr = DistributedTrainer(
                cfg,
                TrainConfig(
                    batch_size=dp, use_pallas=True, zero_stage=1,
                    telemetry_level="scalars", collective_timing="full",
                ),
                MeshConfig(data=dp),
            )
        assert tr.collective_timing == "sampled"
        tr_off = DistributedTrainer(
            cfg,
            TrainConfig(
                batch_size=dp, use_pallas=True, zero_stage=1,
                telemetry_level="scalars",
            ),
            MeshConfig(data=dp),
        )
        assert tr_off.collective_timing == "off"
        assert tr_off.collective_sampler is None
        assert tr_off.collective_time_records(force=True) == []


class TestServeMeshTiming:
    def _engine(self, mode):
        from glom_tpu.serve.engine import InferenceEngine
        from glom_tpu.utils.config import GlomConfig

        cfg = GlomConfig(dim=16, levels=2, image_size=8, patch_size=4)
        scfg = ServeConfig(
            buckets=(2,), max_batch=2, iters="auto",
            mesh_data=2, collective_timing=mode,
            collective_timing_interval=1,
        )
        return InferenceEngine(cfg, scfg, name=f"mesh-{mode}")

    def test_sampled_witness_sites_produce_records_and_off_is_absent(
        self,
    ):
        """ISSUE 13 acceptance, serve half: the serve-mesh witness path's
        registered sites produce collective_time records under timing;
        off leaves NONE."""
        eng = self._engine("sampled")
        eng.warmup()
        eng.infer(np.zeros((2, 3, 8, 8), np.float32), n_valid=2)
        recs = eng.collective_time_records()
        sites = {r["site"] for r in recs}
        assert {"quorum_valid_psum", "quorum_exit_psum",
                "comm_time_model"} <= sites
        for r in recs:
            assert schema.validate_record(r) == [], r
            assert r["wall_ms"] > 0
            assert math.isfinite(r["comm_time_model_drift"])
            assert r["engine"] == "mesh-sampled"
        off = self._engine("off")
        off.warmup()
        off.infer(np.zeros((2, 3, 8, 8), np.float32), n_valid=2)
        assert off.collective_time_records() == []

    @pytest.mark.slow  # compiles its own engine; CI telemetry job runs it
    def test_full_mode_brackets_every_execution(self):
        eng = self._engine("full")
        eng.warmup()
        eng.infer(np.zeros((2, 3, 8, 8), np.float32), n_valid=2)
        time.sleep(0.05)
        recs = eng.collective_time_records()
        sites = {r["site"] for r in recs}
        assert {"quorum_valid_psum", "quorum_exit_psum"} <= sites
        per_site = [r for r in recs if r["site"] != "comm_time_model"]
        assert all(r["mode"] == "full" for r in per_site)
        assert all(r["wall_ms"] > 0 for r in per_site)
        # The quorum-exit site rides the while_loop: more executions than
        # the one-shot valid-count psum.
        by = {r["site"]: r for r in per_site}
        assert by["quorum_exit_psum"]["calls"] >= (
            by["quorum_valid_psum"]["calls"]
        )
        # Drained: a second read without dispatches is empty.
        assert eng.collective_time_records() == []

    def test_single_device_engine_resolves_off_loudly(self):
        from glom_tpu.serve.engine import InferenceEngine
        from glom_tpu.utils.config import GlomConfig

        cfg = GlomConfig(dim=16, levels=2, image_size=8, patch_size=4)
        with pytest.warns(UserWarning, match="single-device"):
            eng = InferenceEngine(
                cfg,
                ServeConfig(buckets=(1,), max_batch=1,
                            collective_timing="sampled"),
            )
        assert eng.collective_timing == "off"
