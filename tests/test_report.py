"""tracing/report.py — the MFU rollup and the rolling step timer (the
last tracing module with zero direct coverage). Host-only: fake clocks,
no device, no compiles; stays in tier-1."""

import itertools

import pytest

from glom_tpu.tracing.report import StepTimer, perf_report
from glom_tpu.utils.config import GlomConfig
from glom_tpu.utils.metrics import PEAK_FLOPS, flops_per_column_iter, mfu

CFG = GlomConfig(dim=16, levels=3, image_size=8, patch_size=4)


class TestPerfReport:
    def test_fields_and_values(self):
        r = perf_report(CFG, column_iters_per_sec=100.0, chip="v5e")
        assert r["chip"] == "v5e"
        assert r["num_chips"] == 1
        assert r["column_iters_per_sec_per_chip"] == 100.0
        assert r["flops_per_column_iter"] == flops_per_column_iter(CFG)
        assert r["mfu"] == pytest.approx(
            100.0 * flops_per_column_iter(CFG) / PEAK_FLOPS["v5e"]
        )
        assert r["mfu"] > 0

    def test_multi_chip_divides_the_rate(self):
        r1 = perf_report(CFG, column_iters_per_sec=800.0, chip="v5e")
        r8 = perf_report(
            CFG, column_iters_per_sec=800.0, chip="v5e", num_chips=8
        )
        assert r8["num_chips"] == 8
        assert r8["column_iters_per_sec_per_chip"] == pytest.approx(
            r1["column_iters_per_sec_per_chip"] / 8
        )
        # per-chip MFU scales the same way: 8 chips at the same aggregate
        # rate each do 1/8 of the work
        assert r8["mfu"] == pytest.approx(r1["mfu"] / 8)

    def test_backward_costs_three_x(self):
        fwd = perf_report(CFG, column_iters_per_sec=100.0, chip="v5e")
        bwd = perf_report(
            CFG, column_iters_per_sec=100.0, chip="v5e", backward=True
        )
        assert bwd["mfu"] == pytest.approx(3.0 * fwd["mfu"])
        # consistency with the metrics-layer definition it wraps
        assert bwd["mfu"] == pytest.approx(
            mfu(CFG, 100.0, chip="v5e", backward=True)
        )


class TestStepTimer:
    def test_measures_between_start_and_stop(self, monkeypatch):
        ticks = itertools.count(start=10.0, step=0.25)
        monkeypatch.setattr("time.perf_counter", lambda: next(ticks))
        t = StepTimer()
        t.start()  # 10.0
        dt = t.stop()  # 10.25
        assert dt == pytest.approx(0.25)
        assert t.history == [dt]

    def test_best_is_the_minimum(self, monkeypatch):
        clock = iter([0.0, 1.0, 1.0, 1.5, 1.5, 5.5])
        monkeypatch.setattr("time.perf_counter", lambda: next(clock))
        t = StepTimer()
        for _ in range(3):
            t.start()
            t.stop()
        assert t.history == pytest.approx([1.0, 0.5, 4.0])
        assert t.best == pytest.approx(0.5)

    def test_sync_scalar_is_fetched_before_the_clock_reads(self):
        """The timer's whole point: float(sync_scalar) forces the host
        fetch INSIDE the timed window, so the wall time includes the real
        device sync rather than timing an async dispatch."""
        order = []

        class Scalar:
            def __float__(self):
                order.append("sync")
                return 1.0

        t = StepTimer()
        t.start()
        dt = t.stop(sync_scalar=Scalar())
        order.append("stopped")
        assert order == ["sync", "stopped"]
        assert dt >= 0.0

    def test_stop_without_start_raises(self):
        t = StepTimer()
        with pytest.raises(TypeError):
            t.stop()
