"""utils/timing.py coverage (the chain-timing helpers every bench rides)
— previously untested. No profiler backend, no jit compiles of interest:
the chains here are host fakes with deterministic sleeps, so the module
stays cheap in the tier-1 budget while pinning the contracts the benches
depend on (min-over-repeats, non-finite rejection, degenerate-timing
errors, chain-length calibration)."""

import time

import jax.numpy as jnp
import pytest

from glom_tpu.utils import timing
from glom_tpu.utils.timing import (
    best_fetch_time,
    calibrated_chain_time,
    measure_rtt,
)


class TestBestFetchTime:
    def test_returns_min_over_repeats(self):
        durs = iter([0.03, 0.02, 0.01, 0.02])  # first is the warm call

        def fn(x):
            time.sleep(next(durs))
            return jnp.float32(1.0)

        t = best_fetch_time(fn, None, repeats=3)
        assert 0.005 < t < 0.02  # the min of the timed calls, not the mean

    def test_rejects_nonfinite_warm_call(self):
        with pytest.raises(RuntimeError, match="non-finite"):
            best_fetch_time(lambda: jnp.float32(float("nan")), repeats=2)

    def test_rejects_nonfinite_mid_run(self):
        outs = iter([1.0, 1.0, float("inf")])
        with pytest.raises(RuntimeError, match="non-finite"):
            best_fetch_time(lambda: jnp.float32(next(outs)), repeats=2)

    def test_fetch_is_the_sync(self):
        # fn must return something float() can fetch — the host fetch IS
        # the synchronization contract.
        assert best_fetch_time(lambda: jnp.asarray(2.0), repeats=1) >= 0


class TestMeasureRtt:
    def test_small_positive_and_data_dependent(self):
        x = jnp.ones((4, 4), jnp.float32)
        rtt = measure_rtt(x, repeats=2)
        assert 0 < rtt < 5.0


class TestCalibratedChainTime:
    def test_recovers_known_per_op_cost(self):
        per_op = 2e-4

        def chain(k):
            time.sleep(int(k) * per_op)
            return jnp.float32(1.0)

        measured = calibrated_chain_time(
            chain, jnp.ones((2,), jnp.float32),
            repeats=2, calib_k=4, target_s=0.02,
        )
        # Sleep + fetch overhead only ever inflates; bound loosely enough
        # for a loaded CI box while still pinning the order of magnitude.
        assert per_op * 0.5 < measured < per_op * 10

    def test_chain_length_scales_to_target(self):
        calls = []
        per_op = 1e-3

        def chain(k):
            calls.append(int(k))
            time.sleep(int(k) * per_op)
            return jnp.float32(1.0)

        calibrated_chain_time(
            chain, jnp.ones((2,), jnp.float32),
            repeats=2, calib_k=2, target_s=0.05,
        )
        # last chain sized to ~target_s/per_est ops, clamped >= calib_k
        assert calls[-1] > 2
        assert calls[-1] * per_op == pytest.approx(0.05, rel=0.9)

    def test_degenerate_timing_raises(self, monkeypatch):
        # An RTT estimate larger than the whole chain must error loudly, not return a negative per-op.
        monkeypatch.setattr(timing, "measure_rtt", lambda *a, **k: 100.0)
        with pytest.raises(RuntimeError, match="degenerate"):
            calibrated_chain_time(
                lambda k: jnp.float32(1.0), jnp.ones((2,), jnp.float32),
                repeats=1, calib_k=2, target_s=0.01,
            )

    def test_max_k_clamps_runaway_chains(self):
        calls = []

        def chain(k):
            calls.append(int(k))
            return jnp.float32(1.0)  # ~instant: per_est floors at 1e-7

        try:
            calibrated_chain_time(
                chain, jnp.ones((2,), jnp.float32),
                repeats=1, calib_k=2, target_s=10.0, max_k=64,
            )
        except RuntimeError:
            pass  # degenerate is fine — the clamp is what's under test
        assert max(calls) <= 64
