"""Perf reporting: the MFU rollup and the rolling step timer.

Moved verbatim from the utils/profiling.py stub when it grew into the
tracing package (that module re-exports these for compatibility); built on
the analytic FLOP model in utils/metrics.py.
"""

from __future__ import annotations

import time
from typing import Optional

from glom_tpu.utils.config import GlomConfig
from glom_tpu.utils.metrics import flops_per_column_iter, mfu


def perf_report(
    cfg: GlomConfig,
    *,
    column_iters_per_sec: float,
    chip: str = "v5e",
    num_chips: int = 1,
    backward: bool = False,
) -> dict:
    """Assemble the north-star metrics dict from a measured rate."""
    return {
        "column_iters_per_sec_per_chip": column_iters_per_sec / num_chips,
        "flops_per_column_iter": flops_per_column_iter(cfg),
        "mfu": mfu(
            cfg, column_iters_per_sec / num_chips, chip=chip, backward=backward
        ),
        "chip": chip,
        "num_chips": num_chips,
    }


class StepTimer:
    """Rolling wall-clock step timer that syncs on a supplied scalar (a
    host fetch cannot return before the work that produces it)."""

    def __init__(self):
        self._t0: Optional[float] = None
        self.history: list[float] = []

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, sync_scalar=None) -> float:
        if sync_scalar is not None:
            float(sync_scalar)  # host fetch = real synchronization
        dt = time.perf_counter() - self._t0
        self.history.append(dt)
        return dt

    @property
    def best(self) -> float:
        return min(self.history)
