"""Metrics / observability (SURVEY.md §5: absent in reference — built here).

JSONL metrics writer + the analytic FLOP model used for MFU. The FLOP model
follows SURVEY.md §3.2's hot-loop profile:

  per column-update iteration, per image:
    bottom-up MLP : 2 matmuls over L groups   = 2 * n * L * d * (d*mult) * 2
    top-down  MLP : same over L-1 groups
    consensus     : 2 einsums, O(L * n^2 * d) = 2 * L * n * n * d * 2

A "column-iter" (the north-star unit) = one t-step update of all n*L level
vectors of one image.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import Optional

from glom_tpu.utils.config import GlomConfig


def flops_per_column_iter(cfg: GlomConfig) -> float:
    """FLOPs for one column-update iteration of ONE image (forward only)."""
    n, L, d, m = cfg.num_patches, cfg.levels, cfg.dim, cfg.mult
    ffw = lambda groups: 2 * 2 * n * groups * d * (d * m)  # two matmuls, MACs*2
    bottom_up = ffw(L)
    top_down = ffw(L - 1)
    consensus = 2 * 2 * L * n * n * d  # qk^T and attn@v
    return float(bottom_up + top_down + consensus)


def tokens_flops(cfg: GlomConfig) -> float:
    """Patch embedding FLOPs per image (outside the loop)."""
    return float(2 * cfg.num_patches * cfg.patch_dim * cfg.dim)


# Peak bf16 FLOP/s per chip, keyed by the names detect_chip returns
# (Google Cloud TPU documentation; v5e = "TPU v5 lite": 197 TFLOP/s). A
# device that is not here has no MFU: it is an error, not a default.
PEAK_FLOPS = {
    "v6e": 918e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
}


def detect_chip(device=None) -> str:
    """Map a TPU's device_kind to its PEAK_FLOPS key; an unknown TPU kind
    raises. Off-TPU returns the platform name ("cpu") — a label for
    functional rows, deliberately NOT a PEAK_FLOPS key."""
    import jax

    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return device.platform
    kind = device.device_kind.lower()
    if "v6" in kind:
        return "v6e"
    if "v5" in kind:
        # "TPU v5 lite" = v5e; "TPU v5p"/"TPU v5" = v5p
        return "v5e" if "lite" in kind or "v5e" in kind else "v5p"
    if "v4" in kind:
        return "v4"
    raise ValueError(
        f"unknown TPU device_kind {device.device_kind!r}: add its peak to "
        "PEAK_FLOPS (utils/metrics.py) before reporting utilization on it"
    )


def mfu(
    cfg: GlomConfig,
    column_iters_per_sec: float,
    *,
    chip: str = "v5e",
    backward: bool = False,
) -> float:
    """Model FLOP utilization from measured column-iters/sec/chip."""
    if chip not in PEAK_FLOPS:
        raise ValueError(
            f"no peak FLOP/s for {chip!r}: MFU is a device metric "
            f"(known chips: {sorted(PEAK_FLOPS)})"
        )
    f = flops_per_column_iter(cfg)
    if backward:
        f *= 3.0  # fwd + ~2x bwd
    return column_iters_per_sec * f / PEAK_FLOPS[chip]


def _spec_divisor(spec, axis_sizes: dict) -> int:
    """How many ways a PartitionSpec splits a leaf: the product of the mesh
    axis sizes it names (axis entries may be a name or a tuple of names)."""
    div = 1
    for entry in tuple(spec):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        for name in names:
            div *= int(axis_sizes.get(name, 1))
    return div


def tree_bytes_per_replica(tree, spec_tree, axis_sizes: dict) -> int:
    """Live bytes of a pytree PER REPLICA under a PartitionSpec tree: each
    leaf's global bytes divided by the ways its spec splits it. Pure
    analytics — works from abstract shapes, no device needed (the
    "recorded even when no chip is available" contract)."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs = (
        [None] * len(leaves)  # spec_tree=None: fully replicated
        if spec_tree is None
        else treedef.flatten_up_to(spec_tree)
    )
    total = 0
    for leaf, spec in zip(leaves, specs):
        nbytes = int(np.prod(np.shape(leaf))) * np.dtype(leaf.dtype).itemsize
        if isinstance(spec, PartitionSpec):
            nbytes //= _spec_divisor(spec, axis_sizes)
        total += nbytes
    return total


def live_bytes_model(
    params,
    opt_state,
    *,
    axis_sizes: dict,
    param_specs,
    opt_specs,
    grad_specs,
) -> dict:
    """Per-replica live-bytes for the three train-state tenants the ZeRO
    stages trade between: params (always gathered for the forward), the
    gradient buffer (full at stage<=1, 1/dp shard at stage 2), and the
    optimizer moments (1/dp shard at stage>=1). Spec trees are the SAME
    objects the trainers shard with, so the report can never drift from
    the layout actually trained."""
    return {
        "params_bytes_per_replica": tree_bytes_per_replica(
            params, param_specs, axis_sizes
        ),
        "grads_bytes_per_replica": tree_bytes_per_replica(
            params, grad_specs, axis_sizes
        ),
        "opt_bytes_per_replica": tree_bytes_per_replica(
            opt_state, opt_specs, axis_sizes
        ),
    }


def comm_volume_model(
    grad_bytes: int,
    param_bytes: int,
    dp: int,
    zero_stage: int,
    *,
    grad_accum: int = 1,
) -> dict:
    """Per-replica per-step collective wire bytes of the gradient/update
    path (ring-algorithm costs; SP/TP collectives are priced separately in
    docs/PARALLELISM.md since they depend on activation shapes):

      stage 0 — one allreduce of the full gradient: 2*(dp-1)/dp * G
      stage 1 — reduce-scatter G + all-gather P: (dp-1)/dp * (G + P)
      stage 2 — the reduce-scatter happens once PER MICROBATCH (that is
                what keeps the accumulator sharded): (dp-1)/dp *
                (accum * G + P)"""
    if dp <= 1:
        return {
            "comm_reduce_bytes_per_step": 0,
            "comm_gather_bytes_per_step": 0,
            "comm_bytes_per_step": 0,
        }
    frac = (dp - 1) / dp
    if zero_stage == 0:
        reduce_bytes = int(2 * frac * grad_bytes)
        gather_bytes = 0
    else:
        n_scatters = grad_accum if zero_stage >= 2 else 1
        reduce_bytes = int(frac * grad_bytes * n_scatters)
        gather_bytes = int(frac * param_bytes)
    return {
        "comm_reduce_bytes_per_step": reduce_bytes,
        "comm_gather_bytes_per_step": gather_bytes,
        "comm_bytes_per_step": reduce_bytes + gather_bytes,
    }


class MetricsWriter:
    """Append-only JSONL metrics log, one dict per line, with wall time.

    Every record is stamped with the versioned event schema
    (glom_tpu/telemetry/schema.py: schema_version + kind, inferred when
    the caller didn't stamp) — trainer metrics, watchdog transitions, and
    bench rows all validate against the same contract, which is what lets
    `python -m glom_tpu.telemetry.schema` lint any artifact of record.

    `tensorboard_dir` additionally mirrors numeric scalars to TensorBoard
    via clu.metric_writers (XProf/TensorBoard is the stack's native UI);
    records carrying a `step` key are written at that step, others at an
    internal counter. The JSONL file stays the artifact of record — it is
    what the benches and tests read back."""

    def __init__(
        self,
        path: Optional[str] = None,
        echo: bool = True,
        tensorboard_dir: Optional[str] = None,
    ):
        import threading

        self.path = Path(path) if path else None
        self.echo = echo
        self._t0 = time.time()
        self._seq = 0
        # The watchdog heartbeat thread writes transition events into the
        # same stream as the training loop's records — serialize writes
        # so no JSONL row can interleave mid-line.
        self._lock = threading.Lock()
        if self.path:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            self._fh = self.path.open("a")
        else:
            self._fh = None
        self._tb = None
        if tensorboard_dir:
            try:
                from clu import metric_writers  # deferred: heavy import
            except ImportError as e:
                raise ImportError(
                    "tensorboard_dir requires the optional `clu` package "
                    "(pip install clu); JSONL metrics work without it"
                ) from e
            self._tb = metric_writers.SummaryWriter(tensorboard_dir)

    def write(self, metrics: dict):
        from glom_tpu.telemetry import schema
        from glom_tpu.tracing.flight import observe_event

        rec = schema.stamp({"wall_time": round(time.time() - self._t0, 3), **metrics})
        # Every record of record also lands in the crash flight recorder's
        # ring buffer (no-op until one is registered globally).
        observe_event(rec)
        line = json.dumps(rec)
        with self._lock:
            if self._fh:
                self._fh.write(line + "\n")
                self._fh.flush()
            if self.echo:
                sys.stdout.write(line + "\n")
                sys.stdout.flush()
        if self._tb is not None:
            scalars = {
                k: float(v)
                for k, v in rec.items()
                if isinstance(v, (int, float))
                and not isinstance(v, bool)
                and k != "schema_version"  # constant stamp, not a signal
            }
            with self._lock:
                step = int(scalars.pop("step", self._seq))
                self._seq = step + 1
                if scalars:
                    self._tb.write_scalars(step, scalars)

    def close(self):
        if self._fh:
            self._fh.close()
        if self._tb is not None:
            self._tb.close()
