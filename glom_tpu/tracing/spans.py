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
"""

from __future__ import annotations

import threading
import time
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

# The language models' Pallas kernels, by their `name=`
# (kernels/flash_attention.py). They open no scope of their own: a call runs
# inside the model's attention scope (`attention`; `window_attention`,
# `full_attention`, `cross_attention`; Laguna's two), and its device time
# belongs to that scope. All begin with `attn_`; none matches `loop_*`, `ffw_*`,
# `consensus_*` or `ragged-dot*`, the names by which the benchmark tells the
# routes apart.
LM_KERNELS = (
    # causal (windowed) grouped-query attention forward: online softmax over the scheduled key tiles
    "attn_flash_fwd",
    # its backward: one sweep over key tiles, scores rebuilt from the log-sum-exp, dq resident
    "attn_flash_bwd_onesweep",
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


def _no_annotation(name, **fields):
    """Stands in for jax.profiler.TraceAnnotation where jax is broken or
    absent: the span itself must work in exactly that environment."""
    return nullcontext()


_annotation_cls = None  # resolved by the first span


def _trace_annotation():
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # jax absent, or broken at import: spans still work
            TraceAnnotation = _no_annotation
        _annotation_cls = TraceAnnotation
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
    loop's `step=`) ride both the emitted event and the annotation."""
    stack = _stack()
    parent = stack[-1] if stack else None
    stack.append(name)
    t_wall = time.time() if writer is not None else 0.0
    t0 = time.perf_counter()
    try:
        with _trace_annotation()(name, **fields):
            yield
    finally:
        dur = time.perf_counter() - t0
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
