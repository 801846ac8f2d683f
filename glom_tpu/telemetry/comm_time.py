"""Per-collective wall-time: the measured half of the capacity observatory.

`telemetry/counters.py` prices the manual paths' collectives in BYTES;
ROADMAP's standing backlog was the other axis — the CLOCK. GLOM's
per-iteration consensus makes wall-time a function of communication as much
as compute, and a topology-aware schedule (TASP, PAPERS.md) can only be
*picked* from a time model after the time model is grounded per site
against measurement. This module grounds it:

  * `CollectiveTimeSampler` — the "sampled" timing mode's engine: from the
    site registry a counting trace populated (counters.CollectiveCounters
    .sites), it builds ONE tiny shard_map per registered site that runs
    exactly that collective (same local shape, dtype, axis, scatter/gather
    dim) on the same mesh, and times it outside jit with
    block_until_ready wall clocks (min over repeats — the bench timing
    convention). The number is the ISOLATED collective: an upper bound on
    the blocking cost inside the real step (where XLA may overlap it),
    and exactly the per-site latency/bandwidth point the α-β fit needs.

  * the α-β time model — the classic latency-bandwidth form
    `wall_ms = alpha_ms + beta_ms_per_byte * wire_bytes` (ring collectives
    are linear in payload once per-hop latency is split out), fitted by
    closed-form least squares from the measured points and stamped back
    onto every record as `comm_time_model_ms` + `comm_time_model_drift`
    (the comm_model_drift discipline: a model diverging from measurement
    must be visible on the record itself, not in a notebook).

  * `collective_time_records` — the schema-v7 "collective_time" rows
    (site, axis, collective, bytes, wall_ms, bytes_per_s, mode, model
    drift) plus one `comm_time_model` summary row carrying the fitted
    alpha/beta — what `telemetry compare` classifies as costs and the
    Perfetto export renders as per-(site, axis) counter tracks.

The model math is pure stdlib (it must run over a crashed run's records in
a jax-broken environment); only the sampler imports jax, lazily.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from glom_tpu.telemetry import schema


# -- the α-β time model ------------------------------------------------------


def fit_time_model(points: List[dict]) -> dict:
    """Least-squares `wall_ms = alpha + beta * wire_bytes` over measured
    site points ({wire_bytes, wall_ms}). Degenerate inputs stay honest:
    one point (or all points at one byte size) pins alpha to the mean and
    beta to 0 — a model claiming bandwidth it never measured would fake a
    fit. beta is clamped at 0 (a negative marginal byte cost is noise,
    and extrapolating it would predict negative time)."""
    pts = [
        (float(p["wire_bytes"]), float(p["wall_ms"]))
        for p in points
        if isinstance(p.get("wire_bytes"), (int, float))
        and isinstance(p.get("wall_ms"), (int, float))
    ]
    n = len(pts)
    if n == 0:
        return {"alpha_ms": 0.0, "beta_ms_per_byte": 0.0, "n_points": 0}
    mean_x = sum(x for x, _ in pts) / n
    mean_y = sum(y for _, y in pts) / n
    var_x = sum((x - mean_x) ** 2 for x, _ in pts)
    if var_x <= 0.0:
        return {
            "alpha_ms": round(mean_y, 6),
            "beta_ms_per_byte": 0.0,
            "n_points": n,
        }
    beta = sum((x - mean_x) * (y - mean_y) for x, y in pts) / var_x
    beta = max(0.0, beta)
    alpha = max(0.0, mean_y - beta * mean_x)
    return {
        "alpha_ms": round(alpha, 6),
        "beta_ms_per_byte": beta,
        "n_points": n,
    }


def predict_ms(model: dict, wire_bytes: float) -> float:
    return float(model.get("alpha_ms", 0.0)) + float(
        model.get("beta_ms_per_byte", 0.0)
    ) * float(wire_bytes)


def time_model_drift(wall_ms: float, model_ms: float) -> float:
    """(measured - modeled) / modeled — the comm_model_drift convention,
    including its inf -> 1e9 JSON-safe clamp."""
    if model_ms <= 0.0:
        return 0.0 if wall_ms == 0.0 else 1e9
    drift = (wall_ms - model_ms) / model_ms
    return round(drift, 6)


def collective_time_records(
    samples: List[dict],
    *,
    path: str,
    mode: str,
    model: Optional[dict] = None,
) -> List[dict]:
    """Stamped schema-v7 "collective_time" rows from raw site samples
    ({site, axis, collective, wire_bytes, wall_ms[, calls, wall_ms_max]}).
    The α-β model is fitted from THESE points unless a pre-fitted one is
    passed (an earlier window's model, to price drift against it), and every row stamps its own model drift; a final
    `comm_time_model` row carries the fit itself plus the aggregate
    drift — the one-number health signal the compare gate tracks."""
    if not samples:
        return []
    fitted = model if model is not None else fit_time_model(samples)
    out = []
    total_measured = 0.0
    total_modeled = 0.0
    for s in sorted(samples, key=lambda r: str(r.get("site"))):
        wall = float(s["wall_ms"])
        nbytes = int(s.get("wire_bytes", 0))
        pred = predict_ms(fitted, nbytes)
        total_measured += wall
        total_modeled += pred
        rec = {
            "site": str(s["site"]),
            "axis": s.get("axis"),
            "collective": s.get("collective"),
            "path": path,
            "mode": mode,
            "wire_bytes": nbytes,
            "wall_ms": wall,
            "bytes_per_s": (
                round(nbytes / (wall / 1e3), 1) if wall > 0 else None
            ),
            "comm_time_model_ms": round(pred, 6),
            "comm_time_model_drift": time_model_drift(wall, pred),
        }
        for k in ("calls", "wall_ms_max"):
            if k in s:
                rec[k] = s[k]
        out.append(schema.stamp(rec, kind="collective_time"))
    out.append(
        schema.stamp(
            {
                "site": "comm_time_model",
                "path": path,
                "mode": mode,
                "wall_ms": round(total_measured, 6),
                "alpha_ms": fitted["alpha_ms"],
                "beta_ms_per_byte": fitted["beta_ms_per_byte"],
                "n_points": fitted["n_points"],
                "comm_time_model_ms": round(total_modeled, 6),
                "comm_time_model_drift": time_model_drift(
                    total_measured, total_modeled
                ),
            },
            kind="collective_time",
        )
    )
    return out


# -- the sampled-mode re-dispatch harness ------------------------------------


class CollectiveTimeSampler:
    """Re-dispatches each registered collective site as its own timed
    sub-graph on the live mesh — the "sampled" timing mode.

    Built from a counting trace's site registry (each entry carries the
    SHARD-LOCAL operand shape/dtype, the axis, and the scatter/gather
    dimension, so the rebuilt collective moves exactly the bytes the real
    site moves). Compiles lazily on the first sample (compile time is
    excluded from the timing: the first call warms, then `repeats` timed
    calls take the min — the bench convention); `maybe_sample(step)`
    rate-limits to every `interval`-th call, so a fit loop can invoke it
    at every logging boundary for free in between."""

    def __init__(
        self,
        mesh,
        sites: List[dict],
        *,
        interval: int = 10,
        repeats: int = 2,
    ):
        if interval < 1:
            raise ValueError(f"interval {interval} must be >= 1")
        if repeats < 1:
            raise ValueError(f"repeats {repeats} must be >= 1")
        self.mesh = mesh
        # Only sites that move wire (a k==1 axis registers nothing at the
        # call sites, but a defensive filter keeps a zero-byte site from
        # wasting a compile on a no-op), DEDUPLICATED by what actually
        # determines wall time — (site, axis, collective, payload bytes,
        # dtype): two parameter leaves of different shapes but identical
        # payload ride one timed sub-graph instead of two compiles and
        # two dispatches per sample (their `calls` merge, so the α-β
        # fit's per-point weight is unchanged).
        self._uniq: Dict[tuple, dict] = {}
        self._merge(sites)
        self.interval = int(interval)
        self.repeats = int(repeats)
        self._fns: Dict[str, object] = {}
        self._calls = 0

    @staticmethod
    def _key(s: dict) -> tuple:
        return (
            s["site"], s["axis"], s["collective"], s["wire_bytes"],
            s.get("dtype"),
        )

    def _merge(self, sites: List[dict]) -> None:
        for s in sites:
            if s.get("wire_bytes", 0) <= 0:
                continue
            key = self._key(s)
            if key in self._uniq:
                self._uniq[key]["calls"] += s.get("calls", 1)
            else:
                self._uniq[key] = dict(s)

    @property
    def sites(self) -> List[dict]:
        return list(self._uniq.values())

    def update_sites(self, sites: List[dict]) -> None:
        """Merge sites registered AFTER construction — a lazy mid-traffic
        compile of a new signature adds registry entries, and a frozen
        sampler would silently never time them (their sub-graphs compile
        on the next sample like any first-seen site). Byte-identical
        shapes dedupe exactly as at construction, so re-merging an
        already-known site only bumps its call weight... which would
        DOUBLE-count on repeated update calls — already-known keys are
        therefore skipped entirely here."""
        for s in sites:
            if s.get("wire_bytes", 0) <= 0:
                continue
            self._uniq.setdefault(self._key(s), dict(s))

    def _build(self, site: dict):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax
        from jax.sharding import PartitionSpec as P


        collective = site["collective"]
        axis = site["axis"]
        shape = tuple(site["shape"])
        dtype = jnp.dtype(np.dtype(site["dtype"]))
        dim = int(site.get("dim", 0))

        # The axis here REPLAYS a recorded site registration: it was
        # vocabulary-checked (and wire-counted) at the original call site
        # in parallel/manual.py or serve_mesh.py, so the re-dispatch
        # carries reasoned suppressions rather than a fake static axis.
        def body():
            x = jnp.zeros(shape, dtype)
            if collective == "psum":
                return lax.psum(x, axis)  # glom-lint: ok[collective-coverage] replayed site, axis checked at origin
            if collective == "pmean":
                return lax.pmean(x, axis)  # glom-lint: ok[collective-coverage] replayed site, axis checked at origin
            if collective == "psum_scatter":
                return lax.psum_scatter(  # glom-lint: ok[collective-coverage] replayed site, axis checked at origin
                    x, axis, scatter_dimension=dim, tiled=True
                )
            if collective == "all_gather":
                return lax.all_gather(x, axis, axis=dim, tiled=True)  # glom-lint: ok[collective-coverage] replayed site, axis checked at origin
            raise ValueError(f"unknown collective {collective!r}")

        return jax.jit(
            jax.shard_map(
                body, mesh=self.mesh, in_specs=(), out_specs=P(),
                check_vma=False,
            )
        )

    def sample(self) -> List[dict]:
        """One timed pass over every registered site: min-of-repeats wall
        clock around the jitted collective with a terminal
        block_until_ready. Returns raw site samples (feed them to
        collective_time_records for the stamped rows)."""
        import jax

        out = []
        for site in self.sites:
            key = f"{site['site']}:{site['shape']}"
            fn = self._fns.get(key)
            if fn is None:
                fn = self._fns[key] = self._build(site)
                jax.block_until_ready(fn())  # compile + warm, untimed
            best = float("inf")
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                best = min(best, time.perf_counter() - t0)
            out.append(
                {
                    "site": site["site"],
                    "axis": site["axis"],
                    "collective": site["collective"],
                    "wire_bytes": site["wire_bytes"],
                    "calls": site.get("calls", 1),
                    "wall_ms": round(best * 1e3, 6),
                }
            )
        return out

    def maybe_sample(self, *, path: str) -> List[dict]:
        """Every `interval`-th call: sample + fit + return the stamped
        collective_time records (empty between samples, and on the very
        first call only after `interval` calls have accrued — the loop's
        first boundaries are compile-dominated anyway)."""
        self._calls += 1
        if self._calls % self.interval != 0:
            return []
        return collective_time_records(
            self.sample(), path=path, mode="sampled"
        )
