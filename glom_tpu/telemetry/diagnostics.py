"""In-graph training diagnostics: scalars computed INSIDE the jitted step.

The design constraint is cost: telemetry that adds a host round-trip or a
separate compiled sweep per step gets turned off the moment throughput
matters, and then the next outage is blind again (rounds 4-5). Everything
here is fused into the step the trainer already runs:

  * scalar taps — grad/update/param global norms: three tree-wide
    reductions XLA fuses with the update math (the grad-norm one is the
    same sweep the logging step already paid);
  * the NaN/Inf guard — ONE extra scalar op: a non-finite gradient anywhere
    poisons the grad norm, so `isfinite(loss + grad_norm)` covers the whole
    tree without a second sweep. Policy "skip" drops the update in-graph
    (jnp.where keeps the old params/opt state — the step counter still
    advances so schedules/logs stay aligned); "warn" applies it and flags
    the record. fit_loop turns the flag into a structured anomaly event;
  * per-level consensus-agreement (level "full") — mean cosine between each
    patch vector and its image's mean vector per level, from the forward's
    final state: the "islands of agreement" formation signal (GLOM §9) as
    one [L]-vector per step.

Gating is `TrainConfig.telemetry_level`, resolved ONCE by
resolve_telemetry_level (the same single-source discipline as
resolve_zero_stage) and stamped into every record.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

TELEMETRY_LEVELS = ("off", "scalars", "full")
NONFINITE_POLICIES = ("skip", "warn")


def resolve_telemetry_level(tcfg, *, supports_full: bool = True) -> str:
    """Effective telemetry level for a trainer path — THE single resolution
    source (both trainers call this once and stamp the output, so a record
    can never claim diagnostics that didn't run). supports_full=False (the
    manual shard_map path: the per-shard loss body has no aux channel for
    the final state) degrades "full" to "scalars" loudly."""
    level = tcfg.telemetry_level
    if level not in TELEMETRY_LEVELS:
        raise ValueError(
            f"telemetry_level={level!r}: one of {TELEMETRY_LEVELS}"
        )
    if tcfg.nonfinite_policy not in NONFINITE_POLICIES:
        raise ValueError(
            f"nonfinite_policy={tcfg.nonfinite_policy!r}: one of "
            f"{NONFINITE_POLICIES}"
        )
    if level == "full" and not supports_full:
        warnings.warn(
            "telemetry_level='full' is unavailable on the manual shard_map "
            "path (no aux channel through the per-shard loss body); "
            "running with 'scalars' — the stamped level is the resolved one",
            stacklevel=3,
        )
        return "scalars"
    return level


def nonfinite_flag(loss: jnp.ndarray, grad_norm: jnp.ndarray) -> jnp.ndarray:
    """True when this step's loss or ANY gradient element is non-finite.
    The grad norm is the whole-tree witness: one NaN/Inf anywhere makes the
    sum of squares non-finite, so no per-leaf isfinite sweep is needed."""
    return jnp.logical_not(
        jnp.isfinite(loss.astype(jnp.float32) + grad_norm.astype(jnp.float32))
    )


def guard_update(nonfinite: jnp.ndarray, new_tree, old_tree):
    """Skip-step policy, in-graph: where the step was non-finite, keep the
    old value on every leaf (params AND optimizer state — a poisoned Adam
    moment would re-emit the NaN on the next healthy step)."""
    return jax.tree_util.tree_map(
        lambda new, old: jnp.where(nonfinite, old, new), new_tree, old_tree
    )


def level_agreement(final: jnp.ndarray) -> jnp.ndarray:
    """Per-level consensus-agreement from a final state [b, n, L, d]:
    mean over (b, n) of the cosine between each patch's level vector and
    that image's mean vector at the same level. -> [L] float32, ~1.0 when
    a level has collapsed to one island, ~0 when patches disagree."""
    x = final.astype(jnp.float32)
    eps = 1e-8
    xhat = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + eps)
    mean = jnp.mean(xhat, axis=1, keepdims=True)  # [b, 1, L, d]
    mhat = mean / (jnp.linalg.norm(mean, axis=-1, keepdims=True) + eps)
    return jnp.mean(jnp.sum(xhat * mhat, axis=-1), axis=(0, 1))  # [L]


def scalar_taps(
    *,
    loss: jnp.ndarray,
    grad_norm: jnp.ndarray,
    updates,
    params,
) -> dict:
    """The "scalars" bundle: update/param norms + the non-finite flag
    (grad_norm rides in from the caller — it is shared with the metrics
    the step already computes)."""
    import optax

    return {
        "grad_norm": grad_norm,
        "update_norm": optax.global_norm(updates),
        "param_norm": optax.global_norm(params),
        "nonfinite": nonfinite_flag(loss, grad_norm),
    }


def split_level_agreement(metrics: dict) -> dict:
    """Host-side: explode a metrics dict's [L] `level_agreement` vector
    into per-level scalar keys (consensus_agreement_l0..l{L-1}) so every
    sink — JSONL, TensorBoard, the driver's tail parse — sees flat
    scalars. No-op when the key is absent."""
    if "level_agreement" not in metrics:
        return metrics
    metrics = dict(metrics)
    vec = metrics.pop("level_agreement")
    import numpy as np

    vec = np.asarray(vec)
    for i, v in enumerate(vec.tolist()):
        metrics[f"consensus_agreement_l{i}"] = v
    return metrics
