"""Training objectives.

The reference ships no trainer — its only "training loop" is the README's
self-supervised denoising recipe (README :30-75, SURVEY.md §3.3):

    noised     = img + randn_like(img)
    all_levels = model(noised, return_all=True)     # [T+1, b, n, L, d]
    top        = all_levels[k, :, :, -1]            # mid-iteration top level
    recon      = patches_to_images(top)             # Linear(d -> p*p*c) + unpatchify
    loss       = F.mse_loss(img, recon)

This module provides that objective as a pure, jit/grad/pjit-composable
function. One deliberate optimization over the reference: the loss depends
only on iterations 1..k, so we scan exactly k iterations and take the final
top level instead of materializing the full [T+1, ...] stack — identical
math and gradients (iterations k+1..T are dead code for this loss; torch
autograd also never touches them), but O(1) rather than O(T) activation
memory before remat even enters the picture.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from glom_tpu.models.core import ConsensusFn, GlomParams, glom_forward, init_glom
from glom_tpu.ops.patch import LinearParams, init_linear, tokens_to_image
from glom_tpu.utils.config import (
    GlomConfig,
    HybridLMConfig,
    EvaByteConfig,
    KimiLinearConfig,
    LagunaConfig,
    OuroConfig,
    SambaYConfig,
)


class DenoiseParams(NamedTuple):
    """GLOM params + the reconstruction head from the README recipe."""

    glom: GlomParams
    to_pixels: LinearParams  # Linear(d -> p*p*c)


def init_denoise(key: jax.Array, cfg: GlomConfig, dtype=jnp.float32) -> DenoiseParams:
    k_glom, k_pix = jax.random.split(key)
    return DenoiseParams(
        glom=init_glom(k_glom, cfg, dtype),
        to_pixels=init_linear(k_pix, cfg.dim, cfg.patch_dim, dtype),
    )


class Objective(NamedTuple):
    """What a model family gives the trainer (train/trainer.py): the one
    step builder, fit loop, prefetch, optimizer and records drive whatever
    fills this in. GLOM's denoising is `trainer.denoise_objective`, the
    hybrid language model's next-token loss `lm_objective` below; the
    initial parameters of either come from `init_params(key, cfg)`."""

    # (rng, step, batch) -> what the loss takes beside the batch that is
    # random: drawn on the device, outside the gradient (GLOM's noise)
    draw: Callable[[jax.Array, jnp.ndarray, jnp.ndarray], Any]
    # (params, batch, drawn) -> loss, or (loss, aux) with has_aux; aux is a
    # dict of device scalars that join every record of the step
    loss: Callable[[Any, jnp.ndarray, Any], Any]
    has_aux: bool
    # one example of the batch: the feed yields [batch_size, *batch_shape]
    batch_shape: Tuple[int, ...]
    batch_dtype: Any
    # static routing facts the records carry
    grad_accum: int
    vjp_path: str


# The language-model families: configuration type -> (the model's module, its
# init's name). The families share a stack and a loss: `init(key, cfg)` and the
# module's `lm_loss(params, ids, cfg, compute_dtype=, remat=) -> (loss,
# counters)`. A module is imported when its family is asked for.
_LM_FAMILIES = {
    HybridLMConfig: ("glom_tpu.models.hybrid_lm", "init_hybrid_lm"),
    SambaYConfig: ("glom_tpu.models.sambay", "init_sambay"),
    LagunaConfig: ("glom_tpu.models.laguna", "init_laguna"),
    KimiLinearConfig: ("glom_tpu.models.kimi_linear", "init_kimi_linear"),
    EvaByteConfig: ("glom_tpu.models.evabyte", "init_evabyte"),
    OuroConfig: ("glom_tpu.models.ouro", "init_ouro"),
}


def _lm_family(cfg):
    """(init, loss) of the language-model family `cfg` configures."""
    found = next((f for kind, f in _LM_FAMILIES.items() if isinstance(cfg, kind)), None)
    if found is None:
        raise TypeError(f"no language-model family is configured by a {type(cfg).__name__}")
    module, init = found
    model = importlib.import_module(module)
    return getattr(model, init), model.lm_loss


def init_params(key: jax.Array, cfg):
    """The initial parameters of whichever family `cfg` configures."""
    if isinstance(cfg, GlomConfig):
        return init_denoise(key, cfg)
    return _lm_family(cfg)[0](key, cfg)


def lm_objective(cfg, tcfg) -> Objective:
    """Next-token cross-entropy of a language model (one of `_LM_FAMILIES`, by
    the configuration's type) on
    [batch, seq_len] token ids. Nothing is drawn; the model's step counters
    are the aux. One route: XLA but for attention's scores, per-layer
    recomputation by `tcfg.remat`."""
    lm_loss = _lm_family(cfg)[1]

    if tcfg.compute_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"compute_dtype={tcfg.compute_dtype!r}: must be 'float32' or 'bfloat16'"
        )
    if tcfg.grad_accum not in (None, 1):
        raise ValueError("the language-model objective has no gradient accumulation")
    compute_dtype = jnp.bfloat16 if tcfg.compute_dtype == "bfloat16" else None

    def loss_of(params, ids, drawn):
        return lm_loss(params, ids, cfg, compute_dtype=compute_dtype, remat=tcfg.remat)

    return Objective(
        draw=lambda rng, step, ids: (),
        loss=loss_of,
        has_aux=True,
        batch_shape=(cfg.seq_len,),
        batch_dtype=jnp.int32,
        grad_accum=1,
        vjp_path="lm_xla",
    )


def default_recon_index(iters: int) -> int:
    """Which stacked state feeds the reconstruction head.

    The reference README hardcodes index 7 for L=6 (T=2L=12): the
    mid-iteration top level, after information has gone up and come back
    down once. Generalized as T//2 + 1, which reproduces 7 at T=12.
    """
    return iters // 2 + 1


def denoise_loss(
    params: DenoiseParams,
    img: jnp.ndarray,
    noise: jnp.ndarray,
    cfg: GlomConfig,
    *,
    recon_index: Optional[int] = None,
    iters: Optional[int] = None,
    remat: bool = False,
    compute_dtype=None,
    consensus_fn: Optional[ConsensusFn] = None,
    use_pallas: bool = False,
    unroll: bool = False,
    with_diagnostics: bool = False,
) -> jnp.ndarray:
    """MSE between the clean image and the reconstruction from the noised
    image's top level at iteration `recon_index`.

    with_diagnostics=True (telemetry_level="full") returns (loss, aux)
    where aux carries per-level consensus-agreement stats computed from
    the SAME final state the loss already materializes — one extra [L]
    reduction, no second forward (telemetry/diagnostics.level_agreement)."""
    T = iters if iters is not None else cfg.default_iters
    k = recon_index if recon_index is not None else default_recon_index(T)
    if not 1 <= k <= T:
        raise ValueError(f"recon_index {k} outside 1..{T}")

    with jax.named_scope("noise"):
        noised = img + noise
    final = glom_forward(
        params.glom,
        noised,
        cfg,
        iters=k,  # iterations k+1..T are dead for this loss; don't run them
        remat=remat,
        compute_dtype=compute_dtype,
        consensus_fn=consensus_fn,
        use_pallas=use_pallas,
        unroll=unroll,
    )
    with jax.named_scope("reconstruction"):
        top = final[:, :, -1]  # [b, n, d] — the top level
        recon = tokens_to_image(
            params.to_pixels, top.astype(img.dtype), cfg.patch_size, cfg.image_size
        )
        loss = jnp.mean((img - recon) ** 2)
    if with_diagnostics:
        from glom_tpu.telemetry.diagnostics import level_agreement

        # Stop-gradient: the agreement stat is observability, not a term
        # of the objective — it must not leak into the backward.
        aux = {"level_agreement": level_agreement(jax.lax.stop_gradient(final))}
        return loss, aux
    return loss


def reconstruct(
    params: DenoiseParams,
    img: jnp.ndarray,
    cfg: GlomConfig,
    *,
    recon_index: Optional[int] = None,
    iters: Optional[int] = None,
    compute_dtype=None,
    consensus_fn: Optional[ConsensusFn] = None,
) -> jnp.ndarray:
    """Inference-side reconstruction (for eval / visual inspection).

    Pass the SAME consensus_fn the model was trained with — evaluating a
    custom-consensus model with the default dense op is a silent mismatch.
    """
    T = iters if iters is not None else cfg.default_iters
    k = recon_index if recon_index is not None else default_recon_index(T)
    final = glom_forward(
        params.glom,
        img,
        cfg,
        iters=k,
        compute_dtype=compute_dtype,
        consensus_fn=consensus_fn,
    )
    return tokens_to_image(
        params.to_pixels, final[:, :, -1].astype(img.dtype), cfg.patch_size, cfg.image_size
    )
