"""The user-facing `Glom` class — the reference's public API, preserved.

Reference parity: `Glom(dim=512, levels=6, image_size=224, patch_size=14,
consensus_self=False, local_consensus_radius=0)` and
`forward(img, iters=None, levels=None, return_all=False)`
(glom_pytorch/glom_pytorch.py:76-83, :103). A reference user switches by
changing the import; the constructor accepts the same kwargs (plus a
`backend` flag per the project north star, and JAX-specific extras: `key`,
`param_dtype`, `compute_dtype`, `remat`).

This is a thin object-oriented shell over the functional core: it owns a
params pytree and memoizes jitted forwards per static signature. All real
logic lives in glom_tpu.models.core, which composes with jit/grad/pjit.

Fast paths through the preserved API (round-1 review, weak #4: the
reference surface only reached the slow path):
  * `backend="tpu"` now actually selects the fused Pallas forward
    (level-major carry + fused grouped-MLP + fused consensus/update) when
    running on a TPU — `use_pallas` overrides explicitly.
  * `mesh=` (a MeshConfig or a ready jax Mesh) + `sp_strategy=` runs the
    forward sharded: ring/halo/ulysses consensus over the mesh's 'seq'
    axis, batch over 'data'. With `use_pallas` (the backend="tpu"
    default), sharded inference rides the MANUAL shard_map forward
    (parallel/manual.make_manual_forward) so the fused kernels survive the
    mesh — round-2 review item weak #5 fixed; `use_pallas=False` keeps the
    GSPMD path (where ulysses' all-to-all decomposition lives).
"""

from __future__ import annotations

import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp

from glom_tpu.models.core import GlomParams, glom_forward, init_glom
from glom_tpu.utils.config import GlomConfig, MeshConfig


class Glom:
    def __init__(
        self,
        *,
        dim: int = 512,
        levels: int = 6,
        image_size: int = 224,
        patch_size: int = 14,
        consensus_self: bool = False,
        local_consensus_radius: int = 0,
        backend: str = "tpu",
        key: Optional[jax.Array] = None,
        params: Optional[GlomParams] = None,
        param_dtype=jnp.float32,
        compute_dtype=None,
        remat: bool = False,
        use_pallas: Optional[bool] = None,
        mesh: Optional[Union[MeshConfig, object]] = None,
        sp_strategy: str = "none",
        exit_threshold: float = 1e-3,
        auto_max_iters: Optional[int] = None,
        auto_min_iters: int = 1,
    ):
        if backend not in ("tpu", "cpu", "xla"):
            raise ValueError(
                f"backend={backend!r}: this framework is the native XLA backend; "
                "valid values are 'tpu', 'cpu', 'xla' (all compile via XLA to "
                "whatever jax.devices() exposes)"
            )
        self.config = GlomConfig(
            dim=dim,
            levels=levels,
            image_size=image_size,
            patch_size=patch_size,
            consensus_self=consensus_self,
            local_consensus_radius=local_consensus_radius,
        )
        self.compute_dtype = compute_dtype
        self.remat = remat

        if mesh is not None and isinstance(mesh, MeshConfig):
            from glom_tpu.parallel.mesh import make_mesh  # lazy: avoids cycle

            mesh = make_mesh(mesh)
        if mesh is not None:
            seq = mesh.shape.get("seq", 1)
            if self.config.num_patches % seq != 0:
                raise ValueError(
                    f"patches {self.config.num_patches} not divisible by seq "
                    f"axis {seq}"
                )
        self.mesh = mesh
        self.sp_strategy = sp_strategy
        if use_pallas is None:
            # backend="tpu" means "the fast path": fused kernels, on one
            # chip or through the manual shard_map forward under a mesh.
            use_pallas = backend == "tpu"
        if use_pallas and mesh is not None:
            axes = set(getattr(mesh, "axis_names", ()))
            if not {"data", "seq"} <= axes:
                warnings.warn(
                    "use_pallas with a mesh lacking 'data'/'seq' axes: the "
                    "manual fused forward needs the standard axis names; "
                    "falling back to the GSPMD sharded forward without "
                    "Pallas",
                    stacklevel=2,
                )
                use_pallas = False
        self.use_pallas = use_pallas
        if params is None:
            key = key if key is not None else jax.random.PRNGKey(0)
            params = init_glom(key, self.config, param_dtype)
        self.params = params
        # Consensus early-exit policy for iters="auto" (serve/early_exit):
        # exit once no level's agreement moves more than exit_threshold
        # between iterations, bounded by auto_max_iters (None -> 2L).
        self.exit_threshold = exit_threshold
        self.auto_max_iters = auto_max_iters
        self.auto_min_iters = auto_min_iters
        # Device scalar: how many iterations the last iters="auto" call
        # actually ran (read it host-side with int(...) — that syncs).
        self.last_auto_iters: Optional[jax.Array] = None
        self._jitted = {}

    def _auto_forward(self, return_all):
        """iters='auto' route: the early-exit while_loop forward
        (glom_tpu/serve/early_exit). Single-device only — the sharded
        forwards are fixed-length by construction (collectives inside a
        while_loop body would need per-iteration dispatch)."""
        if return_all:
            raise ValueError(
                "iters='auto' is incompatible with return_all=True: the "
                "early exit makes the number of stacked states data-"
                "dependent, which XLA cannot shape"
            )
        if self.mesh is not None:
            raise NotImplementedError(
                "iters='auto' is single-device (serving buckets replicate "
                "the model); drop mesh= or use a fixed iteration count"
            )
        from glom_tpu.serve.early_exit import glom_forward_auto  # lazy

        max_iters = (
            self.auto_max_iters
            if self.auto_max_iters is not None
            else self.config.default_iters
        )
        sig = ("auto", max_iters, self.exit_threshold, self.auto_min_iters)
        if sig not in self._jitted:

            def fn(params, img, levels):
                final, iters_run, _ = glom_forward_auto(
                    params, img, self.config,
                    max_iters=max_iters,
                    threshold=self.exit_threshold,
                    min_iters=self.auto_min_iters,
                    levels=levels,
                    compute_dtype=self.compute_dtype,
                    use_pallas=self.use_pallas,
                )
                return final, iters_run

            self._jitted[sig] = jax.jit(fn)
        jitted = self._jitted[sig]

        def call(params, img, levels):
            final, iters_run = jitted(params, img, levels)
            self.last_auto_iters = iters_run
            return final

        return call

    def _forward(self, iters, return_all):
        if iters == "auto":
            return self._auto_forward(return_all)
        # Normalize before keying so iters=None and the explicit default share
        # one compiled program; levels-presence is already distinguished by
        # jax.jit's own pytree-structure cache.
        iters = iters if iters is not None else self.config.default_iters
        sig = (iters, return_all)
        if self.mesh is not None and self.use_pallas:
            return self._manual_forward(iters, return_all)
        if sig not in self._jitted:
            consensus_fn = None
            if self.mesh is not None:
                from glom_tpu.parallel.runtime import make_consensus_fn  # lazy

                consensus_fn = make_consensus_fn(
                    self.mesh, self.config, self.sp_strategy
                )

            mesh = self.mesh

            def fn(params, img, levels):
                if mesh is not None:
                    # Pin the batch to the 'data' axis so the mesh kwarg
                    # delivers DP inference even with sp_strategy='none'
                    # (without this, nothing references the mesh and XLA
                    # compiles an unsharded program).
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as P

                    img = jax.lax.with_sharding_constraint(
                        img, NamedSharding(mesh, P("data"))
                    )
                    if levels is not None:
                        levels = jax.lax.with_sharding_constraint(
                            levels, NamedSharding(mesh, P("data", "seq"))
                        )
                return glom_forward(
                    params,
                    img,
                    self.config,
                    iters=iters,
                    levels=levels,
                    return_all=return_all,
                    remat=self.remat,
                    compute_dtype=self.compute_dtype,
                    consensus_fn=consensus_fn,
                    use_pallas=self.use_pallas,
                )

            self._jitted[sig] = jax.jit(fn)
        return self._jitted[sig]

    def _manual_forward(self, iters, return_all):
        """Sharded forward through the manual fused region: the kernels
        survive the mesh (parallel/manual.make_manual_forward). Compiled
        per (iters, return_all, levels-presence)."""
        from glom_tpu.parallel.manual import make_manual_forward  # lazy

        def build(with_levels):
            sig = (iters, return_all, "manual", with_levels)
            if sig not in self._jitted:
                fwd = make_manual_forward(
                    self.mesh,
                    self.config,
                    iters=iters,
                    sp_strategy=self.sp_strategy,
                    compute_dtype=self.compute_dtype,
                    use_pallas=self.use_pallas,
                    return_all=return_all,
                    with_levels=with_levels,
                    remat=self.remat,
                )
                self._jitted[sig] = jax.jit(fwd)
            return self._jitted[sig]

        def fn(params, img, levels):
            if levels is None:
                return build(False)(params, img)
            return build(True)(params, img, levels)

        return fn

    def __call__(
        self,
        img: jnp.ndarray,
        iters: Union[int, str, None] = None,
        levels: Optional[jnp.ndarray] = None,
        return_all: bool = False,
    ) -> jnp.ndarray:
        """forward(img, iters=None, levels=None, return_all=False) — the
        reference signature, jit-compiled and memoized per static config.

        iters="auto" (beyond the reference) runs consensus early exit:
        up to auto_max_iters column updates, stopping once no level's
        agreement moves more than exit_threshold between iterations
        (docs/SERVING.md); the actual count lands on `last_auto_iters`.
        With exit_threshold=0.0 the exit never fires: exactly max_iters
        updates run, and on the reference-layout route (use_pallas=False)
        the output equals the fixed-iters forward BITWISE. With
        use_pallas=True the fixed route runs the fused level-major
        program while the auto route runs the reference-layout body with
        fused FFWs (dense consensus — the while_loop keeps one witness
        across routes), so the two agree to kernel-parity tolerance, not
        bit-for-bit."""
        fn = self._forward(iters, return_all)
        return fn(self.params, img, levels)

    # torch-familiar alias
    forward = __call__
