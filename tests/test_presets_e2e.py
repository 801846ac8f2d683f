"""End-to-end: every BASELINE preset builds a DistributedTrainer on the
8-device virtual mesh under its DECLARED parallelism strategy and completes
one finite training step (round-1 review, next-step #3 — the round-1 gap was
that preset 3 crashed on its own mesh and no test ever ran the presets
distributed).

Model dims/batch are shrunk for CPU speed, but the parts that broke — patch
GRID GEOMETRY (image/patch size, radius), mesh shape, and sp_strategy — are
kept exactly as declared.
"""

import dataclasses

import jax
import numpy as np
import pytest

from glom_tpu.data import gaussian_dataset
from glom_tpu.parallel import DistributedTrainer
from glom_tpu.utils.presets import PRESETS, get_preset


def _tiny(preset, num_devices=8):
    """Shrink compute (dim, levels, batch, iters) while preserving the patch
    grid geometry, mesh, and SP strategy the preset declares."""
    p = preset.scaled_to(num_devices)
    model = dataclasses.replace(p.model, dim=64, levels=min(p.model.levels, 3))
    train = dataclasses.replace(
        p.train,
        batch_size=2 * p.mesh.data,
        iters=2,
        recon_iter_index=1,
        compute_dtype="float32",  # CPU: bf16 is emulated and slow
    )
    return dataclasses.replace(p, model=model, train=train)


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_preset_builds_and_steps_distributed(name):
    p = _tiny(get_preset(name))
    assert p.mesh.num_devices <= len(jax.devices())
    trainer = DistributedTrainer(
        p.model, p.train, p.mesh, sp_strategy=p.sp_strategy
    )
    batch = next(gaussian_dataset(p.train.batch_size, p.model.image_size, seed=0))
    metrics = trainer.step(batch)
    assert np.isfinite(float(metrics["loss"])), (name, metrics)


def test_preset3_resolves_exact_mechanism():
    """Radius 7 on an 8-row grid can never satisfy the one-hop halo
    precondition (4 rows/shard < 7); the preset declares intent ('auto')
    and the selector resolves an EXACT mechanism without crashing
    (round-1 ADVICE medium; round-3 review #3: intent, not mechanism).
    At n=64 global crossover, that mechanism is ulysses (L=6 % seq=2)."""
    from glom_tpu.parallel.runtime import effective_sp_strategy

    p = get_preset("imagenet64-local")
    assert p.sp_strategy == "auto"
    assert effective_sp_strategy(p.model, p.mesh.seq, p.sp_strategy) == "ulysses"


def test_halo_preset_keeps_halo_at_8_devices():
    """The long-context halo flagship (32x32 grid, radius 7, seq=4 -> 8 rows
    per shard >= 7) must still resolve to halo after scaled_to(8)."""
    from glom_tpu.parallel.runtime import effective_sp_strategy

    p = get_preset("imagenet256-local").scaled_to(8)
    assert p.mesh.num_devices <= 8
    assert effective_sp_strategy(p.model, p.mesh.seq, p.sp_strategy) == "halo"


def test_scaled_to_falls_back_when_halo_breaks():
    """Shrinking the mesh must re-resolve the halo precondition instead of
    shipping a config that raises at trainer construction: side=32 at
    seq=8 gives 4 rows per shard < floor(radius)=7, and L=6 % 8 != 0
    forbids ulysses too, so the exact mechanism is ring."""
    import glom_tpu.utils.presets as presets_mod
    from glom_tpu.parallel.runtime import effective_sp_strategy

    base = get_preset("imagenet256-local")
    broken = dataclasses.replace(
        base, mesh=presets_mod.MeshConfig(data=1, seq=8, model=1)
    ).scaled_to(8)
    assert (
        effective_sp_strategy(broken.model, broken.mesh.seq, broken.sp_strategy)
        == "ring"
    )


class TestHybridMesh:
    """Multi-slice (ICI x DCN) topology: BASELINE config 5's pod layout."""

    def test_construction_and_step(self):
        """A 2-slice mesh over the 8 virtual devices builds and completes a
        finite train step (slice-major data axis; same logical axes)."""
        from glom_tpu.utils.config import GlomConfig, MeshConfig, TrainConfig

        mesh_cfg = MeshConfig(data=4, seq=2, num_slices=2)
        cfg = GlomConfig(dim=32, levels=3, image_size=16, patch_size=4)
        tcfg = TrainConfig(batch_size=8, iters=2, recon_iter_index=1, remat=True)
        trainer = DistributedTrainer(cfg, tcfg, mesh_cfg, sp_strategy="ring")
        assert trainer.mesh.shape == {"data": 4, "seq": 2, "model": 1}
        batch = next(gaussian_dataset(8, 16, seed=0))
        assert np.isfinite(float(trainer.step(batch)["loss"]))

    def test_indivisible_slices_rejected(self):
        from glom_tpu.utils.config import MeshConfig

        with pytest.raises(ValueError, match="num_slices"):
            MeshConfig(data=4, num_slices=3)

    def test_pod_preset_declares_slices_and_scales_down(self):
        pod = get_preset("imagenet224-pod")
        assert pod.mesh.num_slices == 4
        small = pod.scaled_to(8)
        # DATA shrinks first (the elastic axis): (64,2,2) -> (2,2,2) on 8
        # devices, preserving the declared seq x model composition so the
        # scaled-down pod still exercises TP+SP with the fused kernels —
        # and a scaled-down mesh is a single-slice deployment, so the DCN
        # split must collapse (it would otherwise force the hybrid-mesh
        # path on a topology that has no 4-way slice factor).
        assert small.mesh.shape == (2, 2, 2)
        assert small.mesh.num_slices == 1
        # Unchanged size keeps the declared multi-slice layout.
        assert pod.scaled_to(256).mesh.num_slices == 4


def test_halo_fallback_warns_in_make_consensus_fn():
    """Direct runtime users get the same safety net: halo with an impossible
    geometry falls back to ring (with a warning) instead of raising."""
    from glom_tpu.parallel.mesh import make_mesh
    from glom_tpu.parallel.runtime import make_consensus_fn
    from glom_tpu.utils.config import GlomConfig, MeshConfig

    mesh = make_mesh(MeshConfig(data=1, seq=2, model=1), jax.devices()[:2])
    cfg = GlomConfig(
        dim=64, levels=2, image_size=64, patch_size=8, local_consensus_radius=7
    )
    with pytest.warns(UserWarning, match="falling back to ring"):
        fn = make_consensus_fn(mesh, cfg, "halo")
    assert fn is not None
