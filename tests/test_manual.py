"""Parity tests for the fully-manual SPMD path (parallel/manual.py): the
whole loss in one shard_map over (data, seq), Pallas kernels per-device.

The contract: for identical params/img/noise, the manual sharded loss and
its gradients equal the single-device dense composition (denoise_loss) to
float tolerance — DP x SP is a physical layout change, not a math change.
"""

import ast
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glom_tpu.parallel.manual import (
    make_manual_loss,
    make_manual_train_step,
    manual_supported,
)
from glom_tpu.parallel.mesh import make_mesh
from glom_tpu.train.objectives import denoise_loss, init_denoise
from glom_tpu.train.trainer import Trainer, create_train_state
from glom_tpu.utils.config import GlomConfig, MeshConfig, TrainConfig

CFG = GlomConfig(dim=16, levels=3, image_size=16, patch_size=4)  # n=16, side=4
TCFG = TrainConfig(batch_size=4, iters=4, recon_iter_index=3)


def _data(key=0):
    rng = np.random.default_rng(key)
    img = jnp.asarray(rng.normal(size=(4, 3, 16, 16)), jnp.float32)
    noise = jnp.asarray(rng.normal(size=(4, 3, 16, 16)), jnp.float32)
    return img, noise


def _ref_loss(params, img, noise, cfg=CFG, tcfg=TCFG):
    return denoise_loss(
        params, img, noise, cfg,
        recon_index=tcfg.recon_iter_index, iters=tcfg.iters,
    )


MESHES = [
    ("dp4", MeshConfig(data=4), "none"),
    ("dp2xsp2-ring", MeshConfig(data=2, seq=2), "ring"),
    ("sp4-ring", MeshConfig(seq=4), "ring"),
    ("dp2xtp2", MeshConfig(data=2, model=2), "none"),
    ("dp2xsp2xtp2-ring", MeshConfig(data=2, seq=2, model=2), "ring"),
]


@pytest.mark.parametrize("name,mesh_cfg,sp", MESHES, ids=[m[0] for m in MESHES])
def test_manual_loss_matches_dense(name, mesh_cfg, sp):
    mesh = make_mesh(mesh_cfg, jax.devices()[: mesh_cfg.num_devices])
    params = init_denoise(jax.random.PRNGKey(0), CFG)
    img, noise = _data()
    loss_fn = make_manual_loss(mesh, CFG, TCFG, sp_strategy=sp)
    got = float(jax.jit(loss_fn)(params, img, noise))
    want = float(jax.jit(_ref_loss)(params, img, noise))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_manual_grads_match_dense():
    """The shard_map transpose must produce the same param gradients as the
    single-device backward (the DP psum + SP collective transposes)."""
    mesh_cfg = MeshConfig(data=2, seq=2)
    mesh = make_mesh(mesh_cfg, jax.devices()[:4])
    params = init_denoise(jax.random.PRNGKey(0), CFG)
    img, noise = _data()
    loss_fn = make_manual_loss(mesh, CFG, TCFG, sp_strategy="ring")
    g_manual = jax.jit(jax.grad(loss_fn))(params, img, noise)
    g_ref = jax.jit(jax.grad(_ref_loss))(params, img, noise)
    flat_m, _ = jax.tree_util.tree_flatten(g_manual)
    flat_r, _ = jax.tree_util.tree_flatten(g_ref)
    for m, r in zip(flat_m, flat_r):
        np.testing.assert_allclose(
            np.asarray(m), np.asarray(r), rtol=2e-4, atol=1e-6
        )


def test_manual_ulysses_matches_dense():
    """Ulysses in the manual region: the all_to_all L-for-n trade must give
    the same loss AND gradients as the dense single-device composition
    (L=4 divisible by seq=2)."""
    cfg = dataclasses.replace(CFG, levels=4)
    mesh = make_mesh(MeshConfig(data=2, seq=2), jax.devices()[:4])
    params = init_denoise(jax.random.PRNGKey(2), cfg)
    img, noise = _data(2)
    loss_fn = make_manual_loss(mesh, cfg, TCFG, sp_strategy="ulysses")
    ref = lambda p, i, n: _ref_loss(p, i, n, cfg=cfg)  # noqa: E731
    got = float(jax.jit(loss_fn)(params, img, noise))
    want = float(jax.jit(ref)(params, img, noise))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    g_manual = jax.jit(jax.grad(loss_fn))(params, img, noise)
    g_ref = jax.jit(jax.grad(ref))(params, img, noise)
    for m, r in zip(
        jax.tree_util.tree_leaves(g_manual), jax.tree_util.tree_leaves(g_ref)
    ):
        np.testing.assert_allclose(
            np.asarray(m), np.asarray(r), rtol=2e-4, atol=1e-6
        )


def test_manual_ulysses_indivisible_falls_back_to_ring():
    """L=3 not divisible by seq=2: warn and use ring (exact anyway)."""
    mesh = make_mesh(MeshConfig(data=2, seq=2), jax.devices()[:4])
    params = init_denoise(jax.random.PRNGKey(0), CFG)
    img, noise = _data()
    with pytest.warns(UserWarning, match="divisible"):
        loss_fn = make_manual_loss(mesh, CFG, TCFG, sp_strategy="ulysses")
    got = float(jax.jit(loss_fn)(params, img, noise))
    want = float(jax.jit(_ref_loss)(params, img, noise))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_manual_tp_grads_match_dense():
    """Hidden-axis TP in the manual region: the hand-written Megatron psum
    plus the shard_map transpose must reproduce the single-device gradients
    for every leaf — sharded FFW weights (local cotangents), replicated
    embeddings (psum'd partials), and the 1/mp-scaled b2."""
    mesh = make_mesh(MeshConfig(data=2, seq=2, model=2), jax.devices()[:8])
    params = init_denoise(jax.random.PRNGKey(0), CFG)
    img, noise = _data()
    loss_fn = make_manual_loss(mesh, CFG, TCFG, sp_strategy="ring")
    g_manual = jax.jit(jax.grad(loss_fn))(params, img, noise)
    g_ref = jax.jit(jax.grad(_ref_loss))(params, img, noise)
    flat_m, _ = jax.tree_util.tree_flatten(g_manual)
    flat_r, _ = jax.tree_util.tree_flatten(g_ref)
    for m, r in zip(flat_m, flat_r):
        np.testing.assert_allclose(
            np.asarray(m), np.asarray(r), rtol=2e-4, atol=1e-6
        )


def test_manual_halo_with_radius_matches_dense():
    cfg = dataclasses.replace(CFG, local_consensus_radius=1)
    mesh = make_mesh(MeshConfig(seq=2), jax.devices()[:2])
    params = init_denoise(jax.random.PRNGKey(1), cfg)
    img, noise = _data(1)
    loss_fn = make_manual_loss(mesh, cfg, TCFG, sp_strategy="halo")
    got = float(jax.jit(loss_fn)(params, img, noise))
    want = float(
        jax.jit(lambda p, i, n: _ref_loss(p, i, n, cfg=cfg))(params, img, noise)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_manual_use_pallas_fallback_matches_dense():
    """use_pallas=True on CPU exercises the fused-path code shape (the
    kernels auto-fall-back to their XLA forms) — values must not change."""
    tcfg = dataclasses.replace(TCFG, use_pallas=True)
    mesh = make_mesh(MeshConfig(data=4), jax.devices()[:4])
    params = init_denoise(jax.random.PRNGKey(0), CFG)
    img, noise = _data()
    loss_fn = make_manual_loss(mesh, CFG, tcfg, sp_strategy="none")
    got = float(jax.jit(loss_fn)(params, img, noise))
    want = float(jax.jit(_ref_loss)(params, img, noise))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_manual_train_step_matches_single_device():
    """One full manual train step (grad + adam) must track the single-device
    Trainer given identical seeds and batch."""
    mesh = make_mesh(MeshConfig(data=2, seq=2), jax.devices()[:4])
    _, optimizer = create_train_state(jax.random.PRNGKey(TCFG.seed), CFG, TCFG)
    step = make_manual_train_step(mesh, CFG, TCFG, optimizer, sp_strategy="ring")

    single = Trainer(CFG, TCFG)
    state, _ = create_train_state(
        jax.random.split(jax.random.PRNGKey(TCFG.seed))[1], CFG, TCFG
    )
    img, _ = _data()
    rng = jax.random.split(jax.random.PRNGKey(TCFG.seed))[1]
    # Same rng path as Trainer.step: split off the step rng.
    step_rng = jax.random.split(rng)[1]
    state2, metrics = jax.jit(step)(state, img, step_rng)
    ref_metrics = single.step(np.asarray(img))
    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-5
    )
    assert int(state2.step) == 1


def test_manual_grad_accum_matches_full_batch():
    """Microbatch accumulation through the manual shard_map region: same
    post-step params as the full-batch manual step."""
    mesh = make_mesh(MeshConfig(data=2, seq=2), jax.devices()[:4])
    img, _ = _data()
    rng = jax.random.PRNGKey(7)
    states = []
    for tcfg in (TCFG, dataclasses.replace(TCFG, grad_accum=2)):
        state, opt = create_train_state(jax.random.PRNGKey(0), CFG, tcfg)
        step = jax.jit(
            make_manual_train_step(mesh, CFG, tcfg, opt, sp_strategy="ring")
        )
        state, metrics = step(state, img, rng)
        assert np.isfinite(float(metrics["loss"]))
        states.append(state)
    for a, b in zip(
        jax.tree_util.tree_leaves(states[0].params),
        jax.tree_util.tree_leaves(states[1].params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_tp_hidden_uses_manual_path():
    """Hidden-axis TP + use_pallas rides the manual shard_map path (round-2
    review item 1: the pod preset must reach the fused kernels), and a
    step's loss matches the single-device trainer."""
    from glom_tpu.parallel import DistributedTrainer

    tcfg = dataclasses.replace(TCFG, use_pallas=True, batch_size=4)
    tr = DistributedTrainer(
        CFG, tcfg, MeshConfig(data=2, model=2), sp_strategy="none"
    )
    assert tr.use_manual
    assert tr.tcfg.use_pallas
    img, _ = _data()
    metrics = tr.step(np.asarray(img))

    single = Trainer(CFG, dataclasses.replace(TCFG, batch_size=4))
    ref_metrics = single.step(np.asarray(img))
    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-5
    )


def test_tp_levels_fallback_clears_use_pallas():
    """EP-style 'levels' TP has no manual-region body: must fall back to
    GSPMD with the flag CLEARED — otherwise glom_forward would emit Mosaic
    custom calls under TP-sharded weights (unpartitionable; invisible on
    CPU where kernels fall back)."""
    from glom_tpu.parallel import DistributedTrainer

    # levels=4: the EP-style spec shards bottom_up's group axis (G = L) over
    # model=2, so L must divide.
    cfg = dataclasses.replace(CFG, levels=4)
    tcfg = dataclasses.replace(TCFG, use_pallas=True, batch_size=4)
    with pytest.warns(UserWarning, match="levels"):
        tr = DistributedTrainer(
            cfg, tcfg, MeshConfig(data=2, model=2), sp_strategy="none",
            tp_axis="levels",
        )
    assert not tr.use_manual
    assert not tr.tcfg.use_pallas


def test_manual_unknown_strategy_raises():
    mesh = make_mesh(MeshConfig(data=2, seq=2), jax.devices()[:4])
    with pytest.raises(ValueError, match="unknown SP strategy"):
        make_manual_loss(mesh, CFG, TCFG, sp_strategy="ulyses")


def test_manual_supported_predicate():
    m_ok = make_mesh(MeshConfig(data=4), jax.devices()[:4])
    m_tp = make_mesh(MeshConfig(data=2, model=2), jax.devices()[:4])
    assert manual_supported(m_ok)
    assert manual_supported(m_tp)  # hidden-axis TP: manual Megatron psum
    assert manual_supported(m_ok, "levels")  # model=1: nothing to shard
    assert not manual_supported(m_tp, "levels")  # EP-style stays GSPMD


class TestShardFusedLoop:
    """The seq=1/mp=1 manual DP shard body dispatches to the whole-loop
    VJP (round 5) — loss and every gradient must match the scan-path
    manual composition, through the real shard_map (DP transpose psum
    composing with the loop's custom_vjp)."""

    # shard-local batch 8 at a loop_supported shape: d=128, n=16, L=4
    LCFG = GlomConfig(dim=128, levels=4, image_size=16, patch_size=4)
    LTCFG = TrainConfig(
        batch_size=16, iters=2, recon_iter_index=2, use_pallas=True
    )

    def _data(self):
        rng = np.random.default_rng(5)
        img = jnp.asarray(rng.normal(size=(16, 3, 16, 16)), jnp.float32)
        noise = jnp.asarray(rng.normal(size=(16, 3, 16, 16)), jnp.float32)
        return img, noise

    def test_gate_engages_at_shard_shape(self):
        """models/core._use_fused_loop is the one gate, at the SHARD-LOCAL
        shapes manual hands it (interpret=True stands in for the chip)."""
        from glom_tpu.models.core import _use_fused_loop

        glom = init_denoise(jax.random.PRNGKey(3), self.LCFG).glom

        def gate(b, n=16, ffw=glom.bottom_up):
            levels = jax.ShapeDtypeStruct((4, b, n, 128), jnp.float32)
            return _use_fused_loop(
                ffw, glom.pos_emb[:n], levels, self.LCFG, 2, False, False, True
            )

        assert gate(8)
        # sub-batched shards stay on the scan path
        assert not gate(2)
        # a sequence-parallel shard holds a band of the rows, a
        # tensor-parallel one a share of the hidden axis: neither is whole
        assert not gate(8, n=8)
        half = jax.tree_util.tree_map(lambda t: t[..., :256], glom.bottom_up)
        assert not gate(8, ffw=half._replace(w2=glom.bottom_up.w2[:, :256]))
        # and off the chip nothing dispatches without interpret
        levels = jax.ShapeDtypeStruct((4, 8, 16, 128), jnp.float32)
        assert not _use_fused_loop(
            glom.bottom_up, glom.pos_emb, levels, self.LCFG, 2, False, False
        )

    # The heaviest single test in the suite (interpret-mode whole-loop VJP
    # under shard_map, ~60-75s): both variants are slow-marked for the
    # tier-1 budget — CI's unfiltered run and tpu_validate keep the
    # manual fused-loop parity gated on every push / chip run.
    @pytest.mark.slow
    @pytest.mark.parametrize(
        "remat", [False, pytest.param(True, marks=pytest.mark.slow)]
    )
    def test_dp2_loop_matches_scan(self, remat):
        mesh = make_mesh(MeshConfig(data=2), jax.devices()[:2])
        tcfg = dataclasses.replace(self.LTCFG, remat=remat)
        params = init_denoise(jax.random.PRNGKey(3), self.LCFG)
        img, noise = self._data()
        # interpret=True engages the whole-loop VJP inside the shards
        # (kernels in interpret mode); the default build resolves to the
        # scan path off-TPU — the XLA-composed reference.
        loss_loop = make_manual_loss(mesh, self.LCFG, tcfg, interpret=True)
        loss_scan = make_manual_loss(mesh, self.LCFG, tcfg)
        l1, g1 = jax.value_and_grad(loss_loop)(params, img, noise)
        l2, g2 = jax.value_and_grad(loss_scan)(params, img, noise)
        np.testing.assert_allclose(float(l1), float(l2), rtol=2e-5)
        for a, b in zip(
            jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
            )

    def test_distributed_trainer_label_follows_dispatch(self, monkeypatch):
        """DistributedTrainer's vjp_path label must say fused_loop exactly
        when the seq=1/mp=1 shard body would dispatch there (on TPU, at
        the loop-supported shard shape) — the label and the dispatch share
        resolve_vjp_path, so this pins the plumbing between them."""
        from glom_tpu.models import core
        from glom_tpu.parallel import DistributedTrainer

        monkeypatch.setattr(core, "_on_tpu", lambda: True)
        tr = DistributedTrainer(
            self.LCFG, self.LTCFG, MeshConfig(data=2), sp_strategy="none"
        )
        assert tr.use_manual
        assert tr.vjp_path == "fused_loop"
        assert tr.grad_accum == 1
        # TP shards never take the loop (scan_only=model>1): label must
        # stay scan-side at the same otherwise-eligible config
        tr_tp = DistributedTrainer(
            self.LCFG, self.LTCFG, MeshConfig(data=2, model=2),
            sp_strategy="none",
        )
        assert tr_tp.vjp_path.startswith("scan_")


# ------------------------------------------------- one loop, written once

_MANUAL_SRC = (
    pathlib.Path(__file__).resolve().parent.parent
    / "glom_tpu" / "parallel" / "manual.py"
)


@pytest.mark.parametrize(
    "name", ["scan", "checkpoint", "fused_glom_loop", "resolve_vjp_path"]
)
def test_manual_holds_no_loop_of_its_own(name):
    """The level-major loop body, its jax.checkpoint, its lax.scan and its
    dispatch to the whole-loop VJP live in models/core.level_major_loop;
    parallel/manual.py slices the shard and calls it. A second copy here is
    what let an optimisation land on one body and miss the other."""
    used = set()
    for node in ast.walk(ast.parse(_MANUAL_SRC.read_text())):
        if isinstance(node, ast.Attribute):
            used.add(node.attr)
        elif isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.ImportFrom):
            used.update(a.name for a in node.names)
    assert name not in used


@pytest.mark.parametrize("return_mode", ["top", "final", "all"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_shard_forward_is_the_single_chip_forward(dtype, return_mode):
    """On a data=2 mesh at seq=1, mp=1 a shard's forward IS
    glom_forward(use_pallas=True) on that shard's rows: one function runs
    in both, so the results are equal to the last bit (this backend's
    matrix product gives a row the same sum whatever the row count, so the
    reference runs the whole batch at once)."""
    from jax.sharding import PartitionSpec as P

    from glom_tpu.models.core import glom_forward
    from glom_tpu.parallel.manual import _forward_local

    T = 3
    mesh = make_mesh(MeshConfig(data=2), jax.devices()[:2])
    glom = jax.tree_util.tree_map(
        lambda t: t.astype(dtype), init_denoise(jax.random.PRNGKey(4), CFG).glom
    )
    img = _data(4)[0].astype(dtype)
    lead = {"top": (), "final": (None,), "all": (None, None)}[return_mode]
    manual = jax.jit(jax.shard_map(
        lambda p, x: _forward_local(
            p, x, CFG, iters=T, seq=1, mp=1, consensus_shard=None,
            remat=False, use_pallas=True, return_mode=return_mode,
        ),
        mesh=mesh, in_specs=(P(), P("data")), out_specs=P(*lead, "data"),
        check_vma=False,
    ))
    got = np.asarray(manual(glom, img).astype(jnp.float32))

    single = jax.jit(lambda p, x: glom_forward(
        p, x, CFG, iters=T, use_pallas=True, return_all=return_mode == "all"
    ))
    ref = np.asarray(single(glom, img).astype(jnp.float32))  # [.., b, n, L, d]
    want = {
        "top": lambda r: r[:, :, -1],                      # [b, n, d]
        "final": lambda r: r.transpose(2, 0, 1, 3),        # [L, b, n, d]
        "all": lambda r: r.transpose(0, 3, 1, 2, 4),       # [T+1, L, b, n, d]
    }[return_mode](ref)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
