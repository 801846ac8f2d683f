"""Full-forward parity with the NumPy oracle + every SURVEY §3.2 subtlety."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glom_tpu.models import Glom, glom_forward, init_glom
from glom_tpu.models.core import contribution_divisor
from glom_tpu.utils.config import GlomConfig
from oracle_np import np_forward, np_local_mask

CFG = GlomConfig(dim=16, levels=3, image_size=8, patch_size=2)  # n=16, tiny


def params_to_np(params):
    def ffw(p):
        return {k: np.asarray(getattr(p, k), np.float64) for k in ("w1", "b1", "w2", "b2")}

    return {
        "token_w": np.asarray(params.token_embed.w, np.float64),
        "token_b": np.asarray(params.token_embed.b, np.float64),
        "pos_emb": np.asarray(params.pos_emb, np.float64),
        "init_levels": np.asarray(params.init_levels, np.float64),
        "bottom_up": ffw(params.bottom_up),
        "top_down": ffw(params.top_down),
    }


@pytest.fixture(scope="module")
def setup():
    params = init_glom(jax.random.PRNGKey(1), CFG)
    img = np.random.default_rng(2).normal(size=(2, 3, 8, 8))
    return params, params_to_np(params), img


class TestForwardParity:
    def test_default_forward(self, setup):
        params, np_params, img = setup
        got = glom_forward(params, jnp.asarray(img, jnp.float32), CFG)
        want = np_forward(np_params, img, levels_cfg=CFG.levels, patch_size=2)
        assert got.shape == (2, 16, 3, 16)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-4)

    def test_default_iters_is_2L(self, setup):
        """Contract #1: default T = 2*levels, observable via return_all count."""
        params, _, img = setup
        all_states = glom_forward(
            params, jnp.asarray(img, jnp.float32), CFG, return_all=True
        )
        assert all_states.shape[0] == 2 * CFG.levels + 1  # T+1 incl. initial

    def test_return_all_includes_initial(self, setup):
        """Contract #6: state 0 is the broadcast init_levels."""
        params, _, img = setup
        all_states = glom_forward(
            params, jnp.asarray(img, jnp.float32), CFG, return_all=True
        )
        want0 = np.broadcast_to(
            np.asarray(params.init_levels)[None, None], all_states.shape[1:]
        )
        np.testing.assert_allclose(np.asarray(all_states[0]), want0, atol=1e-6)
        # and state 1 differs (the loop actually ran)
        assert not np.allclose(np.asarray(all_states[1]), want0)

    def test_explicit_iters(self, setup):
        params, np_params, img = setup
        got = glom_forward(params, jnp.asarray(img, jnp.float32), CFG, iters=4)
        want = np_forward(np_params, img, levels_cfg=CFG.levels, patch_size=2, iters=4)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-4)

    def test_levels_carry_in(self, setup):
        """Contract #7: T iters from a provided state == 2x T/2 chained calls
        (the temporal/video recipe)."""
        params, _, img = setup
        jimg = jnp.asarray(img, jnp.float32)
        full = glom_forward(params, jimg, CFG, iters=4)
        half = glom_forward(params, jimg, CFG, iters=2)
        chained = glom_forward(params, jimg, CFG, iters=2, levels=half)
        np.testing.assert_allclose(
            np.asarray(chained), np.asarray(full), rtol=1e-4, atol=1e-5
        )

    def test_top_level_divisor_is_3(self):
        """Contract #5."""
        div = np.asarray(contribution_divisor(5))
        assert div.shape == (5, 1)
        assert (div[:-1] == 4.0).all() and div[-1] == 3.0

    def test_local_radius_forward_parity(self, setup):
        cfg = GlomConfig(
            dim=16, levels=3, image_size=8, patch_size=2, local_consensus_radius=1
        )
        params, np_params, img = setup
        got = glom_forward(params, jnp.asarray(img, jnp.float32), cfg, iters=3)
        want = np_forward(
            np_params,
            img,
            levels_cfg=3,
            patch_size=2,
            iters=3,
            local_mask=np_local_mask(4, 1),
        )
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-4)

    def test_consensus_self_forward_parity(self, setup):
        cfg = GlomConfig(dim=16, levels=3, image_size=8, patch_size=2, consensus_self=True)
        params, np_params, img = setup
        got = glom_forward(params, jnp.asarray(img, jnp.float32), cfg, iters=3)
        want = np_forward(
            np_params, img, levels_cfg=3, patch_size=2, iters=3, attend_self=True
        )
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-4)

    def test_remat_matches_plain(self, setup):
        params, _, img = setup
        jimg = jnp.asarray(img, jnp.float32)
        plain = glom_forward(params, jimg, CFG)
        remat = glom_forward(params, jimg, CFG, remat=True)
        np.testing.assert_allclose(np.asarray(remat), np.asarray(plain), atol=1e-6)

    def test_grad_flows(self, setup):
        """backward through all T scan iterations (the README training path)."""
        params, _, img = setup
        jimg = jnp.asarray(img, jnp.float32)

        def loss(p):
            return jnp.mean(glom_forward(p, jimg, CFG, remat=True) ** 2)

        g = jax.grad(loss)(params)
        flat = jax.tree_util.tree_leaves(g)
        assert all(np.isfinite(np.asarray(t)).all() for t in flat)
        assert any(np.abs(np.asarray(t)).max() > 0 for t in flat)


class TestGlomAPI:
    def test_reference_signature(self):
        """The reference constructor and forward call, verbatim."""
        model = Glom(dim=16, levels=3, image_size=8, patch_size=2)
        img = jnp.zeros((1, 3, 8, 8))
        out = model(img)
        assert out.shape == (1, 16, 3, 16)
        all_states = model(img, iters=5, return_all=True)
        assert all_states.shape == (6, 1, 16, 3, 16)
        cont = model(img, iters=2, levels=out)
        assert cont.shape == out.shape

    def test_backend_flag(self):
        Glom(dim=16, levels=2, image_size=8, patch_size=2, backend="tpu")
        with pytest.raises(ValueError):
            Glom(dim=16, levels=2, image_size=8, patch_size=2, backend="cuda")

    def test_jit_cache_reused(self):
        model = Glom(dim=16, levels=2, image_size=8, patch_size=2)
        img = jnp.zeros((1, 3, 8, 8))
        model(img)
        model(img)
        assert len(model._jitted) == 1

    def test_config_validation(self):
        with pytest.raises(ValueError):
            GlomConfig(image_size=10, patch_size=3)
        with pytest.raises(ValueError):
            GlomConfig(levels=1)

    def test_backend_tpu_selects_pallas_path(self):
        """backend='tpu' must reach the fused kernel path (review item weak #4:
        round 1's preserved API only ever hit the slow path) and agree with
        the explicit slow path numerically."""
        model = Glom(dim=16, levels=3, image_size=8, patch_size=2, backend="tpu")
        assert model.use_pallas
        slow = Glom(
            dim=16, levels=3, image_size=8, patch_size=2, use_pallas=False,
            params=model.params,
        )
        assert not slow.use_pallas
        img = jnp.asarray(np.random.default_rng(0).normal(size=(1, 3, 8, 8)), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(model(img)), np.asarray(slow(img)), rtol=1e-5, atol=1e-6
        )

    def test_mesh_kwarg_runs_sharded(self):
        """mesh= + sp_strategy= through the preserved API: same results as
        the single-device forward."""
        from glom_tpu.utils.config import MeshConfig

        base = Glom(dim=16, levels=3, image_size=8, patch_size=2, use_pallas=False)
        sharded = Glom(
            dim=16, levels=3, image_size=8, patch_size=2,
            mesh=MeshConfig(data=2, seq=2), sp_strategy="ring",
            params=base.params, use_pallas=False,
        )
        assert not sharded.use_pallas  # GSPMD path carries the sharding
        img = jnp.asarray(
            np.random.default_rng(1).normal(size=(2, 3, 8, 8)), jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(sharded(img)), np.asarray(base(img)), rtol=1e-5, atol=1e-6
        )

    def test_mesh_default_rides_manual_fused_path(self):
        """Round-2 review item weak #5: `Glom(mesh=...)` must reach the fused
        path — the backend='tpu' default keeps use_pallas ON under a mesh
        and routes through the manual shard_map forward, matching the
        single-device forward on final levels, return_all stacks, and the
        temporal levels carry."""
        from glom_tpu.utils.config import MeshConfig

        base = Glom(dim=16, levels=3, image_size=8, patch_size=2, use_pallas=False)
        sharded = Glom(
            dim=16, levels=3, image_size=8, patch_size=2,
            mesh=MeshConfig(data=2, seq=2), sp_strategy="ring",
            params=base.params,
        )
        assert sharded.use_pallas  # the fused path survives the mesh
        img = jnp.asarray(
            np.random.default_rng(1).normal(size=(2, 3, 8, 8)), jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(sharded(img)), np.asarray(base(img)), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(sharded(img, return_all=True)),
            np.asarray(base(img, return_all=True)),
            rtol=1e-5, atol=1e-6,
        )
        lv = base(img, iters=2)
        np.testing.assert_allclose(
            np.asarray(sharded(img, iters=3, levels=lv)),
            np.asarray(base(img, iters=3, levels=lv)),
            rtol=1e-5, atol=1e-6,
        )

    def test_mesh_tp_manual_forward_matches(self):
        """Hidden-TP mesh through the API: the manual Megatron psum in the
        inference forward too."""
        from glom_tpu.utils.config import MeshConfig

        base = Glom(dim=16, levels=3, image_size=8, patch_size=2, use_pallas=False)
        sharded = Glom(
            dim=16, levels=3, image_size=8, patch_size=2,
            mesh=MeshConfig(data=2, seq=2, model=2), sp_strategy="ring",
            params=base.params,
        )
        assert sharded.use_pallas
        img = jnp.asarray(
            np.random.default_rng(2).normal(size=(2, 3, 8, 8)), jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(sharded(img)), np.asarray(base(img)), rtol=1e-5, atol=1e-6
        )

    def test_mesh_without_standard_axes_warns(self):
        import jax as _jax
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(_jax.devices()[:2]).reshape(2), ("x",))
        with pytest.warns(UserWarning, match="axis names"):
            m = Glom(
                dim=16, levels=3, image_size=8, patch_size=2,
                mesh=mesh, use_pallas=True,
            )
        assert not m.use_pallas
