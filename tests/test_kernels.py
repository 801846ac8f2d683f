"""Pallas kernel tests (interpret mode on CPU) vs the XLA/oracle path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glom_tpu.kernels import fused_grouped_ffw
from glom_tpu.ops.ffw import grouped_ffw, init_grouped_ffw


@pytest.fixture(scope="module")
def setup():
    G, d = 4, 128
    params = init_grouped_ffw(jax.random.PRNGKey(0), G, d, mult=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, G, d), jnp.float32)
    return params, x


class TestFusedGroupedFFW:
    def test_forward_matches_xla(self, setup):
        params, x = setup
        got = fused_grouped_ffw(params, x, tile_m=128, interpret=True)
        want = grouped_ffw(params, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
        )

    def test_grad_matches_xla(self, setup):
        params, x = setup

        def loss_fused(p, x_):
            return jnp.mean(fused_grouped_ffw(p, x_, tile_m=128, interpret=True) ** 2)

        def loss_xla(p, x_):
            return jnp.mean(grouped_ffw(p, x_) ** 2)

        g1 = jax.grad(loss_fused, argnums=(0, 1))(params, x)
        g2 = jax.grad(loss_xla, argnums=(0, 1))(params, x)
        for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5
            )

    def test_bwd_kernel_bf16_multi_tile(self, setup):
        """The fused backward kernel in bf16: dw/db accumulate in f32 across
        8 row tiles (M=4*256=1024, bwd tile 128), and the tanh-GELU
        derivative matches the bf16 forward's activation to bf16
        resolution."""
        if jax.devices()[0].platform == "cpu":
            pytest.skip("CPU XLA lacks bf16xbf16->f32 dot; covered on TPU")
        params, _ = setup
        G, d = 4, 128
        pb = jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16), params)
        xb = jax.random.normal(jax.random.PRNGKey(3), (4, 256, G, d), jnp.bfloat16)

        def loss_fused(p, x_):
            return jnp.mean(
                fused_grouped_ffw(p, x_, tile_m=128, interpret=True).astype(
                    jnp.float32
                )
                ** 2
            )

        def loss_xla(p, x_):
            return jnp.mean(grouped_ffw(p, x_).astype(jnp.float32) ** 2)

        g1 = jax.grad(loss_fused, argnums=(0, 1))(pb, xb)
        g2 = jax.grad(loss_xla, argnums=(0, 1))(pb, xb)
        for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32),
                np.asarray(b, np.float32),
                rtol=0.1,
                atol=2e-3,  # bf16 grads + tanh-vs-erf GELU derivative
            )

    def test_fallback_on_unsupported_shape(self, setup):
        params, _ = setup
        # M=6 not divisible by tile -> must silently fall back, still correct
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 4, 128), jnp.float32)
        got = fused_grouped_ffw(params, x, tile_m=128)
        want = grouped_ffw(params, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )

    def test_bf16(self, setup):
        if jax.devices()[0].platform == "cpu":
            pytest.skip("CPU XLA lacks bf16xbf16->f32 dot; covered on TPU")
        params, x = setup
        pb = jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16), params)
        xb = x.astype(jnp.bfloat16)
        got = fused_grouped_ffw(pb, xb, tile_m=128, interpret=True)
        want = grouped_ffw(pb, xb)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
        )

    def test_auto_tile_small_batch(self, setup):
        """batch=1, n=256 -> M=256 must auto-pick tile 256 and use the kernel
        (not silently fall back)."""
        from glom_tpu.kernels.grouped_mlp import _pick_tile

        assert _pick_tile(256) == 256
        assert _pick_tile(4096) == 512
        assert _pick_tile(6) is None
        params, _ = setup
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 256, 4, 128), jnp.float32)
        got = fused_grouped_ffw(params, x, interpret=True)
        want = grouped_ffw(params, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
        )

    def test_bwd_accumulates_f32(self, setup):
        """The custom-VJP backward must pin f32 accumulation on every
        contraction regardless of input dtype (checked via the jaxpr, since
        CPU cannot execute bf16 dots). Walks into pallas_call sub-jaxprs so
        the dots inside the fused backward kernel are covered too."""
        from glom_tpu.kernels.grouped_mlp import _bwd

        def all_dots(jaxpr):
            for e in jaxpr.eqns:
                if e.primitive.name == "dot_general":
                    yield e
                for sub in jax.core.jaxprs_in_params(e.params):
                    yield from all_dots(sub)

        params, _ = setup
        pb = jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16), params)
        f = pb.w1.shape[-1]
        # level-major [G, M, d]: M=256 takes the fused kernel (with and
        # without a saved pre-activation), M=192 the XLA fallback
        for shape, with_pre in [
            ((4, 256, 128), False),
            ((4, 256, 128), True),
            ((4, 192, 128), False),
        ]:
            x = jnp.zeros(shape, jnp.bfloat16)
            g = jnp.zeros_like(x)
            pre = jnp.zeros((shape[0], shape[1], f), jnp.bfloat16) if with_pre else None
            jaxpr = jax.make_jaxpr(
                lambda p, x_, g_: _bwd(64, False, (p, x_, pre), g_)
            )(pb, x, g)
            dots = list(all_dots(jaxpr.jaxpr))
            # saved-pre kernel drops the recompute contraction (5 -> 4);
            # exact counts so a silent fall-back to the recompute kernel
            # (or a lost contraction) both fail
            if shape[1] == 256:
                assert len(dots) == (4 if with_pre else 5), len(dots)
            else:  # XLA fallback path
                assert len(dots) >= 5, "backward lost its contractions?"
            for e in dots:
                assert e.params["preferred_element_type"] == jnp.float32

    def test_add_kwarg_fallback_matches_explicit(self, setup):
        """f32 (no fold: bf16-only path) add= must equal the explicit
        x + tile(add) composition — the wrapper's fallback correctness."""
        from glom_tpu.kernels import fused_grouped_ffw_lm

        params, _ = setup
        G, n, d = 4, 8, 128
        M = 2 * n
        x = jax.random.normal(jax.random.PRNGKey(5), (G, M, d), jnp.float32)
        a = jax.random.normal(jax.random.PRNGKey(6), (n, d), jnp.float32)

        def loss_add(p, x_, a_):
            out = fused_grouped_ffw_lm(p, x_, add=a_, interpret=True)
            return jnp.mean(out ** 2)

        def loss_exp(p, x_, a_):
            xa = x_ + jnp.tile(a_, (M // n, 1))[None]
            out = fused_grouped_ffw_lm(p, xa, interpret=True)
            return jnp.mean(out ** 2)

        v1, g1 = jax.value_and_grad(loss_add, argnums=(0, 1, 2))(params, x, a)
        v2, g2 = jax.value_and_grad(loss_exp, argnums=(0, 1, 2))(params, x, a)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-6)
        for t1, t2 in zip(
            jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)
        ):
            np.testing.assert_allclose(
                np.asarray(t1), np.asarray(t2), rtol=1e-5, atol=1e-6
            )

    def test_add_fold_kernels_match_explicit(self, setup):
        """The FOLD path itself (f32 under interpret — CI coverage of
        _mlp_kernel_add / _mlp_bwd_kernel_saved_add and the whole-grid da
        accumulation): forward and ALL grads incl. da must equal the
        explicit x + tile(add) composition."""
        from glom_tpu.kernels import fused_grouped_ffw_lm
        from glom_tpu.kernels.grouped_mlp import _pick_tile
        from glom_tpu.ops.ffw import init_grouped_ffw

        G, n, d = 3, 128, 128
        M = 2 * n
        params = init_grouped_ffw(jax.random.PRNGKey(9), G, d, mult=4)
        x = jax.random.normal(jax.random.PRNGKey(10), (G, M, d), jnp.float32)
        a = jax.random.normal(jax.random.PRNGKey(11), (n, d), jnp.float32)
        assert _pick_tile(M, d, 4 * d, 4) % n == 0  # the fold gate holds

        def loss_add(p, x_, a_):
            out = fused_grouped_ffw_lm(p, x_, add=a_, interpret=True)
            return jnp.mean(out ** 2)

        def loss_exp(p, x_, a_):
            xa = x_ + jnp.tile(a_, (M // n, 1))[None]
            out = fused_grouped_ffw_lm(p, xa, interpret=True)
            return jnp.mean(out ** 2)

        v1, g1 = jax.value_and_grad(loss_add, argnums=(0, 1, 2))(params, x, a)
        v2, g2 = jax.value_and_grad(loss_exp, argnums=(0, 1, 2))(params, x, a)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
        for t1, t2 in zip(
            jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)
        ):
            np.testing.assert_allclose(
                np.asarray(t1), np.asarray(t2), rtol=2e-4, atol=1e-5
            )

    def test_bwd_xla_fallback_grad(self, setup):
        """M=192 has no 128-divisible bwd tile -> _bwd must take the
        barrier+XLA fallback (with explicit fwd tile 64) and still match the
        reference gradients."""
        params, _ = setup
        x = jax.random.normal(jax.random.PRNGKey(5), (3, 64, 4, 128), jnp.float32)

        def loss_fused(p, x_):
            return jnp.mean(fused_grouped_ffw(p, x_, tile_m=64, interpret=True) ** 2)

        def loss_xla(p, x_):
            return jnp.mean(grouped_ffw(p, x_) ** 2)

        g1 = jax.grad(loss_fused, argnums=(0, 1))(params, x)
        g2 = jax.grad(loss_xla, argnums=(0, 1))(params, x)
        for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5
            )


class TestFusedConsensusUpdate:
    """Blockwise consensus + 4-way mean kernel vs the dense XLA composition."""

    def _reference(self, levels_lm, bu_lm, td_lm, side, radius, attend_self):
        from glom_tpu.kernels.consensus_update import _xla_reference

        return _xla_reference(
            levels_lm, bu_lm, td_lm,
            side=side, radius=radius, attend_self=attend_self,
        )

    def _rand(self, key, L, B, n, d):
        k1, k2, k3 = jax.random.split(key, 3)
        levels = jax.random.normal(k1, (L, B, n, d), jnp.float32)
        bu = jax.random.normal(k2, (L, B, n, d), jnp.float32)
        td = jax.random.normal(k3, (L - 1, B, n, d), jnp.float32)
        return levels, bu, td

    @pytest.mark.parametrize("radius", [0.0, 2.0, 7.0])
    @pytest.mark.parametrize("attend_self", [False, True])
    def test_matches_dense(self, radius, attend_self):
        from glom_tpu.kernels import fused_consensus_update

        L, B, side, d = 3, 2, 8, 128
        n = side * side
        levels, bu, td = self._rand(jax.random.PRNGKey(0), L, B, n, d)
        got = fused_consensus_update(
            levels, bu, td,
            side=side, radius=radius, attend_self=attend_self, interpret=True,
        )
        want = self._reference(levels, bu, td, side, radius, attend_self)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_multirow_tiles_online_softmax(self):
        """n large enough that the j-loop runs multiple online-softmax steps:
        side=24 -> n=576, tile 64 -> 9 j-tiles per row-tile, exercising the
        exp(m - m_new) carry correction, fully-masked-row self-healing, and
        the block-sparsity j-window arithmetic."""
        from glom_tpu.kernels.consensus_update import _fused, _pick_tile

        L, B, side, d = 2, 1, 24, 128
        n = side * side
        assert _pick_tile(n) < n, "tile must split n or this test is vacuous"
        levels, bu, td = self._rand(jax.random.PRNGKey(1), L, B, n, d)
        # radius 3 on side 24: live window is a band; far j-tiles are skipped
        got = _fused(levels, bu, td, side, 3.0, False, True)
        want = self._reference(levels, bu, td, side, 3.0, False)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=2e-5
        )

    def test_grad_matches_dense(self):
        from glom_tpu.kernels import fused_consensus_update

        L, B, side, d = 3, 1, 4, 128
        n = side * side
        levels, bu, td = self._rand(jax.random.PRNGKey(2), L, B, n, d)

        def loss_fused(lv, b_, t_):
            out = fused_consensus_update(
                lv, b_, t_, side=side, radius=2.0, interpret=True,
                bwd_impl="blockwise",
            )
            return jnp.mean(out ** 2)

        def loss_ref(lv, b_, t_):
            return jnp.mean(self._reference(lv, b_, t_, side, 2.0, False) ** 2)

        g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(levels, bu, td)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(levels, bu, td)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5
            )

    @pytest.mark.parametrize("radius", [0.0, 3.0])
    def test_grad_multirow_tiles(self, radius):
        """Backward across many i/j tiles (side=24 -> n=576, tile 64): the
        dq kernel's recomputed stats must match what the dkv kernel reads
        back, the block-sparse windows must cover exactly the live band in
        BOTH kernels (i-major and j-major), and ds must vanish on the
        replaced diagonal."""
        from glom_tpu.kernels.consensus_update import _fused, _xla_reference

        L, B, side, d = 2, 1, 24, 128
        n = side * side
        levels, bu, td = self._rand(jax.random.PRNGKey(7), L, B, n, d)

        def loss_fused(lv, b_, t_):
            out = _fused(lv, b_, t_, side, radius, False, True, "blockwise")
            return jnp.mean(out ** 2)

        def loss_ref(lv, b_, t_):
            out = _xla_reference(
                lv, b_, t_, side=side, radius=radius, attend_self=False
            )
            return jnp.mean(out ** 2)

        g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(levels, bu, td)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(levels, bu, td)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
            )

    @pytest.mark.parametrize("radius", [0.0, 3.0])
    def test_grad_two_pass_fallback(self, radius, monkeypatch):
        """Rows too long for the one-sweep kernel's resident dq block fall
        back to the two-pass dq/dkv kernels — forced here by disabling the
        one-sweep eligibility so both generations stay covered."""
        from glom_tpu.kernels import consensus_update as cu

        monkeypatch.setattr(cu, "_onesweep_ok", lambda *a: False)
        L, B, side, d = 2, 1, 24, 128
        n = side * side
        levels, bu, td = self._rand(jax.random.PRNGKey(8), L, B, n, d)

        def loss_fused(lv, b_, t_):
            out = cu._fused(lv, b_, t_, side, radius, False, True, "blockwise")
            return jnp.mean(out ** 2)

        def loss_ref(lv, b_, t_):
            out = cu._xla_reference(
                lv, b_, t_, side=side, radius=radius, attend_self=False
            )
            return jnp.mean(out ** 2)

        g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(levels, bu, td)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(levels, bu, td)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
            )

    def test_dense_stats_bwd_matches(self):
        """The explicit stats-based dense backward (bwd_impl='dense'
        through the custom_vjp) vs plain autodiff of the XLA reference."""
        from glom_tpu.kernels.consensus_update import _fused, _xla_reference

        L, B, side, d = 3, 2, 4, 128
        n = side * side
        levels, bu, td = self._rand(jax.random.PRNGKey(9), L, B, n, d)

        def loss_fused(lv, b_, t_):
            out = _fused(lv, b_, t_, side, 0.0, False, True, "dense")
            return jnp.mean(out ** 2)

        def loss_ref(lv, b_, t_):
            out = _xla_reference(
                lv, b_, t_, side=side, radius=0.0, attend_self=False
            )
            return jnp.mean(out ** 2)

        g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(levels, bu, td)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(levels, bu, td)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5
            )

    def test_streamed_forward_matches(self, monkeypatch):
        """The large-n streamed forward layout (j as a windowed inner grid
        axis, (m,l,acc) in scratch) must match the resident-row kernel and
        the dense reference — forced here by dropping _FWD_ROW_LIMIT so
        interpret mode exercises it at test size, incl. the saved-stats
        path through the blockwise backward."""
        from glom_tpu.kernels import consensus_update as cu

        L, B, side, d = 2, 1, 24, 128
        n = side * side
        levels, bu, td = self._rand(jax.random.PRNGKey(5), L, B, n, d)
        for radius in (0.0, 3.0):
            want = self._reference(levels, bu, td, side, radius, False)
            monkeypatch.setattr(cu, "_FWD_ROW_LIMIT", 1)
            got = cu._fused(levels, bu, td, side, radius, False, True, "auto")
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-3, atol=2e-5
            )

            def loss(lv):
                out = cu._fused(lv, bu, td, side, radius, False, True,
                                "blockwise")
                return jnp.mean(out ** 2)

            def loss_ref(lv):
                out = cu._xla_reference(
                    lv, bu, td, side=side, radius=radius, attend_self=False
                )
                return jnp.mean(out ** 2)

            g1 = jax.grad(loss)(levels)
            monkeypatch.undo()
            g2 = jax.grad(loss_ref)(levels)
            np.testing.assert_allclose(
                np.asarray(g1), np.asarray(g2), rtol=2e-3, atol=2e-5
            )

    def test_grad_dense_dispatch_matches_blockwise(self):
        """Both sides of the backward dispatch (dense-recompute VJP vs the
        streamed blockwise kernels) must produce the same gradients; 'auto'
        must agree with whichever side it picks."""
        from glom_tpu.kernels.consensus_update import _fused

        L, B, side, d = 2, 1, 8, 128
        n = side * side
        levels, bu, td = self._rand(jax.random.PRNGKey(11), L, B, n, d)

        def grads(impl):
            def loss(lv, b_, t_):
                out = _fused(lv, b_, t_, side, 0.0, False, True, impl)
                return jnp.mean(out ** 2)

            return jax.grad(loss, argnums=(0, 1, 2))(levels, bu, td)

        g_block, g_dense, g_auto = grads("blockwise"), grads("dense"), grads("auto")
        for a, b, c in zip(g_block, g_dense, g_auto):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5
            )
            np.testing.assert_allclose(
                np.asarray(c), np.asarray(b), rtol=2e-3, atol=1e-5
            )

    # (shape, side, radius, bwd_impl) -> blockwise? Each case names the
    # evidence its branch rests on: "PR 26" is the v5e full train step
    # (PERF.md section 6), "B=1 file" is results/longctx_bench.jsonl.
    DISPATCH_CASES = [
        # flagship train (B=64, single-tile row): batched regime -> blockwise
        pytest.param((6, 64, 256, 512), 16, 0.0, "auto", True, id="flagship-b64-n256"),
        # small-batch inference-style at n=256 -> dense
        pytest.param((6, 2, 256, 512), 16, 0.0, "auto", False, id="b2-n256"),
        # PR 26, the cell: 373.34 -> 317.72 ms a step
        pytest.param((6, 32, 1024, 512), 32, 7.0, "auto", True, id="local1024-b32-r7"),
        # PR 26, point C: 169.67 -> 147.26 ms at B=16, 85.54 -> 72.27 at B=8
        pytest.param((6, 16, 1024, 512), 32, 7.0, "auto", True, id="n1024-b16-r7"),
        pytest.param((6, 8, 1024, 512), 32, 7.0, "auto", True, id="n1024-b8-r7"),
        # below the batch threshold: unmeasured at the train step, stays
        # on the dense side of the B=1 file
        pytest.param((6, 4, 1024, 512), 32, 7.0, "auto", False, id="n1024-b4-r7"),
        # PR 26, point B: global rows at n=1024, 373.26 -> 322.25 ms at B=32
        pytest.param((6, 32, 1024, 512), 32, 0.0, "auto", True, id="n1024-b32-global"),
        pytest.param((6, 8, 1024, 512), 32, 0.0, "auto", True, id="n1024-b8-global"),
        # B=1 file: dense autodiff wins (0.281 vs 0.388 at n=1024)
        pytest.param((6, 1, 1024, 512), 32, 0.0, "auto", False, id="n1024-b1-global"),
        # PR 26, point D: n=576 tiles at 64 and the kernels lose there,
        # 171.63 -> 188.07 ms
        pytest.param((6, 32, 576, 512), 24, 7.0, "auto", False, id="n576-tile64"),
        # the other square n in (512, 4096) that tiles at 256
        pytest.param((6, 8, 2304, 512), 48, 0.0, "auto", True, id="n2304-b8-global"),
        # B=1 file: long global rows (any batch), the one-sweep kernel wins
        pytest.param((6, 1, 4096, 512), 64, 0.0, "auto", True, id="n4096-b1-global"),
        pytest.param((6, 8, 4096, 512), 64, 0.0, "auto", True, id="n4096-b8-global"),
        pytest.param((6, 1, 9216, 512), 96, 0.0, "auto", True, id="n9216-b1-global"),
        # n=4096, radius 7 on side 64: band covers <1/2 the row -> blockwise
        pytest.param((6, 1, 4096, 512), 64, 7.0, "auto", True, id="n4096-b1-r7"),
        # n=16384 global (side 128): one-sweep dq block still fits
        pytest.param((6, 1, 16384, 512), 128, 0.0, "auto", True, id="n16384-b1-global"),
        # forced sides are honored
        pytest.param((6, 64, 256, 512), 16, 0.0, "blockwise", True, id="forced-block"),
        pytest.param((6, 1, 4096, 512), 64, 7.0, "dense", False, id="forced-dense"),
        pytest.param((6, 32, 1024, 512), 32, 7.0, "dense", False, id="forced-dense-1024"),
    ]

    @pytest.mark.parametrize("shape,side,radius,impl,want", DISPATCH_CASES)
    def test_bwd_dispatch_predicate(self, shape, side, radius, impl, want):
        """The dispatch as the chip decided it: batched long rows at the
        256-wide tile go to the blockwise kernels whatever the radius (PR
        26's runs on the v5e); long global rows go to the one-sweep kernel
        from n=4096 up, small-batch mid rows stay dense and a truly-sparse
        local band goes blockwise (the B=1 file); forced sides are
        honored. No environment variable takes part."""
        from glom_tpu.kernels.consensus_update import _use_blockwise_bwd

        assert _use_blockwise_bwd(shape, side, radius, impl) is want

    @pytest.mark.parametrize(
        "preset,b,want",
        [
            # the cell: batch 32 of n=1024 under a radius-7 window
            ("imagenet256-local", 32, "scan_blockwise"),
            # a pure-DP run of it at the preset's data=2: 16 a shard
            ("imagenet256-local", 16, "scan_blockwise"),
            # the flagship and every preset at n <= 512 keep their route
            ("imagenet224-dp8", 64, "fused_loop"),
            ("imagenet224-dp8", 8, "fused_loop"),
            ("imagenet64-local", 64, "fused_loop"),
            ("mnist", 32, "fused_loop"),
            ("cifar10", 64, "fused_loop"),
        ],
    )
    def test_route_by_preset(self, preset, b, want):
        """resolve_vjp_path is the dispatch's one consumer outside the
        module: the trainer's label, the records' vjp_path and the
        benchmark's `route` follow it. assume_on_tpu bypasses only the
        platform check; on the CPU the dense budget falls back to 2 GB and
        the cell's scores are 1.61 GB, so the memory gate does not mask
        the rule."""
        from glom_tpu.models.core import resolve_vjp_path
        from glom_tpu.train.trainer import resolve_route_keys
        from glom_tpu.utils.presets import get_preset

        p = get_preset(preset)
        k, itemsize = resolve_route_keys(p.model, p.train)
        got = resolve_vjp_path(
            p.model, b, k, remat=p.train.remat, use_pallas=True,
            itemsize=itemsize, assume_on_tpu=True,
        )
        assert got == want

    def test_grad_auto_in_batched_long_row_region_matches_dense(self):
        """The smallest shape the batched long-row branch admits (B=8,
        n=1024, tile 256): `auto` must reach the one-sweep kernels through
        the public entry point and agree with the forced dense side."""
        from glom_tpu.kernels import fused_consensus_update
        from glom_tpu.kernels.consensus_update import _use_blockwise_bwd

        L, B, side, d = 2, 8, 32, 128
        n = side * side
        assert _use_blockwise_bwd((L, B, n, d), side, 7.0, "auto", 4)
        levels, bu, td = self._rand(jax.random.PRNGKey(11), L, B, n, d)

        def loss(impl):
            def f(lv, b_, t_):
                out = fused_consensus_update(
                    lv, b_, t_, side=side, radius=7.0, interpret=True,
                    bwd_impl=impl,
                )
                return jnp.mean(out ** 2)
            return f

        grad_auto = jax.grad(loss("auto"), argnums=(0, 1, 2))
        traced = str(jax.make_jaxpr(grad_auto)(levels, bu, td))
        assert "consensus_update_fwd" in traced
        assert "consensus_update_bwd_onesweep" in traced
        g1 = grad_auto(levels, bu, td)
        g2 = jax.grad(loss("dense"), argnums=(0, 1, 2))(levels, bu, td)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
            )

    def test_top_level_divisor_and_zero_topdown(self):
        """Top level must ignore td entirely and divide by 3 (reference
        :121-122/:130): poisoning td's clamped top tile must not change out."""
        from glom_tpu.kernels import fused_consensus_update

        L, B, side, d = 3, 1, 4, 128
        n = side * side
        levels, bu, td = self._rand(jax.random.PRNGKey(3), L, B, n, d)
        out1 = fused_consensus_update(
            levels, bu, td, side=side, interpret=True
        )
        td_poison = td.at[-1].set(1e6)
        out2 = fused_consensus_update(
            levels, bu, td_poison, side=side, interpret=True
        )
        # top level identical (never reads td), level L-2 changes
        np.testing.assert_allclose(
            np.asarray(out1[-1]), np.asarray(out2[-1]), rtol=0, atol=0
        )
        assert not np.allclose(np.asarray(out1[-2]), np.asarray(out2[-2]))


def _mask_cases():
    """(side, radius, tile_a, tile_b, origin_a, origin_b): a-axis = a score
    tile's second-to-last dim, b-axis its last. side 32 / tile 256 at tile
    offsets -1, 0, +1 and both edges of the row; side 24 / tile 64 (a tile
    that is no multiple of `side`, origins that are no multiple either);
    side 16 with the forward's 128 x 256 tiles."""
    geometries = (
        [(32, 256, 256, 256 * i, 256 * j)
         for i, j in ((1, 0), (1, 1), (1, 2), (0, 0), (0, 1), (3, 3), (3, 2))]
        + [(24, 64, 64, 64 * i, 64 * j)
           for i, j in ((0, 0), (1, 0), (4, 3), (4, 4), (4, 5), (8, 8), (8, 6))]
        + [(16, 128, 256, 0, 0), (16, 128, 256, 128, 0)]
    )
    return [
        pytest.param(side, radius, ta, tb, oa, ob,
                     id=f"side{side}-r{radius}-{ta}x{tb}-at{oa},{ob}")
        for side, ta, tb, oa, ob in geometries
        for radius in (0.0, 1.0, 7.0, 7.5)
    ]


class TestConsensusMaskPredicate:
    """_apply_masks takes the local-window mask from per-row and per-column
    grid coordinates (PR 30). It is still the reference's predicate, tile by
    tile: ops/consensus.build_local_mask's block, and the diagonal."""

    @pytest.mark.parametrize("transposed", [False, True], ids=["s", "s2"])
    @pytest.mark.parametrize("attend_self", [False, True], ids=["noself", "self"])
    @pytest.mark.parametrize(
        "side,radius,tile_a,tile_b,origin_a,origin_b", _mask_cases()
    )
    def test_tile_equals_reference_block(
        self, side, radius, tile_a, tile_b, origin_a, origin_b, attend_self,
        transposed,
    ):
        from glom_tpu.kernels.consensus_update import (
            _NEG_MAX, _apply_masks, _tile_ids,
        )
        from glom_tpu.ops.consensus import build_local_mask
        from glom_tpu.utils.helpers import TOKEN_ATTEND_SELF_VALUE

        if transposed:  # the dkv / one-sweep kernels' s2: keys on the a-axis
            tile_a, tile_b = tile_b, tile_a
            origin_a, origin_b = origin_b, origin_a
        a = np.arange(origin_a, origin_a + tile_a)
        b = np.arange(origin_b, origin_b + tile_b)
        want = np.ones((tile_a, tile_b), np.float32)
        if not attend_self:
            want[a[:, None] == b[None, :]] = TOKEN_ATTEND_SELF_VALUE
        far = build_local_mask(side, radius)
        if far is not None:
            want[far[np.ix_(a, b)]] = _NEG_MAX

        got = _apply_masks(
            jnp.ones((1, tile_a, tile_b), jnp.float32),
            _tile_ids(origin_a, tile_a, 0), _tile_ids(origin_b, tile_b, 1),
            side=side, radius=radius, attend_self=attend_self,
        )
        np.testing.assert_array_equal(np.asarray(got[0]), want)


def _eqns(jaxpr):
    """Every equation of a traced program, nested programs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


class TestConsensusMaskCost:
    """The CPU's guard on what only the chip can time (PR 30): in the traced
    programs of the two n=1024 kernels no integer division runs on a score
    tile, and the radius mask adds at most 12 tile-shaped equations to the
    radius-0 build (it added 40)."""

    # d = 128, not the cell's 512: the forward's radius-0 build takes a
    # j-tile of 512, and no [tile, d] block may pass for a score tile
    L, B, n, d, side = 2, 1, 1024, 128, 32

    def _tile_eqns(self, kernel, radius):
        from glom_tpu.kernels import consensus_update as cu

        L, B, n, d = self.L, self.B, self.n, self.d
        bf16, f32 = jnp.bfloat16, jnp.float32
        lv = jax.ShapeDtypeStruct((L, B, n, d), bf16)
        td = jax.ShapeDtypeStruct((L - 1, B, n, d), bf16)
        st = jax.ShapeDtypeStruct((L, B, n, 1), f32)
        kw = dict(
            side=self.side, radius=radius, attend_self=False, interpret=True
        )
        tile = cu._pick_tile(n)
        if kernel == "consensus_update_bwd_onesweep":
            traced = jax.make_jaxpr(
                lambda x, g, m, l, c: cu._consensus_bwd_onesweep(
                    x, g, m, l, c, **kw
                )
            )(lv, lv, st, st, lv)
            shape = (tile, tile)
        else:
            traced = jax.make_jaxpr(
                lambda x, bu, t: cu._forward(
                    x, bu, t, save_stats=True, save_cons=True, **kw
                )
            )(lv, lv, td)
            shape = (tile, cu._pick_tile(n, cap=512 if radius <= 0 else 256))
        return [
            eqn
            for eqn in _eqns(traced.jaxpr)
            if eqn.primitive.name not in ("jit", "pjit")  # wrappers, not work
            and any(
                getattr(o.aval, "shape", ())[-2:] == shape for o in eqn.outvars
            )
        ]

    @pytest.mark.parametrize(
        "kernel", ["consensus_update_bwd_onesweep", "consensus_update_fwd"]
    )
    def test_radius_mask_runs_on_vectors(self, kernel):
        banded = self._tile_eqns(kernel, 7.0)
        assert len(banded) >= 10  # the walk did find the kernel's body
        on_tiles = [e.primitive.name for e in banded]
        assert not {"div", "rem"} & {
            e.primitive.name
            for e in banded
            if any(
                jnp.issubdtype(o.aval.dtype, jnp.integer) for o in e.outvars
            )
        }, on_tiles
        assert len(banded) - len(self._tile_eqns(kernel, 0.0)) <= 12, on_tiles


class TestFusedForwardParity:
    """The use_pallas=True fused level-major forward must match the
    reference-layout path on every contract point (CPU: kernels fall back to
    XLA, so this locks the LAYOUT/plumbing; kernel math is locked above in
    interpret mode and on TPU)."""

    def _cfg(self, **kw):
        from glom_tpu.utils.config import GlomConfig

        base = dict(dim=128, levels=4, image_size=32, patch_size=8)
        base.update(kw)
        return GlomConfig(**base)

    def _run(self, cfg, **kw):
        from glom_tpu.models.core import glom_forward, init_glom

        params = init_glom(jax.random.PRNGKey(0), cfg)
        img = jax.random.normal(jax.random.PRNGKey(1), (2, 3, cfg.image_size, cfg.image_size))
        ref = glom_forward(params, img, cfg, use_pallas=False, **kw)
        fused = glom_forward(params, img, cfg, use_pallas=True, **kw)
        return ref, fused

    def test_forward(self):
        ref, fused = self._run(self._cfg())
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), rtol=2e-4, atol=2e-5)

    def test_return_all_and_radius(self):
        ref, fused = self._run(self._cfg(local_consensus_radius=2), return_all=True, iters=3)
        assert fused.shape == ref.shape  # [T+1, b, n, L, d]
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), rtol=2e-4, atol=2e-5)

    def test_levels_carry_in(self):
        from glom_tpu.models.core import glom_forward, init_glom

        cfg = self._cfg()
        params = init_glom(jax.random.PRNGKey(0), cfg)
        img = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 32, 32))
        lv = glom_forward(params, img, cfg, iters=2)
        ref = glom_forward(params, img, cfg, iters=2, levels=lv, use_pallas=False)
        fused = glom_forward(params, img, cfg, iters=2, levels=lv, use_pallas=True)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), rtol=2e-4, atol=2e-5)

    def test_grad_and_remat(self):
        from glom_tpu.models.core import glom_forward, init_glom

        cfg = self._cfg()
        params = init_glom(jax.random.PRNGKey(0), cfg)
        img = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 32, 32))

        def loss(p, up, rm):
            return jnp.mean(glom_forward(p, img, cfg, iters=2, use_pallas=up, remat=rm) ** 2)

        g_ref = jax.grad(loss)(params, False, False)
        g_fused = jax.grad(loss)(params, True, True)
        for a, b in zip(jax.tree_util.tree_leaves(g_ref), jax.tree_util.tree_leaves(g_fused)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5)


class TestFusedLoop:
    """The hand-rolled whole-loop VJP (kernels/fused_loop.py) vs a reference
    loop composed from the XLA ops (models.core.update_step) — forward and
    EVERY cotangent (both FFWs' weights, pos_emb, tokens, levels0)."""

    L, B, n, d, side = 4, 8, 16, 128, 4

    def _inputs(self, dtype=jnp.float32):
        from glom_tpu.ops.ffw import init_grouped_ffw

        k = jax.random.split(jax.random.PRNGKey(0), 5)
        bu = init_grouped_ffw(k[0], self.L, self.d, 4, dtype)
        td = init_grouped_ffw(k[1], self.L - 1, self.d, 4, dtype)
        pos = jax.random.normal(k[2], (self.n, self.d), dtype)
        tokens = jax.random.normal(k[3], (self.B, self.n, self.d), dtype)
        lv0 = jax.random.normal(k[4], (self.L, self.B, self.n, self.d), dtype)
        return bu, td, pos, tokens, lv0

    def _ref_loop(self, bu_p, td_p, pos, tokens, lv0, iters, radius, attend_self):
        from functools import partial

        from glom_tpu.models.core import contribution_divisor, update_step
        from glom_tpu.ops.consensus import build_local_mask, consensus_attention

        class P:  # update_step only touches these three fields
            bottom_up, top_down, pos_emb = bu_p, td_p, pos

        levels = jnp.transpose(lv0, (1, 2, 0, 3))  # [B, n, L, d]
        bottom = tokens[:, :, None, :]
        pos4 = pos[None, :, None, :]
        div = contribution_divisor(self.L)
        cons = partial(
            consensus_attention,
            attend_self=attend_self,
            local_mask=build_local_mask(self.side, radius),
        )
        for _ in range(iters):
            levels = update_step(P, levels, bottom, pos4, div, consensus_fn=cons)
        return jnp.transpose(levels, (2, 0, 1, 3))

    @pytest.mark.parametrize(
        "radius,attend_self", [(0.0, False), (1.5, False), (0.0, True)]
    )
    def test_forward_and_grads(self, radius, attend_self):
        from glom_tpu.kernels.fused_loop import fused_glom_loop, loop_supported

        assert loop_supported(self.L, self.B, self.n, self.d, 4 * self.d, 4, 3, self.n)
        args = self._inputs()
        iters = 3

        def loss_loop(*a):
            out = fused_glom_loop(
                *a, iters, self.side, radius, attend_self, True
            )
            return jnp.mean(out**2), out

        def loss_ref(*a):
            out = self._ref_loop(*a, iters, radius, attend_self)
            return jnp.mean(out**2), out

        (l1, o1), g1 = jax.value_and_grad(loss_loop, argnums=tuple(range(5)), has_aux=True)(*args)
        (l2, o2), g2 = jax.value_and_grad(loss_ref, argnums=tuple(range(5)), has_aux=True)(*args)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-4, atol=2e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
            )

    def test_single_iteration(self):
        """iters=1 exercises the no-combine backward variant alone."""
        from glom_tpu.kernels.fused_loop import fused_glom_loop

        args = self._inputs()

        def loss_loop(*a):
            return jnp.mean(
                fused_glom_loop(*a, 1, self.side, 0.0, False, True) ** 2
            )

        def loss_ref(*a):
            return jnp.mean(self._ref_loop(*a, 1, 0.0, False) ** 2)

        g1 = jax.grad(loss_loop, argnums=tuple(range(5)))(*args)
        g2 = jax.grad(loss_ref, argnums=tuple(range(5)))(*args)
        for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
            )

    def test_two_levels(self):
        """L=2 exercises the final-combine else branch (no middle slice)."""
        from glom_tpu.kernels.fused_loop import fused_glom_loop
        from glom_tpu.ops.ffw import init_grouped_ffw

        L, B, n, d = 2, 8, 16, 128
        k = jax.random.split(jax.random.PRNGKey(7), 5)
        args = (
            init_grouped_ffw(k[0], L, d, 4),
            init_grouped_ffw(k[1], L - 1, d, 4),
            jax.random.normal(k[2], (n, d)),
            jax.random.normal(k[3], (B, n, d)),
            jax.random.normal(k[4], (L, B, n, d)),
        )
        old_L = type(self).L
        type(self).L = L
        try:
            def loss_loop(*a):
                return jnp.mean(
                    fused_glom_loop(*a, 2, self.side, 0.0, False, True) ** 2
                )

            def loss_ref(*a):
                return jnp.mean(self._ref_loop(*a, 2, 0.0, False) ** 2)

            g1 = jax.grad(loss_loop, argnums=tuple(range(5)))(*args)
            g2 = jax.grad(loss_ref, argnums=tuple(range(5)))(*args)
        finally:
            type(self).L = old_L
        for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
            )

    def test_zero_iters_not_dispatched(self):
        from glom_tpu.kernels.fused_loop import loop_supported

        assert not loop_supported(6, 64, 256, 512, 2048, 2, 0, 256)

    def test_primal_matches_vjp_forward(self):
        """The no-grad primal (plain [L]-carry body) and the vjp forward
        (the [L+1]-slot body) are different computations of the same math —
        both must match the reference."""
        from glom_tpu.kernels.fused_loop import fused_glom_loop

        args = self._inputs()
        primal = fused_glom_loop(*args, 3, self.side, 0.0, False, True)
        ref = self._ref_loop(*args, 3, 0.0, False)
        np.testing.assert_allclose(
            np.asarray(primal), np.asarray(ref), rtol=2e-4, atol=2e-5
        )

    def test_dispatch_gate(self):
        """loop_supported must reject the shapes the kernels cannot tile."""
        from glom_tpu.kernels.fused_loop import loop_supported

        ok = loop_supported(6, 64, 256, 512, 2048, 2, 7, 256)
        assert ok  # the flagship training shape
        assert not loop_supported(6, 64, 1024, 512, 2048, 2, 7, 1024)  # n too big
        assert not loop_supported(6, 1, 6, 512, 2048, 2, 7, 6)  # untileable M
        assert not loop_supported(6, 64, 256, 512, 2048, 2, 7, 128)  # pos mismatch

    # The local-mask radius exercises the identical remat machinery on a
    # different mask — slow-marked for the tier-1 budget; CI runs it.
    @pytest.mark.parametrize(
        "radius", [0.0, pytest.param(1.5, marks=pytest.mark.slow)]
    )
    def test_remat_matches_nonremat(self, radius):
        """remat=True drops the pre-activation residuals and recomputes them
        in the backward via the first-matmul-only kernel — the SAME
        f32-accumulate dot + cast the forward would have saved, so every
        cotangent must match the non-remat VJP bit-exactly."""
        from glom_tpu.kernels.fused_loop import fused_glom_loop

        args = self._inputs()

        def loss(remat):
            def f(*a):
                return jnp.mean(
                    fused_glom_loop(
                        *a, 3, self.side, radius, False, True, remat
                    )
                    ** 2
                )

            return f

        g0 = jax.grad(loss(False), argnums=tuple(range(5)))(*args)
        g1 = jax.grad(loss(True), argnums=tuple(range(5)))(*args)
        for a, b in zip(
            jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # Env-gated special mode, ~25-35s of interpret-mode backward: slow-
    # marked for the tier-1 budget; CI runs it unfiltered and
    # tpu_validate.py covers the real-chip variant.
    @pytest.mark.slow
    def test_unchained_backward_matches(self, monkeypatch):
        """The unchained backward variant (pod per-TP-rank d=1024-class
        shapes, where in-kernel accumulator chaining exceeds the
        working-set budget) must produce the same cotangents as the
        chained flagship variant — same kernels' math, the cross-iteration
        dw/da accumulation just moves to XLA adds."""
        from glom_tpu.kernels import fused_loop

        args = self._inputs()

        def loss(*a):
            return jnp.mean(
                fused_loop.fused_glom_loop(*a, 3, self.side, 0.0, False, True)
                ** 2
            )

        g_chained = jax.grad(loss, argnums=tuple(range(5)))(*args)
        monkeypatch.setattr(fused_loop, "_chain_ws_ok", lambda *a: False)
        g_unchained = jax.grad(loss, argnums=tuple(range(5)))(*args)
        for a, b in zip(
            jax.tree_util.tree_leaves(g_chained),
            jax.tree_util.tree_leaves(g_unchained),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            )

    def test_pod_per_rank_shape_admitted(self):
        """BASELINE config 5's per-TP-rank shape (L=12, d=1024, f/mp=2048,
        batch 16, remat) must ride the fused loop via the unchained
        backward — the regime round 4 left on the scan path."""
        from glom_tpu.kernels.fused_loop import _chain_ws_ok, loop_supported

        assert loop_supported(12, 16, 256, 1024, 2048, 2, 13, 256, remat=True)
        # ...through the unchained variant specifically:
        from glom_tpu.kernels.grouped_mlp import _pick_bwd_tile

        bt = _pick_bwd_tile(16 * 256, 1024, 2048, 2)
        assert bt is not None and not _chain_ws_ok(bt, 1024, 2048, 2, 256)
        # the flagship stays on the (measured-faster) chained variant
        bt_f = _pick_bwd_tile(64 * 256, 512, 2048, 2)
        assert _chain_ws_ok(bt_f, 512, 2048, 2, 256)

    def test_remat_admits_bigger_residuals(self):
        """The remat residual stack (carry + stats only) fits shapes the
        full stack cannot: flagship batch 128 x 12 iters is 20.6GB of
        non-remat residuals (> the 10GB budget) but 2.8GB under remat —
        BASELINE config 5's regime rides the fused loop now."""
        from glom_tpu.kernels.fused_loop import loop_supported

        assert not loop_supported(6, 128, 256, 512, 2048, 2, 12, 256)
        assert loop_supported(6, 128, 256, 512, 2048, 2, 12, 256, remat=True)


@pytest.mark.parametrize("package", ["kernels", "models", "train"])
def test_no_program_module_reads_the_environment(package):
    """What a step computes is decided by its arguments and shapes alone:
    the last GLOM_* switch (it chose a second grid layout of the
    whole-loop VJP) went in PR 29 as the consensus one did in PR 26.
    Deployment settings (mesh.py, startup.py) live in other packages."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent / "glom_tpu" / package
    readers = [
        f"{path.relative_to(root.parent)}:{node.lineno}"
        for path in sorted(root.rglob("*.py"))
        for node in ast.walk(ast.parse(path.read_text()))
        if (isinstance(node, ast.Attribute) and node.attr in ("environ", "getenv")
            and isinstance(node.value, ast.Name) and node.value.id == "os")
        or (isinstance(node, ast.ImportFrom) and node.module == "os"
            and any(a.name in ("environ", "getenv") for a in node.names))
    ]
    assert readers == []
