"""TPU-side validation: the checks the CPU test suite must skip.

The pytest suite (tests/) runs on a forced-CPU virtual mesh, where bf16
dots don't exist and Pallas runs in interpret mode — so bf16 kernel
parity and real-Mosaic compilation are asserted here, on hardware, and
the outcome is committed as `results/tpu_validation.jsonl`.

Run: `python tpu_validate.py` on a TPU host. Exits nonzero on any failure
and off TPU (nothing here means anything on another platform); writes one
JSON record per check plus a summary line.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


RESULTS = []


class _Skipped(Exception):
    """Raise inside a check to record it as passed-but-skipped (e.g. a
    multi-device check on a 1-chip environment)."""


def check(name):
    def deco(fn):
        def run():
            t0 = time.time()
            try:
                fn()
                rec = {"check": name, "ok": True}
            except _Skipped as e:
                rec = {"check": name, "ok": True, "skipped": True,
                       "reason": str(e)}
            except Exception as e:  # noqa: BLE001 - record and continue
                rec = {"check": name, "ok": False, "error": f"{type(e).__name__}: {e}"[:300]}
            rec["seconds"] = round(time.time() - t0, 1)
            RESULTS.append(rec)
            print(json.dumps(rec), flush=True)
        return run
    return deco


def _bf16_tree(t):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), t)


@check("grouped_ffw_bf16_forward_parity")
def check_ffw_fwd():
    from glom_tpu.kernels import fused_grouped_ffw
    from glom_tpu.ops.ffw import grouped_ffw, init_grouped_ffw

    params = _bf16_tree(init_grouped_ffw(jax.random.PRNGKey(0), 6, 512, mult=4))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256, 6, 512), jnp.bfloat16)
    got = fused_grouped_ffw(params, x)
    want = grouped_ffw(params, x)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
    )


@check("grouped_ffw_bf16_grad_parity_multitile")
def check_ffw_grad():
    from glom_tpu.kernels import fused_grouped_ffw
    from glom_tpu.ops.ffw import grouped_ffw, init_grouped_ffw

    params = _bf16_tree(init_grouped_ffw(jax.random.PRNGKey(0), 4, 128, mult=4))
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 256, 4, 128), jnp.bfloat16)

    def lf(p, x_):
        return jnp.mean(fused_grouped_ffw(p, x_).astype(jnp.float32) ** 2)

    def lx(p, x_):
        return jnp.mean(grouped_ffw(p, x_).astype(jnp.float32) ** 2)

    g1 = jax.jit(jax.grad(lf, argnums=(0, 1)))(params, x)
    g2 = jax.jit(jax.grad(lx, argnums=(0, 1)))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=0.1, atol=2e-3
        )


def _consensus_case(side, radius, dtype, rtol, atol, grad, bwd_impl="blockwise"):
    """grad checks default to FORCING the blockwise kernels — under 'auto'
    the measured-crossover dispatch would route these shapes to the dense
    VJP and the Pallas backward would go unvalidated on hardware."""
    from glom_tpu.kernels.consensus_update import _fused, _xla_reference

    L, B, d = 6, 2, 512
    n = side * side
    ks = jax.random.split(jax.random.PRNGKey(side + int(radius)), 3)
    levels = jax.random.normal(ks[0], (L, B, n, d), dtype)
    bu = jax.random.normal(ks[1], (L, B, n, d), dtype)
    td = jax.random.normal(ks[2], (L - 1, B, n, d), dtype)

    if grad:
        def lf(lv, b_, t_):
            return jnp.mean(
                _fused(lv, b_, t_, side, radius, False, False, bwd_impl)
                .astype(jnp.float32) ** 2
            )

        def lr(lv, b_, t_):
            return jnp.mean(
                _xla_reference(lv, b_, t_, side=side, radius=radius, attend_self=False).astype(jnp.float32) ** 2
            )

        got = jax.jit(jax.grad(lf, argnums=(0, 1, 2)))(levels, bu, td)
        want = jax.jit(jax.grad(lr, argnums=(0, 1, 2)))(levels, bu, td)
        for a, b in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=rtol, atol=atol
            )
    else:
        got = jax.jit(lambda *a: _fused(*a, side, radius, False, False))(levels, bu, td)
        want = _xla_reference(levels, bu, td, side=side, radius=radius, attend_self=False)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=rtol, atol=atol
        )


@check("grouped_ffw_bf16_add_fold_parity")
def check_ffw_add_fold():
    """The folded positional addend (add=) must equal the explicit
    x + tile(add) composition — forward AND all grads including da (the
    pos-emb cotangent reduced in-kernel across the whole grid)."""
    from glom_tpu.kernels import fused_grouped_ffw_lm
    from glom_tpu.ops.ffw import init_grouped_ffw

    G, b, n, d = 5, 4, 256, 512
    M = b * n
    params = _bf16_tree(init_grouped_ffw(jax.random.PRNGKey(0), G, d, mult=4))
    x = jax.random.normal(jax.random.PRNGKey(1), (G, M, d), jnp.bfloat16)
    a = jax.random.normal(jax.random.PRNGKey(2), (n, d), jnp.bfloat16)

    def loss_fold(p, x_, a_):
        out = fused_grouped_ffw_lm(p, x_, add=a_)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    def loss_explicit(p, x_, a_):
        xa = x_ + jnp.tile(a_, (M // n, 1))[None]
        out = fused_grouped_ffw_lm(p, xa)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    v1, g1 = jax.jit(jax.value_and_grad(loss_fold, argnums=(0, 1, 2)))(
        params, x, a
    )
    v2, g2 = jax.jit(jax.value_and_grad(loss_explicit, argnums=(0, 1, 2)))(
        params, x, a
    )
    np.testing.assert_allclose(float(v1), float(v2), rtol=2e-3)
    for t1, t2 in zip(
        jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)
    ):
        np.testing.assert_allclose(
            np.asarray(t1, np.float32), np.asarray(t2, np.float32),
            rtol=5e-2, atol=5e-2,
        )


@check("consensus_bf16_forward_parity_n256")
def check_cons_fwd_256():
    _consensus_case(16, 0.0, jnp.bfloat16, 5e-2, 5e-2, grad=False)


@check("consensus_bf16_forward_parity_n1024_radius7")
def check_cons_fwd_1024():
    _consensus_case(32, 7.0, jnp.bfloat16, 5e-2, 5e-2, grad=False)


@check("consensus_f32_grad_parity_n256")
def check_cons_grad_f32():
    _consensus_case(16, 0.0, jnp.float32, 2e-3, 2e-5, grad=True)


@check("consensus_bf16_grad_parity_n1024")
def check_cons_grad_bf16():
    _consensus_case(32, 0.0, jnp.bfloat16, 0.1, 2e-2, grad=True)


@check("consensus_bf16_grad_parity_n1024_radius7")
def check_cons_grad_bf16_r7():
    _consensus_case(32, 7.0, jnp.bfloat16, 0.1, 2e-2, grad=True)


@check("consensus_bf16_grad_dispatch_auto_n1024")
def check_cons_grad_auto():
    """The 'auto' dispatch side (dense VJP at this shape) on hardware."""
    _consensus_case(32, 0.0, jnp.bfloat16, 0.1, 2e-2, grad=True, bwd_impl="auto")


@check("fused_loop_bf16_grad_parity")
def check_fused_loop_grads():
    """The hand-rolled whole-loop VJP (kernels/fused_loop.py) vs the
    XLA-composed reference loop, in bf16 on real Mosaic: forward and every
    cotangent (FFW weights, pos_emb, tokens, levels0)."""
    from functools import partial

    from glom_tpu.kernels.fused_loop import fused_glom_loop, loop_supported
    from glom_tpu.models.core import contribution_divisor, update_step
    from glom_tpu.ops.consensus import build_local_mask, consensus_attention
    from glom_tpu.ops.ffw import init_grouped_ffw

    L, B, n, d, side, iters = 6, 8, 256, 512, 16, 3
    assert loop_supported(L, B, n, d, 4 * d, 2, iters, n)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    bu = _bf16_tree(init_grouped_ffw(k[0], L, d, 4))
    td = _bf16_tree(init_grouped_ffw(k[1], L - 1, d, 4))
    pos = jax.random.normal(k[2], (n, d), jnp.bfloat16)
    tokens = jax.random.normal(k[3], (B, n, d), jnp.bfloat16)
    lv0 = jax.random.normal(k[4], (L, B, n, d), jnp.bfloat16)

    def loss_loop(*a):
        return jnp.mean(
            fused_glom_loop(*a, iters, side, 0.0, False, False).astype(
                jnp.float32
            )
            ** 2
        )

    def loss_ref(bu_p, td_p, pos_, tokens_, lv0_):
        class P:
            bottom_up, top_down, pos_emb = bu_p, td_p, pos_

        cons = partial(
            consensus_attention,
            attend_self=False,
            local_mask=build_local_mask(side, 0.0),
        )
        levels = jnp.transpose(lv0_, (1, 2, 0, 3))
        bottom = tokens_[:, :, None, :]
        div = contribution_divisor(L)
        for _ in range(iters):
            levels = update_step(
                P, levels, bottom, pos_[None, :, None, :], div,
                consensus_fn=cons,
            )
        return jnp.mean(jnp.transpose(levels, (2, 0, 1, 3)).astype(jnp.float32) ** 2)

    args = (bu, td, pos, tokens, lv0)
    g1 = jax.jit(jax.grad(loss_loop, argnums=tuple(range(5))))(*args)
    g2 = jax.jit(jax.grad(loss_ref, argnums=tuple(range(5))))(*args)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.1, atol=3e-3,
        )


def _fused_loop_args(key=0):
    from glom_tpu.ops.ffw import init_grouped_ffw

    L, B, n, d = 6, 8, 256, 512
    k = jax.random.split(jax.random.PRNGKey(key), 5)
    return (
        _bf16_tree(init_grouped_ffw(k[0], L, d, 4)),
        _bf16_tree(init_grouped_ffw(k[1], L - 1, d, 4)),
        jax.random.normal(k[2], (n, d), jnp.bfloat16),
        jax.random.normal(k[3], (B, n, d), jnp.bfloat16),
        jax.random.normal(k[4], (L, B, n, d), jnp.bfloat16),
    )


@check("fused_loop_primal_vs_vjp_forward")
def check_fused_loop_primal_vs_vjp_forward():
    """The no-grad primal (plain [L]-carry body) and the VJP forward (the
    [L+1]-slot body) are SEPARATE computations of the same math, kept
    equal only by tests (the 2% forward-bench split, fused_loop.py) — this
    pins their parity on real Mosaic explicitly, not as a side effect of
    the grad check (round-4 weak #4)."""
    from glom_tpu.kernels.fused_loop import fused_glom_loop

    args = _fused_loop_args()
    primal = jax.jit(
        lambda *a: fused_glom_loop(*a, 3, 16, 0.0, False, False)
    )(*args)

    def via_vjp(*a):
        out, _ = jax.vjp(
            lambda bu, td, pos, tok, lv: fused_glom_loop(
                bu, td, pos, tok, lv, 3, 16, 0.0, False, False
            ),
            *a,
        )
        return out

    vjp_fwd = jax.jit(via_vjp)(*args)
    np.testing.assert_allclose(
        np.asarray(primal, np.float32), np.asarray(vjp_fwd, np.float32),
        rtol=2e-2, atol=2e-3,
    )


@check("fused_loop_remat_grad_parity")
def check_fused_loop_remat_grads():
    """remat=True (recompute-per-iteration backward, BASELINE config 5's
    regime on the fused loop) vs remat=False on real Mosaic: the
    recomputed pre-activations run the same f32-accumulate matmul the
    forward would have saved, so the cotangents must agree tightly."""
    from glom_tpu.kernels.fused_loop import fused_glom_loop, loop_supported

    assert loop_supported(6, 8, 256, 512, 2048, 2, 3, 256, remat=True)
    args = _fused_loop_args(1)

    def loss(remat):
        def f(*a):
            return jnp.mean(
                fused_glom_loop(*a, 3, 16, 0.0, False, False, remat).astype(
                    jnp.float32
                )
                ** 2
            )

        return f

    g0 = jax.jit(jax.grad(loss(False), argnums=tuple(range(5))))(*args)
    g1 = jax.jit(jax.grad(loss(True), argnums=tuple(range(5))))(*args)
    for a, b in zip(
        jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-4, atol=1e-6,
        )


@check("banded_ragged_consensus_parity")
def check_banded_consensus():
    """The streaming banded kernel (kernels/banded_consensus.py, reached
    by ragged_attention="banded-pallas") on real Mosaic at the flagship
    page shape: f32 (the dtype its dots run in) and bf16 storage, mixed
    row lengths with intra-row pads, vs the jnp banded route on every
    row's valid span."""
    from glom_tpu.kernels import banded_ragged_consensus
    from glom_tpu.serve.early_exit import banded_ragged_consensus_attention

    pt, L, d = 64, 6, 512
    counts = [256, 100, 64, 196]
    pages = [-(-c // pt) for c in counts]
    T = sum(pages) * pt
    row_start = np.zeros((T,), np.int32)
    row_len = np.zeros((T,), np.int32)
    starts, off = [], 0
    for c, k in zip(counts, pages):
        s0 = off * pt
        starts.append(s0)
        row_start[s0:s0 + k * pt] = s0
        row_len[s0:s0 + k * pt] = c
        off += k
    kw = dict(
        row_start=jnp.asarray(row_start), row_len=jnp.asarray(row_len),
        window=max(pages) * pt, page_tokens=pt,
    )
    for dtype, tol in ((jnp.float32, 2e-2), (jnp.bfloat16, 5e-2)):
        # x8: logits of std ~0.35, so the softmax is not the uniform
        # average a unit-scale state gives (which any kernel would match).
        lv = 8.0 * jax.random.normal(jax.random.PRNGKey(0), (T, L, d))
        lv = lv.astype(dtype)
        got = jax.jit(lambda x: banded_ragged_consensus(x, **kw))(lv)
        with jax.default_matmul_precision("highest"):
            want = banded_ragged_consensus_attention(lv, **kw)
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        for c, s0 in zip(counts, starts):
            err = np.max(np.abs(got[s0:s0 + c] - want[s0:s0 + c]))
            scale = np.max(np.abs(want[s0:s0 + c]))
            assert err <= tol * scale, (dtype.__name__, c, err, scale)


@check("tp_composition_megatron_psum")
def check_tp_composition():
    """TP x Pallas on REAL hardware: the manual-region Megatron psum
    (parallel/manual.py) composed with the fused kernels, vs single-device
    training from identical state/data. Needs >= 2 devices (the four-chip
    host); on one chip it records 'skipped' and the summary counts it."""
    if len(jax.devices()) < 2:
        raise _Skipped("1 device visible; TP needs >= 2")
    from glom_tpu.parallel import DistributedTrainer
    from glom_tpu.train.trainer import Trainer
    from glom_tpu.utils.config import GlomConfig, MeshConfig, TrainConfig

    cfg = GlomConfig(dim=256, levels=4, image_size=32, patch_size=4)
    tcfg = TrainConfig(batch_size=8, learning_rate=3e-4,
                       compute_dtype="bfloat16", use_pallas=True)
    single = Trainer(cfg, tcfg)
    dist = DistributedTrainer(
        cfg, tcfg, MeshConfig(data=1, seq=1, model=2), tp_axis="hidden"
    )
    assert dist.use_manual, "TP check fell off the manual fused path"
    img = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (8, 3, 32, 32), jnp.float32)
    )
    for i in range(4):
        m1 = single.step(jnp.asarray(img))
        m2 = dist.step(img)
        rel = abs(float(m1["loss"]) - float(m2["loss"])) / max(
            abs(float(m1["loss"])), 1e-9
        )
        assert rel < 5e-2, (i, float(m1["loss"]), float(m2["loss"]))


@check("train_step_bf16_loss_decreases")
def check_train():
    from glom_tpu.train.trainer import create_train_state, make_train_step
    from glom_tpu.utils.config import GlomConfig, TrainConfig

    cfg = GlomConfig(dim=256, levels=4, image_size=64, patch_size=8)
    tcfg = TrainConfig(batch_size=8, learning_rate=3e-4,
                       compute_dtype="bfloat16", use_pallas=True)
    state, optimizer = create_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg, optimizer))
    img = jax.random.normal(jax.random.PRNGKey(1), (8, 3, 64, 64), jnp.float32)
    losses = []
    for i in range(8):
        state, m = step(state, img, jax.random.fold_in(jax.random.PRNGKey(2), i))
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


@check("train_step_bf16_pallas_vs_xla_trajectory")
def check_train_cross_path():
    """The production (Pallas, level-major, save-pre backward) train step
    and the plain-XLA step must produce closely tracking bf16 loss
    trajectories from identical state/data/noise — a whole-step cross-path
    guard the CPU suite cannot run (no real bf16 dots there)."""
    from glom_tpu.train.trainer import create_train_state, make_train_step
    from glom_tpu.utils.config import GlomConfig, TrainConfig

    cfg = GlomConfig(dim=256, levels=4, image_size=64, patch_size=8)
    img = jax.random.normal(jax.random.PRNGKey(1), (8, 3, 64, 64), jnp.float32)

    def run(use_pallas):
        tcfg = TrainConfig(batch_size=8, learning_rate=3e-4,
                           compute_dtype="bfloat16", use_pallas=use_pallas,
                           scan_unroll=use_pallas)
        state, optimizer = create_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        step = jax.jit(make_train_step(cfg, tcfg, optimizer))
        losses = []
        for i in range(6):
            state, m = step(state, img, jax.random.fold_in(jax.random.PRNGKey(2), i))
            losses.append(float(m["loss"]))
        return losses

    lp, lx = run(True), run(False)
    assert all(np.isfinite(lp)) and all(np.isfinite(lx)), (lp, lx)
    worst = max(abs(a - b) / max(abs(b), 1e-9) for a, b in zip(lp, lx))
    assert worst < 5e-2, (worst, lp, lx)


def main():
    from glom_tpu.utils.startup import enable_compile_cache, require_tpu

    enable_compile_cache()
    dev = require_tpu("tpu_validate.py")
    for fn in (
        check_ffw_fwd, check_ffw_grad, check_ffw_add_fold,
        check_cons_fwd_256, check_cons_fwd_1024,
        check_cons_grad_f32, check_cons_grad_bf16, check_cons_grad_bf16_r7,
        check_cons_grad_auto,
        check_fused_loop_grads,
        check_fused_loop_primal_vs_vjp_forward,
        check_fused_loop_remat_grads,
        check_banded_consensus,
        check_tp_composition,
        check_train, check_train_cross_path,
    ):
        fn()
    ok = all(r["ok"] for r in RESULTS)
    summary = {
        "summary": True,
        "device_kind": dev["kind"],
        "device_count": dev["count"],
        "jax": jax.__version__,
        "passed": sum(r["ok"] for r in RESULTS),
        "skipped": sum(bool(r.get("skipped")) for r in RESULTS),
        "total": len(RESULTS),
    }
    print(json.dumps(summary), flush=True)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "tpu_validation.jsonl"), "w") as f:
        for rec in RESULTS + [summary]:
            f.write(json.dumps(rec) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
