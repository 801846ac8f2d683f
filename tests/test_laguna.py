"""The Laguna language model (models/laguna.py) against its plain reference
(benchmark/reference/laguna_ref.py) at a size the CPU holds: hidden 64, five
layers (full + dense, three sliding, full; 4 and 8 query heads over 2 KV
heads of 16), window 16, 80 tokens, 4 of 16 experts held. The loss and every
gradient leaf; the shares of every chip adding up to the uncut layer with the
shared expert counted once; causality and the window's edge; the rotary
frequencies against the closed form and the untouched half under the partial
rotation; the gate, the routed scaling, the YaRN ramp and the partial rotation
each missed by a program without them; the uncut model's 33.44B parameters.

The query block is cut to 16 and the key block to 8 for these tests, so that
80 tokens are five query blocks and the window layers slice keys away.

Tolerances: float32 against the float32 reference differs by summation order
only (5e-5 of a leaf's scale). In bfloat16 the program rounds every product's
operands and the residual stream to 8 bits of mantissa, which over five
layers comes to under 3% of a gradient leaf's scale; the experts' and the
router's leaves get 25%, because a routing choice is discontinuous and a few
of 160 tokens choose otherwise after rounding.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_laguna as wl
from benchmark.reference import laguna_ref as ref
from glom_tpu.models import hybrid_lm, laguna
from glom_tpu.utils.config import LagunaConfig
from glom_tpu.utils.presets import get_preset

TINY = get_preset("laguna-tiny").model
FULL = get_preset("laguna-xs2-ep8vp8").model
F32_TOL, BF16_TOL, BF16_ROUTED_TOL = 5e-5, 0.03, 0.25


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(hybrid_lm, "ATTN_QUERY_BLOCK", 16)
    monkeypatch.setattr(hybrid_lm, "ATTN_KEY_BLOCK", 8)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    monkeypatch.setattr(ref, "EXPERT_ROWS", 32)


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def ids_for(cfg, seed=0, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, cfg.seq_len), 0, cfg.vocab_size)


def program_grads(cfg, w, ids, dtype=None, remat=True, loss=laguna.lm_loss):
    (value, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: loss(p, ids, cfg, compute_dtype=dtype, remat=remat),
        has_aux=True))(wl.to_program_params(w))
    return float(value), wl.from_program_params(grads), counters


def worst_leaf(grads, want):
    scale = float(np.median([np.linalg.norm(v) for v in want.values()]))
    return max((float(np.linalg.norm(np.asarray(grads[name], np.float32) - np.asarray(want[name])))
                / max(float(np.linalg.norm(want[name])), scale), name) for name in want)


# ------------------------------------------------- the stack against the reference


def test_the_loss_and_every_gradient_leaf_match_the_reference():
    model = dataclasses.asdict(TINY)
    w, ids = wl.make_weights(3, model), ids_for(TINY)
    loss, grads, counters = program_grads(TINY, w, ids)
    want_loss, want, chosen = ref.loss_and_grads(w, ids, model)
    assert abs(loss - float(want_loss)) < 1e-5 * float(want_loss)
    assert set(grads) == set(want) == set(wl.shapes(model))
    assert worst_leaf(grads, want)[0] < F32_TOL, worst_leaf(grads, want)
    assert all(float(np.linalg.norm(v)) > 0 for v in want.values())   # no leaf is off the path
    # 80 tokens in query blocks of 16, key blocks of 8: a full layer multiplies
    # 2 + 4 + 6 + 8 + 10 key blocks, a window layer 2 + 4 x 4 (15 keys before
    # a block's first and its own 16: 31 keys); two full layers and three
    assert float(counters["attn_key_blocks_full"]) == 30 * 2
    assert float(counters["attn_key_blocks_window"]) == 18 * 3
    # the program's routing is the reference's, and its counters count it
    got = laguna.routing_choices(wl.to_program_params(w), ids, TINY)
    assert np.array_equal(np.sort(np.asarray(got), -1),
                          np.sort(np.stack(chosen).reshape(got.shape), -1))
    here = (np.stack(chosen) >= TINY.expert_offset) & (
        np.stack(chosen) < TINY.expert_offset + TINY.num_experts)
    assert float(counters["moe_pairs_here"]) == pytest.approx(here.sum() / 4)


def test_bfloat16_stays_within_its_band_of_float32():
    w, ids = wl.make_weights(5, dataclasses.asdict(TINY)), ids_for(TINY, 1)
    loss32, g32, _ = program_grads(TINY, w, ids)
    loss16, g16, _ = program_grads(TINY, w, ids, dtype=jnp.bfloat16)
    assert abs(loss16 - loss32) < 2e-3 * loss32
    routed = {name for name in g32 if name.rpartition(".")[2] in (
        "router", "e_gate", "e_up", "e_down")}
    rest = lambda g: {name: v for name, v in g.items() if name not in routed}
    assert worst_leaf(rest(g16), rest(g32))[0] < BF16_TOL, worst_leaf(rest(g16), rest(g32))
    assert worst_leaf(g16, g32)[0] < BF16_ROUTED_TOL, worst_leaf(g16, g32)


def test_recomputation_changes_nothing():
    w, ids = wl.make_weights(7, dataclasses.asdict(TINY)), ids_for(TINY, 2)
    loss_a, grads_a, _ = program_grads(TINY, w, ids, remat=True)
    loss_b, grads_b, _ = program_grads(TINY, w, ids, remat=False)
    assert loss_a == loss_b
    assert max(rel(grads_a[name], grads_b[name]) for name in grads_a) < 1e-6


@pytest.mark.parametrize("dtype, loss_tol, delta_tol", [("float32", 2e-6, 1e-4),
                                                        ("bfloat16", 2e-3, 0.1)])
def test_three_adam_steps_follow_the_reference(dtype, loss_tol, delta_tol):
    """The trainer's own step from the benchmark's weights against the
    reference's three steps: the losses, and the parameters' change where the
    reference vouches for it (`change_compared`)."""
    from glom_tpu.train.trainer import TrainState, default_optimizer, make_train_step
    from glom_tpu.utils.config import TrainConfig

    model = dataclasses.asdict(TINY)
    tcfg = TrainConfig(batch_size=2, learning_rate=3e-4, compute_dtype=dtype, remat=True)
    opt = default_optimizer(tcfg)
    params = wl.to_program_params(wl.make_weights(17, model))
    state = TrainState(params=params, opt_state=opt.init(params), step=jnp.zeros((), jnp.int32))
    step = jax.jit(make_train_step(TINY, tcfg, opt))
    batches = [np.asarray(ids_for(TINY, seed=30 + i)) for i in range(3)]
    losses = []
    for ids in batches:
        state, metrics = step(state, jnp.asarray(ids), jax.random.PRNGKey(0))
        losses.append(float(metrics["loss"]))
    want = ref.train_reference(lambda: wl.make_weights(17, model), batches, model, lr=3e-4)
    assert np.allclose(losses, want["losses"], rtol=loss_tol, atol=0)
    w0 = wl.make_weights(17, model)
    delta = {k: float(jnp.linalg.norm(v - w0[k]))
             for k, v in wl.from_program_params(state.params).items()}
    compared = ref.change_compared(want)
    assert set(compared) == set(want["delta_norms"])   # no gradient here is within Adam's eps
    scale = float(np.median(list(compared.values())))
    worst = max((abs(delta[name] - norm) / max(norm, scale), name)
                for name, norm in compared.items())
    assert worst[0] < delta_tol, worst


# --------------------------------------------- what a wrong program would miss


def without(monkeypatch, what):
    """The program with one piece of the mathematics left out."""
    if what == "the_gate":
        monkeypatch.setattr(laguna, "head_gate", lambda p, u, dtype: jnp.ones(
            u.shape[:-1] + (p["gate"].shape[1],), u.dtype))
    elif what == "the_routed_scaling":
        return dataclasses.replace(TINY, moe_routed_scaling_factor=1.0)
    elif what == "the_yarn_ramp":          # every rotated dimension interpolated
        return dataclasses.replace(TINY, yarn_beta_fast=1e-9, yarn_beta_slow=1e-9 / 2)
    elif what == "the_attention_factor":
        return dataclasses.replace(TINY, yarn_attention_factor=1.0)
    elif what == "the_partial_rotation":   # a full layer's whole head rotated
        return dataclasses.replace(TINY, partial_rotary_factor=1.0)
    return TINY


@pytest.mark.parametrize("what", ["the_gate", "the_routed_scaling", "the_yarn_ramp",
                                  "the_attention_factor", "the_partial_rotation"])
def test_a_program_without_a_piece_of_the_mathematics_misses_the_reference(monkeypatch, what):
    """Each by far more than a sound program's distance (F32_TOL): nothing is
    left out because the result stays inside a tolerance."""
    model = dataclasses.asdict(TINY)
    w, ids = wl.make_weights(11, model), ids_for(TINY, 3)
    want_loss, want, _ = ref.loss_and_grads(w, ids, model)
    wrong = without(monkeypatch, what)
    loss, grads, _ = program_grads(wrong, w, ids)
    assert worst_leaf(grads, want)[0] > 100 * F32_TOL, (what, worst_leaf(grads, want))


# ----------------------------------------------------------------- the rotation


def test_the_yarn_frequencies_are_the_closed_form():
    """Laguna-XS.2's own numbers: 32 frequencies over the 64 rotated
    dimensions; low = floor(5.66) = 5, high = ceil(15.80) = 16; up to j = 5
    the extrapolated theta^(-2j/64), from j = 16 on that over 64, a straight
    line between in the mixing weight."""
    freq, factor = laguna.rope_frequencies("F", FULL)
    assert freq.shape == (32,) and factor == pytest.approx(1.4158883083359672)
    j = np.arange(32)
    f_e = 500000.0 ** (-2.0 * j / 64)
    edge = lambda b: 64 * np.log(4096 / (b * 2 * np.pi)) / (2 * np.log(500000.0))
    assert (np.floor(edge(64)), np.ceil(edge(1))) == (5, 16)
    ramp = np.clip((j - 5) / (16 - 5), 0, 1)
    assert np.allclose(freq, f_e / 64 * ramp + f_e * (1 - ramp), rtol=1e-6)
    assert np.allclose(freq[:6], f_e[:6], rtol=1e-6) and np.allclose(
        freq[16:], f_e[16:] / 64, rtol=1e-6)
    assert f_e[10] / 64 < freq[10] < f_e[10]
    # attention_factor is what the library derives from the factor: 0.1 ln(64) + 1
    assert factor == pytest.approx(0.1 * np.log(64.0) + 1.0)
    sliding, one = laguna.rope_frequencies("S", FULL)
    assert one == 1.0 and np.allclose(sliding, 10000.0 ** (-2.0 * np.arange(64) / 128), rtol=1e-6)
    for kind in "FS":
        want, want_factor = ref.inverse_frequencies(kind, dataclasses.asdict(FULL))
        got, got_factor = laguna.rope_frequencies(kind, FULL)
        assert np.allclose(got, want, rtol=1e-6) and got_factor == want_factor


def test_a_partial_rotation_leaves_the_upper_half_of_a_head_untouched():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 80, 2, 3, 128))
    full, sliding = laguna.rope(x, "F", FULL), laguna.rope(x, "S", FULL)
    assert jnp.array_equal(full[..., 64:], x[..., 64:])
    assert not jnp.any(full[:, 1:, ..., :64] == x[:, 1:, ..., :64])
    assert not jnp.any(sliding[:, 1:, ..., :32] == x[:, 1:, ..., :32])   # the fast dimensions
    # position 0 is rotated by nothing, and scaled by the factor where there is one
    assert jnp.allclose(full[:, 0, ..., :64], x[:, 0, ..., :64] * 1.4158883083359672)
    assert jnp.array_equal(sliding[:, 0], x[:, 0])
    # halves paired: dimension i turns with dimension i + 32, by position x frequency
    freq, factor = laguna.rope_frequencies("F", FULL)
    t, i = 7, 3
    c, s = np.cos(t * freq[i]) * factor, np.sin(t * freq[i]) * factor
    assert np.allclose(full[0, t, 0, 0, i], x[0, t, 0, 0, i] * c - x[0, t, 0, 0, i + 32] * s,
                       atol=1e-5)
    assert np.allclose(full[0, t, 0, 0, i + 32], x[0, t, 0, 0, i + 32] * c + x[0, t, 0, 0, i] * s,
                       atol=1e-5)
    # the scores of two positions depend on their distance alone
    shifted = laguna.rope(jnp.roll(x, 5, axis=1), "F", FULL)
    assert jnp.allclose(jnp.sum(shifted[:, 25, 0, 0, :64] * shifted[:, 15, 1, 0, :64], -1),
                        jnp.sum(full[:, 20, 0, 0, :64] * full[:, 10, 1, 0, :64], -1),
                        rtol=1e-4, atol=1e-4)


def sliced_rope(x, attention, cfg):
    """The rotation as the program wrote it before PR 37, kept as the oracle:
    a head's halves sliced apart, rotated in float32 and concatenated."""
    freq, factor = laguna.rope_frequencies(attention, cfg)
    half = freq.shape[0]
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = (jnp.cos(angle) * factor).reshape(shape), (jnp.sin(angle) * factor).reshape(shape)
    x32 = x.astype(jnp.float32)
    x1, x2, rest = x32[..., :half], x32[..., half:2 * half], x32[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1).astype(x.dtype)


# q of the layer's kind (48 or 64 query heads over 8 KV heads) and k; 200 tokens
# are no multiple of 128
ROPE_SHAPES = {("F", "q"): (2, 200, 8, 6, 128), ("S", "q"): (2, 200, 8, 8, 128),
               ("F", "k"): (2, 200, 8, 128), ("S", "k"): (2, 200, 8, 128)}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kind,which", sorted(ROPE_SHAPES))
def test_the_one_pass_rotation_is_the_sliced_one(kind, which, dtype):
    """Outputs and gradients, op by op on the CPU (under `jit` its compiler
    contracts the two formulas' multiply-adds differently, by one float32
    rounding): bit for bit in bfloat16, where the product with the permutation
    is exact and both round the float32 sum once; within 1e-6 in float32."""
    shape = ROPE_SHAPES[kind, which]
    x = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    dy = jax.random.normal(jax.random.PRNGKey(2), shape).astype(dtype)
    got, got_vjp = jax.vjp(lambda x: laguna.rope(x, kind, FULL), x)
    want, want_vjp = jax.vjp(lambda x: sliced_rope(x, kind, FULL), x)
    (got_dx,), (want_dx,) = got_vjp(dy), want_vjp(dy)
    assert got.dtype == got_dx.dtype == x.dtype and got.shape == got_dx.shape == shape
    if dtype == "bfloat16":
        bits = lambda a: np.asarray(a).view(np.uint16)
        assert np.array_equal(bits(got), bits(want))
        assert np.array_equal(bits(got_dx), bits(want_dx))
    else:
        assert np.allclose(got, want, rtol=0, atol=1e-6)
        assert np.allclose(got_dx, want_dx, rtol=0, atol=1e-6)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


@pytest.mark.parametrize("kind", ["F", "S"])
def test_the_rotation_neither_slices_nor_concatenates_a_head(kind):
    """The pin on structure: forward and transpose are products with a
    [128, 128] permutation and elementwise work over whole heads. A slice, a
    pad (a slice's transpose), a concatenate or a gather of a head's parts is
    what sent float32 arrays through HBM (PERF.md, PR 37)."""
    x = jnp.zeros(ROPE_SHAPES[kind, "q"], jnp.bfloat16)
    forward = jax.make_jaxpr(lambda x: laguna.rope(x, kind, FULL))(x)
    backward = jax.make_jaxpr(lambda x, dy: jax.vjp(lambda x: laguna.rope(x, kind, FULL), x)[1](dy))(x, x)
    # the second traces the forward too, whose output it does not use
    for jaxpr, passes in ((forward, 1), (backward, 2)):
        names = [eqn.primitive.name for eqn in equations(jaxpr.jaxpr)]
        assert names.count("dot_general") == passes, names
        assert not {"slice", "dynamic_slice", "pad", "concatenate", "gather", "scatter-add",
                    "dynamic_update_slice"} & set(names), names
        # bfloat16 at both ends: the only arrays of x's shape in another type are float32
        # values between the product and the rounding
        assert [str(v.aval.dtype) for v in jaxpr.jaxpr.outvars] == ["bfloat16"]


# ------------------------------------------------------- causality, the window


def logits_of(cfg, w):
    @jax.jit
    def logits(ids):
        x, _, _ = laguna.hidden_states(w, ids, cfg)
        return jnp.einsum("btd,dv->btv", laguna.rms_norm(x, w["final_norm"], cfg.rms_norm_eps),
                          w["head"])
    return logits


def test_no_position_sees_a_later_token():
    """Every token from position t on replaced: the logits before t stay what
    they were, bit for bit, and the logits at t do not. t inside a query block
    and at a block's edge."""
    logits = logits_of(TINY, wl.to_program_params(wl.make_weights(23, dataclasses.asdict(TINY))))
    ids = ids_for(TINY, seed=41)
    base = logits(ids)
    for t in (7, 16, 32, 61):
        later = ids.at[:, t:].set((ids[:, t:] + 1 + t) % TINY.vocab_size)
        got = logits(later)
        assert jnp.array_equal(got[:, :t], base[:, :t]), t
        assert not jnp.array_equal(got[:, t], base[:, t]), t


def test_a_window_layers_output_at_t_ignores_tokens_before_its_window():
    """One sliding layer, window 16: its output at t is unchanged, bit for
    bit, when the layer's input before t - 15 changes, and changed when the
    input at t - 15, the oldest key it sees, does. A full layer sees all."""
    p = wl.to_program_params(wl.make_weights(29, dataclasses.asdict(TINY)))["layers"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 80, TINY.hidden_size))
    for kind, layer_p in (("S", p[1]), ("F", p[4])):
        f = jax.jit(lambda x: laguna.attention_mixer(kind, layer_p, x, TINY, None)[0])
        base = f(x)
        for t in (20, 47, 79):
            edge = t - TINY.sliding_window + 1                      # the oldest key seen
            before = f(x.at[:, :edge].add(1.0))
            at_edge = f(x.at[:, edge].add(1.0))
            assert jnp.array_equal(before[:, t], base[:, t]) == (kind == "S"), (kind, t)
            assert not jnp.array_equal(at_edge[:, t], base[:, t]), (kind, t)


# ----------------------------------------------------------------- the shares


def uncut(cfg=TINY, **over) -> LagunaConfig:
    return dataclasses.replace(cfg, num_experts=cfg.num_experts_total, expert_offset=0, **over)


def test_the_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """An expert layer's MLP half for all 4 chips of the tiny deployment
    (experts 0-3, 4-7, 8-11, 12-15), the shared expert counted once, against
    the reference's whole layer with all 16 held."""
    whole = uncut()
    model = dataclasses.asdict(whole)
    w = ref.layer_weights(wl.make_weights(7, model), 1)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 80, whole.hidden_size))
    u2 = laguna.rms_norm(x, w["norm2"], whole.rms_norm_eps).reshape(-1, whole.hidden_size)
    total = laguna.swiglu(u2, w["s_gate"], w["s_up"], w["s_down"], None)   # alike on every chip
    held = TINY.num_experts
    for offset in range(0, whole.num_experts_total, held):
        share = dataclasses.replace(whole, num_experts=held, expert_offset=offset)
        mine = dict(w, **{k: w[k][offset:offset + held] for k in ("e_gate", "e_up", "e_down")})
        total = total + hybrid_lm.moe_routed(mine, u2, share, None, family=hybrid_lm.SWIGLU)[0]
    rnd = lambda v: v
    want = []
    for b in range(2):
        u = ref.rms_norm(x[b], w["norm2"], model["rms_norm_eps"])
        want.append(ref.moe_routed(w, u, model, rnd)[0]
                    + ref.swiglu(u, w["s_gate"], w["s_up"], w["s_down"], rnd))
    assert rel(total.reshape(x.shape), jnp.stack(want)) < F32_TOL


def test_the_vocabularys_row_slices_give_the_whole_vocabularys_logits():
    """The head's columns and the embedding's rows a chip holds are a slice:
    a chip's logits are the whole vocabulary's over its rows, for ids drawn
    from them."""
    model = dataclasses.asdict(TINY)
    w = wl.make_weights(13, model)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, TINY.seq_len), 32, 64)
    whole = ref.logits(w, ids, model)
    rows = slice(32, 64)
    mine = dict(w, embed=w["embed"][rows], head=w["head"][:, rows])
    got = ref.logits(mine, ids - 32, dict(model, vocab_size=32))
    assert rel(got, whole[..., rows]) < 1e-6
    cfg = dataclasses.replace(TINY, vocab_size=32)
    assert rel(logits_of(cfg, wl.to_program_params(mine))(ids - 32), whole[..., rows]) < F32_TOL


# ------------------------------------------------------ configuration, weights


def test_the_presets():
    assert FULL.kinds == (("F", "D"), ("S", "E"), ("S", "E"), ("S", "E"), ("F", "E"))
    assert [FULL.heads(a) for a, _ in FULL.kinds] == [48, 64, 64, 64, 48]
    assert (FULL.rotary_dim("F"), FULL.rotary_dim("S")) == (64, 128)
    assert (FULL.num_experts, FULL.expert_offset, FULL.num_experts_total) == (32, 96, 256)
    assert FULL.vocab_size * 8 == 100352 and FULL.seq_len == 8192
    assert laguna.param_count(FULL) == 691_623_936
    published = LagunaConfig()
    assert published.layer_types == ("F" + "SSS") * 10 and published.mlp_layer_types[:2] == "DE"
    assert sum(a == "F" for a in published.layer_types) == 10
    # 33.44B: the published "33.4B"; an elementwise gate would read 34.07B
    assert laguna.param_count(published) == 33_442_596_864
    assert round(laguna.param_count(published) / 1e9, 1) == 33.4
    assert TINY.kinds == FULL.kinds and [TINY.heads(a) for a, _ in TINY.kinds] == [4, 8, 8, 8, 4]


@pytest.mark.parametrize("bad", [dict(num_hidden_layers=41), dict(layer_types="FSSX" * 10),
                                 dict(num_sliding_attention_heads=60), dict(expert_offset=250),
                                 dict(partial_rotary_factor=0.26)])
def test_a_share_that_does_not_fit_is_refused(bad):
    with pytest.raises(ValueError):
        LagunaConfig(**bad)


def test_the_flat_weights_and_the_programs_tree_are_one_to_one():
    model = dataclasses.asdict(TINY)
    w = wl.make_weights(1, model)
    shapes = jax.tree_util.tree_map(lambda x: x.shape, wl.to_program_params(w))
    assert shapes == laguna.param_shapes(TINY)
    back = wl.from_program_params(wl.to_program_params(w))
    assert set(back) == set(w) and all(back[k] is w[k] for k in w)
    init = laguna.init_laguna(jax.random.PRNGKey(0), TINY)
    assert jax.tree_util.tree_map(lambda x: x.shape, init) == laguna.param_shapes(TINY)
    assert float(jnp.std(init["layers"][1]["o"])) == pytest.approx(
        0.02 / np.sqrt(2 * TINY.num_hidden_layers_total), rel=0.1)
