"""The hybrid language model (models/hybrid_lm.py) against its plain
reference (benchmark/reference/nemotron_h_ref.py) at a size the CPU holds:
hidden 64, latent 32, 16 experts of which 4 are held, 4 a token, 8 Mamba-2
heads in 2 groups, chunk 8, 64 tokens. Each mixer and the whole stack,
forward, loss and gradients; the chunked scan against the recurrence; the
share tests (what all the chips of a layer compute adds up to the uncut
layer); no pair dropped under any imbalance.

Tolerances: float32 against the float32 reference differs by summation
order only (3e-5 of the output's scale). In bfloat16 the program rounds
every product's operands and the residual stream to 8 bits of mantissa
(2^-9 relative a rounding); over the five layers that comes to under 2% of
an output's scale and under 6% of a gradient leaf's, which is what is
allowed.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_lm
from benchmark.reference import nemotron_h_ref as ref
from glom_tpu.models import hybrid_lm as lm
from glom_tpu.utils.config import HybridLMConfig
from glom_tpu.utils.presets import get_preset

CFG = get_preset("hybrid-lm-tiny").model
MODEL = dataclasses.asdict(CFG)
F32_TOL, BF16_TOL, BF16_GRAD_TOL = 3e-5, 0.02, 0.06


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def weights():
    return weights_lm.make_weights(3, MODEL)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, CFG.vocab_size)


def first_layer(kind):
    return CFG.pattern.index(kind)


def stream(seed=1, t=64, d=None):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, t, d or CFG.hidden_size), jnp.float32)


# ----------------------------------------------------------- mixers, the stack


@pytest.mark.parametrize("dtype, tol", [(None, F32_TOL), (jnp.bfloat16, BF16_TOL)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_a_layer_matches_the_reference(weights, kind, dtype, tol):
    w = ref.layer_weights(weights, first_layer(kind))
    x = stream()
    mixer = {"M": lm.mamba_mixer, "*": lm.attention_mixer,
             "E": lambda *a: lm.moe_mixer(*a)[0]}[kind]
    # the mixer's own output, without the residual it is added to
    got = mixer(w, x if dtype is None else x.astype(dtype), CFG, dtype)
    want = jnp.stack([ref.layer(kind, w, x[b], MODEL)[0] - x[b] for b in range(2)])
    assert got.dtype == (dtype or jnp.float32)
    assert rel(got, want) < tol


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_a_layers_gradients_match_the_reference(weights, kind):
    w = ref.layer_weights(weights, first_layer(kind))
    x, cot = stream(), stream(2)
    got = jax.grad(lambda w, x: jnp.sum(lm.layer(kind, w, x, CFG, None)[0] * cot),
                   argnums=(0, 1))(w, x)
    want = jax.grad(lambda w, x: sum(jnp.sum(ref.layer(kind, w, x[b], MODEL)[0] * cot[b])
                                     for b in range(2)), argnums=(0, 1))(w, x)
    for leaf in want[0]:
        assert rel(got[0][leaf], want[0][leaf]) < 2e-5, leaf
    assert rel(got[1], want[1]) < 2e-5


@pytest.mark.parametrize("dtype, tol, grad_tol", [(None, F32_TOL, 2e-5),
                                                  (jnp.bfloat16, 2e-3, BF16_GRAD_TOL)],
                         ids=["float32", "bfloat16"])
def test_the_stacks_loss_and_gradients_match_the_reference(weights, ids, dtype, tol, grad_tol):
    params = weights_lm.to_program_params(weights)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: lm.lm_loss(p, ids, CFG, compute_dtype=dtype), has_aux=True))(params)
    want_loss, want_grads, _ = ref.loss_and_grads(weights, ids, MODEL)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < tol
    got = weights_lm.from_program_params(grads)
    assert set(got) == set(want_grads)
    # a leaf's error against its own norm or the median leaf's (the routed
    # experts' gradients are tiny at these widths), as benchmark/correct.py
    median = float(np.median([np.linalg.norm(np.asarray(v)) for v in want_grads.values()]))
    for leaf, want in want_grads.items():
        err = np.linalg.norm(np.asarray(got[leaf], np.float32) - np.asarray(want))
        assert err / max(np.linalg.norm(np.asarray(want)), median) < grad_tol, leaf
    assert set(counters) == set(lm.COUNTERS)


def test_recomputation_changes_nothing(weights, ids):
    params = weights_lm.to_program_params(weights)
    f = lambda remat: jax.jit(jax.value_and_grad(
        lambda p: lm.lm_loss(p, ids, CFG, remat=remat)[0]))(params)
    (l0, g0), (l1, g1) = f(False), f(True)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        assert rel(a, b) < 1e-5


def test_the_programs_routing_choices_are_the_references(weights, ids):
    got = np.asarray(lm.routing_choices(weights_lm.to_program_params(weights), ids, CFG))
    _, want = ref.forward(weights, ids, MODEL)
    want = np.stack([np.asarray(c) for c in want]).reshape(got.shape)
    assert got.shape == (CFG.pattern.count("E"), 2 * 64, CFG.num_experts_per_tok)
    assert (np.sort(got, -1) == np.sort(want, -1)).all()


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-5), ("bfloat16", 0.05)])
def test_three_adam_steps_follow_the_reference(dtype, tol):
    """The trainer's own step builder and optimizer, three steps, against
    the reference's loss, gradient and Adam."""
    from glom_tpu.train.trainer import TrainState, default_optimizer, make_train_step
    from glom_tpu.utils.config import TrainConfig

    tcfg = TrainConfig(batch_size=2, learning_rate=3e-4, compute_dtype=dtype, remat=True)
    opt = default_optimizer(tcfg)
    w0 = weights_lm.make_weights(5, MODEL)
    params = weights_lm.to_program_params(w0)
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    step = jax.jit(make_train_step(CFG, tcfg, opt))
    batches = [np.asarray(jax.random.randint(jax.random.PRNGKey(i), (2, 64), 0, CFG.vocab_size))
               for i in range(3)]
    losses = []
    for b in batches:
        state, metrics = step(state, jnp.asarray(b), jax.random.PRNGKey(0))
        losses.append(float(metrics["loss"]))
    want = ref.train_reference(lambda: weights_lm.make_weights(5, MODEL), batches, MODEL,
                               lr=3e-4)
    assert np.allclose(losses, want["losses"], rtol=tol if dtype == "float32" else 2e-3)
    now = weights_lm.from_program_params(state.params)
    for leaf, norm in want["delta_norms"].items():
        got = float(jnp.linalg.norm((now[leaf] - w0[leaf]).ravel()))
        # Adam's first steps move every element by about lr whatever the
        # gradient's size: in bfloat16 an element whose gradient is nearly
        # zero may move the other way, so the norms agree loosely
        assert got == pytest.approx(norm, rel=1e-3 if dtype == "float32" else 0.2), leaf


# ------------------------------------------------------------------- the scan


def scan_inputs(t, seed=0):
    g, r, p, n = CFG.n_groups, CFG.mamba_num_heads // CFG.n_groups, CFG.mamba_head_dim, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (2, t, g, r, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, t, g, r)) - 1.0)
    a = -jnp.exp(jax.random.normal(k[2], (g, r)))
    return x, dt, a, jax.random.normal(k[3], (2, t, g, n)), jax.random.normal(k[4], (2, t, g, n))


def recurrence(x, dt, a, b, c):
    """The reference's step-at-a-time recurrence on the scan's own layout."""
    bsz, t, g, r, p = x.shape
    per_head = lambda v: jnp.repeat(v, r, axis=1)
    y = [ref._recurrence(x[i].reshape(t, g * r, p), dt[i].reshape(t, g * r), a.reshape(-1),
                         per_head(b[i]), per_head(c[i])) for i in range(bsz)]
    return jnp.stack(y).reshape(x.shape)


@pytest.mark.parametrize("t", [64, 61, 8, 5, 130])
def test_the_chunked_scan_is_the_recurrence(t):
    """At lengths that are and are not a multiple of the chunk (8), shorter
    than one chunk, and longer than the reference's block of steps."""
    x, dt, a, b, c = scan_inputs(t)
    assert rel(lm.ssd_chunked(x, dt, a, b, c, 8), recurrence(x, dt, a, b, c)) < 1e-5


def test_the_chunked_scans_gradients_are_the_recurrences():
    x, dt, a, b, c = scan_inputs(61, seed=1)
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    got = jax.grad(lambda *v: jnp.sum(lm.ssd_chunked(*v, 8) * cot), argnums=(0, 1, 2, 3, 4))(
        x, dt, a, b, c)
    want = jax.grad(lambda *v: jnp.sum(recurrence(*v) * cot), argnums=(0, 1, 2, 3, 4))(
        x, dt, a, b, c)
    for g_, w_ in zip(got, want):
        assert np.isfinite(np.asarray(g_)).all() and rel(g_, w_) < 2e-5


def test_the_chunk_size_changes_nothing():
    x, dt, a, b, c = scan_inputs(48, seed=2)
    assert rel(lm.ssd_chunked(x, dt, a, b, c, 16), lm.ssd_chunked(x, dt, a, b, c, 4)) < 1e-5


# ------------------------------------------------------------------ the shares


def uncut(**over) -> HybridLMConfig:
    """The tiny model with every expert, head and group held by one chip."""
    return dataclasses.replace(CFG, n_routed_experts=CFG.n_routed_experts_total,
                               expert_offset=0, **over)


def test_the_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    whole = uncut()
    model = dataclasses.asdict(whole)
    w = ref.layer_weights(weights_lm.make_weights(7, model), whole.pattern.index("E"))
    x = stream(4)
    u2 = lm.rms_norm(x, w["norm"], whole.layer_norm_epsilon).reshape(-1, whole.hidden_size)
    total = lm.moe_shared(w, u2, None)  # what every chip computes alike, counted once
    held = CFG.n_routed_experts
    for offset in range(0, whole.n_routed_experts_total, held):
        share = dataclasses.replace(whole, n_routed_experts=held, expert_offset=offset)
        mine = dict(w, w1=w["w1"][offset:offset + held], w2=w["w2"][offset:offset + held])
        total = total + lm.moe_routed(mine, u2, share, None)[0]
    want = jnp.stack([ref.layer("E", w, x[b], model)[0] - x[b] for b in range(2)])
    assert rel(total.reshape(x.shape), want) < F32_TOL


def test_the_mamba_head_shares_add_up_to_the_uncut_layer():
    """Two chips, one group of four heads each: the out-projected parts add
    up. A share takes its heads' columns of the in-projection (z, x, B, C,
    dt), its channels of the conv, and its rows of the out-projection."""
    whole = CFG  # 8 heads in 2 groups
    model = dataclasses.asdict(whole)
    w = ref.layer_weights(weights_lm.make_weights(7, model), whole.pattern.index("M"))
    x = stream(5)
    di, gn = whole.mamba_inner, whole.n_groups * whole.ssm_state_size
    share = dataclasses.replace(whole, mamba_num_heads=4, n_groups=1)
    sdi, sn, sh = share.mamba_inner, share.ssm_state_size, share.mamba_num_heads
    total = 0.0
    for s in range(2):
        cols = np.concatenate([
            np.arange(s * sdi, (s + 1) * sdi),                            # z
            di + np.arange(s * sdi, (s + 1) * sdi),                       # x
            2 * di + np.arange(s * sn, (s + 1) * sn),                     # B
            2 * di + gn + np.arange(s * sn, (s + 1) * sn),                # C
            2 * di + 2 * gn + np.arange(s * sh, (s + 1) * sh)])           # dt
        conv = cols[sdi:2 * sdi + 2 * sn] - di
        heads = slice(s * sh, (s + 1) * sh)
        mine = dict(w, in_proj=w["in_proj"][:, cols], conv_w=w["conv_w"][conv],
                    conv_b=w["conv_b"][conv], dt_bias=w["dt_bias"][heads],
                    A_log=w["A_log"][heads], D=w["D"][heads],
                    gnorm=w["gnorm"][s * sdi:(s + 1) * sdi],
                    out_proj=w["out_proj"][s * sdi:(s + 1) * sdi])
        total = total + lm.mamba_mixer(mine, x, share, None)
    want = jnp.stack([ref.layer("M", w, x[b], model)[0] - x[b] for b in range(2)])
    assert rel(total, want) < F32_TOL


def test_the_attention_head_shares_add_up_to_the_uncut_layer():
    """Two chips, one KV head and its two query heads each."""
    whole = CFG  # 4 query heads over 2 KV heads
    model = dataclasses.asdict(whole)
    w = ref.layer_weights(weights_lm.make_weights(7, model), whole.pattern.index("*"))
    x = stream(6)
    share = dataclasses.replace(whole, num_attention_heads=2, num_key_value_heads=1)
    q, kv = 2 * whole.head_dim, whole.head_dim
    total = 0.0
    for s in range(2):
        mine = dict(w, q=w["q"][:, s * q:(s + 1) * q], k=w["k"][:, s * kv:(s + 1) * kv],
                    v=w["v"][:, s * kv:(s + 1) * kv], o=w["o"][s * q:(s + 1) * q])
        total = total + lm.attention_mixer(mine, x, share, None)
    want = jnp.stack([ref.layer("*", w, x[b], model)[0] - x[b] for b in range(2)])
    assert rel(total, want) < F32_TOL


# --------------------------------------------------------- no pair is dropped


def forced_choices(case, n):
    """[n, k] experts every token chooses, and equal weights."""
    k, lo, hi = CFG.num_experts_per_tok, CFG.expert_offset, CFG.expert_offset + CFG.n_routed_experts
    outside = [e for e in range(CFG.n_routed_experts_total) if not lo <= e < hi]
    if case == "every_token_the_same_held_expert":
        row = [lo + 1] + outside[:k - 1]
    elif case == "every_token_every_held_expert":
        row = list(range(lo, hi))
    else:  # no token any held expert
        row = outside[:k]
    top_i = jnp.tile(jnp.asarray(row, jnp.int32), (n, 1))
    return top_i, jnp.full((n, k), CFG.routed_scaling_factor / k, jnp.float32)


@pytest.mark.parametrize("case", ["every_token_the_same_held_expert",
                                  "every_token_every_held_expert",
                                  "no_token_any_held_expert"])
def test_no_pair_is_dropped_under_any_imbalance(weights, case):
    w = ref.layer_weights(weights, first_layer("E"))
    u2 = stream(8).reshape(-1, CFG.hidden_size)
    top_i, top_w = forced_choices(case, u2.shape[0])
    f = lambda w: lm.moe_routed(w, u2, CFG, None, choices=(top_i, top_w))
    out, counters, _ = f(w)
    # the same sum written out: every token through every held expert it
    # chose, with the weight it gave it
    v = u2 @ w["down"]
    latent = sum(top_w[0, j] * ref.relu2(v @ w["w1"][e - CFG.expert_offset])
                 @ w["w2"][e - CFG.expert_offset]
                 for j, e in enumerate(np.asarray(top_i[0]))
                 if CFG.expert_offset <= e < CFG.expert_offset + CFG.n_routed_experts)
    want = latent @ w["up"] if not isinstance(latent, int) else jnp.zeros_like(u2)
    held = int(jnp.sum((top_i[0] >= CFG.expert_offset)
                       & (top_i[0] < CFG.expert_offset + CFG.n_routed_experts)))
    assert float(counters["moe_pairs_here"]) == held * u2.shape[0]
    assert float(counters["moe_max_expert_load"]) == (u2.shape[0] if held else 0)
    assert float(counters["moe_rows_computed"]) >= float(counters["moe_pairs_here"])
    if held:
        assert rel(out, want) < F32_TOL
    else:
        assert float(jnp.max(jnp.abs(out))) == 0.0 and float(jnp.max(jnp.abs(want))) == 0.0
    grads = jax.grad(lambda w: jnp.sum(f(w)[0] ** 2))(w)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads.values())


def test_rows_past_the_groups_may_hold_anything(weights, ids, monkeypatch):
    """The TPU's grouped product leaves the rows past the last group
    unwritten, in its output and in its input's gradient. Here they are
    filled with NaN: loss and gradients stay what they were."""
    real = jax.lax.ragged_dot

    def tail(rows, group_sizes):
        return (jnp.arange(rows) >= jnp.sum(group_sizes))[:, None]

    @jax.custom_vjp
    def poisoned(x, w, group_sizes):
        return jnp.where(tail(x.shape[0], group_sizes), jnp.nan, real(x, w, group_sizes))

    def fwd(x, w, group_sizes):
        return poisoned(x, w, group_sizes), (x, w, group_sizes)

    def bwd(res, g):
        x, w, group_sizes = res
        dx, dw = jax.vjp(lambda x, w: real(x, w, group_sizes), x, w)[1](g)
        return jnp.where(tail(x.shape[0], group_sizes), jnp.nan, dx), dw, None

    poisoned.defvjp(fwd, bwd)
    params = weights_lm.to_program_params(weights)
    f = lambda: jax.value_and_grad(lambda p: lm.lm_loss(p, ids, CFG)[0])(params)
    want_loss, want = f()
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    loss, grads = f()
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all() and rel(a, b) < 1e-5


def test_dispatch_sorts_by_expert_and_gives_every_group_a_row_of_room():
    top_i = jnp.asarray([[4, 0, 7, 9], [5, 4, 1, 2], [7, 6, 5, 4]], jnp.int32)  # held: 4..7
    pair, valid, sizes = lm.dispatch(top_i, CFG)
    assert sizes.tolist() == [3 + 1, 2 + 1, 1 + 1, 2 + 1]
    assert pair.shape[0] == 3 * 4 + 4 and int(valid.sum()) == 8
    experts = np.asarray(top_i).reshape(-1)[np.asarray(pair)][np.asarray(valid)]
    assert experts.tolist() == sorted(experts.tolist())


# ------------------------------------------------------ configuration, weights


def test_the_config_holds_a_slice_of_the_published_pattern():
    full = get_preset("nemotron3-super-ep64tp8").model
    assert full.pattern == "EMEMEMEMEM*" and len(full.hybrid_override_pattern) == 88
    assert (full.pattern.count("E"), full.pattern.count("M"), full.pattern.count("*")) == (5, 5, 1)
    assert full.mamba_inner == 1024 and full.mamba_conv_dim == 1280
    assert lm.mamba_in_width(full) == 2320


@pytest.mark.parametrize("bad", [dict(num_hidden_layers=9), dict(n_groups=3),
                                 dict(expert_offset=14), dict(num_key_value_heads=3)])
def test_a_share_that_does_not_fit_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_the_full_presets_parameters_are_counted():
    full = get_preset("nemotron3-super-ep64tp8").model
    assert lm.param_count(full) == 700_862_960
    shapes = weights_lm.shapes(dataclasses.asdict(full))
    assert sum(int(np.prod(s)) for s in shapes.values()) == 700_862_960
    flat = weights_lm.from_program_params(lm.param_shapes(full))
    assert flat == shapes


def test_the_programs_initial_values_follow_the_stated_families():
    p = lm.init_hybrid_lm(jax.random.PRNGKey(0), CFG)
    shapes = lm.param_shapes(CFG)
    assert jax.tree_util.tree_map(lambda a: a.shape, p) == shapes
    m = p["layers"][first_layer("M")]
    scale = (2.0 * CFG.num_hidden_layers_total) ** -0.5
    assert float(jnp.std(m["in_proj"])) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(m["out_proj"])) == pytest.approx(0.02 * scale, rel=0.1)
    assert float(jnp.min(m["norm"])) == 1.0 and float(jnp.max(m["D"])) == 1.0
    dt = jax.nn.softplus(m["dt_bias"])
    assert float(dt.min()) >= CFG.time_step_min * 0.99 and float(dt.max()) <= CFG.time_step_max * 1.01
    assert float(jnp.exp(m["A_log"]).min()) >= 1.0 and float(jnp.exp(m["A_log"]).max()) <= 16.0
    assert all(l.dtype == jnp.float32 for l in jax.tree_util.tree_leaves(p))


def test_the_flat_weights_and_the_programs_tree_are_one_to_one(weights):
    tree = weights_lm.to_program_params(weights)
    assert len(tree["layers"]) == CFG.num_hidden_layers
    back = weights_lm.from_program_params(tree)
    assert set(back) == set(weights) and all(back[k] is weights[k] for k in weights)
