"""The hybrid language model (models/hybrid_lm.py) against its plain
reference (benchmark/reference/nemotron_h_ref.py) at a size the CPU holds:
hidden 64, latent 32, 16 experts of which 4 are held, 4 a token, 8 Mamba-2
heads in 2 groups, chunk 8, 64 tokens. Each mixer and the whole stack,
forward, loss and gradients; the chunked scan against the recurrence; the
share tests (what all the chips of a layer compute adds up to the uncut
layer); no pair dropped under any imbalance, at any rung of the ladder of
row counts (a share of 2 of 32 experts and 1,536 tokens make it 1,024 /
4,096 rows; the tiny preset's has the one full rung).

Tolerances: float32 against the float32 reference differs by summation
order only (3e-5 of the output's scale). In bfloat16 the program rounds
every product's operands and the residual stream to 8 bits of mantissa
(2^-9 relative a rounding); over the five layers that comes to under 2% of
an output's scale and under 6% of a gradient leaf's, which is what is
allowed.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_lm
from benchmark.reference import nemotron_h_ref as ref
from glom_tpu.models import hybrid_lm as lm
from glom_tpu.utils.config import HybridLMConfig
from glom_tpu.utils.presets import get_preset
from tests.test_swiglu import eqns_of

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
    mixer = {"M": lm.mamba_mixer, "*": lambda *a: lm.attention_mixer(*a)[0],
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


# -------------------------------------------- the shared expert's backward rule


KEEP = jax.checkpoint_policies.save_only_these_names(*lm.KEPT_NAMES)  # run_stack's
SHARED_ROWS, (SHARED_D, SHARED_F) = 96, (64, 84)  # the cell's 8,192 x 4,096 x 5,376, cut
# bfloat16, a gradient against the float64 gradient of the same rounded inputs: a rounding
# to bfloat16 is at most 2^-9 of each element. Autodiff of the plain expression rounds u,
# the weights, dh and h; the rule rounds dpre as well, ONE more cast (which the chip's
# default precision does to the plain form's float32 operand too, and the CPU's does not).
# 2^-8 holds both, and the rule may stand over the plain form's own error by that one
# cast, 2^-9, and no further.
SHARED_BF16_TOL, ONE_CAST = 2.0 ** -8, 2.0 ** -9


def plain_shared(p, u2, dtype):
    """`moe_shared` as it stood before it had a backward rule of its own."""
    h = lm.relu2(lm._mm(u2, lm._cast(p["s1"], dtype))).astype(u2.dtype)
    return lm._mm(h, lm._cast(p["s2"], dtype)).astype(u2.dtype)


def shared_inputs(dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = {"s1": jax.random.normal(k[0], (SHARED_D, SHARED_F)) * SHARED_D ** -0.5,
         "s2": jax.random.normal(k[1], (SHARED_F, SHARED_D)) * SHARED_F ** -0.5}
    u2 = jax.random.normal(k[2], (SHARED_ROWS, SHARED_D)).astype(dtype or jnp.float32)
    return p, u2, jax.random.normal(k[3], (SHARED_ROWS, SHARED_D)).astype(u2.dtype)


def shared_grads(fn, p, u2, dy, dtype, remat=False):
    call = lambda p, u2: fn(p, u2, dtype)
    if remat:
        call = jax.checkpoint(call, policy=KEEP)
    return jax.jit(jax.grad(lambda p, u2: jnp.sum((call(p, u2) * dy).astype(jnp.float32)),
                            argnums=(0, 1)))(p, u2)


def shared_float64_grads(p, u2, dy, dtype):
    """The gradient of sum(out * dy) in float64, from the inputs as the
    products see them (u2 as it is, the weights cast to `dtype`)."""
    as64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    w1, w2 = as64(lm._cast(p["s1"], dtype)), as64(lm._cast(p["s2"], dtype))
    u, dy = as64(u2), as64(dy)
    pre = u @ w1
    dpre = (dy @ w2.T) * 2.0 * np.maximum(pre, 0.0)
    return {"s1": u.T @ dpre, "s2": np.square(np.maximum(pre, 0.0)).T @ dy}, dpre @ w1.T


@pytest.mark.parametrize("remat", [False, True], ids=["alone", "recomputed"])
@pytest.mark.parametrize("dtype", [None, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_shared_experts_rule_is_autodiff_of_the_plain_expression(dtype, remat):
    """The forward bit for bit in both types, jitted or under differentiation
    (where the forward rule runs). float32: nothing is rounded, the rule's
    three products are autodiff's, and every gradient is autodiff's bit for
    bit. bfloat16: each gradient within 2^-8 of the float64 one and no more
    than one cast (2^-9) over the plain form's own error."""
    p, u2, dy = shared_inputs(dtype)
    theirs = plain_shared(p, u2, dtype)
    for ours in (jax.jit(lm.moe_shared, static_argnums=2)(p, u2, dtype),
                 jax.vjp(lambda p, u2: lm.moe_shared(p, u2, dtype), p, u2)[0]):
        assert ours.dtype == theirs.dtype == u2.dtype
        assert np.array_equal(np.asarray(ours, np.float32), np.asarray(theirs, np.float32))
    (gp, gu), (wp, wu) = (shared_grads(lm.moe_shared, p, u2, dy, dtype, remat),
                          shared_grads(plain_shared, p, u2, dy, dtype))
    ep, eu = shared_float64_grads(p, u2, dy, dtype)
    for a, b, x, e in ((gp["s1"], wp["s1"], p["s1"], ep["s1"]),
                       (gp["s2"], wp["s2"], p["s2"], ep["s2"]), (gu, wu, u2, eu)):
        assert a.dtype == b.dtype == x.dtype and a.shape == x.shape
        if dtype is None:
            assert np.array_equal(np.asarray(a), np.asarray(b))
            continue
        if a.dtype == jnp.bfloat16:     # du2's own rounding on the way out, in both
            e = np.asarray(jnp.asarray(e, jnp.float32).astype(jnp.bfloat16), np.float64)
        err = lambda g: rel(np.asarray(g, np.float32), e)
        assert err(a) < SHARED_BF16_TOL and err(a) < err(b) + ONE_CAST


def test_an_expert_layers_backward_reads_staged_operands(weights):
    """The backward of one `E` layer, recomputed as `run_stack` recomputes it,
    in bfloat16: two barriers, dy [rows, d] as it arrives and then h and dpre
    [rows, fs] in the compute type, whose inputs are roundings (no product's
    result) and whose outputs the three products after dh's read and nothing
    else does. Every product with an operand of the shared expert's width
    (the recomputed up product, dh's and those three) takes two bfloat16
    operands: none reads a float32 array of that width."""
    w = ref.layer_weights(weights, first_layer("E"))
    x = stream().astype(jnp.bfloat16)
    fs, rows = CFG.moe_shared_expert_intermediate_size, x.shape[0] * x.shape[1]
    held = jax.checkpoint(lambda w, x: lm.layer("E", w, x, CFG, jnp.bfloat16)[0], policy=KEEP)
    _, pull = jax.vjp(held, w, x)
    eqns = list(eqns_of(jax.make_jaxpr(pull)(x).jaxpr))
    shape = lambda v: getattr(v.aval, "shape", ())
    wide = [e for e in eqns if e.primitive.name == "dot_general"
            and any(fs in shape(v) for v in e.invars)]
    assert len(wide) == 5
    for e in wide:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16, jnp.bfloat16]
        assert e.params["preferred_element_type"] == jnp.float32
    first, barrier = [e for e in eqns if e.primitive.name == "optimization_barrier"]
    kind = lambda n: [((rows, n), jnp.bfloat16)]
    assert [(shape(v), v.aval.dtype) for v in first.outvars] == kind(CFG.hidden_size)
    assert [(shape(v), v.aval.dtype) for v in barrier.outvars] == kind(fs) * 2
    made_by = {id(v): e.primitive.name for e in eqns for v in e.outvars}
    assert all(made_by[id(v)] == "convert_element_type" for v in barrier.invars)
    reads = lambda e: [sum(v is s for v in e.invars) for s in barrier.outvars]
    readers = [e for e in eqns if any(reads(e))]
    assert len(readers) == 3 and all(e in wide for e in readers)
    # h feeds W2's gradient; dpre W1's gradient and du2
    assert [sum(reads(e)[i] for e in readers) for i in range(2)] == [1, 2]
    before = [e for e in wide if eqns.index(e) < eqns.index(barrier)]
    assert len(before) == 2 and not any(e in readers for e in before)


def test_the_counter_counts_the_expert_layers(weights, ids):
    """`shared_backward_staged`: the `E` layers, whether recomputed or not
    (the rule is the function's)."""
    params = weights_lm.to_program_params(weights)
    for remat in (False, True):
        counters = jax.jit(lambda p: lm.lm_loss(p, ids, CFG, remat=remat)[1])(params)
        assert float(counters["shared_backward_staged"]) == CFG.pattern.count("E") == 2
    assert float(lm.shared_backward_staged([{}, {"attn_on_kernels": 1}])) == 0
    assert float(lm.shared_backward_staged([{"relu2_mlp_calls": 1}, {}])) == 1


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
        total = total + lm.attention_mixer(mine, x, share, None)[0]
    want = jnp.stack([ref.layer("*", w, x[b], model)[0] - x[b] for b in range(2)])
    assert rel(total, want) < F32_TOL


# --------------------------------------------------------- no pair is dropped

# A share small enough for a ladder: k = 4 of 32 experts scored, 2 held, so
# 1,536 tokens send 384 pairs here when the router is balanced and at most
# 3,072; more experts a token than experts held, so that the full count
# (4,096) is no other array's size.
LADDER = dataclasses.replace(CFG, n_routed_experts_total=32, n_routed_experts=2, expert_offset=4)
LADDER_TOKENS = 1536
CASES = ["every_token_the_same_held_expert", "every_token_every_held_expert",
         "no_token_any_held_expert"]


def ladder_cases():
    """The pair counts at each small rung's edge: the last that fits it (the
    rung less the rows of room), and one more."""
    rungs = lm.row_rungs(LADDER_TOKENS, LADDER)
    assert len(rungs) > 1 and rungs[-1] == 4096 and all(r % lm.ROW_TILE == 0 for r in rungs)
    held = LADDER.n_routed_experts
    return [(LADDER, pairs) for r in rungs[:-1] for pairs in (r - held, r - held + 1)]


def forced_choices(cfg, case, n):
    """[n, k] experts every token chooses, and weights. `case` is a name, or
    a count of pairs on the experts held: as many tokens as it takes choose
    them all, one more token the rest, the others none."""
    k, held, lo = cfg.num_experts_per_tok, cfg.n_routed_experts, cfg.expert_offset
    outside = [e for e in range(cfg.n_routed_experts_total) if not lo <= e < lo + held]
    rows = {"every_token_the_same_held_expert": [lo + 1] + outside[:k - 1],
            "every_token_every_held_expert": (list(range(lo, lo + held)) + outside)[:k],
            "no_token_any_held_expert": outside[:k]}
    if case in rows:
        top_i = np.tile(np.asarray(rows[case], np.int32), (n, 1))
    else:
        top_i = np.tile(np.asarray(outside[:k], np.int32), (n, 1))
        whole, rest = divmod(case, min(k, held))
        top_i[:whole, :min(k, held)] = np.arange(lo, lo + min(k, held))
        top_i[whole, :rest] = np.arange(lo, lo + rest)
    weights = 0.5 + jax.random.uniform(jax.random.PRNGKey(11), (n, k))
    weights = weights / jnp.sum(weights, -1, keepdims=True) * cfg.routed_scaling_factor
    return jnp.asarray(top_i), weights


def expert_layer(cfg, seed=3):
    model = dataclasses.asdict(cfg)
    return ref.layer_weights(weights_lm.make_weights(seed, model), cfg.pattern.index("E"))


@pytest.mark.parametrize("cfg, case", [(CFG, c) for c in CASES] + [(LADDER, c) for c in CASES]
                         + ladder_cases(),
                         ids=lambda v: "ladder" if v is LADDER else "tiny" if v is CFG else str(v))
def test_no_pair_is_dropped_under_any_imbalance(cfg, case, monkeypatch):
    n = 128 if cfg is CFG else LADDER_TOKENS
    w = expert_layer(cfg)
    u2 = jax.random.normal(jax.random.PRNGKey(8), (n, cfg.hidden_size), jnp.float32)
    top_i, top_w = forced_choices(cfg, case, n)
    lo, held, k = cfg.expert_offset, cfg.n_routed_experts, cfg.num_experts_per_tok
    here = (top_i >= lo) & (top_i < lo + held)
    pairs = int(jnp.sum(here))

    def run():
        f = lambda w, u2, tw: lm.moe_routed(w, u2, cfg, None, choices=(top_i, tw))
        out, counters, _ = jax.jit(f)(w, u2, top_w)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(f(*a)[0] ** 2), argnums=(0, 1, 2)))(
            w, u2, top_w)
        return out, counters, grads

    out, counters, grads = run()
    # the same sum written out: every token through every held expert it
    # chose, with the weight it gave it
    v = u2 @ w["down"]
    latent = sum(jnp.where(here[:, j, None] & (top_i[:, j, None] == lo + e), top_w[:, j, None], 0)
                 * (ref.relu2(v @ w["w1"][e]) @ w["w2"][e])
                 for j in range(k) for e in range(held))
    assert rel(out, latent @ w["up"]) < F32_TOL if pairs else float(jnp.max(jnp.abs(out))) == 0.0
    # the counters say what was run: the smallest rung that holds the pairs
    # and the experts' rows of room
    rungs = lm.row_rungs(n, cfg)
    rows = next(r for r in rungs if r >= pairs + held)
    assert float(counters["moe_pairs_here"]) == pairs
    assert float(counters["moe_rows_computed"]) == rows
    assert float(counters["moe_rows_full_share"]) == (rows == rungs[-1])
    assert float(counters["moe_max_expert_load"]) == int(jnp.max(jnp.sum(
        here[:, :, None] & (top_i[:, :, None] == lo + jnp.arange(held)), axis=(0, 1))))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))
    if len(rungs) > 1:
        # the single program at the full count gives the same, whichever rung ran
        monkeypatch.setattr(lm, "row_rungs",
                            lambda n, cfg, *loads, real=lm.row_rungs: real(n, cfg, *loads)[-1:])
        want_out, want_counters, want_grads = run()
        assert float(want_counters["moe_rows_computed"]) == rungs[-1]
        for a, b in zip(jax.tree_util.tree_leaves((out, grads)),
                        jax.tree_util.tree_leaves((want_out, want_grads))):
            assert rel(a, b) < 1e-6


def poison_the_rows_past_the_groups(monkeypatch):
    """`lax.ragged_dot` with NaN in the rows past the last group, in its
    output and in its input's gradient: what the TPU's leaves unwritten."""
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
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)


@pytest.mark.parametrize("where", ["the_whole_stack", "a_small_rung"])
def test_rows_past_the_groups_may_hold_anything(weights, ids, monkeypatch, where):
    """The TPU's grouped product leaves the rows past the last group
    unwritten, in its output and in its input's gradient. Here they are
    filled with NaN: loss and gradients stay what they were, in the tiny
    stack (one rung) and in an expert layer whose pairs take the ladder's
    first rung."""
    if where == "the_whole_stack":
        params = weights_lm.to_program_params(weights)
        f = lambda: jax.value_and_grad(lambda p: lm.lm_loss(p, ids, CFG)[0])(params)
    else:
        w = expert_layer(LADDER)
        x = jax.random.normal(jax.random.PRNGKey(8), (1, LADDER_TOKENS, LADDER.hidden_size))

        def loss(w, x):
            out, counters, _ = lm.layer("E", w, x, LADDER, None)
            return jnp.sum(out ** 2), counters

        f = lambda: jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(w, x)
        assert float(f()[0][1]["moe_rows_computed"]) == lm.row_rungs(LADDER_TOKENS, LADDER)[0]
    want = f()
    poison_the_rows_past_the_groups(monkeypatch)
    got = f()
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all() and rel(a, b) < 1e-5


def inner_jaxprs(eqn, but_the_last_branch=False):
    """The jaxprs an equation holds (a `cond`'s branches, a `remat`'s or a
    `custom_vjp_call`'s body, ...)."""
    found = []
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        if but_the_last_branch and eqn.primitive.name == "cond" and name == "branches":
            vals = vals[:-1]
        found += [getattr(v, "jaxpr", v) for v in vals
                  if hasattr(getattr(v, "jaxpr", v), "eqns")]
    return found


def shapes_outside_the_last_branch(jaxpr):
    """Every array shape in a jaxpr and the jaxprs inside it, but for the
    last branch of each `cond` (a ladder's full rung)."""
    shapes = set()
    for eqn in jaxpr.eqns:
        shapes |= {tuple(v.aval.shape) for v in list(eqn.invars) + list(eqn.outvars)
                   if hasattr(v.aval, "shape")}
        for sub in inner_jaxprs(eqn, but_the_last_branch=True):
            shapes |= shapes_outside_the_last_branch(sub)
    return shapes


def conds_in(jaxpr):
    return sum((eqn.primitive.name == "cond") + sum(conds_in(j) for j in inner_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def test_only_the_full_rung_holds_arrays_of_the_full_row_count():
    """In the step's gradient, forward, recomputation and backward: an array
    with the full count of rows lives in the full rung's own branch and
    nowhere else. (Differentiating the `switch` itself fails this: every
    branch then returns the full rung's intermediates.)"""
    cfg = dataclasses.replace(LADDER, seq_len=LADDER_TOKENS)
    k, rungs = cfg.num_experts_per_tok, lm.row_rungs(LADDER_TOKENS, cfg)
    full = rungs[-1]
    params = jax.eval_shape(lambda: lm.init_hybrid_lm(jax.random.PRNGKey(0), cfg))
    ids = jax.ShapeDtypeStruct((1, LADDER_TOKENS), jnp.int32)
    grad = jax.make_jaxpr(jax.grad(lambda p, ids: lm.lm_loss(p, ids, cfg)[0]))(params, ids)
    # a forward and a backward choice an expert layer; the recomputation's
    # forward choice may be there or pruned
    layers = cfg.pattern.count("E")
    assert 2 * layers <= conds_in(grad.jaxpr) <= 3 * layers
    outside = shapes_outside_the_last_branch(grad.jaxpr)
    assert any(s[:1] == rungs[:1] for s in outside)
    assert not [s for s in outside if full in s]
    # the check sees what it is for: the plain derivative of the choice
    # returns the full rung's intermediates from every branch
    w = jax.eval_shape(lambda: expert_layer(cfg))

    def plain(w, u2):
        top_i, top_w = lm.route(w, u2, cfg)
        diff = (u2 @ w["down"], top_w, (w["w1"], w["w2"]), w["up"])
        branches = [functools.partial(lm.expert_rows, rows, k, lm.relu2_experts)
                    for rows in rungs]
        return jnp.sum(jax.lax.switch(0, branches, diff, lm.dispatch(top_i, cfg)))

    u2 = jax.ShapeDtypeStruct((LADDER_TOKENS, cfg.hidden_size), jnp.float32)
    unioned = jax.make_jaxpr(jax.grad(plain))(w, u2).jaxpr
    assert [s for s in shapes_outside_the_last_branch(unioned) if full in s]


def test_a_share_that_holds_most_of_the_experts_traces_no_choice():
    """Where the first rung is no smaller than the full count there is one
    rung and the single program: the tiny preset, and any uncut layer."""
    whole = dataclasses.replace(LADDER, n_routed_experts=32, expert_offset=0)
    for cfg, n in ((CFG, 128), (uncut(), 128), (whole, LADDER_TOKENS)):
        assert len(lm.row_rungs(n, cfg)) == 1
        w = jax.eval_shape(lambda: expert_layer(cfg))
        u2 = jax.ShapeDtypeStruct((n, cfg.hidden_size), jnp.float32)
        out = lambda w, u2, cfg=cfg: jnp.sum(lm.moe_routed(w, u2, cfg, None)[0])
        assert conds_in(jax.make_jaxpr(jax.grad(out))(w, u2).jaxpr) == 0
    full = get_preset("nemotron3-super-ep64tp8").model
    assert lm.row_rungs(8192, full) == (6144, 66560)


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
