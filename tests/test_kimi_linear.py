"""The Kimi Linear language model (models/kimi_linear.py) against its plain
reference (benchmark/reference/kimi_linear_ref.py) at a size the CPU holds:
hidden 64, five layers (KDA + dense, KDA, KDA, latent attention, KDA; 4 heads
of 16; a latent of 32 beside a shared key part of 8), 80 tokens, 4 of 16
experts held. The loss and every gradient leaf; the delta rule in chunks
against the recurrence a position at a time, from one chunk to several and
from mild decays to harsher ones than the seeded draws give; the in-chunk
solve; causality, bit for bit; the shares of every chip adding up to the
uncut layer with the shared expert counted once; each piece of the
mathematics missed by a program without it; the uncut model's 49.1B
parameters.

The chunk is cut to 32 positions and the segment to two chunks, the query
block to 16 and the key block to 8 for these tests, so that 80 tokens are
three chunks in two segments (the last chunk padded, and one more of padding
alone) and five query blocks.

Tolerances: float32 against the float32 reference differs by summation order
and by the chunked form's algebra (5e-5 of a leaf's scale; the recurrence
itself agrees to 5e-6 of the output's norm). In bfloat16 the program rounds
every product's operands and the residual stream to 8 bits of mantissa, which
over five layers comes to under 6% of a gradient leaf's scale (beta's projection reads 4%); the experts'
and the router's leaves get 25%, because a routing choice is discontinuous
and a few of 160 tokens choose otherwise after rounding.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_kimi as wk
from benchmark.reference import kimi_linear_ref as ref
from benchmark.reference import laguna_ref
from glom_tpu.models import hybrid_lm
from glom_tpu.models import kimi_linear as kl
from glom_tpu.utils.config import KimiLinearConfig
from glom_tpu.utils.presets import get_preset
from tests.test_flash_attention import parents_policy

TINY = get_preset("kimi-linear-tiny").model
FULL = get_preset("kimi-linear-ep32vp8").model
F32_TOL, BF16_TOL, BF16_ROUTED_TOL = 5e-5, 0.06, 0.25


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(hybrid_lm, "ATTN_QUERY_BLOCK", 16)
    monkeypatch.setattr(hybrid_lm, "ATTN_KEY_BLOCK", 8)
    monkeypatch.setattr(kl, "KDA_CHUNK", 32)
    monkeypatch.setattr(kl, "KDA_SEGMENT", 2)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    monkeypatch.setattr(ref, "SCAN_BLOCK", 16)
    monkeypatch.setattr(laguna_ref, "EXPERT_ROWS", 32)


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def ids_for(cfg, seed=0, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, cfg.seq_len), 0, cfg.vocab_size)


def program_grads(cfg, w, ids, dtype=None, remat=True):
    (value, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: kl.lm_loss(p, ids, cfg, compute_dtype=dtype, remat=remat),
        has_aux=True))(wk.to_program_params(w))
    return float(value), wk.from_program_params(grads), counters


def worst_leaf(grads, want):
    scale = float(np.median([np.linalg.norm(v) for v in want.values()]))
    return max((float(np.linalg.norm(np.asarray(grads[name], np.float32) - np.asarray(want[name])))
                / max(float(np.linalg.norm(want[name])), scale), name) for name in want)


# ------------------------------------------------- the stack against the reference


def test_the_loss_and_every_gradient_leaf_match_the_reference():
    model = dataclasses.asdict(TINY)
    w, ids = wk.make_weights(3, model), ids_for(TINY)
    loss, grads, counters = program_grads(TINY, w, ids)
    want_loss, want, chosen = ref.loss_and_grads(w, ids, model)
    assert abs(loss - float(want_loss)) < 1e-5 * float(want_loss)
    assert set(grads) == set(want) == set(wk.shapes(model))
    assert worst_leaf(grads, want)[0] < F32_TOL, worst_leaf(grads, want)
    assert all(float(np.linalg.norm(v)) > 0 for v in want.values())   # no leaf is off the path
    # 80 tokens in chunks of 32: three chunks a row, two rows, four KDA layers; the latent
    # layer multiplies 2 + 4 + 6 + 8 + 10 key blocks of 8 in query blocks of 16
    assert float(counters["kda_chunks"]) == 3 * 2 * 4
    assert float(counters["attn_key_blocks_full"]) == 30
    assert -60 < float(counters["kda_log_decay_min"]) < -5   # a fast channel over 32 positions
    # the program's routing is the reference's, and its counters count it
    got = kl.routing_choices(wk.to_program_params(w), ids, TINY)
    assert np.array_equal(np.sort(np.asarray(got), -1),
                          np.sort(np.stack(chosen).reshape(got.shape), -1))
    here = (np.stack(chosen) >= TINY.expert_offset) & (
        np.stack(chosen) < TINY.expert_offset + TINY.num_experts)
    assert float(counters["moe_pairs_here"]) == pytest.approx(here.sum() / 4)


def test_bfloat16_stays_within_its_band_of_float32():
    w, ids = wk.make_weights(5, dataclasses.asdict(TINY)), ids_for(TINY, 1)
    loss32, g32, _ = program_grads(TINY, w, ids)
    loss16, g16, _ = program_grads(TINY, w, ids, dtype=jnp.bfloat16)
    assert abs(loss16 - loss32) < 2e-3 * loss32
    routed = {name for name in g32 if name.rpartition(".")[2] in (
        "router", "e_gate", "e_up", "e_down")}
    rest = lambda g: {name: v for name, v in g.items() if name not in routed}
    assert worst_leaf(rest(g16), rest(g32))[0] < BF16_TOL, worst_leaf(rest(g16), rest(g32))
    assert worst_leaf(g16, g32)[0] < BF16_ROUTED_TOL, worst_leaf(g16, g32)


def test_recomputation_changes_nothing():
    w, ids = wk.make_weights(7, dataclasses.asdict(TINY)), ids_for(TINY, 2)
    loss_a, grads_a, _ = program_grads(TINY, w, ids, remat=True)
    loss_b, grads_b, _ = program_grads(TINY, w, ids, remat=False)
    assert loss_a == loss_b
    assert max(rel(grads_a[name], grads_b[name]) for name in grads_a) < 5e-6


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
    params = wk.to_program_params(wk.make_weights(17, model))
    state = TrainState(params=params, opt_state=opt.init(params), step=jnp.zeros((), jnp.int32))
    step = jax.jit(make_train_step(TINY, tcfg, opt))
    batches = [np.asarray(ids_for(TINY, seed=30 + i)) for i in range(3)]
    losses = []
    for ids in batches:
        state, metrics = step(state, jnp.asarray(ids), jax.random.PRNGKey(0))
        losses.append(float(metrics["loss"]))
    want = ref.train_reference(lambda: wk.make_weights(17, model), batches, model, lr=3e-4)
    assert np.allclose(losses, want["losses"], rtol=loss_tol, atol=0)
    w0 = wk.make_weights(17, model)
    delta = {k: float(jnp.linalg.norm(v - w0[k]))
             for k, v in wk.from_program_params(state.params).items()}
    compared = ref.change_compared(want)
    assert set(compared) == set(want["delta_norms"])   # no gradient here is within Adam's eps
    scale = float(np.median(list(compared.values())))
    worst = max((abs(delta[name] - norm) / max(norm, scale), name)
                for name, norm in compared.items())
    assert worst[0] < delta_tol, worst


# --------------------------------------------- what a wrong program would miss


def without(monkeypatch, what):
    """The program with one piece of the mathematics left out."""
    if what == "the_decay":
        real = kl.kda_chunked
        monkeypatch.setattr(kl, "kda_chunked", lambda q, k, v, g, beta: real(q, k, v, 0 * g, beta))
    elif what == "the_delta_correction":      # plain gated linear attention: S += beta k v^T
        monkeypatch.setattr(kl, "unit_lower_inverse", lambda strict: jnp.broadcast_to(
            jnp.eye(strict.shape[-1], dtype=strict.dtype), strict.shape))
    elif what == "the_key_norm":
        monkeypatch.setattr(kl, "l2norm", lambda x: x)
    elif what == "the_routed_scaling":
        return dataclasses.replace(TINY, routed_scaling_factor=1.0)
    elif what == "the_shared_key_part":       # the heads' keys without kr
        real = kl.blocked_attention
        nope = TINY.qk_nope_head_dim
        monkeypatch.setattr(kl, "blocked_attention", lambda q, k, v: real(
            q, k.at[..., nope:].set(0), v))
    elif what == "the_latents_norm":
        real = kl.rms_norm
        monkeypatch.setattr(kl, "rms_norm", lambda x, w, eps: (
            x if x.shape[-1] == TINY.kv_lora_rank else real(x, w, eps)))
    return TINY


@pytest.mark.parametrize("what", ["the_decay", "the_delta_correction", "the_key_norm",
                                  "the_routed_scaling", "the_shared_key_part",
                                  "the_latents_norm"])
def test_a_program_without_a_piece_of_the_mathematics_misses_the_reference(monkeypatch, what):
    """Each by far more than a sound program's distance (F32_TOL): nothing is
    left out because the result stays inside a tolerance."""
    model = dataclasses.asdict(TINY)
    w, ids = wk.make_weights(11, model), ids_for(TINY, 3)
    want_loss, want, _ = ref.loss_and_grads(w, ids, model)
    wrong = without(monkeypatch, what)
    loss, grads, _ = program_grads(wrong, w, ids)
    assert worst_leaf(grads, want)[0] > 100 * F32_TOL, (what, worst_leaf(grads, want))


# ------------------------------------------------------ the delta rule in chunks


def scan_inputs(t, harshness, seed=0, bsz=2, heads=4, d=16):
    """q, k normed, v, log-decays uniform in [-harshness, 0], beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = kl.l2norm(jax.random.normal(ks[0], (bsz, t, heads, d))) * d ** -0.5
    k = kl.l2norm(jax.random.normal(ks[1], (bsz, t, heads, d)))
    v = jax.random.normal(ks[2], (bsz, t, heads, d))
    g = -harshness * jax.random.uniform(ks[3], (bsz, t, heads, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (bsz, t, heads)))
    return q, k, v, g, beta


# the seeded draws give a channel at most 16 x softplus(inverse softplus(0.1) + 0.9) = 3.7 a
# position: 6 is harsher, and over a chunk of 64 it is exp(-380) against float32's exp(-87)
@pytest.mark.parametrize("t, chunk, harshness", [
    (16, 64, 1.0), (64, 64, 1.0), (80, 32, 1.0), (48, 64, 1.0),
    (200, 64, 0.0), (200, 64, 0.01), (200, 64, 1.0), (200, 64, 6.0)],
    ids=["one_subchunk", "one_chunk", "three_chunks_padded", "a_chunk_of_48", "no_decay",
         "mild", "a_fast_channel_four_chunks_padded", "harsher_than_any_draw"])
def test_the_chunked_delta_rule_is_the_recurrence(t, chunk, harshness, monkeypatch):
    """Outputs and all five gradients against the recurrence a position at a
    time, and no number that is not finite anywhere: the decays are formed as
    differences that are never positive."""
    monkeypatch.setattr(kl, "KDA_CHUNK", chunk)
    args = scan_inputs(t, harshness, seed=t)
    got, lowest = jax.jit(kl.kda_chunked)(*args)
    want = jax.vmap(ref.delta_rule)(*args)
    assert bool(jnp.all(jnp.isfinite(got))) and rel(got, want) < 5e-6
    if harshness:
        assert float(lowest) < -0.2 * harshness * min(t, chunk)   # the sum of uniform draws
    else:
        assert float(lowest) == 0.0
    cot = jax.random.normal(jax.random.PRNGKey(9), got.shape)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * cot), argnums=(0, 1, 2, 3, 4)))(
        *args)
    for name, a, b in zip("q k v g beta".split(), grads(lambda *a: kl.kda_chunked(*a)[0]),
                          grads(lambda *a: jax.vmap(ref.delta_rule)(*a))):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, b) < 2e-5, (name, rel(a, b))


@pytest.mark.parametrize("constant", ["SCAN_STATE_DTYPE", "SCAN_SOLVE_DTYPE"])
def test_the_delta_rule_in_bfloat16_is_hundreds_of_times_further_from_the_recurrence(
        constant, monkeypatch):
    """The two types the configuration states as float32, each with a name
    (the benchmark's `kda_scan_diff` is this comparison at the cell's size):
    as they are, float32's rounding; with either in bfloat16, bfloat16's."""
    assert kl.SCAN_STATE_DTYPE == kl.SCAN_SOLVE_DTYPE == jnp.float32
    args = scan_inputs(200, 1.0, seed=5)
    want = jax.vmap(ref.delta_rule)(*args)
    jax.clear_caches()   # the segment's checkpoint keeps its trace by function and shapes
    try:
        sound = rel(jax.jit(kl.kda_chunked)(*args)[0], want)
        monkeypatch.setattr(kl, constant, jnp.bfloat16)
        jax.clear_caches()
        faulty = rel(jax.jit(kl.kda_chunked)(*args)[0], want)
    finally:
        jax.clear_caches()
    assert sound < 5e-6 and faulty > 1e-4 and faulty > 300 * sound, (sound, faulty)


def test_a_product_of_two_exponentials_would_overflow_where_the_differences_do_not():
    """The trap `decayed_products` is written round: at the seeded decays
    exp(-G_j) is infinite inside a chunk of 64, and so is the factored form's
    result."""
    q, k, v, g, beta = scan_inputs(64, 4.0, seed=2, bsz=1, heads=1, d=16)
    cum = jnp.cumsum(g[0, :, 0].astype(jnp.float32), axis=0)            # [64, 16]
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-cum))))
    naive = (k[0, :, 0] * jnp.exp(cum)) @ (k[0, :, 0] * jnp.exp(-cum)).T
    assert not bool(jnp.all(jnp.isfinite(naive)))
    got = kl.decayed_products(q[0, :, 0][None], k[0, :, 0][None], cum[None])
    assert bool(jnp.all(jnp.isfinite(got)))
    exact = jnp.sum(k[0, :, None, 0] * k[0, None, :, 0]
                    * jnp.exp(jnp.minimum(cum[:, None] - cum[None, :], 0.0)), -1)
    assert rel(jnp.tril(got[0, 1]), jnp.tril(exact)) < 1e-6
    assert float(jnp.max(jnp.abs(jnp.triu(got[0], 1)))) == 0.0


@pytest.mark.parametrize("c", [1, 2, 16, 48, 64])
def test_the_in_chunk_solve_inverts_a_unit_lower_triangle(c):
    """Against the identity, for strict lower triangles the size of the delta
    rule's (beta k_i . k_j: at most 1) and for keys that repeat (all ones),
    where the product form would cancel."""
    n = jnp.tril(jax.random.uniform(jax.random.PRNGKey(c), (3, c, c), minval=-1.0), -1)
    for strict in (0.3 * n, jnp.tril(jnp.ones((1, c, c)), -1)):
        inv = kl.unit_lower_inverse(strict)
        eye = jnp.eye(c)
        assert float(jnp.max(jnp.abs((eye + strict) @ inv - eye))) < 1e-5
        assert float(jnp.max(jnp.abs(jnp.triu(inv, 1)))) == 0.0


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.tree_util.tree_leaves(list(eqn.params.values()),
                                             is_leaf=lambda x: hasattr(x, "eqns")):
            sub = getattr(sub, "jaxpr", sub)          # a closed jaxpr's
            if hasattr(sub, "eqns"):
                yield from equations(sub)


def segment_runs(f, *args):
    """(forward, transposed) runs of `_kda_segment` in f's jaxpr, however
    deep: a segment takes one cumulative sum of its log-decays and nothing
    else here takes one; its transpose is the same sum reversed."""
    reverse = [eqn.params["reverse"] for eqn in equations(jax.make_jaxpr(f)(*args).jaxpr)
               if eqn.primitive.name == "cumsum"]
    return reverse.count(False), reverse.count(True)


def test_the_chunks_go_in_segments_that_are_recomputed_and_come_out_the_same(monkeypatch):
    """200 positions in chunks of 32: seven chunks as one segment, and as four
    segments of two (the last chunk padding), the state handed from segment to
    segment. The forward pass runs the segments once, in one loop, and keeps
    nothing of their insides; the gradient runs them once more, inside the
    backward rule's loop from the last segment to the first, and transposes
    that run."""
    args = scan_inputs(200, 1.0, seed=4)
    monkeypatch.setattr(kl, "KDA_SEGMENT", 8)
    whole, lowest = kl.kda_chunked(*args)
    monkeypatch.setattr(kl, "KDA_SEGMENT", 2)
    parts, lowest_parts = kl.kda_chunked(*args)
    assert rel(parts, whole) < 1e-6
    assert float(lowest) == float(lowest_parts)
    # the loops by (trips, reversed): the segments', and the chunks' inside a segment
    loops = lambda f: sorted((eqn.params["length"], eqn.params["reverse"])
                             for eqn in equations(jax.make_jaxpr(f)(*args).jaxpr)
                             if eqn.primitive.name == "scan")
    assert "custom_vjp_call" in str(jax.make_jaxpr(kl.kda_chunked)(*args))
    assert loops(kl.kda_chunked) == [(2, False), (4, False)]
    assert segment_runs(kl.kda_chunked, *args) == (1, 0)
    grad = jax.grad(lambda *a: jnp.sum(kl.kda_chunked(*a)[0]), argnums=(0, 1, 2, 3, 4))
    assert segment_runs(grad, *args) == (2, 1)
    assert loops(grad) == [(2, False), (2, False), (2, True), (4, False), (4, True)]


def checkpointed_scan(q, k, v, g, beta):
    """`kda_chunked` as it was before it had a derivative of its own: the same
    layout, each segment a `jax.checkpoint` under one `lax.scan`, which JAX
    differentiates. The oracle of the backward rule."""
    bsz, t, h, d = q.shape
    chunk = min(kl.KDA_CHUNK, -(-t // kl.KDA_SUBCHUNK) * kl.KDA_SUBCHUNK)
    segments = -(-t // (chunk * kl.KDA_SEGMENT))
    z = -(-t // (chunk * segments))
    pad = segments * z * chunk - t

    def layout(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x.reshape(bsz, segments, z, chunk, *x.shape[2:]), 4, 1)
        return jnp.moveaxis(x.reshape(bsz * h, segments, z, chunk, *x.shape[5:]), 1, 0)

    @jax.checkpoint
    def segment(state, xs):
        state, o, lowest = kl._kda_segment(state, *xs)
        return state, (o, lowest)

    _, (o, lowest) = jax.lax.scan(segment, jnp.zeros((bsz * h, d, d), kl.SCAN_STATE_DTYPE),
                                  tuple(layout(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, h, segments * z * chunk, d)
    return jnp.moveaxis(o, 1, 2)[:, :t], jax.lax.stop_gradient(jnp.min(lowest))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("t", [128, 200], ids=["whole_segments", "padded"])
def test_the_backward_rule_is_the_checkpointed_scans_gradient(t, dtype):
    """Both results and the gradients to q, k, v, g and beta against the form
    the rule replaces, at a length of two whole segments and at one whose last
    chunk is part padding and whose last segment half: the same operations in
    the same order, so the same bits, with q, k, v in float32 and in the
    cell's bfloat16 (g and beta are float32 in both)."""
    q, k, v, g, beta = scan_inputs(t, 1.0, seed=t)
    args = (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def both(f):
        def loss(*a):
            o, lowest = f(*a)
            return jnp.sum(o.astype(jnp.float32) * cot), (o, lowest)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)

    got, (o, lowest) = both(kl.kda_chunked)
    want, (want_o, want_lowest) = both(checkpointed_scan)
    assert o.dtype == want_o.dtype == dtype and jnp.array_equal(o, want_o)
    assert float(lowest) == float(want_lowest)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert float(jnp.linalg.norm(b.astype(jnp.float32))) > 0, name
        assert jnp.array_equal(a, b), (name, rel(a, b))


def test_the_second_result_has_no_gradient():
    """`kda_log_decay_min` is a counter: a loss that reads it moves nothing."""
    args = scan_inputs(64, 1.0, seed=6)
    grads = jax.grad(lambda *a: kl.kda_chunked(*a)[1], argnums=(0, 1, 2, 3, 4))(*args)
    assert all(float(jnp.max(jnp.abs(x))) == 0.0 for x in grads)


# ------------------------------------------- what a recomputed KDA layer keeps


def tiny_loss(dtype=None, remat=True, seed=7):
    w, ids = wk.to_program_params(wk.make_weights(seed, dataclasses.asdict(TINY))), ids_for(TINY, 2)
    return (lambda p: kl.lm_loss(p, ids, TINY, compute_dtype=dtype, remat=remat)), w


KDA_LAYERS = sum(m == "K" for m, _ in TINY.kinds)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no_remat"])
def test_the_recomputation_reads_the_delta_rules_results_and_does_not_run_it_again(remat):
    """The gradient's program holds two forward runs of a segment a KDA layer
    (the forward pass's, and the backward rule's own recomputation of the
    segment it transposes) and one transposed, recomputed or not; the records'
    counter says how many layers' recomputation read what the forward kept.
    Under the parent's policy, which keeps a layer's inputs alone, the layer's
    recomputation runs the segments a third time."""
    loss, params = tiny_loss(jnp.bfloat16, remat)
    grad = lambda: jax.grad(lambda p: loss(p)[0])     # a new function a count: a new trace
    assert segment_runs(grad(), params) == (2 * KDA_LAYERS, KDA_LAYERS)
    assert float(jax.jit(loss)(params)[1]["kda_forward_kept"]) == (KDA_LAYERS if remat else 0)
    with parents_policy():
        assert segment_runs(grad(), params) == ((3 if remat else 2) * KDA_LAYERS, KDA_LAYERS)


def test_the_kept_results_change_no_bit_of_a_gradient():
    """Against `run_stack` with the parent's policy: the same loss and the
    same gradient leaves, bit for bit, in float32 (in bfloat16 the CPU keeps a
    rebuilt intermediate in float32 where it fuses it with its consumer:
    `test_flash_attention.test_the_kept_results_change_no_bit_of_a_gradient`)."""
    loss, params = tiny_loss()
    value_and_grad = lambda: jax.jit(jax.value_and_grad(lambda p: loss(p)[0]))(params)
    got = value_and_grad()
    with parents_policy():
        want = value_and_grad()
    leaves = jax.tree_util.tree_leaves
    assert len(leaves(got)) == len(leaves(want)) > 60
    assert all(jnp.array_equal(a, b) for a, b in zip(leaves(got), leaves(want)))


def test_a_recomputed_kda_layer_keeps_the_output_and_the_entering_states(capsys):
    """Beyond the layer's input: o [B, T, H, D] in the compute type and the
    states entering the segments [segments, B x H, D, D] in float32, under
    their two names; none of q, k, v, g, beta and nothing of a segment's
    insides. With the parent's policy, neither."""
    cfg = dataclasses.replace(TINY, layer_offset=1, num_hidden_layers=1)   # one KDA layer, experts
    assert cfg.kinds == (("K", "E"),)
    params = kl.init_kimi_linear(jax.random.PRNGKey(3), cfg)
    ids = ids_for(cfg, 4)

    def saved():
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(lambda p: jnp.sum(kl.hidden_states(
            p, ids, cfg, compute_dtype=jnp.bfloat16, remat=True)[0].astype(jnp.float32)), params)
        return [line.split(" from /")[0] for line in capsys.readouterr().out.splitlines()
                if " from the argument " not in line and " from a constant" not in line]

    h, d, t = cfg.linear_num_heads, cfg.linear_head_dim, cfg.seq_len
    segments = -(-t // (32 * 2))
    inputs = [f"i32[2,{t},1] output of broadcast_in_dim",
              f"bf16[2,{t},{cfg.hidden_size}] output of convert_element_type"]
    # o, which the forward pass reads too (`after`, a recomputation of its own), comes out
    # of that and loses its name, as `flash_attention`'s output does
    assert saved() == inputs + [
        f"f32[{segments},{2 * h},{d},{d}] named '{hybrid_lm.KDA_KEPT_STATES}'",
        f"bf16[2,{t},{h},{d}] output of remat2"]
    with parents_policy():
        assert saved() == inputs


# ------------------------------------------------- causality, the latent attention


def logits_of(cfg, w):
    @jax.jit
    def logits(ids):
        x, _, _ = kl.hidden_states(w, ids, cfg)
        return jnp.einsum("btd,dv->btv", kl.rms_norm(x, w["final_norm"], cfg.rms_norm_eps),
                          w["head"])
    return logits


def test_no_position_sees_a_later_token():
    """Every token from position t on replaced: the logits before t stay what
    they were, bit for bit, and the logits at t do not. t inside a chunk, at a
    chunk's edge, at a sub-chunk's edge, and near the end: through the
    convolutions' taps, the chunks' lower triangles, the carried state and the
    latent attention's mask."""
    logits = logits_of(TINY, wk.to_program_params(wk.make_weights(23, dataclasses.asdict(TINY))))
    ids = ids_for(TINY, seed=41)
    base = logits(ids)
    for t in (7, 16, 32, 61, 64):
        later = ids.at[:, t:].set((ids[:, t:] + 1 + t) % TINY.vocab_size)
        got = logits(later)
        assert jnp.array_equal(got[:, :t], base[:, :t]), t
        assert not jnp.array_equal(got[:, t], base[:, t]), t


def test_the_latent_attention_has_no_positions():
    """One latent layer without its mask would be a set function; with the
    causal mask its output at the last position is unchanged when the tokens
    before it change places, which no rotation of q or k would allow."""
    p = wk.to_program_params(wk.make_weights(29, dataclasses.asdict(TINY)))["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 80, TINY.hidden_size))
    f = jax.jit(lambda x: kl.mla_mixer(p, x, TINY, None)[0])
    swapped = x.at[:, 10].set(x[:, 50]).at[:, 50].set(x[:, 10])
    assert rel(f(swapped)[:, -1], f(x)[:, -1]) < 1e-5
    assert rel(f(swapped)[:, 30], f(x)[:, 30]) > 1e-3      # position 30 saw 10 and not 50


# ----------------------------------------------------------------- the shares


def uncut(cfg=TINY, **over) -> KimiLinearConfig:
    return dataclasses.replace(cfg, num_experts=cfg.num_experts_total, expert_offset=0, **over)


def test_the_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """An expert layer's MLP half for all 4 chips of the tiny deployment
    (experts 0-3, 4-7, 8-11, 12-15), the shared expert counted once, against
    the reference's whole layer with all 16 held."""
    whole = uncut()
    model = dataclasses.asdict(whole)
    w = ref.layer_weights(wk.make_weights(7, model), 1)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 80, whole.hidden_size))
    u2 = kl.rms_norm(x, w["norm2"], whole.rms_norm_eps).reshape(-1, whole.hidden_size)
    total = kl.swiglu(u2, w["s_gate"], w["s_up"], w["s_down"], None)   # alike on every chip
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
    w = wk.make_weights(13, model)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, TINY.seq_len), 32, 64)
    whole = ref.logits(w, ids, model)
    rows = slice(32, 64)
    mine = dict(w, embed=w["embed"][rows], head=w["head"][:, rows])
    got = ref.logits(mine, ids - 32, dict(model, vocab_size=32))
    assert rel(got, whole[..., rows]) < 1e-6
    cfg = dataclasses.replace(TINY, vocab_size=32)
    assert rel(logits_of(cfg, wk.to_program_params(mine))(ids - 32), whole[..., rows]) < F32_TOL


# ------------------------------------------------------ configuration, weights


def test_the_presets():
    assert FULL.kinds == (("K", "D"), ("K", "E"), ("K", "E"), ("A", "E"), ("K", "E")) == TINY.kinds
    assert (FULL.num_experts, FULL.expert_offset, FULL.num_experts_total) == (8, 88, 256)
    assert FULL.vocab_size * 8 == 163840 and FULL.seq_len == 16384
    assert kl.param_count(FULL) == 602_433_408
    published = KimiLinearConfig()
    kda = [i + 1 for i, m in enumerate(published.layer_types) if m == "K"]
    assert kda == [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26]
    assert [i + 1 for i, m in enumerate(published.layer_types) if m == "A"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert [f for _, f in published.kinds] == ["D"] + ["E"] * 26
    # 49.1B: the published "48B"; 3.1B of them multiply a token (the 8 chosen experts, the
    # rest whole, the embedding's rows looked up): its "A3B"
    assert kl.param_count(published) == 49_122_675_072
    routed = 26 * 256 * 3 * 2304 * 1024
    embedding = 163840 * 2304
    assert round((kl.param_count(published) - routed * (1 - 8 / 256) - embedding) / 1e9, 1) == 3.1
    # a KDA mixer 39.5M, a latent mixer 29.1M: the issue's count
    mixer = lambda kind: hybrid_lm.count_shapes(kl.layer_shapes(kind, "D", FULL)) - (
        3 * 2304 * 9216 + 2304)
    assert (round(mixer("K") / 1e6, 1), round(mixer("A") / 1e6, 1)) == (39.5, 29.1)


@pytest.mark.parametrize("bad", [dict(num_hidden_layers=28), dict(layer_types="KKKX" * 6 + "KKA"),
                                 dict(layer_offset=25, num_hidden_layers=3),
                                 dict(expert_offset=250), dict(layer_types="KKKA")])
def test_a_share_that_does_not_fit_is_refused(bad):
    with pytest.raises(ValueError):
        KimiLinearConfig(**bad)


def test_the_flat_weights_and_the_programs_tree_are_one_to_one():
    model = dataclasses.asdict(TINY)
    w = wk.make_weights(1, model)
    shapes = jax.tree_util.tree_map(lambda x: x.shape, wk.to_program_params(w))
    assert shapes == kl.param_shapes(TINY)
    back = wk.from_program_params(wk.to_program_params(w))
    assert set(back) == set(w) and all(back[k] is w[k] for k in w)
    init = kl.init_kimi_linear(jax.random.PRNGKey(0), TINY)
    assert jax.tree_util.tree_map(lambda x: x.shape, init) == kl.param_shapes(TINY)
    assert float(jnp.std(init["layers"][1]["o"])) == pytest.approx(
        0.02 / np.sqrt(2 * TINY.num_hidden_layers_total), rel=0.1)
    # the recurrence's parameters, seeded alike by the program and by the benchmark
    for leaves in (init["layers"][0], ref.layer_weights(w, 0)):
        a = np.exp(np.asarray(leaves["A_log"]))
        dt = np.log1p(np.exp(np.asarray(leaves["dt_bias"])))
        assert 1.0 <= a.min() and a.max() <= 16.0
        assert 0.99e-3 < dt.min() and dt.max() < 1.01e-1
