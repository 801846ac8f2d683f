"""Ouro (`models/ouro.py`) against its plain reference
(`benchmark/reference/ouro_ref.py`) on seeded weights at the tiny size: the
loss and every leaf's gradient; the loop (one pass is the plain stack, four
passes are an untied model of four copies, a looped leaf's gradient the sum of
the copies'); the exit distribution; causality at every pass; and what the
family shares with the older five, which has to lower to what it lowered to.

CPU only: what is checked is results and programs' text, never a time.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_ouro as wo
from benchmark.reference import ouro_ref as ref
from glom_tpu.models import evabyte, hybrid_lm, kimi_linear, laguna, ouro, sambay
from glom_tpu.utils.presets import get_preset

TINY = get_preset("ouro-tiny").model
LEAVES = sorted(wo.shapes(dataclasses.asdict(TINY)))
PASSES, LAYERS = TINY.total_ut_steps, TINY.num_hidden_layers


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def seeded(cfg, seed=5, length=None):
    model = dataclasses.asdict(cfg)
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, length or cfg.seq_len), dtype=np.int32)
    return model, wo.make_weights(seed, model), jnp.asarray(ids)


@pytest.fixture(scope="module")
def both():
    """(the reference's loss and gradients, the program's) on the tiny preset."""
    model, w, ids = seeded(TINY)
    with jax.default_matmul_precision("highest"):
        want = ref.loss_and_grads(w, ids, model)
        loss, grads = jax.value_and_grad(lambda p: ouro.lm_loss(p, ids, TINY)[0])(
            wo.to_program_params(w))
    return want, (loss, wo.from_program_params(grads))


def test_the_loss_is_the_references(both):
    (want, _), (got, _) = both
    assert abs(float(got) - float(want)) < 2e-6 * float(want)
    # near ln(vocabulary) less beta times an entropy of at most ln(passes)
    assert abs(float(want) - np.log(TINY.vocab_size)) < 0.1


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_is_the_references(both, leaf):
    (_, want), (_, got) = both
    assert got[leaf].shape == want[leaf].shape
    assert rel(got[leaf], want[leaf]) < 5e-6, leaf
    assert float(jnp.linalg.norm(want[leaf])) > 0      # the gate's two among them


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_fault_of_the_control_moves_the_references_first_gradient(both, fault):
    """What `control_ouro.py` puts in the program's place reads far from the
    sound reference at the tiny size already (the limits are the chip's)."""
    model, w, ids = seeded(TINY)
    (_, want), _ = both
    with jax.default_matmul_precision("highest"):
        _, got = ref.loss_and_grads(w, ids, model, fault=fault)
    assert max(rel(got[k], want[k]) for k in want) > 0.3


@pytest.mark.parametrize("length", [83, 33])
def test_a_row_of_any_length_goes_through_as_the_reference_has_it(length):
    model, w, ids = seeded(TINY, seed=7, length=length)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(w, ids, model)
        got = ouro.logits(wo.to_program_params(w), ids, TINY)
    assert got.shape == want.shape == (PASSES, 2, length, TINY.vocab_size)
    assert rel(got, want) < 2e-6


# ------------------------------------------------------------------- the loop


def test_one_pass_is_the_plain_stack_with_its_final_norm_and_todays_loss():
    """`total_ut_steps` 1: `run_stack` with one pass and the closing norm is
    the plain stack followed by the norm, and the loss is the
    plain next-token mean (the exit distribution is all on the one pass, its
    entropy zero)."""
    cfg = dataclasses.replace(TINY, total_ut_steps=1)
    _, w, ids = seeded(cfg)
    params = wo.to_program_params(w)
    closed, counted = ouro.hidden_states(params, ids, cfg)

    def held(p, x, side):
        return ouro.layer(p, x, cfg, None)[0], side, None

    x, _ = hybrid_lm.run_stack(params, ids, [held] * LAYERS)
    h = hybrid_lm.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    # (the scan's one trip is compiled whole, the plain loop runs op by op: a rounding apart)
    assert rel(closed[0], h) < 1e-6 and closed.shape[0] == 1 and len(counted) == LAYERS
    loss, counters = ouro.lm_loss(params, ids, cfg)
    plain = hybrid_lm.next_token_loss(h.reshape(-1, h.shape[-1]), params["head"], ids)
    assert abs(float(loss) - float(plain)) < 1e-6
    assert float(counters["exit_entropy"]) == 0.0 and float(counters["exit_mass_last"]) == 1.0


@pytest.fixture(scope="module")
def untied():
    """The looped model's gradient beside that of an untied model of PASSES x
    LAYERS layers and PASSES closing norms, written out as a plain loop and
    given copies of the looped weights."""
    _, w, ids = seeded(TINY, seed=9)
    params = wo.to_program_params(w)
    looped = jax.grad(lambda p: ouro.lm_loss(p, ids, TINY, remat=False)[0])(params)

    def untied_loss(copies, norms, rest):
        x = rest["embed"][ids]
        closed = []
        for layers, g_f in zip(copies, norms):
            for p in layers:
                x = ouro.layer(p, x, TINY, None)[0]
            x = hybrid_lm.rms_norm(x, g_f, TINY.rms_norm_eps)
            closed.append(x)
        return ouro.exit_weighed_loss(rest, jnp.stack(closed), ids, TINY)[0]

    copies = tuple(jax.tree_util.tree_map(jnp.copy, params["layers"]) for _ in range(PASSES))
    norms = tuple(jnp.copy(params["final_norm"]) for _ in range(PASSES))
    rest = {k: params[k] for k in ("embed", "head", "gate_w", "gate_b")}
    loss = untied_loss(copies, norms, rest)
    return params, ids, looped, loss, jax.grad(untied_loss, argnums=(0, 1, 2))(copies, norms, rest)


def test_four_passes_are_an_untied_model_of_four_copies(untied):
    params, ids, _, loss, _ = untied
    assert abs(float(ouro.lm_loss(params, ids, TINY)[0]) - float(loss)) < 1e-6


@pytest.mark.parametrize("leaf", sorted(ouro.layer_shapes(TINY)) + ["final_norm"])
def test_a_looped_leafs_gradient_is_the_sum_of_its_copies(untied, leaf):
    _, _, looped, _, (g_copies, g_norms, _) = untied
    if leaf == "final_norm":
        assert rel(looped[leaf], sum(g_norms)) < 5e-6
        return
    for i in range(LAYERS):
        uses = [g_copies[t][i][leaf] for t in range(PASSES)]
        assert rel(looped["layers"][i][leaf], sum(uses)) < 5e-6
        # no one use is the whole of it: the last pass alone is another gradient
        assert rel(looped["layers"][i][leaf], uses[-1]) > 0.1


def test_the_unlooped_leaves_gradients_are_the_untied_models(untied):
    _, _, looped, _, (_, _, g_rest) = untied
    for leaf, g in g_rest.items():
        # (the gate's two are sums of terms that nearly cancel)
        assert rel(looped[leaf], g) < (2e-5 if leaf.startswith("gate") else 5e-6), leaf


def test_recomputation_changes_no_gradient(untied):
    params, ids, looped, _, _ = untied
    remat = jax.grad(lambda p: ouro.lm_loss(p, ids, TINY, remat=True)[0])(params)
    for a, b in zip(jax.tree_util.tree_leaves(remat), jax.tree_util.tree_leaves(looped)):
        assert rel(a, b) < 5e-6


def test_the_lowered_gradient_holds_one_leaf_a_looped_weight():
    """The parameters of the lowered gradient are the tree's leaves, each
    once: LAYERS query matrices go in and LAYERS gradients of their shape
    come out, whatever the passes; and every application is a checkpoint of
    its own (LAYERS of them in the body of the scan over the passes)."""
    _, w, ids = seeded(TINY)
    params = wo.to_program_params(w)
    grad = jax.grad(lambda p: ouro.lm_loss(p, ids, TINY)[0])
    lowered = jax.jit(grad).lower(params)
    text = lowered.as_text()
    main = re.search(r"func\.func public @main\((.*?)\) -> \((.*?)\) \{", text, re.S)
    q = "tensor<%dx%dxf32>" % ouro.layer_shapes(TINY)["q"]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    assert n_leaves == LAYERS * 11 + 5
    assert main.group(1).count("%arg") == n_leaves
    # q, k, v, o share a shape here: four leaves a layer in, four gradients a layer out
    assert main.group(1).count(q) == main.group(2).count(q) == 4 * LAYERS
    jaxpr = jax.make_jaxpr(lambda p: ouro.lm_loss(p, ids, TINY)[0])(params)
    # the loop is one scan of PASSES trips whose body checkpoints each layer under
    # `run_stack`'s policy (the loss blocks' and the XLA loop's checkpoints carry none)
    loop, blocks = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert loop.params["length"] == PASSES and blocks.params["length"] == 1    # the loss's rows
    remats = [e for e in loop.params["jaxpr"].jaxpr.eqns if e.primitive.name == "remat2"
              and e.params["policy"] is not None]
    assert len(remats) == LAYERS


# ------------------------------------------------------- the exit distribution


def test_the_exit_distribution_sums_to_one_and_is_the_products_written_out():
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (PASSES, 50))
    p = jnp.exp(ouro.exit_distribution(z))
    assert np.allclose(np.asarray(p.sum(axis=0)), 1.0, atol=2e-6) and bool((p > 0).all())
    assert rel(p, ref.exit_distribution(jax.nn.sigmoid(z))) < 2e-6
    lam = jax.nn.sigmoid(z)
    assert np.allclose(np.asarray(p[0]), np.asarray(lam[0]), rtol=1e-5)
    assert np.allclose(np.asarray(p[-1]), np.asarray(jnp.prod(1 - lam[:-1], axis=0)), rtol=1e-5)
    # the last pass's own lambda is read by nothing
    assert jnp.array_equal(ouro.exit_distribution(z.at[-1].add(5.0)), ouro.exit_distribution(z))


def test_the_gates_gradient_comes_through_the_weights_of_the_passes_losses(both):
    """The gate's two leaves get a gradient (through p(t) as the weights of
    `next_token_loss` and through the entropy term), and it is the
    reference's."""
    (_, want), (_, got) = both
    for leaf in ("gate_w", "gate_b"):
        assert float(jnp.linalg.norm(got[leaf])) > 0 and rel(got[leaf], want[leaf]) < 5e-6
    # without the entropy term the gradient is another: both paths carry some of it
    model, w, ids = seeded(TINY)
    cfg = dataclasses.replace(TINY, exit_entropy_beta=0.0)
    g = jax.grad(lambda p: ouro.lm_loss(p, ids, cfg)[0])(wo.to_program_params(w))
    assert float(jnp.linalg.norm(g["gate_w"])) > 0
    assert rel(g["gate_w"], got["gate_w"]) > 0.05


@pytest.mark.parametrize("scale", [1.0, 60.0])
def test_a_gate_that_has_made_up_its_mind_leaves_both_losses_finite_and_alike(scale):
    """Three steps of Adam at the cell's size drive |h . w_gate| past 20: a
    sigmoid rounds to 1 in float32 and the later passes' mass to 0. The
    program's logarithms and the reference's plain products (0 log 0 = 0)
    give the same loss and the same gradient to the gate and to the states."""
    model, w, ids = seeded(TINY, seed=13)
    w = dict(w, gate_w=scale * w["gate_w"])
    params = wo.to_program_params(w)
    closed, _ = ouro.hidden_states(params, ids, TINY)
    z = ouro.gate_logits(params, closed)
    assert (float(jnp.abs(z).max()) > 40) == (scale > 1)

    def program(gate_w, closed):
        return ouro.exit_weighed_loss(dict(params, gate_w=gate_w), closed, ids, TINY)[0]

    def reference(gate_w, closed):
        one = lambda hs, row: ref.sequence_loss(w["head"], gate_w, w["gate_b"], hs, row, model)
        return jnp.sum(jax.vmap(one, in_axes=(1, 0))(closed, ids)) / (ids.shape[0] * (
            ids.shape[1] - 1))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(program, argnums=(0, 1))(w["gate_w"], closed)
        want = jax.value_and_grad(reference, argnums=(0, 1))(w["gate_w"], closed)
    assert np.isfinite(float(want[0])) and abs(float(got[0]) - float(want[0])) < 2e-6 * float(
        want[0])
    for a, b in zip(got[1], want[1]):
        assert bool(jnp.isfinite(b).all()) and rel(a, b) < 2e-5


# ------------------------------------------------------------------ causality


@pytest.mark.parametrize("t", [1, 31, 64, 79])
def test_no_position_sees_a_later_token_at_any_pass(t):
    """Trap 12's test: when every token from t on changes, the logits before
    t keep every bit at every pass (pass t + 1 reads pass t's stream position
    by position, and attention is causal at every pass), and those at t
    change."""
    _, w, ids = seeded(TINY, seed=11)
    params = wo.to_program_params(w)
    base = ouro.logits(params, ids, TINY)
    later = ids.at[:, t:].set((ids[:, t:] + 1 + t % 3) % TINY.vocab_size)
    got = ouro.logits(params, later, TINY)
    for n in range(PASSES):
        assert jnp.array_equal(got[n, :, :t], base[n, :, :t]), n
        assert not jnp.array_equal(got[n, :, t], base[n, :, t]), n


# ------------------------------------------------------ what the family shares


def test_unit_weights_are_todays_mean_and_no_weights_are_todays_program():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    h, head = jax.random.normal(ks[0], (2 * 2100, 32)), jax.random.normal(ks[1], (32, 50))
    ids = jax.random.randint(ks[2], (2, 2100), 0, 50)       # two row blocks of the loss
    weights = jax.random.uniform(ks[3], (2 * 2100,))
    plain = jax.jit(jax.value_and_grad(hybrid_lm.next_token_loss, argnums=(0, 1)))(h, head, ids)
    unit = jax.jit(jax.value_and_grad(
        lambda h, head: hybrid_lm.next_token_loss(h, head, ids, weights=jnp.ones_like(weights)),
        argnums=(0, 1)))(h, head)
    assert all(jnp.array_equal(a, b) for a, b in zip(jax.tree_util.tree_leaves(unit),
                                                     jax.tree_util.tree_leaves(plain)))
    # weighed: the sum of w_i CE_i over the positions with a next token, over their count
    logp = jax.nn.log_softmax(h @ head, axis=-1).reshape(2, 2100, 50)
    ce = -jnp.take_along_axis(logp[:, :-1], ids[:, 1:, None], axis=-1)[..., 0]
    want = jnp.sum(ce * weights.reshape(2, 2100)[:, :-1]) / (2 * 2099)
    got, g = jax.value_and_grad(
        lambda wt: hybrid_lm.next_token_loss(h, head, ids, weights=wt))(weights)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    assert rel(g.reshape(2, 2100)[:, :-1], ce / (2 * 2099)) < 1e-5     # d loss / d w_i = CE_i / n
    assert not g.reshape(2, 2100)[:, -1].any()


def run_stack_before_the_loop(params, ids, layers, *, compute_dtype=None, remat=True, side=None):
    """`hybrid_lm.run_stack` as it stood before it took passes."""
    with jax.named_scope("embed"):
        x = hybrid_lm._cast(params["embed"][ids], compute_dtype)
    keep = jax.checkpoint_policies.save_only_these_names(*hybrid_lm.KEPT_NAMES)
    aux = []
    for f, p in zip(layers, params["layers"]):
        x, side, a = (jax.checkpoint(f, policy=keep) if remat else f)(p, x, side)
        aux.append(a)
    return x, aux


def lowered(f, *args):
    """The program's text without locations and without the functions' names."""
    text = jax.jit(f).lower(*args).compiler_ir().operation.get_asm(enable_debug_info=False)
    return re.sub(r"@[\w.]+", "@f", text)


FIVE = {"hybrid_lm": (hybrid_lm, hybrid_lm.init_hybrid_lm, "hybrid-lm-tiny"),
        "sambay": (sambay, sambay.init_sambay, "sambay-tiny"),
        "laguna": (laguna, laguna.init_laguna, "laguna-tiny"),
        "kimi_linear": (kimi_linear, kimi_linear.init_kimi_linear, "kimi-linear-tiny"),
        "evabyte": (evabyte, evabyte.init_evabyte, "evabyte-tiny")}


@pytest.mark.parametrize("family", sorted(FIVE))
def test_the_five_families_steps_lower_to_what_they_lowered_to(family, monkeypatch):
    """With the stack as it stood before the loop put back in its place, a
    family's gradient lowers to the same text, locations and names apart: one
    pass and nothing between is the program the five had."""
    model, init, preset = FIVE[family]
    cfg = get_preset(preset).model
    params = init(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.seq_len), 0, cfg.vocab_size)
    grad = jax.grad(lambda p: model.lm_loss(p, ids, cfg, compute_dtype=jnp.bfloat16)[0])
    now = lowered(grad, params)
    monkeypatch.setattr(model, "run_stack", run_stack_before_the_loop)
    assert lowered(grad, params) == now


def test_the_model_copies_nothing_of_the_shared_stack():
    """`models/ouro.py` defines no norm, product, attention, SwiGLU or
    rotation of its own: the names are the shared modules' objects."""
    assert ouro.rms_norm is hybrid_lm.rms_norm and ouro.run_stack is hybrid_lm.run_stack
    assert ouro.blocked_attention is hybrid_lm.blocked_attention
    assert ouro.next_token_loss is hybrid_lm.next_token_loss
    assert ouro.swiglu is laguna.swiglu and ouro.rope is evabyte.rope
    source = open(ouro.__file__).read()
    assert not re.search(r"rsqrt|softmax|silu|\bcos\b|\bsin\b", source.split('"""', 2)[2])
