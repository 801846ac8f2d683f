"""`laguna.swiglu`, the dense SwiGLU that three families call (Laguna's and
Kimi Linear's dense layer and shared experts, EvaByte's MLPs), against the
plain formula it was: the value bit for bit, the gradients of its own
backward rule against autodiff of the plain formula and against a float64
reference, the same under `run_stack`'s recomputation, and the lowered
backward: every product takes operands in the compute type, dy stands behind
a barrier, and the one elementwise pass that writes h, dgate and dup stands
behind a second that the five products after it read.

The widths are the three cells' (hidden, intermediate) ratios at a size the
CPU holds: `evabyte.train` 4,096 x 11,008; `lagunaxs2.train` 2,048 x 8,192
dense and 2,048 x 512 shared; `kimilinear.train` 2,304 x 9,216 dense and
2,304 x 1,024 shared.

Tolerances. float32: the rule and autodiff differ by a product's summation
order (1e-6 of a leaf's norm). bfloat16, against the float64 gradient of the
same rounded inputs: an operand's rounding to bfloat16 is at most 2^-9 of
each element, some 2^-9 / sqrt(3) = 1.1e-3 of a norm; the plain formula's
backward rounds u, the weights, dh and h, the rule dgate and dup as well
(which the chip's default precision does to the plain formula's float32
operands too, and the CPU's does not). BF16_TOL = 2^-8 holds both, and the
rule may stand over the plain formula's own error by one more operand's
rounding, 2^-9, and no further.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glom_tpu.models import evabyte, hybrid_lm, kimi_linear, laguna
from glom_tpu.models.hybrid_lm import _cast, _mm
from glom_tpu.utils.presets import get_preset

WIDTHS = {"evabyte_mlp": (64, 172), "laguna_dense": (64, 256), "laguna_shared": (64, 16),
          "kimi_dense": (72, 288), "kimi_shared": (72, 32)}
ROWS = (2, 48)          # [B, T]
F32_TOL, BF16_TOL, ONE_ROUNDING = 1e-6, 2.0 ** -8, 2.0 ** -9
KEEP = jax.checkpoint_policies.save_only_these_names(*hybrid_lm.KEPT_NAMES)  # run_stack's


def plain(u, w_gate, w_up, w_down, dtype):
    """The function as it stood before it had a backward rule of its own."""
    h = jax.nn.silu(_mm(u, _cast(w_gate, dtype))) * _mm(u, _cast(w_up, dtype))
    return _mm(h.astype(u.dtype), _cast(w_down, dtype)).astype(u.dtype)


def inputs(widths, dtype, seed=0):
    d, f = WIDTHS[widths]
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = jax.random.normal(k[0], ROWS + (d,)).astype(dtype or jnp.float32)
    weights = (jax.random.normal(k[1], (d, f)) * d ** -0.5,
               jax.random.normal(k[2], (d, f)) * d ** -0.5,
               jax.random.normal(k[3], (f, d)) * f ** -0.5)
    return (u, *weights), jax.random.normal(k[4], ROWS + (d,)).astype(u.dtype)


def grads(fn, args, dy, dtype, remat=False):
    call = lambda *xs: fn(*xs, dtype)
    if remat:
        call = jax.checkpoint(call, policy=KEEP)
    return jax.jit(jax.grad(lambda *xs: jnp.sum((call(*xs) * dy).astype(jnp.float32)),
                            argnums=(0, 1, 2, 3)))(*args)


def float64_grads(args, dy, dtype):
    """The gradient of sum(out * dy) in float64, from the inputs as the
    products see them (u as it is, the weights cast to `dtype`)."""
    u, wg, wu, wd = (np.asarray(_cast(a, dtype).astype(jnp.float32), np.float64) for a in args)
    u, dy = u.reshape(-1, u.shape[-1]), np.asarray(dy.astype(jnp.float32), np.float64)
    dy = dy.reshape(u.shape)
    gate, up = u @ wg, u @ wu
    sig = 1.0 / (1.0 + np.exp(-gate))
    dh = dy @ wd.T
    dgate, dup = dh * up * sig * (1.0 + gate * (1.0 - sig)), dh * gate * sig
    du = dgate @ wg.T + dup @ wu.T
    return du.reshape(args[0].shape), u.T @ dgate, u.T @ dup, (gate * sig * up).T @ dy


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_the_value_is_the_plain_formulas_bit_for_bit(widths, dtype):
    args, _ = inputs(widths, dtype)
    ours, theirs = jax.jit(laguna.swiglu, static_argnums=4)(*args, dtype), plain(*args, dtype)
    assert ours.dtype == theirs.dtype == args[0].dtype
    assert np.array_equal(np.asarray(ours, np.float32), np.asarray(theirs, np.float32))
    # under differentiation the forward rule runs: the same value again
    out, _ = jax.vjp(lambda *xs: laguna.swiglu(*xs, dtype), *args)
    assert np.array_equal(np.asarray(out, np.float32), np.asarray(theirs, np.float32))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputed"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_float32_gradients_are_autodiffs(widths, remat):
    args, dy = inputs(widths, None)
    ours, theirs = grads(laguna.swiglu, args, dy, None, remat), grads(plain, args, dy, None)
    for a, b, x in zip(ours, theirs, args):
        assert a.dtype == b.dtype == x.dtype == jnp.float32 and a.shape == x.shape
        assert rel(a, b) < F32_TOL


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputed"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_bfloat16_gradients_are_within_one_rounding_of_an_operand(widths, remat):
    args, dy = inputs(widths, jnp.bfloat16)
    ours = grads(laguna.swiglu, args, dy, jnp.bfloat16, remat)
    theirs = grads(plain, args, dy, jnp.bfloat16)
    exact = float64_grads(args, dy, jnp.bfloat16)
    for a, b, x, e in zip(ours, theirs, args, exact):
        assert a.dtype == b.dtype == x.dtype and a.shape == x.shape
        if a.dtype == jnp.bfloat16:     # du's own rounding on the way out, in both
            e = np.asarray(jnp.asarray(e, jnp.float32).astype(jnp.bfloat16), np.float64)
        assert rel(a, e) < BF16_TOL
        assert rel(a, e) < rel(b, e) + ONE_ROUNDING


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_recomputation_changes_no_bit_of_a_gradient(dtype):
    args, dy = inputs("evabyte_mlp", dtype)
    for a, b in zip(grads(laguna.swiglu, args, dy, dtype, remat=True),
                    grads(laguna.swiglu, args, dy, dtype)):
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


# ----------------------------------------------------------- the lowered backward


def eqns_of(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns_of(sub)


def backward_jaxpr(widths, dtype, remat):
    args, dy = inputs(widths, dtype)
    call = lambda *xs: laguna.swiglu(*xs, dtype)
    _, pull = jax.vjp(jax.checkpoint(call, policy=KEEP) if remat else call, *args)
    return jax.make_jaxpr(pull)(dy).jaxpr, WIDTHS[widths]


@pytest.mark.parametrize("remat, products", [(False, 6), (True, 8)],
                         ids=["plain", "recomputed"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_every_product_of_the_backward_takes_two_bfloat16_operands(widths, remat, products):
    """dh, the three weights' gradients and du's two; under `run_stack`'s
    recomputation also gate's and up's, and not the down product, whose
    result the backward has no use for."""
    jaxpr, _ = backward_jaxpr(widths, jnp.bfloat16, remat)
    dots = [e for e in eqns_of(jaxpr) if e.primitive.name == "dot_general"]
    assert len(dots) == products
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16, jnp.bfloat16]
        assert e.params["preferred_element_type"] == jnp.float32


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputed"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_the_barriers_stand_between_the_operands_and_the_products(widths, remat, dtype):
    """Two barriers. The first holds dy [B, T, d] as it arrives; dh's product
    and W_down's gradient read it from there. The second holds three arrays
    [B, T, f] in u's type: h, dgate, dup. What goes into it is the
    elementwise pass's (a rounding, or in float32 the arithmetic itself: no
    product's result); what comes out is read by the five products after dh
    and by nothing else, each of them taking one of the three as it left the
    barrier. Before it, dh's product and the recomputed two alone."""
    jaxpr, (d, f) = backward_jaxpr(widths, dtype, remat)
    eqns = list(eqns_of(jaxpr))
    barriers = [e for e in eqns if e.primitive.name == "optimization_barrier"]
    assert [len(e.outvars) for e in barriers] == [1, 3]
    first, barrier = barriers
    kind = lambda rows: [(ROWS + (rows,), dtype or jnp.float32)]
    assert [(v.aval.shape, v.aval.dtype) for v in first.outvars] == kind(d)
    assert [(v.aval.shape, v.aval.dtype) for v in barrier.outvars] == kind(f) * 3
    made_by = {id(v): e.primitive.name for e in eqns for v in e.outvars}
    assert all(made_by[id(v)] != "dot_general" for v in barrier.invars)
    if dtype is not None:
        assert all(made_by[id(v)] == "convert_element_type" for v in barrier.invars)
    reads = lambda e, held: [sum(v is s for v in e.invars) for s in held.outvars]
    readers = [e for e in eqns if any(reads(e, barrier))]       # of h, dgate, dup
    assert len(readers) == 5 and all(e.primitive.name == "dot_general" for e in readers)
    assert all(sum(reads(e, barrier)) == 1 for e in readers)
    # h feeds W_down's gradient, dgate and dup a weight's gradient and a term of du each
    assert [sum(reads(e, barrier)[i] for e in readers) for i in range(3)] == [1, 2, 2]
    at = eqns.index(barrier)
    before = [e for e in eqns[:at] if e.primitive.name == "dot_general"]
    assert len(before) == (3 if remat else 1) and not any(e in readers for e in before)
    # dy: dh's product before the pass, and W_down's gradient, which also reads h
    of_dy = [e for e in eqns if any(reads(e, first))]
    assert len(of_dy) == 2 and all(e.primitive.name == "dot_general" for e in of_dy)
    assert sorted(sum(reads(e, barrier)) for e in of_dy) == [0, 1] and of_dy[0] in before


# ------------------------------------------------------------------ the counter


FAMILIES = {"evabyte": (evabyte, "init_evabyte", "evabyte-tiny"),
            "laguna": (laguna, "init_laguna", "laguna-tiny"),
            "kimi_linear": (kimi_linear, "init_kimi_linear", "kimi-linear-tiny")}


@pytest.mark.parametrize("family, calls", [("evabyte", 3), ("laguna", 5), ("kimi_linear", 5)])
def test_the_counter_counts_the_steps_swiglu_calls(family, calls, monkeypatch):
    """A dense MLP or a shared expert a layer: the tiny EvaByte's three
    layers, the tiny Laguna's and Kimi Linear's dense layer and four shared
    experts (the cells': 4, 5, 5). The rule is the function's, so the count
    does not ask whether the layer is recomputed: here it is not, and the
    families' `test_*_train.py` read the same numbers from the records of
    `fit` under recomputation."""
    monkeypatch.setattr(hybrid_lm, "ATTN_QUERY_BLOCK", 16)
    monkeypatch.setattr(hybrid_lm, "ATTN_KEY_BLOCK", 8)
    module, init, preset = FAMILIES[family]
    cfg = get_preset(preset).model       # the model of `benchmark/tests/tiny_*.py`'s cell
    assert "swiglu_backward_staged" in module.COUNTERS
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.seq_len), 0, cfg.vocab_size)
    counters = jax.jit(lambda p: module.lm_loss(p, ids, cfg, remat=False)[1])(
        getattr(module, init)(jax.random.PRNGKey(0), cfg))
    assert float(counters["swiglu_backward_staged"]) == calls == cfg.num_hidden_layers


def test_the_counter_reads_zero_where_no_swiglu_runs():
    assert float(laguna.swiglu_backward_staged([{}, {"attn_on_kernels": 1}])) == 0
    assert float(laguna.swiglu_backward_staged([{"swiglu_calls": 1}, {}])) == 1
