"""`sambay.gated_mlp`, the gated part of SambaY's MLP (one joined gate/up
weight, gate_up rounded to the compute type as it leaves its product),
against the plain formula it was: the value bit for bit, the gradients of its
own backward rule against autodiff of the plain formula and against a float64
reference, the same under `run_stack`'s recomputation, and the lowered
backward: every product takes operands in the compute type, dy stands behind
a barrier, and the one elementwise pass that writes h, dgate and dup stands
behind a second that the three products after it read. `tests/test_swiglu.py`
holds the same for `laguna.swiglu`; its helpers are imported where they know
no function.

The widths are `phi4flash.train`'s ratio (hidden 2,560 x intermediate 10,240)
at a size the CPU holds, and two beside it so that no shape is special.

Tolerances. float32: the rule and autodiff differ by a product's summation
order (1e-6 of a leaf's norm). bfloat16, against the float64 gradient of the
same rounded inputs: here gate_up, h, dh, dgate and dup are bfloat16 arrays in
the plain formula's backward as well (the forward rounds gate_up and works in
the compute type from there), so the rule rounds nothing that autodiff did
not, and both read 4.1e-3 to 5.1e-3: the elementwise arithmetic in bfloat16
rounds three times an element where `laguna.swiglu`'s float32 pass rounds
once, hence BF16_TOL = 2^-7 where `test_swiglu.py` has 2^-8. The rule may
stand over the plain formula's own error by one operand's rounding, 2^-9, and
no further.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glom_tpu.models import hybrid_lm, sambay
from glom_tpu.models.hybrid_lm import _cast, _mm
from glom_tpu.utils.presets import get_preset
from tests.test_swiglu import F32_TOL, KEEP, ONE_ROUNDING, ROWS, eqns_of, rel

WIDTHS = {"phi4_mlp": (64, 256), "narrow": (64, 16), "odd": (72, 160)}
BF16_TOL = 2.0 ** -7


def plain(u, w_gate_up, w_down, dtype):
    """The gated part of `sambay.mlp` as it stood before it had a backward
    rule of its own."""
    gate, up = jnp.split(_mm(u, _cast(w_gate_up, dtype)).astype(u.dtype), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, _cast(w_down, dtype)).astype(u.dtype)


def inputs(widths, dtype, seed=0):
    d, f = WIDTHS[widths]
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    u = jax.random.normal(k[0], ROWS + (d,)).astype(dtype or jnp.float32)
    weights = (jax.random.normal(k[1], (d, 2 * f)) * d ** -0.5,
               jax.random.normal(k[2], (f, d)) * f ** -0.5)
    return (u, *weights), jax.random.normal(k[3], ROWS + (d,)).astype(u.dtype)


def grads(fn, args, dy, dtype, remat=False):
    call = lambda *xs: fn(*xs, dtype)
    if remat:
        call = jax.checkpoint(call, policy=KEEP)
    return jax.jit(jax.grad(lambda *xs: jnp.sum((call(*xs) * dy).astype(jnp.float32)),
                            argnums=(0, 1, 2)))(*args)


def float64_grads(args, dy, dtype):
    """The gradient of sum(out * dy) in float64, from the inputs as the
    products see them (u as it is, the weights cast to `dtype`)."""
    u, wgu, wd = (np.asarray(_cast(a, dtype).astype(jnp.float32), np.float64) for a in args)
    u, dy = u.reshape(-1, u.shape[-1]), np.asarray(dy.astype(jnp.float32), np.float64)
    dy = dy.reshape(u.shape)
    gate, up = np.split(u @ wgu, 2, axis=-1)
    sig = 1.0 / (1.0 + np.exp(-gate))
    dh = dy @ wd.T
    d_gate_up = np.concatenate([dh * up * sig * (1.0 + gate * (1.0 - sig)), dh * gate * sig], -1)
    return (d_gate_up @ wgu.T).reshape(args[0].shape), u.T @ d_gate_up, (gate * sig * up).T @ dy


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_the_value_is_the_plain_formulas_bit_for_bit(widths, dtype):
    args, _ = inputs(widths, dtype)
    ours = jax.jit(sambay.gated_mlp, static_argnums=3)(*args, dtype)
    theirs = jax.jit(plain, static_argnums=3)(*args, dtype)
    assert ours.dtype == theirs.dtype == args[0].dtype
    assert np.array_equal(np.asarray(ours, np.float32), np.asarray(theirs, np.float32))
    # under differentiation the forward rule runs: the same value again
    out = jax.jit(lambda *xs: jax.vjp(lambda *ys: sambay.gated_mlp(*ys, dtype), *xs)[0])(*args)
    assert np.array_equal(np.asarray(out, np.float32), np.asarray(theirs, np.float32))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputed"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_float32_gradients_are_autodiffs(widths, remat):
    args, dy = inputs(widths, None)
    ours, theirs = grads(sambay.gated_mlp, args, dy, None, remat), grads(plain, args, dy, None)
    for a, b, x in zip(ours, theirs, args):
        assert a.dtype == b.dtype == x.dtype == jnp.float32 and a.shape == x.shape
        assert rel(a, b) < F32_TOL


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputed"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_bfloat16_gradients_are_within_one_rounding_of_an_operand(widths, remat):
    args, dy = inputs(widths, jnp.bfloat16)
    ours = grads(sambay.gated_mlp, args, dy, jnp.bfloat16, remat)
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
    args, dy = inputs("phi4_mlp", dtype)
    for a, b in zip(grads(sambay.gated_mlp, args, dy, dtype, remat=True),
                    grads(sambay.gated_mlp, args, dy, dtype)):
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


# ----------------------------------------------------------- the lowered backward


def backward_jaxpr(widths, dtype, remat):
    args, dy = inputs(widths, dtype)
    call = lambda *xs: sambay.gated_mlp(*xs, dtype)
    _, pull = jax.vjp(jax.checkpoint(call, policy=KEEP) if remat else call, *args)
    return jax.make_jaxpr(pull)(dy).jaxpr, WIDTHS[widths]


@pytest.mark.parametrize("remat, products", [(False, 4), (True, 5)],
                         ids=["plain", "recomputed"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_every_product_of_the_backward_takes_two_bfloat16_operands(widths, remat, products):
    """dh, the two weights' gradients and du; under `run_stack`'s
    recomputation also the joined gate_up product, and not the down product,
    whose result the backward has no use for."""
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
    """Two barriers, counted as primitives of the jaxpr (`jax.checkpoint`
    lowers to barriers of its own, which are not in it). The first holds dy
    [B, T, d] as it arrives; dh's product and W_down's gradient read it from
    there. The second holds three arrays [B, T, f] in u's type: h, dgate,
    dup. No product's result goes into it. h is read by W_down's gradient
    alone; dgate and dup by the one join that makes [dgate, dup] [B, T, 2f]
    (which the compiler folds into its readers' operands, as it did
    autodiff's: a joined array behind the barrier cost a pass of its own),
    and that by W_gate_up's gradient and du's product alone. Before the
    barrier, dh's product and the recomputed one alone."""
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
    readers_of = lambda v: [e for e in eqns if any(x is v for x in e.invars)]
    h, dgate, dup = barrier.outvars
    (of_h,), (join,), (same,) = readers_of(h), readers_of(dgate), readers_of(dup)
    assert join is same and join.primitive.name == "concatenate"
    assert [v is s for v, s in zip(join.invars, (dgate, dup))] == [True, True]
    joined, = join.outvars
    assert (joined.aval.shape, joined.aval.dtype) == kind(2 * f)[0]
    of_joined = readers_of(joined)
    assert len(of_joined) == 2 and of_h not in of_joined
    assert all(e.primitive.name == "dot_general" for e in [of_h, *of_joined])
    at = eqns.index(barrier)
    before = [e for e in eqns[:at] if e.primitive.name == "dot_general"]
    assert len(before) == (2 if remat else 1)
    assert not any(e in before for e in [of_h, *of_joined])
    # dy: dh's product before the pass, and W_down's gradient, which also reads h
    of_dy = readers_of(first.outvars[0])
    assert len(of_dy) == 2 and of_dy[0] in before and of_dy[1] is of_h


# ------------------------------------------------------------------ the counter


def test_the_counter_counts_the_steps_layers(monkeypatch):
    """An MLP a layer, whatever its mixer: the tiny preset's six (the
    cell's: 6). The rule is the function's, so the count does not ask whether
    the layer is recomputed: here it is not, and `tests/test_sambay_train.py`
    reads the same number from the records of `fit` under recomputation."""
    monkeypatch.setattr(hybrid_lm, "ATTN_QUERY_BLOCK", 16)
    monkeypatch.setattr(hybrid_lm, "ATTN_KEY_BLOCK", 8)
    cfg = get_preset("sambay-tiny").model
    assert "mlp_backward_staged" in sambay.COUNTERS
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.seq_len), 0, cfg.vocab_size)
    counters = jax.jit(lambda p: sambay.lm_loss(p, ids, cfg, remat=False)[1])(
        sambay.init_sambay(jax.random.PRNGKey(0), cfg))
    assert float(counters["mlp_backward_staged"]) == cfg.num_hidden_layers == len(cfg.kinds)


def test_the_counter_reads_zero_where_no_mlp_runs():
    assert float(sambay.mlp_backward_staged([{}, {"attn_on_kernels": 1}])) == 0
    assert float(sambay.mlp_backward_staged([{"gated_mlp_calls": 1}, {}])) == 1


def test_the_mlp_is_the_plain_formula_of_the_norms_output():
    """`sambay.mlp` keeps its signature; what it computes is the plain formula
    of the layer norm's output, bit for bit (its scope is held with the
    vocabulary's, in `tests/test_sambay_train.py`)."""
    cfg = get_preset("sambay-tiny").model
    d, f = cfg.hidden_size, cfg.intermediate_size
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    p = {"norm2_w": 1.0 + 0.1 * jax.random.normal(k[0], (d,)), "norm2_b": jnp.full((d,), 0.05),
         "gate_up": jax.random.normal(k[1], (d, 2 * f)) * d ** -0.5,
         "down": jax.random.normal(k[2], (f, d)) * f ** -0.5}
    x = jax.random.normal(k[3], (2, 24, d)).astype(jnp.bfloat16)
    ours = jax.jit(lambda p, x: sambay.mlp(p, x, cfg, jnp.bfloat16))(p, x)
    theirs = jax.jit(lambda p, x: plain(
        sambay.layer_norm(x, p["norm2_w"], p["norm2_b"], cfg.layer_norm_eps),
        p["gate_up"], p["down"], jnp.bfloat16))(p, x)
    assert np.array_equal(np.asarray(ours, np.float32), np.asarray(theirs, np.float32))
