"""The SambaY language model (models/sambay.py) against its plain reference
(benchmark/reference/sambay_ref.py) at a size the CPU holds: hidden 64, 8
heads over 4 KV heads of 8, window 16, 80 tokens, the whole rule at N = 8
(MWMWMFGX) and a stage that starts above layer 0. The loss and every gradient
leaf; the chunked selective scan against the recurrence a position at a
time; the window that is skipped against the window that is masked; the
memory and the shared keys and values as the only way to a gradient; the
rule's 32 kinds written out; the vocabulary's shares.

The query block is cut to 16, the key block to 8 and the scan's chunk to 32
positions in segments of 8 for these tests, so that 80 tokens are five query
blocks, the window layer slices keys away and the scan carries its state over
three chunks.

Tolerances: float32 against the float32 reference differs by summation
order only (5e-5 of a leaf's scale; the scan multiplies decays in another
order; 5e-4 for the lambda vectors, whose gradients are what is left when two
nearly equal attention outputs cancel). In bfloat16 the program rounds every
product's operands and the residual stream to 8 bits of mantissa; over eight
layers that comes to under 3% of a gradient leaf's scale, which is what is
allowed (the lambda vectors get 20%).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_sambay as ws
from benchmark.reference import sambay_ref as ref
from glom_tpu.models import hybrid_lm, sambay
from glom_tpu.utils.config import SambaYConfig, layer_kind
from glom_tpu.utils.presets import get_preset

TINY = get_preset("sambay-tiny").model
STAGE = dataclasses.replace(TINY, layer_offset=2, num_hidden_layers=6)   # MWMFGX of N = 8
F32_TOL, F32_LAMBDA_TOL, BF16_TOL, BF16_LAMBDA_TOL = 5e-5, 5e-4, 0.03, 0.2


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(hybrid_lm, "ATTN_QUERY_BLOCK", 16)
    monkeypatch.setattr(hybrid_lm, "ATTN_KEY_BLOCK", 8)
    monkeypatch.setattr(sambay, "SCAN_CHUNK", 32)
    monkeypatch.setattr(sambay, "SCAN_SEGMENT", 8)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    monkeypatch.setattr(ref, "SCAN_BLOCK", 16)


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def ids_for(cfg, seed=0, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, cfg.seq_len), 0, cfg.vocab_size)


def program_grads(cfg, w, ids, dtype=None, remat=True):
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: sambay.lm_loss(p, ids, cfg, compute_dtype=dtype, remat=remat),
        has_aux=True))(ws.to_program_params(w))
    return float(loss), ws.from_program_params(grads), counters


# ------------------------------------------------- the stack against the reference


@pytest.mark.parametrize("cfg", [TINY, STAGE], ids=["the_whole_rule_at_n8", "a_stage_from_layer_2"])
def test_the_loss_and_every_gradient_leaf_match_the_reference(cfg):
    model = dataclasses.asdict(cfg)
    w, ids = ws.make_weights(3, model), ids_for(cfg)
    loss, grads, counters = program_grads(cfg, w, ids)
    want_loss, want = ref.loss_and_grads(w, ids, model)
    assert abs(loss - float(want_loss)) < 1e-5 * float(want_loss)
    assert set(grads) == set(want) == set(ws.shapes(model))
    scale = float(np.median([np.linalg.norm(v) for v in want.values()]))
    for name in want:
        gap = float(np.linalg.norm(np.asarray(grads[name]) - np.asarray(want[name])))
        tol = F32_LAMBDA_TOL if ".lambda_" in name else F32_TOL
        assert gap < tol * max(float(np.linalg.norm(want[name])), scale), name
        assert float(np.linalg.norm(want[name])) > 0, name   # no leaf is off the path
    # 80 tokens in query blocks of 16, key blocks of 8: a full-length layer
    # multiplies 2 + 4 + 6 + 8 + 10 key blocks, the window layer 2 + 4 x 4
    # (15 keys before a block's first and its own 16: 31 keys)
    n_window, n_full = cfg.kinds.count("W"), cfg.kinds.count("F") + cfg.kinds.count("X")
    assert float(counters["attn_key_blocks_full"]) == 30 * n_full
    assert float(counters["attn_key_blocks_window"]) == 18 * n_window
    assert float(counters["scan_chunks"]) == 3   # 80 positions in chunks of 32


def test_bfloat16_stays_within_its_band_of_float32():
    model = dataclasses.asdict(TINY)
    w, ids = ws.make_weights(5, model), ids_for(TINY, 1)
    loss32, g32, _ = program_grads(TINY, w, ids)
    loss16, g16, _ = program_grads(TINY, w, ids, dtype=jnp.bfloat16)
    assert abs(loss16 - loss32) < 2e-3 * loss32
    scale = float(np.median([np.linalg.norm(v) for v in g32.values()]))
    for name in g32:
        tol = BF16_LAMBDA_TOL if ".lambda_" in name else BF16_TOL
        gap = float(np.linalg.norm(np.asarray(g16[name], np.float32) - np.asarray(g32[name])))
        assert gap < tol * max(float(np.linalg.norm(g32[name])), scale), name


def test_recomputation_changes_nothing():
    w, ids = ws.make_weights(7, dataclasses.asdict(TINY)), ids_for(TINY, 2)
    loss_a, grads_a, _ = program_grads(TINY, w, ids, remat=True)
    loss_b, grads_b, _ = program_grads(TINY, w, ids, remat=False)
    assert loss_a == loss_b
    for name in grads_a:
        assert rel(grads_a[name], grads_b[name]) < 1e-6, name


# ------------------------------------------------------------ the selective scan


def scan_inputs(t, seed=0, bsz=2, ch=12, n=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (bsz, t, ch))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, t, ch)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (ch, n)))
    return x, dt, a, jax.random.normal(ks[3], (bsz, t, n)), jax.random.normal(ks[4], (bsz, t, n))


def a_position_at_a_time(x, dt, a, b, c):
    return jax.vmap(lambda x, dt, b, c: ref.recurrence(x, dt, a, b, c))(x, dt, b, c)


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("t", [64, 61, 32, 5, 130])   # chunk 32, segment 8
def test_the_chunked_scan_is_the_recurrence_a_position_at_a_time(t, what):
    args = scan_inputs(t, seed=t)
    chunked = lambda *v: sambay.selective_scan(*v)[0]
    if what == "forward":
        y, chunks = sambay.selective_scan(*args)
        assert chunks == -(-t // 32) and rel(y, a_position_at_a_time(*args)) < 2e-6
        return
    cot = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = jax.grad(lambda *v: jnp.sum(chunked(*v) * cot), argnums=range(5))(*args)
    want = jax.grad(lambda *v: jnp.sum(a_position_at_a_time(*v) * cot), argnums=range(5))(*args)
    for g, w_, name in zip(got, want, "x dt a b c".split()):
        assert rel(g, w_) < 1e-5, name


def test_the_scans_schedule_changes_nothing(monkeypatch):
    args = scan_inputs(96, seed=4)
    base = sambay.selective_scan(*args)[0]                        # chunk 32, segment 8

    def scheduled(chunk, segment):
        monkeypatch.setattr(sambay, "SCAN_CHUNK", chunk)
        monkeypatch.setattr(sambay, "SCAN_SEGMENT", segment)
        return sambay.selective_scan(*args)[0]

    for chunk, segment in ((96, 96), (16, 2), (48, 16), (1024, 32)):  # the last: the module's
        assert rel(scheduled(chunk, segment), base) < 2e-6
    with pytest.raises(ValueError):
        scheduled(30, 8)


@pytest.mark.parametrize("cfg", [TINY, STAGE], ids=["the_whole_rule_at_n8", "a_stage_from_layer_2"])
def test_no_position_sees_a_later_token(cfg):
    """Every token from position t on replaced: the logits before t, which
    are what predicts tokens up to t, stay what they were, bit for bit, and
    the logits at t do not (position t's own token is its input). t inside a
    query block, at a block's edge, and at a scan chunk's edge. This is what
    says that a loss far under ln(vocabulary) on batches seen before is the
    batches learnt by heart (PERF.md section 7, trap 12)."""
    w = ws.to_program_params(ws.make_weights(23, dataclasses.asdict(cfg)))

    @jax.jit
    def logits(ids):
        x, _ = sambay.hidden_states(w, ids, cfg)
        h = sambay.layer_norm(x, w["final_norm_w"], w["final_norm_b"], cfg.layer_norm_eps)
        return jnp.einsum("btd,vd->btv", h, w["embed"])

    ids = ids_for(cfg, seed=41)
    base = logits(ids)
    for t in (7, 16, 32, 61):
        later = ids.at[:, t:].set((ids[:, t:] + 1 + t) % cfg.vocab_size)
        got = logits(later)
        assert jnp.array_equal(got[:, :t], base[:, :t]), t
        assert not jnp.array_equal(got[:, t], base[:, t]), t


# ------------------------------------------------------------------ the window


def attention_inputs(t, seed=0, bsz=2, g=2, r=2, d=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (bsz, t, g, r, d)), jax.random.normal(ks[1], (bsz, t, g, d)),
            jax.random.normal(ks[2], (bsz, t, g, dv)))


def masked_softmax_attention(q, k, v, window=None):
    """Whole [T, T] scores under the mask: what the blocks must add up to."""
    t = q.shape[1]
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * q.shape[-1] ** -0.5
    qpos, kpos = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = kpos <= qpos
    if window is not None:
        seen &= kpos > qpos - window
    return jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("window, same_as_full", [(80, True), (200, True), (16, False), (1, False)])
def test_a_window_at_least_the_length_is_full_attention_and_a_shorter_one_is_not(
        window, same_as_full):
    q, k, v = attention_inputs(80)
    blocked = jax.jit(hybrid_lm.blocked_attention, static_argnums=3)
    full, full_blocks, on_kernels = blocked(q, k, v, None)
    got, blocks, _ = blocked(q, k, v, window)
    assert on_kernels == 0   # the XLA loop
    assert (rel(got, full) < 1e-6) == same_as_full
    assert (blocks == full_blocks) == same_as_full and blocks <= full_blocks
    assert rel(got, masked_softmax_attention(q, k, v, window)) < 2e-6


@pytest.mark.parametrize("window", [None, 16, 24])
def test_blocked_attention_and_its_gradients_over_several_query_blocks(window):
    q, k, v = attention_inputs(75, seed=1)   # four blocks of 16 and one of 11
    cot = jax.random.normal(jax.random.PRNGKey(5), (*q.shape[:-1], v.shape[-1]))
    got = jax.jit(jax.grad(lambda *a: jnp.sum(hybrid_lm.blocked_attention(*a, window)[0] * cot),
                           argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(masked_softmax_attention(*a, window) * cot),
                            argnums=(0, 1, 2)))(q, k, v)
    for g, w_ in zip(got, want):
        assert rel(g, w_) < 2e-6


def _product_axes(f, *args):
    """Every axis length of every operand of every product in f's program,
    the sub-programs of its checkpointed blocks included."""
    sizes = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                sizes.update(s for var in eqn.invars for s in var.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return sizes


def test_a_window_layer_multiplies_no_key_block_its_queries_cannot_see():
    """In the forward and in the recomputed backward: no product of the
    window layer's gradient program has an axis longer than a query block
    and the window before it (16 + 15), where full attention's reach 80."""
    q, k, v = attention_inputs(80)
    grad_of = lambda window: jax.grad(
        lambda q, k, v: jnp.sum(hybrid_lm.blocked_attention(q, k, v, window)[0]), argnums=(0, 1, 2))
    assert max(_product_axes(grad_of(16), q, k, v)) == 16 + 15
    assert max(_product_axes(grad_of(None), q, k, v)) == 80


# ------------------------------------------- what the memory and the shared KV carry


def test_gradients_reach_the_producers_through_the_gmu_and_the_cross_attention_alone():
    """Layer N/2's out-projection and layer N/2 + 1's o-projection set to
    zero: what those layers add to the residual stream no longer depends on
    their other parameters, so a gradient reaches those only by the memory
    (read by the GMU) and by the shared keys and values (read by the
    cross-attention). The queries of layer N/2 + 1 are read by nobody else
    and get none."""
    model = dataclasses.asdict(TINY)
    w, ids = ws.make_weights(11, model), ids_for(TINY, 3)
    half = TINY.num_hidden_layers_total // 2
    w[f"L{half:02d}.out_proj"] = jnp.zeros_like(w[f"L{half:02d}.out_proj"])
    w[f"L{half + 1:02d}.o"] = jnp.zeros_like(w[f"L{half + 1:02d}.o"])
    _, grads, _ = program_grads(TINY, w, ids)
    _, want = ref.loss_and_grads(w, ids, model)
    for leaf in ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias", "A_log", "D"):
        name = f"L{half:02d}.{leaf}"
        assert float(jnp.linalg.norm(grads[name])) > 0 and rel(grads[name], want[name]) < 1e-4, name
    q_width = TINY.num_attention_heads * TINY.head_dim
    qkv = grads[f"L{half + 1:02d}.qkv"]
    assert float(jnp.max(jnp.abs(qkv[:, :q_width]))) == 0.0
    assert float(jnp.linalg.norm(qkv[:, q_width:])) > 0
    assert rel(qkv, want[f"L{half + 1:02d}.qkv"]) < 1e-4
    assert rel(grads[f"L{half + 1:02d}.qkv_b"], want[f"L{half + 1:02d}.qkv_b"]) < 1e-4
    # and with the readers cut off as well, nothing reaches them at all
    for i, kind in enumerate(TINY.kinds):
        if kind == "G":
            w[f"L{i:02d}.out_proj"] = jnp.zeros_like(w[f"L{i:02d}.out_proj"])
        if kind == "X":
            w[f"L{i:02d}.o"] = jnp.zeros_like(w[f"L{i:02d}.o"])
    _, grads, _ = program_grads(TINY, w, ids)
    assert float(jnp.linalg.norm(grads[f"L{half:02d}.in_proj"])) == 0.0
    assert float(jnp.linalg.norm(grads[f"L{half + 1:02d}.qkv"])) == 0.0


# ------------------------------------------------------------ the rule, the shares


def test_the_kinds_of_layers_0_to_31_are_the_published_rules():
    written_out = ("M W M W M W M W M W M W M W M W "   # 0-15: Mamba and window attention
                   "M F "                               # 16 makes the memory, 17 the shared KV
                   "G X G X G X G X G X G X G X").split()
    assert [layer_kind(i, 32) for i in range(32)] == written_out
    assert SambaYConfig().kinds == "".join(written_out)
    assert [written_out.count(k) for k in "MWFGX"] == [9, 8, 1, 7, 7]
    stage = get_preset("phi4-mini-flash-stage6vp8").model
    assert stage.kinds == "MWMFGX" and stage.layer_offset == 14
    assert ref.layer_kinds(dataclasses.asdict(SambaYConfig())) == "".join(written_out)
    assert ref.layer_kinds(dataclasses.asdict(stage)) == "MWMFGX"
    assert [sambay.lambda_init(i) for i in (15, 17)] == [ref.lambda_init(15), ref.lambda_init(17)]


@pytest.mark.parametrize("bad", [
    dict(layer_offset=5, num_hidden_layers=3),      # a GMU and a cross-attention without layer 4
    dict(layer_offset=6, num_hidden_layers=2),
    dict(layer_offset=4, num_hidden_layers=5),      # past the last layer
    dict(num_key_value_heads=3), dict(num_attention_heads=6),
    dict(num_hidden_layers=0), dict(mb_per_layer=3)])
def test_a_stage_that_does_not_hold_what_it_reads_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, **bad)


def test_the_presets_parameters_are_counted():
    stage = get_preset("phi4-mini-flash-stage6vp8").model
    assert sambay.param_count(stage) == 697_094_272        # 11.15 GB at 16 bytes
    whole = sambay.param_count(SambaYConfig())
    assert 3.84e9 < whole < 3.86e9                          # the published "3.8B"
    shapes = ws.shapes(dataclasses.asdict(stage))
    assert sum(int(np.prod(s)) for s in shapes.values()) == 697_094_272
    per_layer = [hybrid_lm.count_shapes(sambay.layer_shapes(k, stage)) for k in "MWFGX"]
    assert [round(n / 1e6, 2) for n in per_layer] == [119.9, 98.32, 98.32, 104.87, 91.77]


def test_the_flat_weights_and_the_programs_tree_are_one_to_one():
    model = dataclasses.asdict(TINY)
    w = ws.make_weights(3, model)
    tree = ws.to_program_params(w)
    assert jax.tree_util.tree_map(lambda a: a.shape, tree) == sambay.param_shapes(TINY)
    back = ws.from_program_params(tree)
    assert set(back) == set(w) and all(back[k] is w[k] for k in w)
    init = sambay.init_sambay(jax.random.PRNGKey(0), TINY)
    assert jax.tree_util.tree_structure(init) == jax.tree_util.tree_structure(tree)
    assert float(jnp.max(jnp.abs(init["layers"][0]["conv_b"]))) == 0.0   # the program's biases
    assert float(jnp.std(w["L00.conv_b"])) > 0                           # the benchmark's


def test_the_vocabularys_row_slices_give_the_whole_vocabularys_logits():
    """Eight chips hold 16 rows each of the tied embedding. A chip whose
    tokens come from its own rows computes, with its slice alone, the hidden
    states the whole model computes and its 16 columns of the whole model's
    logits; and for one hidden state the eight slices' logits, side by side,
    are the whole vocabulary's."""
    model = dataclasses.asdict(TINY)
    w = ws.make_weights(13, model)
    rows = TINY.vocab_size // 8

    @functools.partial(jax.jit, static_argnums=0)
    def program_logits(cfg, w, ids):
        params = ws.to_program_params(w)
        x, _ = sambay.hidden_states(params, ids, cfg)
        h = sambay.layer_norm(x, params["final_norm_w"], params["final_norm_b"],
                              cfg.layer_norm_eps)
        return h, jnp.einsum("btd,vd->btv", h, params["embed"])

    share = dataclasses.replace(TINY, vocab_size=rows)
    for j in (0, 3, 7):
        local = ids_for(share, seed=20 + j, batch=1)            # ids among the rows held
        w_j = dict(w, embed=w["embed"][j * rows:(j + 1) * rows])
        _, got = program_logits(share, w_j, local)
        h, whole = program_logits(TINY, w, local + j * rows)
        assert rel(got, whole[:, :, j * rows:(j + 1) * rows]) < 1e-6, j
        assert rel(whole, ref.logits(w, local + j * rows, model)) < 2e-5
    sliced = [jnp.einsum("btd,vd->btv", h, w["embed"][j * rows:(j + 1) * rows]) for j in range(8)]
    assert rel(jnp.concatenate(sliced, axis=-1), whole) < 1e-6


# ---------------------------------------------------------------- three steps


@pytest.mark.parametrize("dtype, loss_tol, delta_tol", [("float32", 2e-6, 1e-4),
                                                        ("bfloat16", 2e-3, 0.15)])
def test_three_adam_steps_follow_the_reference(dtype, loss_tol, delta_tol):
    """The trainer's own step from the benchmark's weights, against the
    reference's three steps: the losses and the parameters' change, where the
    reference vouches for it (`change_compared`: a bias on the keys moves
    every score of a query alike, so its true gradient is zero, and Adam
    divides what rounding leaves of it by its own size). The band in bfloat16
    is the lambda vectors': their gradient is what is left when two nearly
    equal attention outputs cancel, and Adam takes its sign."""
    from glom_tpu.train.trainer import TrainState, default_optimizer, make_train_step
    from glom_tpu.utils.config import TrainConfig

    cfg = STAGE
    model = dataclasses.asdict(cfg)
    tcfg = TrainConfig(batch_size=2, learning_rate=3e-4, compute_dtype=dtype, remat=True)
    opt = default_optimizer(tcfg)
    params = ws.to_program_params(ws.make_weights(17, model))
    state = TrainState(params=params, opt_state=opt.init(params), step=jnp.zeros((), jnp.int32))
    step = jax.jit(make_train_step(cfg, tcfg, opt))
    batches = [np.asarray(ids_for(cfg, seed=30 + i)) for i in range(3)]
    losses = []
    for ids in batches:
        state, metrics = step(state, jnp.asarray(ids), jax.random.PRNGKey(0))
        losses.append(float(metrics["loss"]))
    want = ref.train_reference(lambda: ws.make_weights(17, model), batches, model, lr=3e-4)
    assert np.allclose(losses, want["losses"], rtol=loss_tol, atol=0)
    w0 = ws.make_weights(17, model)
    was = ref.bias_parts(w0, model)
    delta = {k: float(jnp.linalg.norm(v - was[k])) for k, v in ref.bias_parts(
        ws.from_program_params(state.params), model).items()}
    compared = ref.change_compared(want)
    assert {k for k in want["delta_norms"] if k.endswith("qkv_b.k")} == {
        "L01.qkv_b.k", "L03.qkv_b.k"} <= set(want["delta_norms"]) - set(compared)
    scale = float(np.median(list(compared.values())))
    worst = max((abs(delta[name] - norm) / max(norm, scale), name)
                for name, norm in compared.items())
    assert worst[0] < delta_tol, worst
