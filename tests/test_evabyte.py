"""EvaByte (`models/evabyte.py`) against its plain reference
(`benchmark/reference/evabyte_ref.py`) on seeded weights at the tiny size:
the loss and every leaf's gradient; the mask, case by case; the heads' shares
of a layer; the eight-headed loss; and what the family shares with the older
four, which has to compile to what it compiled to.

CPU only: what is checked is results and programs' text, never a time.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_evabyte as we
from benchmark.reference import evabyte_ref as ref
from glom_tpu.kernels import flash_attention as fa
from glom_tpu.models import evabyte, hybrid_lm, kimi_linear, laguna, sambay
from glom_tpu.utils.config import EvaByteConfig
from glom_tpu.utils.presets import get_preset

TINY = get_preset("evabyte-tiny").model
LEAVES = sorted(we.shapes(dataclasses.asdict(TINY)))


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def seeded(cfg, seed=5, length=None):
    model = dataclasses.asdict(cfg)
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, length or cfg.seq_len), dtype=np.int32)
    return model, we.make_weights(seed, model), jnp.asarray(ids)


@pytest.fixture(scope="module")
def both():
    """(the reference's loss and gradients, the program's) on the tiny preset."""
    model, w, ids = seeded(TINY)
    with jax.default_matmul_precision("highest"):
        want = ref.loss_and_grads(w, ids, model)
        loss, grads = jax.value_and_grad(lambda p: evabyte.lm_loss(p, ids, TINY)[0])(
            we.to_program_params(w))
    return want, (loss, we.from_program_params(grads))


def test_the_loss_is_the_references(both):
    (want, _), (got, _) = both
    assert abs(float(got) - float(want)) < 2e-6 * float(want)
    assert abs(float(want) - np.log(TINY.vocab_size)) < 0.05


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_is_the_references(both, leaf):
    (_, want), (_, got) = both
    assert got[leaf].shape == want[leaf].shape
    assert rel(got[leaf], want[leaf]) < 5e-6, leaf
    assert float(jnp.linalg.norm(want[leaf])) > 0      # phi and mu among them: a summary is read


@pytest.mark.parametrize("length", [83, 64, 33], ids=["no_whole_chunk_at_the_end", "whole_windows",
                                                     "one_byte_of_a_second_window"])
def test_a_row_of_any_length_goes_through_the_xla_loop_as_the_reference_has_it(length):
    model, w, ids = seeded(TINY, seed=7, length=length)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(w, ids, model)
        got = evabyte.logits(we.to_program_params(w), ids, TINY)
    assert got.shape == want.shape == (2, length, TINY.num_pred_heads, TINY.vocab_size)
    assert rel(got, want) < 2e-6


# ------------------------------------------------------------------- the mask


def test_the_kernels_mask_is_the_references_written_out_from_l_and_c():
    """Every (query, key) and (query, summary) pair at the model's window and
    chunk over two windows: `flash_attention._seen` under `Aligned` says what
    `evabyte_ref.visible` says."""
    model = dict(dataclasses.asdict(EvaByteConfig()))
    t = 2 * model["window_size"]
    qpos = jnp.arange(t)[:, None]
    want = np.asarray(ref.visible(qpos, t, model))
    mask = fa.Aligned(model["window_size"], model["chunk_size"], t)
    got = np.asarray(fa._seen(qpos, jnp.arange(t + mask.summaries)[None, :], mask))
    assert got.shape == want.shape == (t, t + t // 16) and (got == want).all()
    w = model["window_size"]
    assert want[w].sum() == 1 + w // 16 and want[w - 1].sum() == w and want[2 * w - 1].sum() == (
        w + w // 16)


@pytest.mark.parametrize("window", [0, 1, 2])
def test_a_query_at_the_start_of_window_n_sees_n_windows_of_summaries_and_one_key(window):
    """Through the program's attention itself: the first query of window n
    reads exactly the summaries of the n W / c chunks before it and its own
    key; changing any other key, value or summary changes no bit of it."""
    cfg = dataclasses.replace(TINY, seq_len=96)
    t, w, c, h, d = 96, cfg.window_size, cfg.chunk_size, cfg.num_attention_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(window), 5)
    q, k, v = (jax.random.normal(key, (1, t, h, d)) for key in ks[:3])
    khat, vhat = (jax.random.normal(key, (1, t // c, h, d)) for key in ks[3:])
    at = window * w
    base = evabyte.eva_attention(q, k, v, khat, vhat, cfg)[0][:, at]
    seen_keys, seen_summaries = np.zeros(t, bool), np.zeros(t // c, bool)
    seen_keys[at], seen_summaries[:window * w // c] = True, True
    noise = lambda x, seen: x + jnp.where(jnp.asarray(seen)[None, :, None, None], 0.0, 1.0)
    same = evabyte.eva_attention(q, noise(k, seen_keys), noise(v, seen_keys),
                                 noise(khat, seen_summaries), noise(vhat, seen_summaries), cfg)[0]
    assert jnp.array_equal(same[:, at], base)
    for name, moved in (("own value", (k, noise(v, ~seen_keys), khat, vhat)),
                        ("summary", (k, v, khat, noise(vhat, ~seen_summaries)))):
        changed = evabyte.eva_attention(q, *moved, cfg)[0][:, at]
        assert jnp.array_equal(changed, base) == (name == "summary" and window == 0), name


def test_the_first_window_sees_no_summary():
    """With other `phi` and `mu` the logits of the first window's positions
    keep every bit; the second window's do not."""
    model, w, ids = seeded(TINY, seed=3)
    other = {k: (v + 0.5 if k.rpartition(".")[2] in ("phi", "mu") else v) for k, v in w.items()}
    base = evabyte.logits(we.to_program_params(w), ids, TINY)
    got = evabyte.logits(we.to_program_params(other), ids, TINY)
    first = TINY.window_size
    assert jnp.array_equal(got[:, :first], base[:, :first])
    assert not jnp.array_equal(got[:, first], base[:, first])


@pytest.mark.parametrize("t", [1, 31, 32, 33, 64, 79])
def test_no_position_sees_a_later_byte(t):
    """Trap 12's test: when every byte from t on changes, the logits before t
    keep every bit (a summary holds only bytes of windows that are over), and
    those at t change."""
    _, w, ids = seeded(TINY, seed=11)
    params = we.to_program_params(w)
    base = evabyte.logits(params, ids, TINY)
    later = ids.at[:, t:].set((ids[:, t:] + 1 + t % 3) % TINY.vocab_size)
    got = evabyte.logits(params, later, TINY)
    assert jnp.array_equal(got[:, :t], base[:, :t])
    assert not jnp.array_equal(got[:, t], base[:, t])


# ------------------------------------------------------------------ the share


def test_the_four_head_shares_sum_to_the_uncut_layer():
    """The deployment's arithmetic: four chips hold a layer's heads a quarter
    each, the MLP and the norms whole. Each computes `o_share Wo_share`; their
    sum, the MLP and the norms counted once, is the uncut reference layer. In
    the reference and in the program."""
    full = dataclasses.replace(TINY, num_attention_heads=4, num_hidden_layers=1,
                               num_hidden_layers_total=1)
    share = dataclasses.replace(full, num_attention_heads=1)
    model, w, ids = seeded(full, seed=9)
    lw = ref.layer_weights(w, 0)
    x = w["embed"][ids[0]]
    d = full.head_dim

    def held(i):
        cols = slice(i * d, (i + 1) * d)
        return {**lw, "q": lw["q"][:, cols], "k": lw["k"][:, cols], "v": lw["v"][:, cols],
                "o": lw["o"][cols], "phi": lw["phi"][i:i + 1], "mu": lw["mu"][i:i + 1]}

    with jax.default_matmul_precision("highest"):
        whole = ref.layer(lw, x, model)
        u = ref.norm(x, lw["norm1"], model["rms_norm_eps"])
        terms = [ref.attention(held(i), u, dataclasses.asdict(share), lambda a: a)
                 for i in range(4)]
        mixed = x + sum(terms)
        u2 = ref.norm(mixed, lw["norm2"], model["rms_norm_eps"])
        summed = mixed + ref.swiglu(u2, lw["w_gate"], lw["w_up"], lw["w_down"], lambda a: a)
        assert rel(summed, whole) < 2e-6
        # the program's shares: each chip's attention half is x + its term
        program = [evabyte.attention_mixer(held(i), x[None], share, None)[0][0] - x
                   for i in range(4)]
        for got, want in zip(program, terms):
            assert rel(got, want) < 5e-6
        assert rel(evabyte.layer(lw, x[None], full, None)[0][0], whole) < 2e-6


# ------------------------------------------------------------------- the loss


def the_loss_before_the_heads(h, head, ids):
    """`hybrid_lm.next_token_loss` as it stood before it took K heads."""
    def block_nll(h, head, targets):
        logits = hybrid_lm._mm(h, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]

    bsz, t = ids.shape
    targets = jnp.roll(ids, -1, axis=1).reshape(-1)
    has_next = jnp.tile(jnp.arange(t) < t - 1, bsz)
    total = jnp.zeros((), jnp.float32)
    for first in range(0, bsz * t, hybrid_lm.LOSS_ROW_BLOCK):
        rows = slice(first, min(bsz * t, first + hybrid_lm.LOSS_ROW_BLOCK))
        nll = jax.checkpoint(block_nll)(h[rows], head, targets[rows])
        total = total + jnp.sum(jnp.where(has_next[rows], nll, 0.0))
    return total / (bsz * (t - 1))


def lowered(f, *args):
    """The program's text without locations and without the functions' names."""
    text = jax.jit(f).lower(*args).compiler_ir().operation.get_asm(enable_debug_info=False)
    return re.sub(r"@[\w.]+", "@f", text)


def test_one_head_of_the_generalised_loss_is_todays_loss_bit_for_bit():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h, head = jax.random.normal(ks[0], (2 * 2100, 32)), jax.random.normal(ks[1], (32, 50))
    ids = jax.random.randint(ks[2], (2, 2100), 0, 50)       # two row blocks of the loss
    both = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(h, head, ids)
    got, want = both(hybrid_lm.next_token_loss), both(the_loss_before_the_heads)
    assert all(jnp.array_equal(a, b) for a, b in zip(jax.tree_util.tree_leaves(got),
                                                     jax.tree_util.tree_leaves(want)))
    assert lowered(jax.grad(hybrid_lm.next_token_loss), h, head, ids) == lowered(
        jax.grad(the_loss_before_the_heads), h, head, ids)


def test_k_heads_are_k_shifted_losses_weighed_alike():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    k, v, t = 3, 11, 40
    h, head = jax.random.normal(ks[0], (2 * t, 16)), jax.random.normal(ks[1], (16, k * v))
    ids = jax.random.randint(ks[2], (2, t), 0, v)
    got = hybrid_lm.next_token_loss(h, head, ids, k)
    logp = jax.nn.log_softmax((h @ head).reshape(2, t, k, v), axis=-1)
    total = sum(-float(logp[b, p, m, ids[b, p + 1 + m]])
                for b in range(2) for m in range(k) for p in range(t - 1 - m))
    assert abs(float(got) - total / (2 * sum(t - 1 - m for m in range(k)))) < 1e-5


FOUR = {"hybrid_lm": (hybrid_lm, hybrid_lm.init_hybrid_lm, "hybrid-lm-tiny"),
        "sambay": (sambay, sambay.init_sambay, "sambay-tiny"),
        "laguna": (laguna, laguna.init_laguna, "laguna-tiny"),
        "kimi_linear": (kimi_linear, kimi_linear.init_kimi_linear, "kimi-linear-tiny")}


@pytest.mark.parametrize("family", sorted(FOUR))
def test_the_four_families_steps_compile_to_what_they_compiled_to(family, monkeypatch):
    """The shared code this family touched keeps its programs: with the loss
    as it stood before the heads put back in its place, a family's gradient
    lowers to the same text, locations and names apart; and `run_stack` still
    casts the embedding's rows to the compute type, so the older families'
    stream is bfloat16 where theirs was (EvaByte's float32 stream is its own:
    it hands `run_stack` no compute type)."""
    model, init, preset = FOUR[family]
    cfg = get_preset(preset).model
    params = init(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.seq_len), 0, cfg.vocab_size)
    grad = jax.grad(lambda p: model.lm_loss(p, ids, cfg, compute_dtype=jnp.bfloat16)[0])
    now = lowered(grad, params)
    monkeypatch.setattr(model, "next_token_loss", the_loss_before_the_heads)
    assert lowered(grad, params) == now
    streams = re.findall(r"tensor<2x%dx%dx(\w+)>" % (cfg.seq_len, cfg.hidden_size), now)
    assert streams and "bf16" in streams


def test_the_stream_is_float32_and_the_products_are_the_compute_types():
    _, w, ids = seeded(TINY)
    params = we.to_program_params(w)
    x, _ = evabyte.hidden_states(params, ids, TINY, compute_dtype=jnp.bfloat16)
    assert x.dtype == jnp.float32
    text = lowered(lambda p: evabyte.lm_loss(p, ids, TINY, compute_dtype=jnp.bfloat16)[0], params)
    dots = re.findall(r"stablehlo.dot_general.*?: \((tensor<[^>]*>), (tensor<[^>]*>)\)", text)
    wide = [d for d in dots if str(TINY.intermediate_size) in d[1]]
    assert wide and all("bf16" in a and "bf16" in b for a, b in wide)
    # the zero norm weights of the program's init are a plain RMSNorm
    y = evabyte.offset_norm(x, jnp.zeros(x.shape[-1]), 1e-5, None)
    assert rel(y, hybrid_lm.rms_norm(x, jnp.ones(x.shape[-1]), 1e-5)) < 1e-6
