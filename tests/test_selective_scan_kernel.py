"""The selective scan's kernels (kernels/selective_scan.py) in interpret mode
against the recurrence a position at a time (benchmark/reference/
sambay_ref.recurrence), and `sambay.selective_scan`'s choice between them and
its XLA form.

Sizes the CPU holds in a few seconds: two rows of 8, 16 and 24 positions in
time blocks of 8 (one block; two, so that the state is carried and the kept
entering state is read; three), 256 channels in two channel blocks of 128 (so
that every block's state waits its turn in scratch, dA leaves a block at a
time, and dB and dC add up over the blocks), 16 states (two sublane tiles
folded). Tolerances are those `tests/test_sambay.py` holds the XLA form to
against the same reference: 2e-6 forward, 1e-5 a gradient in float32. With x,
b and c in bfloat16 the kernels widen them as the reference is handed them
widened, so what differs is the rounding of what leaves in bfloat16 (y, dx,
dB, dC: 2^-9 an element, held to 4e-3 a leaf); dt, A, the state and d(dt), dA
stay float32 and keep the float32 tolerances.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_sambay as ws
from benchmark.reference import sambay_ref as ref
from glom_tpu.kernels import selective_scan as ss
from glom_tpu.models import sambay
from glom_tpu.utils.presets import get_preset

BLOCK, CHANNEL_BLOCK, CHANNELS, STATES = 8, 128, 256, 16
RESULTS = ("forward", "x", "dt", "a", "b", "c")
TOL = {jnp.float32: dict(forward=2e-6, x=1e-5, dt=1e-5, a=1e-5, b=1e-5, c=1e-5),
       jnp.bfloat16: dict(forward=4e-3, x=4e-3, dt=1e-5, a=1e-5, b=4e-3, c=4e-3)}


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def scan_inputs(t, dtype, seed=0, bsz=2, ch=CHANNELS, n=STATES):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (bsz, t, ch)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, t, ch)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (ch, n)))
    b, c = (jax.random.normal(k, (bsz, t, n)).astype(dtype) for k in ks[3:5])
    return (x, dt, a, b, c), jax.random.normal(ks[5], (bsz, t, ch)).astype(dtype)


def a_position_at_a_time(x, dt, a, b, c):
    f32 = jnp.float32
    return jax.vmap(lambda x, dt, b, c: ref.recurrence(x, dt, a, b, c))(
        x.astype(f32), dt, b.astype(f32), c.astype(f32))


@functools.lru_cache(maxsize=None)
def both_sides(blocks: int, dtype):
    """{result: (the kernels', the reference's)} for `blocks` time blocks."""
    args, cot = scan_inputs(blocks * BLOCK, dtype, seed=blocks)
    kernels = lambda *v: ss.selective_scan(*v, time_block=BLOCK, channel_block=CHANNEL_BLOCK,
                                           interpret=True)

    def forward_and_gradients(f):
        y, vjp = jax.vjp(f, *args)
        return (y,) + vjp(cot.astype(y.dtype))

    got = forward_and_gradients(kernels)
    want = forward_and_gradients(a_position_at_a_time)
    assert got[0].dtype == dtype and [g.dtype for g in got[1:]] == [v.dtype for v in args]
    return dict(zip(RESULTS, zip(got, want)))


@pytest.mark.parametrize("what", RESULTS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [1, 2, 3])
def test_the_kernels_are_the_recurrence_a_position_at_a_time(blocks, dtype, what):
    got, want = both_sides(blocks, dtype)[what]
    assert got.shape == want.shape and bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    assert rel(got, want) < TOL[dtype][what]


def test_eight_sums_come_out_as_rows():
    """`_sums_as_rows`: row k of the result is the sum of the k-th array's 8
    rows, by 7 rotations where 8 reductions take 24."""
    from jax.experimental import pallas as pl

    parts = jax.random.normal(jax.random.PRNGKey(0), (8, 8, 128))

    def kernel(p_ref, o_ref):
        o_ref[...] = ss._sums_as_rows([p_ref[k] for k in range(8)])

    got = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                         interpret=True)(parts)
    assert rel(got, jnp.sum(parts, axis=1)) < 1e-6


@pytest.mark.parametrize("t, channels, states, want", [
    (8192, 5120, 16, (256, 512)),     # phi4flash.train's
    (80, 128, 16, (80, 128)),         # a length under a block: one block of whole groups
    (77, 256, 8, (80, 256)),
    (8192, 5120 + 64, 16, None),      # channels that fill no whole register
    (8192, 5120, 12, None),           # states that fill no whole sublane tile
])
def test_the_blocks_the_kernels_serve(t, channels, states, want):
    assert ss.blocks(t, channels, states) == want


# ------------------------------------------------ `sambay.selective_scan`'s choice


STAGE = dataclasses.replace(get_preset("sambay-tiny").model, layer_offset=2,
                            num_hidden_layers=6)   # MWMFGX of N = 8: two Mamba layers


def stage_loss_and_grads(ids, w):
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: sambay.lm_loss(p, ids, STAGE, compute_dtype=None, remat=True),
        has_aux=True))(ws.to_program_params(w))
    return float(loss), ws.from_program_params(grads), counters


def test_on_the_cpu_the_xla_form_runs_and_the_counter_says_so():
    assert jax.devices()[0].platform == "cpu" and not ss.on_tpu()
    assert sambay.scan_kernel_blocks(80, 128, 16) is None
    args, _ = scan_inputs(16, jnp.float32)
    lowered = jax.jit(lambda *v: sambay.selective_scan(*v)[0]).lower(*args).as_text()
    assert "tpu_custom_call" not in lowered and "while" in lowered
    w = ws.make_weights(3, dataclasses.asdict(STAGE))
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, STAGE.seq_len), 0, STAGE.vocab_size)
    _, _, counters = stage_loss_and_grads(ids, w)
    assert float(counters["scan_on_kernels"]) == 0 and float(counters["scan_chunks"]) == 1
    assert "scan_on_kernels" in sambay.COUNTERS


def test_with_the_kernels_a_step_counts_its_mamba_layers_and_reads_the_same(monkeypatch):
    """The chip's branch, taken here by answering for the chip and handing it
    the kernels in interpret mode: 80 positions in time blocks of 32 (padded
    to 96 with dt = 0 steps, three blocks), 128 channels, 16 states. The loss
    and every gradient leaf against the XLA form's, under recomputation (a
    layer's forward kernel runs twice, its backward once)."""
    w = ws.make_weights(3, dataclasses.asdict(STAGE))
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, STAGE.seq_len), 0, STAGE.vocab_size)
    loss_xla, grads_xla, _ = stage_loss_and_grads(ids, w)

    monkeypatch.setattr(ss, "TIME_BLOCK", 32)
    monkeypatch.setattr(ss, "on_tpu", lambda: True)
    monkeypatch.setattr(ss, "selective_scan", functools.partial(ss.selective_scan, interpret=True))
    assert sambay.scan_kernel_blocks(80, 128, 16) == (32, 128)
    assert sambay.scan_kernel_blocks(80, 96, 16) is None      # a width that does not tile: XLA
    loss, grads, counters = stage_loss_and_grads(ids, w)
    assert float(counters["scan_on_kernels"]) == STAGE.kinds.count("M") == 2
    assert float(counters["scan_chunks"]) == 3
    assert abs(loss - loss_xla) < 1e-6 * loss_xla
    scale = float(np.median([np.linalg.norm(v) for v in grads_xla.values()]))
    for name in grads_xla:
        gap = float(np.linalg.norm(np.asarray(grads[name]) - np.asarray(grads_xla[name])))
        assert gap < 2e-5 * max(float(np.linalg.norm(grads_xla[name])), scale), name
