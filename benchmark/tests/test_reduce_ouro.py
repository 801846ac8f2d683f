"""The Ouro step's reduction by scope (`reduce_ouro.py`, which hands its tuple
to `reduce_laguna.by_scopes`) and the cell's six readers: on made-up ops, on a
small recorded trace of two consecutive steps of `ouro26b.train` on a TPU v5
lite (PR 46; each instruction's events within a step merged into one: the
passes are the trips of a loop, and a body's instruction is an event a trip,
trap 19), on an empty context, and on a step that is another family's."""
import gzip
import importlib.util
import json
import os

import pytest

from benchmark import flops_ouro, harness
from benchmark import reduce_evabyte as re_
from benchmark import reduce_kimi as rk
from benchmark import reduce_laguna as rg
from benchmark import reduce_ouro as ro
from benchmark import reduce_phases as rp

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("ouro_attention_time_pct.train", "ouro_exit_loss_time_pct.train",
           "ouro_sandwich_norm_time_pct.train", "ouro_flash_roofline.train",
           "ouro_matmul_roofline.train", "ouro_applications_kept_pct.train")
FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%c"
MATMUL = ("%fusion.{} = bf16[2,4096,5632]{{2,1,0}} fusion(bf16[2,4096,2048] %p), kind=kOutput, "
          "calls=%c")
KERNEL = ('%{}.{} = bf16[2,16,1,4096,128]{{4,3,2,1,0}} custom-call(bf16[8] %p), '
          'custom_call_target="tpu_custom_call"')
RECORDED = "trace_ouro26b_train_2steps.json.gz"


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("ou_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _model():
    with open(os.path.join(harness.BENCH_DIR, "configs", "ouro-2.6b-stage8.json")) as fh:
        return json.load(fh)["model"]


def test_the_vocabulary_is_the_programs():
    from glom_tpu.tracing import spans

    assert ro.OURO_DEVICE_PHASES == spans.OURO_DEVICE_PHASES
    assert not set(ro.OURO_DEVICE_PHASES) & set(rp.DEVICE_PHASES)
    assert re_.EVABYTE_DEVICE_PHASES == spans.EVABYTE_DEVICE_PHASES   # the accepted copy stays


def _ops():
    body = "jit(step)/jvp(ut_close)/while/body/closed_call/checkpoint/"
    back = "jit(step)/transpose(jvp(ut_close))/while/body/closed_call/checkpoint/"
    return [(MATMUL.format(1), 100, 100, body + "ouro_in/...k,kn->...n/dot_general:"),
            (FUSION.format(2), 200, 50, body + "ouro_in/rope/mul:"),
            (KERNEL.format("attn_flash_fwd", 3), 250, 50, body + "full_attention/pallas_call:"),
            (KERNEL.format("attn_flash_bwd_onesweep", 4), 300, 100,
             back + "full_attention/pallas_call:"),
            (MATMUL.format(5), 400, 50, body + "ouro_out/dot_general:"),
            (FUSION.format(6), 450, 50, body + "sandwich_norm/add:"),
            (MATMUL.format(7), 500, 300, body + "dense_mlp/dot_general:"),
            (FUSION.format(8), 800, 25, "jit(step)/jvp(ut_close)/while/body/dynamic_update_slice:"),
            (FUSION.format(9), 825, 25, body.replace("closed_call/", "") + "ut_close/mul:"),
            (FUSION.format(10), 850, 25, "jit(step)/jvp(exit_gate)/log_sigmoid:"),
            (MATMUL.format(11), 875, 75,
             "jit(step)/jvp(lm_head_loss)/while/body/checkpoint/dot_general:"),
            (FUSION.format(12), 950, 50, "jit(step)/optimizer/mul:"),
            (FUSION.format(13), 1000, 100, "jit(step)/add:"),
            (FUSION.format(14), 5000, 50, "jit(other)/embed/add:")]   # another program's op


def test_step_by_scope_sums_to_the_step_and_counts_products_and_kernels_apart():
    modules = [("jit_step(1)", 100, 1000), ("jit_other(2)", 5000, 50)]
    r = ro.reduce([{"ops": _ops(), "modules": modules}])
    assert r["runs"] == 1 and r["step_s"] == pytest.approx(1000e-9)
    assert r["by_phase"] == pytest.approx({
        "ouro_in": 150e-9, "full_attention": 150e-9, "ouro_out": 50e-9, "sandwich_norm": 50e-9,
        "dense_mlp": 300e-9, "ut_close": 50e-9, "exit_gate": 25e-9, "lm_head_loss": 75e-9,
        "optimizer": 50e-9, ro.UNATTRIBUTED: 100e-9})
    assert r["by_kernel"] == pytest.approx(
        {"attn_flash_fwd": 50e-9, "attn_flash_bwd_onesweep": 100e-9})
    assert r["kernel_s"] == pytest.approx(150e-9)
    assert r["product_s"] == pytest.approx((100 + 50 + 100 + 50 + 300 + 75) * 1e-9)   # trap 14
    assert "under no scope: 10.00% of the step" in ro.tables(r)[0]
    # a step that closes no pass and opens no gate is another family's, and the other way round
    theirs = [(n, s, d, p.replace("ut_close", "mlp").replace("exit_gate", "mlp"))
              for n, s, d, p in _ops()]
    assert ro.reduce([{"ops": theirs, "modules": modules}]) is None
    assert ro.reduce([{"ops": [], "modules": []}]) is None
    # (SambaY's reducer takes any step that opens `full_attention`, which is its name too for
    # the mask without a window; its readers list their own cell)
    for other in (re_, rg, rk):
        assert other.reduce([{"ops": _ops(), "modules": modules}]) is None


def _ctx(result, monkeypatch, **over):
    monkeypatch.setattr(ro, "for_run", lambda ctx: result if ctx.get("trace") else None)
    rec = {"kind": "train_step", "ut_steps": 4.0, "layer_applications": 32.0,
           "attn_forward_kept": 32.0, "attn_key_blocks_full": 9216.0}
    ctx = {"kind": "train", "chips": 1, "batch": 2, "seq_len": 4096, "steps": 6,
           "steps_traced": 6, "device_kind": "TPU v5 lite", "model": _model(),
           "trace": {"window_s": 2.0}, "records": [rec, dict(rec), {"kind": "span"}]}
    ctx.update(over)
    return ctx


def test_the_readers_on_a_made_up_reduction(monkeypatch):
    result = {"runs": 2, "step_s": 1.300, "product_s": 1.000, "kernel_s": 0.160,
              "by_phase": {"ouro_in": 0.150, "full_attention": 0.195, "ouro_out": 0.060,
                           "sandwich_norm": 0.039, "dense_mlp": 0.520, "ut_close": 0.013,
                           "exit_gate": 0.013, "lm_head_loss": 0.221},
              "by_kernel": {"attn_flash_fwd": 0.050, "attn_flash_bwd_onesweep": 0.110}}
    ctx = _ctx(result, monkeypatch)      # a reader is loaded after the patch and imports it
    assert reader("ouro_attention_time_pct.train")(ctx) == pytest.approx(15.0)
    assert reader("ouro_exit_loss_time_pct.train")(ctx) == pytest.approx(18.0)
    assert reader("ouro_sandwich_norm_time_pct.train")(ctx) == pytest.approx(4.0)
    assert reader("ouro_applications_kept_pct.train")(ctx) == pytest.approx(100.0)
    need = flops_ouro.attention_kernel_ops_and_bytes(ctx["model"], 2, 4096)
    assert reader("ouro_flash_roofline.train")(ctx) == pytest.approx(
        100 * need["ops"] / 197e12 / 0.160)
    assert 35 < reader("ouro_flash_roofline.train")(ctx) < 50
    flops = flops_ouro.step_flops(ctx["model"], 2, 4096)
    assert reader("ouro_matmul_roofline.train")(ctx) == pytest.approx(100 * flops / 1.0 / 197e12)
    assert 55 < reader("ouro_matmul_roofline.train")(ctx) < 60
    # a step on the XLA loop keeps nothing: 0, and a reading all the same
    rec = dict(ctx["records"][0], attn_forward_kept=0.0)
    assert reader("ouro_applications_kept_pct.train")(dict(ctx, records=[rec])) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """What the parent commit's run of another cell gives these readers: no
    counter, no scope of this family's, and no failure."""
    assert reader(name)({}) is None
    assert reader(name)({"kind": "train", "records": [], "steps": 8, "trace": None}) is None
    theirs = {"kind": "train_step", "attn_forward_kept": 4.0}      # another family's record
    assert reader(name)({"kind": "train", "records": [theirs], "steps": 8, "trace": None}) is None


# ------------------------------------------------------- the recorded trace


def _recorded(name):
    with gzip.open(os.path.join(HERE, name)) as fh:
        return {k: [tuple(e) for e in v] for k, v in json.load(fh)["devices"][0].items()}


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(os.path.join(HERE, RECORDED)):
        pytest.skip("no recorded trace of the cell in this checkout")
    return _recorded(RECORDED)


def test_recorded_steps_by_scope_and_by_kernel(recorded):
    r = ro.reduce([recorded])
    assert r["runs"] == 2 and 0.8 < r["step_s"] < 2.5
    assert sum(r["by_phase"].values()) == pytest.approx(r["step_s"])
    share = {k: 100 * v / r["step_s"] for k, v in r["by_phase"].items()}
    assert set(ro.OURO_DEVICE_PHASES) <= set(share)
    # the dense MLP is the largest scope; the four heads near their share of the FLOPs; the norms
    # a sandwich adds, the closing norm and the loop's copies a few percent
    assert share["dense_mlp"] == max(share.values()) and share["dense_mlp"] > 25
    assert 8 < share["lm_head_loss"] + share["exit_gate"] < 30
    assert 5 < share["full_attention"] < 30
    assert 1 < share["sandwich_norm"] + share["ut_close"] < 15
    assert share.get(ro.UNATTRIBUTED, 0.0) < 10
    assert set(r["by_kernel"]) == {"attn_flash_fwd", "attn_flash_bwd_onesweep"}
    assert r["kernel_s"] < r["by_phase"]["full_attention"]
    assert r["kernel_s"] < r["product_s"] < r["step_s"]
    model = _model()
    need = flops_ouro.attention_kernel_ops_and_bytes(model, 2, 4096)
    assert 10 < 100 * need["ops"] / 197e12 / r["kernel_s"] < 100
    assert 20 < 100 * flops_ouro.step_flops(model, 2, 4096) / r["product_s"] / 197e12 < 100


def test_the_other_vocabularies_read_the_step_as_not_theirs(recorded):
    glom = rp.reduce_phases([recorded], [])
    assert set(glom["step"]["by_phase"]) <= {rp.UNATTRIBUTED, "optimizer", "step_metrics"}
    assert {"attn_flash_fwd", "attn_flash_bwd_onesweep"} <= set(glom["step"]["by_kernel"])
    assert not any(k.startswith(("ragged-dot", "selective_scan"))
                   for k in glom["step"]["by_kernel"])
    for other in (re_, rg, rk):
        assert other.reduce([recorded]) is None
    for name in ("trace_phi4flash_train_2steps.json.gz", "trace_evabyte_train_2steps.json.gz",
                 "trace_lagunaxs2_train_2steps.json.gz", "trace_kimilinear_train_2steps.json.gz"):
        assert ro.reduce([_recorded(name)]) is None, name
