"""The EvaByte step's reduction by scope (`reduce_evabyte.py`, which hands its
tuple to `reduce_laguna.by_scopes`) and the cell's five readers: on made-up
ops, on a small recorded trace of two consecutive steps of `evabyte.train` on
a TPU v5 lite (PR 42; each instruction's events within a step merged into
one), on an empty context, and on a step that is another family's."""
import gzip
import importlib.util
import json
import os

import pytest

from benchmark import flops_evabyte, harness
from benchmark import reduce_evabyte as re_
from benchmark import reduce_kimi as rk
from benchmark import reduce_laguna as rg
from benchmark import reduce_lm as rl
from benchmark import reduce_phases as rp
from benchmark import reduce_sambay as rs

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("eva_attention_time_pct.train", "eva_summary_time_pct.train",
           "eva_flash_roofline.train", "eva_key_blocks_visited_pct.train",
           "evabyte_matmul_roofline.train")
FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%c"
MATMUL = "%fusion.{} = bf16[16384,11008]{{1,0}} fusion(bf16[16384,4096] %p), kind=kOutput, calls=%c"
KERNEL = ('%{}.{} = bf16[1,8,1,16384,128]{{4,3,2,1,0}} custom-call(bf16[8] %p), '
          'custom_call_target="tpu_custom_call"')
RECORDED = "trace_evabyte_train_2steps.json.gz"


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("ev_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _model():
    with open(os.path.join(harness.BENCH_DIR, "configs", "evabyte-stage4tp4.json")) as fh:
        return json.load(fh)["model"]


def test_the_vocabulary_is_the_programs():
    from glom_tpu.tracing import spans

    assert re_.EVABYTE_DEVICE_PHASES == spans.EVABYTE_DEVICE_PHASES
    assert not set(re_.EVABYTE_DEVICE_PHASES) & set(rp.DEVICE_PHASES)
    # the accepted copies stay as they were
    assert rl.LM_DEVICE_PHASES == spans.LM_DEVICE_PHASES
    assert rs.SAMBAY_DEVICE_PHASES == spans.SAMBAY_DEVICE_PHASES
    assert rg.LAGUNA_DEVICE_PHASES == spans.LAGUNA_DEVICE_PHASES
    assert rk.KIMI_DEVICE_PHASES == spans.KIMI_DEVICE_PHASES


def _ops():
    inner = "jit(step)/jvp(checkpoint)/"
    return [(MATMUL.format(1), 100, 100, inner + "eva_in/...k,kn->...n/dot_general:"),
            (FUSION.format(2), 200, 50, inner + "eva_in/rope/mul:"),
            (FUSION.format(3), 250, 50, inner + "eva_summary/reduce_sum:"),
            (KERNEL.format("attn_flash_fwd", 4), 300, 50, inner + "eva_attention/pallas_call:"),
            (KERNEL.format("attn_flash_bwd_onesweep", 5), 350, 100,
             "jit(step)/transpose(jvp(checkpoint))/eva_attention/pallas_call:"),
            (FUSION.format(6), 450, 50, inner + "eva_attention/concatenate:"),
            (MATMUL.format(7), 500, 50, inner + "eva_out/dot_general:"),
            (MATMUL.format(8), 550, 350, inner + "dense_mlp/dot_general:"),
            (MATMUL.format(9), 900, 50, "jit(step)/jvp(lm_head_loss)/checkpoint/dot_general:"),
            (FUSION.format(10), 950, 50, "jit(step)/optimizer/mul:"),
            (FUSION.format(11), 1000, 100, inner + "add:"),
            (FUSION.format(12), 5000, 50, "jit(other)/embed/add:")]   # another program's op


def test_step_by_scope_sums_to_the_step_and_counts_products_and_kernels_apart():
    modules = [("jit_step(1)", 100, 1000), ("jit_other(2)", 5000, 50)]
    r = re_.reduce([{"ops": _ops(), "modules": modules}])
    assert r["runs"] == 1 and r["step_s"] == pytest.approx(1000e-9)
    assert r["by_phase"] == pytest.approx({
        "eva_in": 150e-9, "eva_summary": 50e-9, "eva_attention": 200e-9, "eva_out": 50e-9,
        "dense_mlp": 350e-9, "lm_head_loss": 50e-9, "optimizer": 50e-9, re_.UNATTRIBUTED: 100e-9})
    assert r["by_kernel"] == pytest.approx(
        {"attn_flash_fwd": 50e-9, "attn_flash_bwd_onesweep": 100e-9})
    assert r["kernel_s"] == pytest.approx(150e-9)
    assert r["product_s"] == pytest.approx((100 + 50 + 100 + 50 + 350 + 50) * 1e-9)   # trap 14
    assert "under no scope: 10.00% of the step" in re_.tables(r)[0]
    # a step without the summariser's scope is another family's, and the other way round
    theirs = [(n, s, d, p.replace("eva_summary", "mlp").replace("eva_", "kda_"))
              for n, s, d, p in _ops()]
    assert re_.reduce([{"ops": theirs, "modules": modules}]) is None
    assert re_.reduce([{"ops": [], "modules": []}]) is None
    for other in (rg, rk, rs):
        assert other.reduce([{"ops": _ops(), "modules": modules}]) is None


def _ctx(result, monkeypatch, **over):
    monkeypatch.setattr(re_, "for_run", lambda ctx: result if ctx.get("trace") else None)
    rec = {"kind": "train_step", "attn_key_blocks_local": 1280.0, "attn_key_blocks_summary": 640.0,
           "eva_summary_keys": 4096.0, "attn_forward_kept": 4.0, "lm_pred_heads": 8.0}
    ctx = {"kind": "train", "chips": 1, "batch": 1, "seq_len": 16384, "steps": 6,
           "steps_traced": 6, "device_kind": "TPU v5 lite", "model": _model(),
           "trace": {"window_s": 2.0}, "records": [rec, dict(rec), {"kind": "span"}]}
    ctx.update(over)
    return ctx


def test_the_readers_on_a_made_up_reduction(monkeypatch):
    result = {"runs": 2, "step_s": 0.800, "product_s": 0.640, "kernel_s": 0.016,
              "by_phase": {"eva_in": 0.060, "eva_summary": 0.012, "eva_attention": 0.032,
                           "eva_out": 0.020, "dense_mlp": 0.600},
              "by_kernel": {"attn_flash_fwd": 0.005, "attn_flash_bwd_onesweep": 0.011}}
    ctx = _ctx(result, monkeypatch)      # a reader is loaded after the patch and imports it
    assert reader("eva_attention_time_pct.train")(ctx) == pytest.approx(4.0)
    assert reader("eva_summary_time_pct.train")(ctx) == pytest.approx(1.5)
    assert reader("eva_key_blocks_visited_pct.train")(ctx) == pytest.approx(100 * 1920 / 1728)
    need = flops_evabyte.attention_kernel_ops_and_bytes(ctx["model"], 1, 16384)
    assert reader("eva_flash_roofline.train")(ctx) == pytest.approx(
        100 * need["ops"] / 197e12 / 0.016)
    assert 30 < reader("eva_flash_roofline.train")(ctx) < 45
    flops = flops_evabyte.step_flops(ctx["model"], 1, 16384)
    assert reader("evabyte_matmul_roofline.train")(ctx) == pytest.approx(
        100 * flops / 0.640 / 197e12)
    assert 45 < reader("evabyte_matmul_roofline.train")(ctx) < 55


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """What the parent commit's run of another cell gives these readers: no
    counter, no scope of this family's, and no failure."""
    assert reader(name)({}) is None
    assert reader(name)({"kind": "train", "records": [], "steps": 8, "trace": None}) is None


def test_the_trace_readers_find_nothing_in_a_run_without_a_trace():
    rec = {"kind": "train_step", "attn_key_blocks_local": 1280.0, "attn_key_blocks_summary": 640.0}
    ctx = {"kind": "train", "records": [rec], "steps": 6, "trace": None, "chips": 1,
           "model": _model(), "seq_len": 16384}
    for name in READERS[:3] + READERS[4:]:
        assert reader(name)(ctx) is None
    assert reader(READERS[3])(ctx) == pytest.approx(111.111, abs=1e-3)


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
    r = re_.reduce([recorded])
    assert r["runs"] == 2 and 0.4 < r["step_s"] < 2.0
    assert sum(r["by_phase"].values()) == pytest.approx(r["step_s"])
    share = {k: 100 * v / r["step_s"] for k, v in r["by_phase"].items()}
    assert set(re_.EVABYTE_DEVICE_PHASES) <= set(share)
    # the dense MLP is most of the step; the attention's scores and the summariser a few percent
    assert share["dense_mlp"] == max(share.values()) and share["dense_mlp"] > 50
    assert 0.5 < share["eva_attention"] < 15 and 0.1 < share["eva_summary"] < 10
    assert share.get(re_.UNATTRIBUTED, 0.0) < 10
    assert set(r["by_kernel"]) == {"attn_flash_fwd", "attn_flash_bwd_onesweep"}
    assert r["kernel_s"] < r["by_phase"]["eva_attention"]
    assert r["kernel_s"] < r["product_s"] < r["step_s"]
    model = _model()
    need = flops_evabyte.attention_kernel_ops_and_bytes(model, 1, 16384)
    assert 10 < 100 * need["ops"] / 197e12 / r["kernel_s"] < 100
    assert 20 < 100 * flops_evabyte.step_flops(model, 1, 16384) / r["product_s"] / 197e12 < 100


def test_the_other_vocabularies_read_the_step_as_not_theirs(recorded):
    glom = rp.reduce_phases([recorded], [])
    assert set(glom["step"]["by_phase"]) <= {rp.UNATTRIBUTED, "optimizer", "step_metrics"}
    assert {"attn_flash_fwd", "attn_flash_bwd_onesweep"} <= set(glom["step"]["by_kernel"])
    assert not any(k.startswith("ragged-dot") for k in glom["step"]["by_kernel"])
    for other in (rg, rk, rs):
        assert other.reduce([recorded]) is None
    for name in ("trace_phi4flash_train_2steps.json.gz", "trace_nemotron3super_train_2steps.json.gz",
                 "trace_lagunaxs2_train_2steps.json.gz", "trace_kimilinear_train_2steps.json.gz"):
        assert re_.reduce([_recorded(name)]) is None, name
