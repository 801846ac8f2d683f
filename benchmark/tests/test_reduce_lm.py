"""The language-model step's reduction by scope (`reduce_lm.py`) and its four
readers: on made-up ops, on a small recorded trace of two consecutive steps of
`nemotron3super.train` on a TPU v5 lite (PR 27), on an empty context, and on
a step that is not the language model's."""
import gzip
import importlib.util
import json
import os

import pytest

from benchmark import harness
from benchmark import reduce_lm as rl
from benchmark import reduce_phases as rp

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("moe_routed_time_pct.train", "ssd_scan_time_pct.train",
           "lm_matmul_roofline.train", "moe_expert_rows_fill_pct.train")
RAGGED = ('%ragged-dot-none.3 = bf16[65544,2688]{1,0} custom-call(s32[1] %a), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%c"
MATMUL = "%fusion.{} = bf16[8192,5376]{{1,0}} fusion(bf16[8192,4096] %p), kind=kOutput, calls=%c"


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_vocabulary_is_the_programs():
    from glom_tpu.tracing import spans

    assert rl.LM_DEVICE_PHASES == spans.LM_DEVICE_PHASES
    assert set(rl.STEP_BUILDER_PHASES) <= set(spans.DEVICE_PHASES)
    assert set(rl.MOE_ROUTED_PHASES) <= set(rl.LM_DEVICE_PHASES)
    assert not set(rl.LM_DEVICE_PHASES) & set(rp.DEVICE_PHASES)


def test_phase_of_takes_the_innermost_scope_then_the_ragged_dots_name():
    assert rl.phase_of("jit(train_step)/jvp(checkpoint)/moe_shared/...k,kn->...n/dot_general:",
                       MATMUL.format(1)) == "moe_shared"
    assert rl.phase_of("jit(train_step)/transpose(jvp(checkpoint))/moe_combine/step_metrics/"
                       "reduce_sum:") == "step_metrics"
    assert rl.phase_of("jit(train_step)/optimizer/mul:") == "optimizer"
    assert rl.phase_of("jit(train_step)/jvp(checkpoint)/add:") == rl.UNATTRIBUTED
    assert rl.phase_of("", RAGGED) == "moe_experts"
    assert rl.phase_of("", RAGGED.replace("ragged-dot-none", "loop_ffw_fwd")) == rl.UNATTRIBUTED
    assert rl.is_product(RAGGED) and rl.is_product(MATMUL.format(2))
    assert not rl.is_product(FUSION.format(3))


def test_step_by_scope_sums_to_the_step_and_counts_products_apart():
    ops = [("%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b", 100, 800, ""),
           (MATMUL.format(1), 100, 300, "jit(step)/jvp(checkpoint)/moe_shared/dot_general:"),
           (FUSION.format(2), 400, 100, "jit(step)/jvp(checkpoint)/moe_dispatch/gather:"),
           (RAGGED, 500, 200, ""),
           (FUSION.format(3), 700, 100, "jit(step)/jvp(checkpoint)/ssd_scan/exp:"),
           (FUSION.format(4), 800, 100, "jit(step)/optimizer/mul:"),
           (FUSION.format(5), 5000, 50, "jit(other)/embed/add:")]   # another program's op
    modules = [("jit_step(1)", 100, 800), ("jit_other(2)", 5000, 50)]
    r = rl.reduce_step(ops, modules)
    assert r["runs"] == 1 and r["step_s"] == pytest.approx(800e-9)
    assert r["by_phase"] == pytest.approx({"moe_shared": 300e-9, "moe_dispatch": 100e-9,
                                           "moe_experts": 200e-9, "ssd_scan": 100e-9,
                                           "optimizer": 100e-9})
    assert r["product_s"] == pytest.approx(500e-9)
    assert rl.reduce([{"ops": ops, "modules": modules}])["step_s"] == pytest.approx(800e-9)
    glom = [(n, s, d, p.replace("moe_shared", "loop").replace("moe_dispatch", "loop")
             .replace("ssd_scan", "consensus")) for n, s, d, p in ops if n != RAGGED]
    assert rl.reduce([{"ops": glom, "modules": modules}]) is None  # not this family's step


def _ctx(result, monkeypatch, **over):
    monkeypatch.setattr(rl, "for_run", lambda ctx: result if ctx.get("trace") else None)
    with open(os.path.join(harness.BENCH_DIR, "configs", "nemotron3-super-ep64tp8.json")) as fh:
        model = json.load(fh)["model"]
    rec = lambda pairs, load: {"kind": "train_step", "moe_pairs_here": pairs,
                               "moe_rows_computed": 65544.0, "moe_max_expert_load": load}
    ctx = {"kind": "train", "chips": 1, "batch": 1, "seq_len": 8192, "steps": 6,
           "steps_traced": 6, "device_kind": "TPU v5 lite", "model": model,
           "trace": {"window_s": 2.0},
           "records": [rec(3000.0, 1500.0), rec(2632.0, 900.0), {"kind": "span"}]}
    ctx.update(over)
    return ctx


def test_the_readers_on_a_made_up_reduction(monkeypatch):
    result = {"runs": 2, "step_s": 0.350, "product_s": 0.200,
              "by_phase": {"moe_router": 0.020, "moe_dispatch": 0.040, "moe_experts": 0.010,
                           "moe_combine": 0.050, "moe_shared": 0.090, "ssd_scan": 0.007},
              "product_by_phase": {}}
    ctx = _ctx(result, monkeypatch)
    assert reader("moe_routed_time_pct.train")(ctx) == pytest.approx(100 * 0.120 / 0.350)
    assert reader("ssd_scan_time_pct.train")(ctx) == pytest.approx(2.0)
    assert reader("moe_expert_rows_fill_pct.train")(ctx) == pytest.approx(100 * 2816 / 65544)
    from benchmark import flops_lm

    need = flops_lm.train_flops_per_step(ctx["model"], 1, 8192, 2816.0)
    assert reader("lm_matmul_roofline.train")(ctx) == pytest.approx(
        100 * need / 0.200 / 197e12)
    assert 50 < reader("lm_matmul_roofline.train")(ctx) < 60


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert reader(name)({}) is None
    assert reader(name)({"kind": "train", "records": [], "steps": 8, "trace": None}) is None


# ------------------------------------------------------- the recorded trace


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "trace_nemotron3super_train_2steps.json.gz")) as fh:
        fx = json.load(fh)
    return {k: [tuple(e) for e in v] for k, v in fx["devices"][0].items()}


def test_recorded_steps_by_scope(recorded):
    r = rl.reduce([recorded])
    assert r["runs"] == 2
    assert sum(r["by_phase"].values()) == pytest.approx(r["step_s"])
    share = {k: 100 * v / r["step_s"] for k, v in r["by_phase"].items()}
    assert set(rl.LM_DEVICE_PHASES) <= set(share) and share["optimizer"] > 1
    # the shared expert is the largest scope; the routed experts' four scopes
    # together cost more than it, for 2% of the FLOPs
    assert share["moe_shared"] == max(share.values())
    routed = sum(share[p] for p in rl.MOE_ROUTED_PHASES)
    assert 25 < routed < 50 and share["moe_experts"] < 16
    assert share["ssd_scan"] < 5 and share.get(rl.UNATTRIBUTED, 0.0) < 10
    assert 0.4 < r["product_s"] / r["step_s"] < 0.7
    # the GLOM vocabulary's reduction of the same step finds only the step builder's scopes
    glom = rp.reduce_phases([recorded], [])
    assert glom["speaks_vocabulary"]
    assert set(glom["step"]["by_phase"]) <= {rp.UNATTRIBUTED, "optimizer", "step_metrics"}
    assert set(glom["step"]["by_kernel"]) == {"ragged-dot-none", "ragged-dot-metadata"}
    assert any("moe_shared" in line for line in rl.tables(r))


def test_the_readers_on_the_recorded_trace(recorded, monkeypatch):
    ctx = _ctx(rl.reduce([recorded]), monkeypatch)
    values = {name: reader(name)(ctx) for name in READERS}
    assert all(v is not None for v in values.values()), values
    assert 25 < values["moe_routed_time_pct.train"] < 50
    assert 0 < values["ssd_scan_time_pct.train"] < 5
    assert 30 < values["lm_matmul_roofline.train"] < 100
    assert values["moe_expert_rows_fill_pct.train"] == pytest.approx(100 * 2816 / 65544)
