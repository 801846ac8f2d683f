"""The SambaY step's reduction by scope (`reduce_sambay.py`) and its four
readers: on made-up ops, on a small recorded trace of two consecutive steps of
`phi4flash.train` on a TPU v5 lite (PR 31; a step's repeated ops, the loops'
iterations, merged into one event each), on an empty context, and on a step
that is another family's."""
import gzip
import importlib.util
import json
import os

import pytest

from benchmark import flops_sambay, harness
from benchmark import reduce_lm as rl
from benchmark import reduce_phases as rp
from benchmark import reduce_sambay as rs

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("selective_scan_time_pct.train", "diff_attention_time_pct.train",
           "window_keys_visited_pct.train", "sambay_matmul_roofline.train")
FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%c"
MATMUL = "%fusion.{} = bf16[8192,20480]{{1,0}} fusion(bf16[8192,2560] %p), kind=kOutput, calls=%c"


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("sb_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_vocabulary_is_the_programs():
    from glom_tpu.tracing import spans

    assert rs.SAMBAY_DEVICE_PHASES == spans.SAMBAY_DEVICE_PHASES
    assert set(rs.STEP_BUILDER_PHASES) <= set(spans.DEVICE_PHASES)
    assert set(rs.ATTENTION_PHASES) <= set(rs.OWN_PHASES) <= set(rs.SAMBAY_DEVICE_PHASES)
    assert not set(rs.SAMBAY_DEVICE_PHASES) & set(rp.DEVICE_PHASES)
    # what tells this family's step from the other language model's
    assert not set(rs.OWN_PHASES) & set(spans.LM_DEVICE_PHASES)
    assert rl.LM_DEVICE_PHASES == spans.LM_DEVICE_PHASES   # the accepted copy stays as it was


def test_phase_of_takes_the_innermost_scope():
    assert rs.phase_of("jit(train_step)/jvp(checkpoint)/mlp/...k,kn->...n/dot_general:") == "mlp"
    assert rs.phase_of("jit(train_step)/transpose(jvp(checkpoint))/rematted_computation/"
                       "selective_scan/while/body/closed_call/while/body/mul:") == "selective_scan"
    assert rs.phase_of("jit(train_step)/jvp(checkpoint)/window_attention/checkpoint/"
                       "bqgrd,bkgd->bgrqk/dot_general:") == "window_attention"
    assert rs.phase_of("jit(train_step)/lm_head_loss/step_metrics/add:") == "step_metrics"
    assert rs.phase_of("jit(train_step)/optimizer/mul:") == "optimizer"
    assert rs.phase_of("jit(train_step)/jvp(checkpoint)/add:") == rs.UNATTRIBUTED
    assert rs.phase_of("") == rs.UNATTRIBUTED


def _ops():
    return [("%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b", 100, 900, ""),
            (MATMUL.format(1), 100, 300, "jit(step)/jvp(checkpoint)/mlp/dot_general:"),
            (FUSION.format(2), 400, 100, "jit(step)/jvp(checkpoint)/selective_scan/while/body/exp:"),
            (MATMUL.format(3), 500, 200, "jit(step)/jvp(checkpoint)/full_attention/dot_general:"),
            (FUSION.format(4), 700, 100, "jit(step)/jvp(checkpoint)/window_attention/exp:"),
            (FUSION.format(5), 800, 100, "jit(step)/optimizer/mul:"),
            (FUSION.format(6), 900, 100, "jit(step)/jvp(checkpoint)/add:"),
            (FUSION.format(7), 5000, 50, "jit(other)/embed/add:")]   # another program's op


def test_step_by_scope_sums_to_the_step_and_counts_products_apart():
    modules = [("jit_step(1)", 100, 900), ("jit_other(2)", 5000, 50)]
    r = rs.reduce_step(_ops(), modules)
    assert r["runs"] == 1 and r["step_s"] == pytest.approx(900e-9)
    assert r["by_phase"] == pytest.approx({
        "mlp": 300e-9, "selective_scan": 100e-9, "full_attention": 200e-9,
        "window_attention": 100e-9, "optimizer": 100e-9, rs.UNATTRIBUTED: 100e-9})
    assert r["product_s"] == pytest.approx(500e-9)
    whole = rs.reduce([{"ops": _ops(), "modules": modules}])
    assert whole["step_s"] == pytest.approx(900e-9)
    assert "under no scope: 11.11% of the step" in rs.tables(whole)[0]
    # the other language model's step opens `mamba_in` and `mlp`-less scopes only
    other = [(n, s, d, p.replace("selective_scan", "ssd_scan").replace("full_attention", "attention")
              .replace("window_attention", "attention")) for n, s, d, p in _ops()]
    assert rs.reduce([{"ops": other, "modules": modules}]) is None
    assert rs.reduce([{"ops": [], "modules": []}]) is None


def _ctx(result, monkeypatch, **over):
    monkeypatch.setattr(rs, "for_run", lambda ctx: result if ctx.get("trace") else None)
    with open(os.path.join(harness.BENCH_DIR, "configs", "phi4-mini-flash-stage6vp8.json")) as fh:
        model = json.load(fh)["model"]
    rec = {"kind": "train_step", "attn_key_blocks_window": 92.0, "attn_key_blocks_full": 576.0,
           "scan_chunks": 8.0}
    ctx = {"kind": "train", "chips": 1, "batch": 1, "seq_len": 8192, "steps": 6,
           "steps_traced": 6, "device_kind": "TPU v5 lite", "model": model,
           "trace": {"window_s": 2.0}, "records": [rec, dict(rec), {"kind": "span"}]}
    ctx.update(over)
    return ctx


def test_the_readers_on_a_made_up_reduction(monkeypatch):
    result = {"runs": 2, "step_s": 0.500, "product_s": 0.300,
              "by_phase": {"selective_scan": 0.100, "window_attention": 0.010,
                           "full_attention": 0.060, "cross_attention": 0.050, "mlp": 0.150},
              "product_by_phase": {}}
    ctx = _ctx(result, monkeypatch)
    assert reader("selective_scan_time_pct.train")(ctx) == pytest.approx(20.0)
    assert reader("diff_attention_time_pct.train")(ctx) == pytest.approx(24.0)
    # one window layer's 92 key blocks over the two full-length layers' 288 each
    assert reader("window_keys_visited_pct.train")(ctx) == pytest.approx(100 * 92 / 288)
    need = flops_sambay.train_flops_per_step(ctx["model"], 1, 8192)
    assert reader("sambay_matmul_roofline.train")(ctx) == pytest.approx(
        100 * need / 0.300 / 197e12)
    assert 60 < reader("sambay_matmul_roofline.train")(ctx) < 70
    masked = _ctx(result, monkeypatch)
    for r in masked["records"][:2]:
        r["attn_key_blocks_window"] = 288.0       # a window that is masked, not skipped
    assert reader("window_keys_visited_pct.train")(masked) == pytest.approx(100.0)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert reader(name)({}) is None
    assert reader(name)({"kind": "train", "records": [], "steps": 8, "trace": None}) is None


def test_the_readers_find_nothing_in_the_other_language_models_run():
    """What the parent of PR 31 gives them: the other model's records and
    no scope of this family's."""
    rec = {"kind": "train_step", "moe_pairs_here": 2816.0, "moe_rows_computed": 6144.0}
    ctx = {"kind": "train", "records": [rec], "steps": 6, "trace": None, "chips": 1,
           "model": {"hybrid_override_pattern": "ME*"}}
    for name in READERS:
        assert reader(name)(ctx) is None


# ------------------------------------------------------- the recorded trace


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "trace_phi4flash_train_2steps.json.gz")) as fh:
        fx = json.load(fh)
    return {k: [tuple(e) for e in v] for k, v in fx["devices"][0].items()}


def test_recorded_steps_by_scope(recorded):
    r = rs.reduce([recorded])
    assert r["runs"] == 2 and 0.6 < r["step_s"] < 0.9
    assert sum(r["by_phase"].values()) == pytest.approx(r["step_s"])
    share = {k: 100 * v / r["step_s"] for k, v in r["by_phase"].items()}
    assert set(rs.SAMBAY_DEVICE_PHASES) <= set(share)
    # the MLPs are the largest scope; the two full-length attentions each cost
    # some 2.5 times the window layer, not the 8 times of their score work:
    # the projections and the blocks' fixed costs are the same in all three
    assert share["mlp"] == max(share.values()) and 20 < share["mlp"] < 40
    assert 2 < share["full_attention"] / share["window_attention"] < 4
    assert abs(share["full_attention"] - share["cross_attention"]) < 2
    assert 30 < sum(share[p] for p in rs.ATTENTION_PHASES) < 55
    assert 8 < share["selective_scan"] < 25
    assert share.get(rs.UNATTRIBUTED, 0.0) < 3          # the true share under no scope
    assert 0.6 < r["product_s"] / r["step_s"] < 0.85
    # the recurrence is elementwise work: next to none of its time is in product ops
    assert r["product_by_phase"].get("selective_scan", 0.0) < 0.1 * r["by_phase"]["selective_scan"]
    # the roofline share the cell's reader would report from these two steps
    with open(os.path.join(harness.BENCH_DIR, "configs", "phi4-mini-flash-stage6vp8.json")) as fh:
        model = json.load(fh)["model"]
    roofline = 100 * flops_sambay.train_flops_per_step(model, 1, 8192) / r["product_s"] / 197e12
    assert 25 < roofline < 100


def test_the_other_vocabularies_find_only_the_step_builders_scopes_there(recorded):
    """GLOM's reduction of the same step (what `step_unattributed_pct.train`
    reads) and the other language model's."""
    glom = rp.reduce_phases([recorded], [])
    assert glom["speaks_vocabulary"]
    assert set(glom["step"]["by_phase"]) <= {rp.UNATTRIBUTED, "optimizer", "step_metrics"}
    assert glom["step"]["by_phase"][rp.UNATTRIBUTED] / glom["step"]["step_s"] > 0.95
    assert glom["step"]["by_kernel"] == {}               # no custom call: the route is XLA only
    other = rl.reduce([recorded])                        # it shares four scopes' names
    assert other is None or not any(other["by_phase"].get(p) for p in
                                    ("ssd_scan", "attention", "moe_experts", "moe_shared"))
    # and the other model's recorded step is none of this family's
    with gzip.open(os.path.join(HERE, "trace_nemotron3super_train_2steps.json.gz")) as fh:
        theirs = {k: [tuple(e) for e in v] for k, v in json.load(fh)["devices"][0].items()}
    assert rs.reduce([theirs]) is None
