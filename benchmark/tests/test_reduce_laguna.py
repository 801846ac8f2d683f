"""The Laguna step's reduction by scope (`reduce_laguna.py`) and the cell's
readers (its own three, and the routed part's two, which are the other
family's: `reduce_lm` reads the four `moe_*` scopes of either step): on
made-up ops, on a small recorded trace of two consecutive steps of
`lagunaxs2.train` on a TPU v5 lite (PR 36), on an empty context, and on a step
that is another family's."""
import gzip
import importlib.util
import json
import os

import pytest

from benchmark import flops_laguna, harness
from benchmark import reduce_laguna as rg
from benchmark import reduce_lm as rl
from benchmark import reduce_phases as rp
from benchmark import reduce_sambay as rs

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("mixed_attention_time_pct.train", "attn_flash_roofline.train",
           "laguna_matmul_roofline.train")
ROUTED_READERS = ("moe_routed_time_pct.train", "moe_expert_rows_fill_pct.train")  # accepted, PR 27
FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%c"
MATMUL = "%fusion.{} = bf16[16384,8192]{{1,0}} fusion(bf16[16384,2048] %p), kind=kOutput, calls=%c"
KERNEL = ('%{}.{} = bf16[2,16,4,8192,128]{{4,3,2,1,0}} custom-call(bf16[8] %p), '
          'custom_call_target="tpu_custom_call"')


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lg_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_vocabulary_is_the_programs():
    from glom_tpu.tracing import spans

    assert rg.LAGUNA_DEVICE_PHASES == spans.LAGUNA_DEVICE_PHASES
    assert set(rg.ATTENTION_PHASES) | set(rg.MOE_ROUTED_PHASES) <= set(rg.LAGUNA_DEVICE_PHASES)
    assert not set(rg.LAGUNA_DEVICE_PHASES) & set(rp.DEVICE_PHASES)
    assert all(k.startswith(rg.ATTENTION_KERNELS) for k in spans.LM_KERNELS)
    # the accepted copies stay as they were
    assert rl.LM_DEVICE_PHASES == spans.LM_DEVICE_PHASES
    assert rs.SAMBAY_DEVICE_PHASES == spans.SAMBAY_DEVICE_PHASES


def _ops():
    inner = "jit(step)/jvp(checkpoint)/"
    return [("%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b", 100, 1000, ""),
            (MATMUL.format(1), 100, 200, inner + "window_attention/...k,kn->...n/dot_general:"),
            (FUSION.format(2), 300, 50, inner + "window_attention/rope/mul:"),
            (KERNEL.format("attn_flash_fwd", 3), 350, 100, inner + "window_attention/pallas_call:"),
            (KERNEL.format("attn_flash_bwd_onesweep", 4), 450, 150,
             "jit(step)/transpose(jvp(checkpoint))/full_attention/pallas_call:"),
            (FUSION.format(5), 600, 50, inner + "full_attention/attn_gate/mul:"),
            (KERNEL.format("ragged-dot-none", 6), 650, 100, ""),
            (FUSION.format(7), 750, 100, inner + "moe_combine/scatter-add:"),
            (MATMUL.format(8), 850, 100, inner + "dense_mlp/dot_general:"),
            (FUSION.format(9), 950, 50, "jit(step)/optimizer/mul:"),
            (FUSION.format(10), 1000, 100, inner + "add:"),
            (FUSION.format(11), 5000, 50, "jit(other)/embed/add:")]   # another program's op


def test_step_by_scope_sums_to_the_step_and_counts_products_and_kernels_apart():
    modules = [("jit_step(1)", 100, 1000), ("jit_other(2)", 5000, 50)]
    r = rg.reduce([{"ops": _ops(), "modules": modules}])
    assert r["runs"] == 1 and r["step_s"] == pytest.approx(1000e-9)
    assert r["by_phase"] == pytest.approx({
        "window_attention": 350e-9, "full_attention": 200e-9, "moe_experts": 100e-9,
        "moe_combine": 100e-9, "dense_mlp": 100e-9, "optimizer": 50e-9,
        rg.UNATTRIBUTED: 100e-9})       # an inner scope's op is the attention phase's round it
    assert r["by_kernel"] == pytest.approx(
        {"attn_flash_fwd": 100e-9, "attn_flash_bwd_onesweep": 150e-9})
    assert r["kernel_s"] == pytest.approx(250e-9)
    # dots, the ragged-dot call AND the attention kernels (trap 14)
    assert r["product_s"] == pytest.approx((200 + 100 + 150 + 100 + 100) * 1e-9)
    assert "under no scope: 10.00% of the step" in rg.tables(r)[0]
    # SambaY's step opens the two attention scopes and no routed part: not this family's
    theirs = [(n, s, d, p.replace("moe_combine", "mlp").replace("dense_mlp", "mlp"))
              for n, s, d, p in _ops() if "ragged" not in n]
    assert rg.reduce([{"ops": theirs, "modules": modules}]) is None
    assert rg.reduce([{"ops": [], "modules": []}]) is None


def _ctx(result, monkeypatch, **over):
    for reduction in (rg, rl):
        monkeypatch.setattr(reduction, "for_run", lambda ctx: result if ctx.get("trace") else None)
    with open(os.path.join(harness.BENCH_DIR, "configs", "laguna-xs2-ep8vp8.json")) as fh:
        model = json.load(fh)["model"]
    rec = {"kind": "train_step", "moe_pairs_here": 16384.0, "moe_rows_computed": 32768.0,
           "attn_key_blocks_window": 1.0, "attn_key_blocks_full": 1.0}
    ctx = {"kind": "train", "chips": 1, "batch": 2, "seq_len": 8192, "steps": 6,
           "steps_traced": 6, "device_kind": "TPU v5 lite", "model": model,
           "trace": {"window_s": 2.0}, "records": [rec, dict(rec), {"kind": "span"}]}
    ctx.update(over)
    return ctx


def test_the_readers_on_a_made_up_reduction(monkeypatch):
    result = {"runs": 2, "step_s": 0.600, "product_s": 0.450, "kernel_s": 0.150,
              "by_phase": {"window_attention": 0.150, "full_attention": 0.180,
                           "moe_router": 0.030, "moe_dispatch": 0.020, "moe_experts": 0.040,
                           "moe_combine": 0.030, "moe_shared": 0.020, "dense_mlp": 0.050},
              "by_kernel": {"attn_flash_fwd": 0.070, "attn_flash_bwd_onesweep": 0.080}}
    ctx = _ctx(result, monkeypatch)
    assert reader("mixed_attention_time_pct.train")(ctx) == pytest.approx(55.0)
    assert reader("moe_routed_time_pct.train")(ctx) == pytest.approx(20.0)
    assert reader("moe_expert_rows_fill_pct.train")(ctx) == pytest.approx(50.0)
    need = flops_laguna.attention_kernel_ops_and_bytes(ctx["model"], 2, 8192)
    assert reader("attn_flash_roofline.train")(ctx) == pytest.approx(
        100 * need["ops"] / 197e12 / 0.150)
    assert 35 < reader("attn_flash_roofline.train")(ctx) < 50
    flops = flops_laguna.train_flops_per_step(ctx["model"], 2, 8192, 16384.0)
    assert reader("laguna_matmul_roofline.train")(ctx) == pytest.approx(
        100 * flops / 0.450 / 197e12)
    assert 40 < reader("laguna_matmul_roofline.train")(ctx) < 50


@pytest.mark.parametrize("name", READERS + ROUTED_READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert reader(name)({}) is None
    assert reader(name)({"kind": "train", "records": [], "steps": 8, "trace": None}) is None


def test_the_trace_readers_find_nothing_in_a_run_without_a_trace():
    """The other model's records and no trace: only the counters' reader,
    which reads the records alone, has something to read."""
    rec = {"kind": "train_step", "moe_pairs_here": 2816.0, "moe_rows_computed": 6144.0}
    ctx = {"kind": "train", "records": [rec], "steps": 6, "trace": None, "chips": 1,
           "model": {"hybrid_override_pattern": "ME*"}}
    for name in READERS + ROUTED_READERS[:1]:
        assert reader(name)(ctx) is None
    assert reader(ROUTED_READERS[1])(ctx) == pytest.approx(100 * 2816 / 6144)


# ------------------------------------------------------- the recorded trace


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "trace_lagunaxs2_train_2steps.json.gz")) as fh:
        fx = json.load(fh)
    return {k: [tuple(e) for e in v] for k, v in fx["devices"][0].items()}


def test_recorded_steps_by_scope_and_by_kernel(recorded):
    r = rg.reduce([recorded])
    assert r["runs"] == 2 and 0.55 < r["step_s"] < 0.8
    assert sum(r["by_phase"].values()) == pytest.approx(r["step_s"])
    share = {k: 100 * v / r["step_s"] for k, v in r["by_phase"].items()}
    assert set(rg.LAGUNA_DEVICE_PHASES) <= set(share)
    # the two attentions are most of the step; three sliding layers cost what
    # two full layers do (six times fewer keys, a third more heads, half more layers)
    attention = sum(share[p] for p in rg.ATTENTION_PHASES)
    assert 55 < attention < 70 and 0.8 < share["window_attention"] / share["full_attention"] < 1.4
    routed = sum(share[p] for p in rg.MOE_ROUTED_PHASES)
    assert 8 < routed < 20
    # the other family's reduction, which `moe_routed_time_pct.train` reads, takes this
    # step too (it opens `embed` and the routed part's scopes) and reads the same share
    theirs = rl.reduce([recorded])
    assert theirs["step_s"] == pytest.approx(r["step_s"])
    assert 100 * sum(theirs["by_phase"][p] for p in rl.MOE_ROUTED_PHASES) / theirs["step_s"] == (
        pytest.approx(routed))
    assert 5 < share["dense_mlp"] < 10 and share["moe_shared"] < share["dense_mlp"]
    assert share.get(rg.UNATTRIBUTED, 0.0) < 10         # the true share under no scope
    # both kernels ran, inside the attention scopes, and are a quarter of the step
    assert set(r["by_kernel"]) == {"attn_flash_fwd", "attn_flash_bwd_onesweep"}
    assert 20 < 100 * r["kernel_s"] / r["step_s"] < 32
    assert r["kernel_s"] < r["by_phase"]["window_attention"] + r["by_phase"]["full_attention"]
    assert r["kernel_s"] < r["product_s"] < r["step_s"]
    # the two shares of a roofline the cell's readers would report from these two steps
    with open(os.path.join(harness.BENCH_DIR, "configs", "laguna-xs2-ep8vp8.json")) as fh:
        model = json.load(fh)["model"]
    need = flops_laguna.attention_kernel_ops_and_bytes(model, 2, 8192)
    assert 25 < 100 * need["ops"] / 197e12 / r["kernel_s"] < 100
    flops = flops_laguna.train_flops_per_step(model, 2, 8192, 16384.0)
    assert 35 < 100 * flops / r["product_s"] / 197e12 < 100


def test_no_float32_score_array_under_the_attention_scopes(recorded):
    """Outside the kernels' calls, no op under `window_attention` or
    `full_attention` writes a float32 array with queries and keys as its two
    last dimensions: the scores stay in the kernels."""
    import re

    leaf, _ = rp.step_ops(recorded["ops"], recorded["modules"])
    for name, _, _, op_name in leaf:
        if not any(p in op_name for p in rg.ATTENTION_PHASES) or rg.is_attention_kernel(name):
            continue
        out = name.partition(" = ")[2].partition(" ")[0]
        for dims in re.findall(r"f32\[([\d,]+)\]", out):
            dims = [int(x) for x in dims.split(",")]
            # [.., heads, queries, keys]: more than three dimensions ending in two of >= 512
            assert not (len(dims) >= 4 and min(dims[-2:]) >= 512 and dims[-1] != 128), name[:200]


def test_the_other_vocabularies_read_the_step_as_not_theirs(recorded):
    """GLOM's reduction (what `step_unattributed_pct.train` reads) finds the
    step builder's scopes and the kernels by name; SambaY's tuple shares the
    two attention scopes' names and `reduce_sambay` takes the step for its
    own (it has no scope of its own there but those two): its readers are not
    read in this cell, whose metrics list their cells."""
    glom = rp.reduce_phases([recorded], [])
    assert set(glom["step"]["by_phase"]) <= {rp.UNATTRIBUTED, "optimizer", "step_metrics"}
    assert glom["step"]["by_phase"][rp.UNATTRIBUTED] / glom["step"]["step_s"] > 0.9
    assert {"attn_flash_fwd", "attn_flash_bwd_onesweep"} <= set(glom["step"]["by_kernel"])
    assert any(k.startswith("ragged-dot") for k in glom["step"]["by_kernel"])
    for name in ("trace_phi4flash_train_2steps.json.gz", "trace_nemotron3super_train_2steps.json.gz"):
        with gzip.open(os.path.join(HERE, name)) as fh:
            theirs = {k: [tuple(e) for e in v] for k, v in json.load(fh)["devices"][0].items()}
        assert rg.reduce([theirs]) is None, name
