"""The Kimi Linear step's reduction by scope (`reduce_kimi.py`, which hands
its tuple to `reduce_laguna.by_scopes`) and the cell's readers (its own five,
and the routed part's two, which are the second family's: `reduce_lm` reads
the four `moe_*` scopes of any step): on made-up ops, on a small recorded
trace of two consecutive steps of `kimilinear.train` on a TPU v5 lite (PR
40; each instruction's events within a step merged into one, the loops'
bodies repeating thousands of times), on an empty context, and on a step that
is another family's."""
import gzip
import importlib.util
import json
import os

import pytest

from benchmark import flops_kimi, harness
from benchmark import reduce_kimi as rk
from benchmark import reduce_laguna as rg
from benchmark import reduce_lm as rl
from benchmark import reduce_phases as rp
from benchmark import reduce_sambay as rs

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("kda_scan_time_pct.train", "linear_attention_time_pct.train",
           "latent_attention_time_pct.train", "mla_flash_roofline.train",
           "kimi_matmul_roofline.train")
ROUTED_READERS = ("moe_routed_time_pct.train", "moe_expert_rows_fill_pct.train")  # accepted, PR 27
FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%c"
MATMUL = "%fusion.{} = bf16[16384,4096]{{1,0}} fusion(bf16[16384,2304] %p), kind=kOutput, calls=%c"
KERNEL = ('%{}.{} = bf16[1,32,1,16384,128]{{4,3,2,1,0}} custom-call(bf16[8] %p), '
          'custom_call_target="tpu_custom_call"')


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("km_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_vocabulary_is_the_programs():
    from glom_tpu.tracing import spans

    assert rk.KIMI_DEVICE_PHASES == spans.KIMI_DEVICE_PHASES
    assert set(rk.LINEAR_ATTENTION_PHASES) < set(rk.OWN_PHASES) <= set(rk.KIMI_DEVICE_PHASES)
    assert set(rl.MOE_ROUTED_PHASES) <= set(rk.KIMI_DEVICE_PHASES)
    assert not set(rk.KIMI_DEVICE_PHASES) & set(rp.DEVICE_PHASES)
    assert not set(rk.OWN_PHASES) & (set(spans.LM_DEVICE_PHASES) | set(spans.SAMBAY_DEVICE_PHASES)
                                     | set(spans.LAGUNA_DEVICE_PHASES))
    # the accepted copies stay as they were
    assert rl.LM_DEVICE_PHASES == spans.LM_DEVICE_PHASES
    assert rs.SAMBAY_DEVICE_PHASES == spans.SAMBAY_DEVICE_PHASES
    assert rg.LAGUNA_DEVICE_PHASES == spans.LAGUNA_DEVICE_PHASES


def _ops():
    inner = "jit(step)/jvp(checkpoint)/"
    return [("%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b", 100, 1000, ""),
            (MATMUL.format(1), 100, 150, inner + "kda_in/...k,kn->...n/dot_general:"),
            (FUSION.format(2), 250, 50, inner + "kda_in/mul:"),
            (MATMUL.format(3), 300, 100, inner + "kda_scan/checkpoint/nid,nde->nie/dot_general:"),
            (FUSION.format(4), 400, 200, inner + "kda_scan/checkpoint/vmap()/checkpoint/exp:"),
            (FUSION.format(5), 600, 50, inner + "kda_out/mul:"),
            (KERNEL.format("attn_flash_fwd", 6), 650, 50, inner + "latent_attention/pallas_call:"),
            (KERNEL.format("attn_flash_bwd_onesweep", 7), 700, 100,
             "jit(step)/transpose(jvp(checkpoint))/latent_attention/pallas_call:"),
            (KERNEL.format("ragged-dot-none", 8), 800, 50, ""),
            (FUSION.format(9), 850, 50, inner + "moe_combine/scatter-add:"),
            (MATMUL.format(10), 900, 50, inner + "dense_mlp/dot_general:"),
            (FUSION.format(11), 950, 50, "jit(step)/optimizer/mul:"),
            (FUSION.format(12), 1000, 100, inner + "add:"),
            (FUSION.format(13), 5000, 50, "jit(other)/embed/add:")]   # another program's op


def test_step_by_scope_sums_to_the_step_and_counts_products_and_kernels_apart():
    modules = [("jit_step(1)", 100, 1000), ("jit_other(2)", 5000, 50)]
    r = rk.reduce([{"ops": _ops(), "modules": modules}])
    assert r["runs"] == 1 and r["step_s"] == pytest.approx(1000e-9)
    assert r["by_phase"] == pytest.approx({
        "kda_in": 200e-9, "kda_scan": 300e-9, "kda_out": 50e-9, "latent_attention": 150e-9,
        "moe_experts": 50e-9, "moe_combine": 50e-9, "dense_mlp": 50e-9, "optimizer": 50e-9,
        rk.UNATTRIBUTED: 100e-9})
    assert r["by_kernel"] == pytest.approx(
        {"attn_flash_fwd": 50e-9, "attn_flash_bwd_onesweep": 100e-9})
    assert r["kernel_s"] == pytest.approx(150e-9)
    # dots, the ragged-dot call AND the attention kernels (trap 14)
    assert r["product_s"] == pytest.approx((150 + 100 + 50 + 100 + 50 + 50) * 1e-9)
    assert "under no scope: 10.00% of the step" in rk.tables(r)[0]
    # a step that opens no delta rule's scope is another family's: Laguna's reduction takes
    # its own recorded step, this one does not, and the other way round
    no_scan = [(n, s, d, p.replace("kda_scan", "mlp").replace("kda_", "mamba_"))
               for n, s, d, p in _ops()]
    assert rk.reduce([{"ops": no_scan, "modules": modules}]) is None
    assert rk.reduce([{"ops": [], "modules": []}]) is None
    assert rg.reduce([{"ops": _ops(), "modules": modules}]) is None
    # the second family's reduction, which the routed part's reader reads, takes the step
    theirs = rl.reduce([{"ops": _ops(), "modules": modules}])
    assert theirs["step_s"] == pytest.approx(r["step_s"])
    assert sum(theirs["by_phase"][p] for p in ("moe_experts", "moe_combine")) == pytest.approx(
        100e-9)


def _ctx(result, monkeypatch, **over):
    for reduction in (rk, rl):
        monkeypatch.setattr(reduction, "for_run", lambda ctx: result if ctx.get("trace") else None)
    with open(os.path.join(harness.BENCH_DIR, "configs", "kimi-linear-ep32vp8.json")) as fh:
        model = json.load(fh)["model"]
    rec = {"kind": "train_step", "moe_pairs_here": 4096.0, "moe_rows_computed": 20480.0,
           "kda_chunks": 1024.0, "kda_log_decay_min": -200.0}
    ctx = {"kind": "train", "chips": 1, "batch": 1, "seq_len": 16384, "steps": 6,
           "steps_traced": 6, "device_kind": "TPU v5 lite", "model": model,
           "trace": {"window_s": 2.0}, "records": [rec, dict(rec), {"kind": "span"}]}
    ctx.update(over)
    return ctx


def test_the_readers_on_a_made_up_reduction(monkeypatch):
    result = {"runs": 2, "step_s": 1.000, "product_s": 0.600, "kernel_s": 0.080,
              "by_phase": {"kda_in": 0.200, "kda_scan": 0.300, "kda_out": 0.050,
                           "latent_attention": 0.120, "moe_router": 0.010, "moe_dispatch": 0.020,
                           "moe_experts": 0.010, "moe_combine": 0.020, "moe_shared": 0.030,
                           "dense_mlp": 0.060},
              "by_kernel": {"attn_flash_fwd": 0.025, "attn_flash_bwd_onesweep": 0.055}}
    ctx = _ctx(result, monkeypatch)
    assert reader("kda_scan_time_pct.train")(ctx) == pytest.approx(30.0)
    assert reader("linear_attention_time_pct.train")(ctx) == pytest.approx(55.0)
    assert reader("latent_attention_time_pct.train")(ctx) == pytest.approx(12.0)
    assert reader("moe_routed_time_pct.train")(ctx) == pytest.approx(6.0)
    assert reader("moe_expert_rows_fill_pct.train")(ctx) == pytest.approx(20.0)
    need = flops_kimi.attention_kernel_ops_and_bytes(ctx["model"], 1, 16384)
    assert reader("mla_flash_roofline.train")(ctx) == pytest.approx(
        100 * need["ops"] / 197e12 / 0.080)
    assert 45 < reader("mla_flash_roofline.train")(ctx) < 60
    flops = flops_kimi.step_flops(ctx["model"], 1, 16384, 4096.0)
    assert reader("kimi_matmul_roofline.train")(ctx) == pytest.approx(
        100 * flops / 0.600 / 197e12)
    assert 30 < reader("kimi_matmul_roofline.train")(ctx) < 40


@pytest.mark.parametrize("name", READERS + ROUTED_READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert reader(name)({}) is None
    assert reader(name)({"kind": "train", "records": [], "steps": 8, "trace": None}) is None


def test_the_trace_readers_find_nothing_in_a_run_without_a_trace():
    """A run's records and no trace: only the counters' reader, which reads
    the records alone, has something to read."""
    rec = {"kind": "train_step", "moe_pairs_here": 4096.0, "moe_rows_computed": 20480.0}
    ctx = {"kind": "train", "records": [rec], "steps": 6, "trace": None, "chips": 1,
           "model": {"layer_types": "KKKA"}}
    for name in READERS + ROUTED_READERS[:1]:
        assert reader(name)(ctx) is None
    assert reader(ROUTED_READERS[1])(ctx) == pytest.approx(20.0)


# ------------------------------------------------------- the recorded trace


def _recorded(name):
    with gzip.open(os.path.join(HERE, name)) as fh:
        return {k: [tuple(e) for e in v] for k, v in json.load(fh)["devices"][0].items()}


@pytest.fixture(scope="module")
def recorded():
    return _recorded("trace_kimilinear_train_2steps.json.gz")


def test_recorded_steps_by_scope_and_by_kernel(recorded):
    r = rk.reduce([recorded])
    assert r["runs"] == 2 and 0.9 < r["step_s"] < 2.0
    assert sum(r["by_phase"].values()) == pytest.approx(r["step_s"])
    share = {k: 100 * v / r["step_s"] for k, v in r["by_phase"].items()}
    assert set(rk.KIMI_DEVICE_PHASES) <= set(share)
    # the delta rule in chunks is the largest scope of the step, and the KDA layers' three
    # scopes together most of it; the one latent layer's attention is a few percent
    linear = sum(share[p] for p in rk.LINEAR_ATTENTION_PHASES)
    assert share["kda_scan"] == max(share.values()) and 30 < share["kda_scan"] < 70
    assert 55 < linear < 85 and 3 < share["latent_attention"] < 15
    routed = sum(share[p] for p in rl.MOE_ROUTED_PHASES)
    assert 1 < routed < 12
    # the second family's reduction, which `moe_routed_time_pct.train` reads, takes this
    # step too (it opens `embed` and the routed part's scopes) and reads the same share
    theirs = rl.reduce([recorded])
    assert theirs["step_s"] == pytest.approx(r["step_s"])
    assert 100 * sum(theirs["by_phase"][p] for p in rl.MOE_ROUTED_PHASES) / theirs["step_s"] == (
        pytest.approx(routed))
    # the true share under no scope: the scan's layout copies and converts, which carry no
    # `op_name` (PERF.md trap 17)
    assert share.get(rk.UNATTRIBUTED, 0.0) < 12
    # both kernels ran, inside the latent attention's scope
    assert set(r["by_kernel"]) == {"attn_flash_fwd", "attn_flash_bwd_onesweep"}
    assert r["kernel_s"] < r["by_phase"]["latent_attention"]
    assert r["kernel_s"] < r["product_s"] < r["step_s"]
    # the two shares of a roofline the cell's readers would report from these two steps
    with open(os.path.join(harness.BENCH_DIR, "configs", "kimi-linear-ep32vp8.json")) as fh:
        model = json.load(fh)["model"]
    need = flops_kimi.attention_kernel_ops_and_bytes(model, 1, 16384)
    assert 40 < 100 * need["ops"] / 197e12 / r["kernel_s"] < 100
    flops = flops_kimi.step_flops(model, 1, 16384, 4096.0)
    assert 15 < 100 * flops / r["product_s"] / 197e12 < 100


def test_the_other_vocabularies_read_the_step_as_not_theirs(recorded):
    """GLOM's reduction (what `step_unattributed_pct.train` reads) finds the
    step builder's scopes and the kernels by name; Laguna's and SambaY's
    reductions find no attention scope of theirs; and this family's finds
    nothing in the other three families' recorded steps."""
    glom = rp.reduce_phases([recorded], [])
    assert set(glom["step"]["by_phase"]) <= {rp.UNATTRIBUTED, "optimizer", "step_metrics"}
    assert glom["step"]["by_phase"][rp.UNATTRIBUTED] / glom["step"]["step_s"] > 0.9
    assert {"attn_flash_fwd", "attn_flash_bwd_onesweep"} <= set(glom["step"]["by_kernel"])
    assert any(k.startswith("ragged-dot") for k in glom["step"]["by_kernel"])
    assert rg.reduce([recorded]) is None and rs.reduce([recorded]) is None
    for name in ("trace_phi4flash_train_2steps.json.gz", "trace_nemotron3super_train_2steps.json.gz",
                 "trace_lagunaxs2_train_2steps.json.gz"):
        assert rk.reduce([_recorded(name)]) is None, name
