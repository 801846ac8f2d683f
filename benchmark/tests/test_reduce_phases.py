"""The phase reduction on a small recorded trace of the tree of PR 24: two
consecutive training steps of local1024.train on a TPU v5 lite, each op with
its `op_name`, and the program's host spans of the same stretch; plus every
reader of PR 24 on that trace, on an empty context, and on what the parent
of PR 24 leaves to read."""
import gzip
import importlib.util
import json
import os

import pytest

from benchmark import harness
from benchmark import reduce_phases as rp
from benchmark import reduce_trace as rt

HERE = os.path.dirname(os.path.abspath(__file__))
NEW_READERS = (
    "data_wait_ms.train", "step_dispatch_ms.train", "data_produce_ms.train",
    "data_stage_ms.train", "consensus_time_pct.train", "optimizer_time_pct.train",
    "step_unattributed_pct.train", "collective_exposed_pct.train",
    "device_idle_attributed_pct.train", "device_idle_data_wait_pct.train")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "trace_local1024_train_2steps.json.gz")) as fh:
        fx = json.load(fh)
    dev = {k: [tuple(e) for e in v] for k, v in fx["devices"][0].items()}
    return dev, [tuple(e) for e in fx["host"]]


def reader(name):
    path = os.path.join(harness.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_vocabulary_is_the_programs():
    from glom_tpu.tracing import spans

    assert rp.HOST_PHASES == spans.HOST_PHASES
    assert rp.DEVICE_PHASES == spans.DEVICE_PHASES
    assert set(rp.STEP_BUILDER_PHASES) <= set(spans.DEVICE_PHASES)


def test_phase_of_takes_the_innermost_scope_then_the_kernels_name():
    mosaic = ('%loop_consensus_bwd.3 = bf16[6,64,256,512]{3,2,1,0} custom-call(bf16[7] %a), '
              'custom_call_target="tpu_custom_call"')
    assert rp.phase_of("jit(train_step)/transpose(jvp(loop))/pallas_call:", mosaic) == "loop"
    assert rp.phase_of("", mosaic) == "loop"
    assert rp.phase_of("", mosaic.replace("loop_consensus_bwd", "consensus_update_bwd_dq")) \
        == "consensus_update"
    assert rp.instruction(mosaic) == "loop_consensus_bwd"
    fusion = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c"
    assert rp.phase_of("jit(train_step)/jvp(loop)/while/body/closed_call/"
                       "consensus_update/jit(norm)/reduce_sum:", fusion) == "consensus_update"
    assert rp.phase_of("jit(train_step)/optimizer/mul:", fusion) == "optimizer"
    assert rp.phase_of("jit(train_step)/jvp()/convert_element_type:", fusion) == rp.UNATTRIBUTED
    ar = "%all-reduce.7 = f32[6,512]{1,0} all-reduce(f32[6,512]{1,0} %f), to_apply=%add"
    assert rp.phase_of("jit(train_step)/transpose(jvp(shard_map))/psum:", ar) == rp.COLLECTIVE
    assert rp.phase_of("jit(train_step)/grad_reduce/psum_scatter:", ar) == "grad_reduce"
    assert rp.primitive("jit(f)/transpose(jvp())/consensus_update/blij,bjld->bild/dot_general:") \
        == "bwd blij,bjld->bild/dot_general"
    assert rp.primitive("jit(f)/jvp(noise)/add:") == "fwd add"


def test_step_by_phase_sums_to_the_step_and_leaves_containers_out():
    f = "%f.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%c"
    ops = [("%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b", 100, 700, ""),
           (f.format(1), 100, 300, "jit(step)/jvp(loop)/while/body/bottom_up/dot_general:"),
           (f.format(2), 400, 400, "jit(step)/jvp(loop)/while/body/consensus/exp:"),
           (f.format(3), 800, 100, "jit(step)/optimizer/mul:"),
           (f.format(4), 900, 100, "jit(step)/jvp()/convert_element_type:"),
           (f.format(5), 5000, 50, "jit(other)/add:")]             # another program's op
    modules = [("jit_step(1)", 100, 900), ("jit_other(2)", 5000, 50)]
    r = rp.reduce_step(ops, modules)
    assert r["runs"] == 1
    assert r["step_s"] == pytest.approx(900e-9)
    assert r["by_phase"] == pytest.approx({"bottom_up": 300e-9, "consensus": 400e-9,
                                           "optimizer": 100e-9, rp.UNATTRIBUTED: 100e-9})
    assert sum(r["by_phase"].values()) == pytest.approx(r["step_s"])


def test_idle_goes_to_the_innermost_span_and_data_wait_is_the_overlap():
    ops = [("%a", 0, 1000, ""), ("%b", 101_000, 1000, ""), ("%c", 302_000, 1000, "")]
    gaps, window = rp.idle_gaps(ops)
    assert gaps == [(1000, 101_000), (102_000, 302_000)] and window == 303_000
    spans = [("host_prefetch_next", 0, 303_000, "worker", 3),
             ("host_data_next", 40_000, 50_000, "loop", 1),      # inside the first gap
             ("host_log_fetch", 290_000, 20_000, "loop", 1)]    # not at the 2nd gap's midpoint
    r = rp.attribute_idle(gaps, window, spans)
    assert r["by_span"] == pytest.approx({"host_data_next": 100e-6,
                                          "host_prefetch_next": 200e-6})
    assert r["idle_s"] == pytest.approx(300e-6) and r["attributed_s"] == pytest.approx(300e-6)
    assert r["data_wait_s"] == pytest.approx(50e-6)
    none = rp.attribute_idle(gaps, window, [])
    assert none["attributed_s"] == 0.0 and none["by_span"] == {rp.NO_SPAN: pytest.approx(300e-6)}
    # a trace opens mid-loop: idle before its first program span is the profiler's start
    late = rp.attribute_idle(gaps, window, [("host_step_dispatch", 150_000, 10_000, "loop", 0),
                                            ("host_prefetch_stage", 90_000, 5_000, "worker", 9)])
    assert late["idle_s"] == pytest.approx(200e-6) and late["attributed_s"] == 0.0


def test_wire_walk_reads_op_names_from_an_xspace(tmp_path):
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def ld(field, payload):
        return varint(field << 3 | 2) + varint(len(payload)) + payload

    def vi(field, n):
        return varint(field << 3) + varint(n)

    stat_meta = lambda i, name: ld(5, vi(1, i) + ld(2, vi(1, i) + ld(2, name)))
    event = lambda i, text, stats: ld(4, vi(1, i) + ld(2, vi(1, i) + ld(2, text) + stats))
    by_value = ld(5, vi(1, 300) + ld(5, b"jit(step)/optimizer/mul:"))
    by_ref = ld(5, vi(1, 300) + vi(7, 301))
    other = ld(5, vi(1, 299) + vi(3, 12345))
    plane = (vi(1, 7) + ld(2, b"/device:TPU:0") + ld(3, b"\x12\x07XLA Ops")
             + stat_meta(299, b"flops") + stat_meta(300, b"tf_op")
             + stat_meta(301, b"jit(step)/noise/add:")
             + event(1, b"%fusion.1 = f32[8]{0} fusion()", other + by_value)
             + event(2, b"%fusion.2 = f32[8]{0} fusion()", by_ref)
             + event(3, b"%copy-done.3 = f32[8]{0} copy-done()", other))
    host = ld(2, b"/host:CPU") + event(1, b"host_data_next", b"")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ld(1, plane) + ld(1, host))
    assert rp.op_names(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]{0} fusion()": "jit(step)/optimizer/mul:",
        "%fusion.2 = f32[8]{0} fusion()": "jit(step)/noise/add:"}}


# ------------------------------------------------------- the recorded trace


def test_recorded_steps_by_phase_and_kernel(recorded):
    dev, host = recorded
    r = rp.reduce_phases([dev], host)
    step = r["step"]
    assert r["speaks_vocabulary"] and step["runs"] == 2
    assert sum(step["by_phase"].values()) == pytest.approx(step["step_s"])
    share = {k: 100 * v / step["step_s"] for k, v in step["by_phase"].items()}
    # n=1024: consensus is XLA under the consensus_update scope, the FFWs are Mosaic
    assert 35 < share["consensus_update"] < 50
    assert 50 < share["bottom_up"] + share["top_down"] < 62
    assert 0 < share["optimizer"] < 2 and 0 < share["noise"] < 1
    assert share.get(rp.UNATTRIBUTED, 0.0) < 5
    assert set(step["by_kernel"]) == {"ffw_fwd", "ffw_bwd"}
    mosaic = sum(step["by_kernel"].values()) / step["step_s"]
    assert mosaic == pytest.approx(
        rt.reduce_lines([{"ops": [o[:3] for o in dev["ops"]], "modules": dev["modules"]}],
                        [])["main_module_mosaic_median_s"] / step["step_s"], rel=0.02)
    assert any("consensus_update" in line and "dot_general" in line
               for line in rp.tables(r))


def test_recorded_idle_is_the_programs_to_name(recorded):
    dev, host = recorded
    assert {h[0] for h in host} <= set(rp.HOST_PHASES)
    steps = sorted(h[4] for h in host if h[0] == "host_step_dispatch")
    assert steps == list(range(steps[0], steps[0] + len(steps)))  # the loop's step index
    idle = rp.reduce_phases([dev], host)["idle"]
    assert 0 <= idle["attributed_s"] <= idle["idle_s"] < 0.05 * idle["window_s"]
    assert idle["data_wait_s"] <= idle["idle_s"]


@pytest.fixture
def traced_ctx(recorded, monkeypatch):
    dev, host = recorded
    result = rp.reduce_phases([dev], host)
    monkeypatch.setattr(rp, "for_run", lambda ctx: result if ctx.get("trace") else None)
    span = lambda name, dur, count: {"kind": "span", "name": name, "dur_s": dur, "count": count}
    return {"kind": "train", "chips": 4, "steps": 8, "steps_traced": 8,
            "trace": {"collective_exposed_s": 0.003, "collective_s": 0.004, "window_s": 0.2},
            "records": [span("host_data_next", 0.004, 8), span("host_step_dispatch", 0.016, 8),
                        span("host_log_fetch", 2.0, 2), span("host_prefetch_next", 0.5, 10),
                        span("host_prefetch_stage", 0.03, 10), {"kind": "train_step"}]}


def test_every_new_reader_on_the_recorded_trace(traced_ctx):
    values = {name: reader(name)(traced_ctx) for name in NEW_READERS}
    assert all(v is not None for v in values.values()), values
    assert values["data_wait_ms.train"] == pytest.approx(0.5)
    assert values["step_dispatch_ms.train"] == pytest.approx(2.0)
    assert values["data_produce_ms.train"] == pytest.approx(50.0)
    assert values["data_stage_ms.train"] == pytest.approx(3.0)
    assert values["collective_exposed_pct.train"] == pytest.approx(1.5)
    assert 35 < values["consensus_time_pct.train"] < 50
    assert 0 < values["optimizer_time_pct.train"] < 2
    assert 0 <= values["step_unattributed_pct.train"] < 5
    assert 0 <= values["device_idle_attributed_pct.train"] <= 100
    assert 0 <= values["device_idle_data_wait_pct.train"] < 1


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert reader(name)({}) is None
    assert reader(name)({"kind": "train", "records": [], "steps": 8, "trace": None}) is None


def test_a_program_without_the_scopes_or_spans_leaves_nothing_to_read(recorded, monkeypatch):
    """What the parent of PR 24 gives: only the forward's scopes on the ops
    and none of the program's spans in the trace."""
    dev, _ = recorded
    old = {"bottom_up", "top_down", "consensus_update", "image_to_tokens", "reconstruction"}
    strip = lambda p: p if rp.phase_of(p, "") in old else "jit(train_step)/mul:"
    parent = dict(dev, ops=[(n.replace("ffw_", "jvp__"), s, d, strip(p))
                            for n, s, d, p in dev["ops"]])
    result = rp.reduce_phases([parent], [])
    assert not result["speaks_vocabulary"]
    monkeypatch.setattr(rp, "for_run", lambda ctx: result)
    ctx = {"kind": "train", "chips": 1, "steps": 8, "steps_traced": 8, "trace": {"window_s": 1.0},
           "records": [{"kind": "span", "name": "host_data_next", "dur_s": 0.004, "count": 8}]}
    values = {name: reader(name)(ctx) for name in NEW_READERS}
    assert values.pop("data_wait_ms.train") == pytest.approx(0.5)  # the parent has this span
    assert set(values.values()) == {None}
