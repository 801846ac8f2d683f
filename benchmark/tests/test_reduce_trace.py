"""The trace reduction on a small recorded trace: two consecutive training
steps of flagship.train on a TPU v5 lite (PR 23), plus one collective and
one idle gap appended by hand (marked synthetic)."""
import gzip
import json
import os

import pytest

from benchmark import reduce_trace as rt

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "trace_flagship_train_2steps.json.gz")) as fh:
        fx = json.load(fh)
    dev = {k: [tuple(e) for e in v] for k, v in fx["devices"][0].items()}
    return dev, [tuple(e) for e in fx["host"]]


def test_busy_idle_and_mosaic_time(recorded):
    dev, host = recorded
    r = rt.reduce_lines([dev], host)
    assert r["n_devices"] == 1
    assert r["main_module"] == "jit_train_step" and r["main_module_runs"] == 2
    assert r["main_module_median_s"] == pytest.approx(0.1053, rel=1e-2)
    assert 0.99 < r["busy_s"] / r["window_s"] <= 1.0       # back-to-back steps
    assert r["mosaic_s"] / r["busy_s"] == pytest.approx(0.946, abs=5e-3)
    assert r["main_module_mosaic_median_s"] == pytest.approx(0.0996, rel=1e-2)
    assert r["collective_s"] == 0.0
    assert r["top_ops"][0][0] == "transpose_jvp___ custom-call:tpu_custom_call"


def test_a_collective_and_a_gap(recorded):
    dev, host = recorded
    end = max(s + d for _, s, d in dev["ops"])
    gap_ns, coll_ns = 3_000_000, 1_000_000
    ar = ("%all-reduce.7 = f32[6,512,2048]{2,1,0:T(8,128)} all-reduce(f32[6,512,2048]"
          "{2,1,0:T(8,128)} %fusion.3), replica_groups={{0,1,2,3}}, to_apply=%add")
    dev = dict(dev, ops=dev["ops"] + [(ar, end + gap_ns, coll_ns)])       # synthetic
    host = host + [("host_log_fetch", end - 1_000_000, gap_ns + 2_000_000),  # synthetic
                   ("$outer frame", end - 50_000_000, 100_000_000)]
    r = rt.reduce_lines([dev], host)
    assert r["collective_s"] == pytest.approx(coll_ns / 1e9)
    assert r["collective_exposed_s"] == pytest.approx(coll_ns / 1e9)  # nothing else runs
    assert r["idle_gaps"][0][0] == "host_log_fetch"                   # the innermost event
    assert r["idle_gaps"][0][1] == pytest.approx(gap_ns / 1e9)
    idle = 1.0 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(gap_ns / (r["window_s"] * 1e9), rel=0.05)


def test_interval_arithmetic_and_names():
    assert rt.union([(0, 5), (1, 3), (4, 8), (10, 12)]) == [(0, 8), (10, 12)]
    assert rt.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    name = ('%x.12 = (bf16[6,16384,512]{2,1,0:T(8,128)(2,1)}, f32[6]{0}) custom-call('
            'bf16[7,16384,512]{2,1,0:T(8,128)(2,1)} %a), custom_call_target="tpu_custom_call"')
    assert rt.opcode(name) == "custom-call"
    assert rt.short_name(name) == "x custom-call:tpu_custom_call"
    assert rt.opcode("%while.4 = (bf16[16]{0}, s32[]{:T(128)}) while((bf16[16]{0}) %t), body=%b") == "while"
    assert rt.is_collective("%ag = f32[8]{0} all-gather-start(f32[2]{0} %p), dimensions={0}")
    assert not rt.is_collective("%f = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop")
    assert rt.has_matmul("%f = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %p), kind=kOutput, calls=%c")
