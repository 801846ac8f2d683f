"""The route requirement of `correct`: the route a training run reports, held
against the Mosaic kernels its traced step shows (`routes/<route>.json`),
against its records, and against the routes its configuration admits."""
import glob
import gzip
import json
import os
import re

import pytest

from benchmark import correct as cmp
from benchmark import harness
from benchmark import reduce_phases as rp

HERE = os.path.dirname(os.path.abspath(__file__))
ROUTES = ("fused_loop", "scan_blockwise", "scan_dense")


def recorded_local1024():
    """The kernel table of two steps of local1024.train recorded on the chip
    at PR 24's tree, on route scan_dense: ffw_bwd, ffw_fwd, consensus in XLA."""
    with gzip.open(os.path.join(HERE, "trace_local1024_train_2steps.json.gz")) as fh:
        dev = json.load(fh)["devices"][0]
    step = rp.reduce_step(*([tuple(e) for e in dev[k]] for k in ("ops", "modules")))
    return step["by_kernel"]


# Seconds a step, by hand: the flagship's kernels as PERF.md section 5 lists
# them; the scan path with the consensus kernels on (the one-sweep backward as
# local1024.train showed it on the chip under GLOM_CONSENSUS_BWD=blockwise in
# PR 25, and the streamed forward with the two-pass backward of long rows); the
# scan path with the Pallas forward and the dense XLA backward, which
# GLOM_CONSENSUS_BWD=dense gives; and a step with no Mosaic call at all, the
# no-Pallas fallback that reports `scan_dense` too.
TABLES = {
    "recorded local1024": recorded_local1024,
    "flagship": lambda: {"loop_ffw_acc_bwd": .0317, "loop_ffw_add_acc_bwd": .0268,
                         "loop_ffw_fwd": .0149, "loop_ffw_add_fwd": .0125,
                         "loop_consensus_bwd": .0075, "loop_consensus_fwd": .0062},
    "blockwise onesweep": lambda: {"ffw_bwd": .1430, "ffw_fwd": .0547,
                                   "consensus_update_fwd": .0237,
                                   "consensus_update_bwd_onesweep": .0559},
    "blockwise streamed": lambda: {"ffw_add_bwd": .1, "ffw_add_fwd": .05,
                                   "consensus_update_streamed_fwd": .02,
                                   "consensus_update_bwd_dq": .02,
                                   "consensus_update_bwd_dkv": .02},
    "pallas forward, dense backward": lambda: {"ffw_bwd": .1429, "ffw_fwd": .0547,
                                               "consensus_update_fwd": .02},
    "no Mosaic kernel": dict,
    "flagship with a scan kernel in it": lambda: {"loop_ffw_fwd": .01, "loop_ffw_bwd": .02,
                                                  "loop_consensus_fwd": .01, "ffw_fwd": .01},
}
PASSES_AS = {
    "recorded local1024": {"scan_dense"},
    "flagship": {"fused_loop"},
    "blockwise onesweep": {"scan_blockwise"},
    "blockwise streamed": {"scan_blockwise"},
    "pallas forward, dense backward": {"scan_dense"},
    "no Mosaic kernel": set(),
    "flagship with a scan kernel in it": set(),
}


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("table", sorted(TABLES))
def test_a_kernel_table_passes_as_its_own_route_only(table, route):
    kernels = TABLES[table]()
    fits, wrong = cmp.kernels_fit(kernels, cmp.route_table(route))
    assert fits == (route in PASSES_AS[table]), (sorted(kernels), wrong)
    assert bool(wrong) != fits
    v = cmp.Verdict()
    cmp.hold_route(v, route, {route}, None, kernels)
    assert v.ok == fits
    assert v.compared["route"] == {"value": route, "limit": "any", "ok": True}
    assert v.compared["route_kernels"]["ok"] == fits
    for name in kernels:  # the names it saw are printed beside the requirement
        assert name in v.compared["route_kernels"]["value"]
        assert name in v.lines[-1]


@pytest.mark.parametrize("expect, admitted", [
    (None, set(ROUTES) | {"scan_sharded"}),
    ("fused_loop", {"fused_loop"}),
    (["fused_loop"], {"fused_loop"}),
    (["scan_dense", "scan_blockwise"], {"scan_dense", "scan_blockwise"}),
])
@pytest.mark.parametrize("route", ROUTES + ("scan_sharded",))
def test_expect_vjp_path_admits_its_members_and_nothing_else(expect, admitted, route):
    v = cmp.Verdict()
    cmp.hold_route(v, route, {route}, expect)
    assert v.ok == (route in admitted)
    assert "route_kernels" not in v.compared  # an untraced run has no kernel table
    assert v.compared["route"]["value"] == route


@pytest.mark.parametrize("paths", [set(), {"scan_blockwise"}, {"scan_dense", "scan_blockwise"},
                                   {None}])
def test_records_that_name_another_route_than_the_trainers_fail(paths):
    v = cmp.Verdict()
    cmp.hold_route(v, "scan_dense", paths, None)
    assert not v.ok
    assert v.compared["route"]["ok"] and not v.compared["records_vjp_path"]["ok"]
    assert "FAILED" in v.lines[-1]


def test_a_route_without_a_file_fails_the_traced_run_and_says_which_file():
    untraced, traced = cmp.Verdict(), cmp.Verdict()
    cmp.hold_route(untraced, "scan_sharded", {"scan_sharded"}, None)
    cmp.hold_route(traced, "scan_sharded", {"scan_sharded"}, None, {"ffw_fwd": .1})
    assert untraced.ok and not traced.ok
    assert "benchmark/routes/scan_sharded.json" in traced.compared["route_kernels"]["limit"]


def test_route_files_speak_the_programs_kernel_names():
    """Every pattern of every route's file matches a name some `pallas_call`
    of the program carries, and every route is one `resolve_vjp_path`
    returns: a rename in the program shows here, not as a traced run that
    cannot be correct."""
    declared = set()
    for path in glob.glob(os.path.join(harness.ROOT, "glom_tpu", "kernels", "*.py")):
        with open(path) as fh:
            declared |= set(re.findall(r'^\s+name="([a-z0-9_]+)",$', fh.read(), re.M))
    assert {"ffw_fwd", "loop_consensus_bwd", "consensus_update_bwd_onesweep"} <= declared
    with open(os.path.join(harness.ROOT, "glom_tpu", "models", "core.py")) as fh:
        core = fh.read()
    files = sorted(glob.glob(os.path.join(harness.BENCH_DIR, "routes", "*.json")))
    assert [os.path.basename(f)[:-len(".json")] for f in files] == sorted(ROUTES)
    for f in files:
        table = cmp.route_table(os.path.basename(f)[:-len(".json")])
        assert f'"{table["route"]}"' in core and f.endswith(table["route"] + ".json")
        assert table["required"]
        for pattern in table["required"] + table["forbidden"]:
            assert any(re.fullmatch(pattern.replace("*", ".*"), n) for n in declared), pattern


def test_only_the_flagship_configuration_pins_its_route():
    pins = {}
    for c in harness.load_manifest()["configs"]:
        with open(os.path.join(harness.ROOT, c["file"])) as fh:
            pins[c["name"]] = cmp.listed(json.load(fh)["bench"].get("expect_vjp_path"))
    assert pins == {"imagenet224-dp8": ["fused_loop"], "imagenet256-local": []}
