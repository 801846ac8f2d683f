"""BENCHMARK.json against the rules a later PR can break by accident: names,
units, files found by name, every cell complete."""
import json
import os
import re

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _manifest():
    return harness.load_manifest()


def test_names_and_units():
    man = _manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in man[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in man["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    e2e = {m["name"] for m in man["end_to_end"]}
    assert "setup_s" in e2e
    for m in man["per_layer"]:
        assert m["moves"] in e2e and 0 < len(m["layer"]) <= 200
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in man["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(1, len(man["workloads"]) // 4)
    assert len(json.dumps(man)) < 64 * 1024


def test_every_cell_finds_its_files_and_reports_enough():
    man = _manifest()
    pairs = set()
    for w in man["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert cell["traffic_file"]["kind"] in ("train", "serve")
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "drivers",
                                           cell["traffic_file"]["kind"] + ".py"))
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
        for m in cell["per_layer"]:
            assert os.path.exists(os.path.join(harness.BENCH_DIR, "layer_metrics",
                                               m["name"] + ".py")), m["name"]
        assert cell["limits"]
    used = {w["config"] for w in man["workloads"]}
    for c in man["configs"]:
        assert c["name"] in used and c["file"].startswith("benchmark/")
        with open(os.path.join(harness.ROOT, c["file"])) as fh:
            cf = json.load(fh)
        assert sorted(cf["reduced"]) == sorted(c["reduced"])
        for width in ("dim", "mult", "patch_size", "levels"):
            assert width not in c["reduced"]


def test_parked_cells_find_their_files_too():
    """A cell waiting in parked/ runs by the same command; a benchmark PR
    moves its entries into BENCHMARK.json."""
    parked = os.path.join(harness.BENCH_DIR, "parked")
    in_manifest = {w["name"] for w in _manifest()["workloads"]}
    for f in sorted(os.listdir(parked)):
        name = f[:-len(".json")]
        assert name not in in_manifest
        cell = harness.load_cell(name)
        assert cell["name"] == name and NAME.match(name) and len(cell["why"]) <= 200
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"] and cell["limits"]
        for m in cell["per_layer"]:
            assert os.path.exists(os.path.join(harness.BENCH_DIR, "layer_metrics",
                                               m["name"] + ".py")), m["name"]


def test_configuration_files_carry_the_presets_widths():
    from glom_tpu.utils.presets import get_preset

    for c in _manifest()["configs"]:
        with open(os.path.join(harness.ROOT, c["file"])) as fh:
            cf = json.load(fh)
        preset = get_preset(cf["preset"])
        for k, v in cf["model"].items():
            assert getattr(preset.model, k) == v, (c["name"], k)
        for k, v in cf["train"].items():
            if k != "batch_per_chip":
                assert getattr(preset.train, k) == v, (c["name"], k)
        for k, v in cf.get("serve", {}).items():
            pv = getattr(preset.serve, k)
            assert (list(pv) if isinstance(pv, tuple) else pv) == v, (c["name"], k)
