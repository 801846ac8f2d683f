"""A cell cut to a size a CPU test can hold: the committed cell's files with
an MNIST-sized model laid over them. Never a measurement."""
import time
import types
from unittest import mock

from benchmark import harness


def on_cpu():
    """The one place the tests switch off the harness's look for a chip."""
    return mock.patch.object(harness, "check_device", lambda dev, chips: None)

MODEL = {"dim": 128, "levels": 4, "image_size": 28, "patch_size": 7, "mult": 4,
         "channels": 3, "local_consensus_radius": 0, "consensus_self": False}


def tiny_cell(name: str, *, compute_dtype: str = "float32") -> dict:
    cell = harness.load_cell(name)
    cf = cell["config_file"]
    cf["preset"] = "mnist"
    cf["model"] = dict(MODEL)
    cf["train"] = {"batch_per_chip": 8, "learning_rate": 3e-4, "noise_std": 0.5,
                   "compute_dtype": compute_dtype, "use_pallas": False}
    cf["serve"] = {"buckets": [1, 2, 4], "max_batch": 4, "iters": "auto",
                   "queue_depth": 64, "compute_dtype": compute_dtype}
    cf["bench"] = {"span_steps": 4, "expect_vjp_path": "scan_dense",
                   "expect_mosaic_calls": False, "reference_block_rows": 4}
    cell["traffic_file"].update(rate_per_s=40.0, image_pool=16, check_requests=8,
                                warmup_seconds=0.5, trace_seconds=0.5)
    return cell


def drive(cell: dict, capsys, *, seed: int = 2**31 + 12345, seconds: float = 1.5):
    """Everything of a run but the look for a chip; returns the result line."""
    import importlib
    import json

    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    driver = importlib.import_module("benchmark.drivers." + cell["traffic_file"]["kind"])
    with on_cpu():
        rc = driver.run(cell, args, harness.Clock(time.perf_counter()))
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    # everything `correct` was decided from: the line's last key, and the
    # last lines of standard error
    assert list(line)[-1] == "compared" and line["compared"]
    said = err.strip().splitlines()[-len(line["compared"]) - 1:]
    assert said[-1] == f"correct: {line['correct']}"
    for text, (name, c) in zip(said, line["compared"].items()):
        assert text.startswith(f"correct: {name} = ") and text.endswith("  ok") == c["ok"]
    return line, out
