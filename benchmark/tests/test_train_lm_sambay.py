"""The SambaY cell at a size a CPU test can hold (`tiny_sambay.py`):
everything of a run but the look for a chip. Sound: `correct` true, every
number beside its limit. With the timed path broken underneath (a window
that forgets its lower edge, a memory that is not handed on, steps that
lose their update), or the reference put in the program's place in float8:
false. And the cell's files against each other and against the catalog's
published numbers."""
import json
import os

import pytest

from benchmark import control_sambay, harness
from benchmark import correct as cmp
from tiny import drive, on_cpu
from tiny_sambay import tiny_sambay_cell

CELL = "phi4flash.train"
NUMBERS = ["first_grad_norm_gap", "first_grad_diff", "param_delta_norm_gap"]   # no loss_gap


def test_sound_run_is_correct(capsys):
    line, out = drive(tiny_sambay_cell(), capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"train_col_iters_per_s_per_chip", "setup_s"}
    assert "compiles in window 0" in out
    compared = line["compared"]
    assert list(compared)[:3] == NUMBERS and "loss_gap = " in out
    assert "'L01.qkv_b.k'" in out.split("not compared")[1]
    assert "loss of a batch outside the pool" in out
    assert compared["route"] == {"value": "lm_xla", "limit": "lm_xla", "ok": True}
    assert compared["records_vjp_path"]["ok"] and compared["spans_with_nonfinite_loss"]["ok"]
    assert "'attn_key_blocks_window': 2.0" in out and "'scan_chunks': 1.0" in out


def test_a_window_without_its_lower_edge_is_not_correct(capsys, monkeypatch):
    """The window layers attend to every key before them: full attention
    where the model has a window of 16."""
    from glom_tpu.models import sambay

    real = sambay.blocked_attention
    monkeypatch.setattr(sambay, "blocked_attention",
                        lambda q, k, v, window=None: real(q, k, v, None))
    line, out = drive(tiny_sambay_cell(), capsys)
    assert line["correct"] is False and "OVER" in out


def test_a_memory_that_stops_its_gradient_is_not_correct(capsys, monkeypatch):
    """The Gated Memory Unit reads the memory, but nothing flows back to the
    layer that made it."""
    import jax

    from glom_tpu.models import sambay

    real = sambay.gmu_mixer
    monkeypatch.setattr(sambay, "gmu_mixer", lambda p, x, memory, cfg, dtype: real(
        p, x, jax.lax.stop_gradient(memory), cfg, dtype))
    line, out = drive(tiny_sambay_cell(), capsys)
    assert line["correct"] is False and "OVER" in out


@pytest.mark.parametrize("fault", list(control_sambay.FAULTS))
def test_steps_that_lose_their_update_are_not_correct(capsys, fault):
    """The fast variant alone, which is step 2 of the first three and two of
    every three in the window: the parameters' change reads a third, which is
    what its limit is set under. Both variants: a state left unchanged reads
    1."""
    with control_sambay.losing_updates(*control_sambay.FAULTS[fault]):
        line, out = drive(tiny_sambay_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    change = line["compared"]["param_delta_norm_gap"]
    assert not change["ok"]
    if fault == "state_unchanged":
        assert change["value"] == 1.0
    else:
        assert 0.28 < change["value"] < 0.36


def test_float8_reference_fails_the_cells_limits(capsys):
    cell = tiny_sambay_cell()
    with on_cpu():
        rows = control_sambay.readings(cell, [11, 2**31 + 7], "float8")
    capsys.readouterr()
    for r in rows:
        assert set(r["sound"]) == set(NUMBERS) == set(cell["limits"])
        assert cmp.judge(r["sound"], cell["limits"])["ok"], r
        assert not cmp.judge(r["control"], cell["limits"])["ok"], r
    summary = control_sambay.summarise(rows)
    assert summary["first_grad_diff"]["sound_worst"] < summary["first_grad_diff"]["control_best"]


# ------------------------------------------------------------ the cell's files


def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs", "phi4-mini-flash-stage6vp8.json")) as fh:
        return json.load(fh)


def test_the_configuration_file_is_the_catalog_row_but_for_what_it_lists_as_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Phi-4-mini-flash-reasoning")
    cf = _config()
    assert cf["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cf[k] != v}
    assert differs == set(cf["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert cf["published"] == {k: row["config"][k] for k in cf["reduced"]}
    assert (cf["num_attention_heads"], cf["num_key_value_heads"], cf["intermediate_size"],
            cf["sliding_window"], cf["hidden_size"]) == (40, 20, 10240, 512, 2560)


def test_the_model_group_is_what_the_top_level_says():
    from glom_tpu.models import sambay
    from glom_tpu.utils.presets import get_preset

    cf = _config()
    for key, value in cf["model"].items():
        if key in cf:
            assert cf[key] == value, key
    assert cf["model"]["layer_norm_eps"] == cf["layer_norm_eps"]
    assert cf["model"]["num_hidden_layers_total"] == cf["published"]["num_hidden_layers"]
    assert 8 * cf["model"]["vocab_size"] == cf["published"]["vocab_size"]
    assert (cf["model"]["layer_offset"], cf["model"]["num_hidden_layers"]) == (14, 6)
    for item in ("mamba_expand", "mamba_d_state", "mamba_d_conv", "mamba_dt_rank", "window_edge",
                 "lambda_init_index", "positions", "packing", "init", "memory"):
        assert cf["assumed"][item], item
    assert "from the published modeling code's constants" in cf["assumed"]["ground"]
    assert "pipeline" in cf["deployment"] and "8 data-parallel replicas" in cf["deployment"]
    held = sambay.param_count(get_preset(cf["preset"]).model)
    assert held == 697_094_272 and "697M" in cf["deployment"] and "11.15 GB" in cf["deployment"]


def test_the_cell_finds_its_files_and_its_readers():
    cell = harness.load_cell(CELL)
    assert cell["traffic_file"]["kind"] == "train_lm_sambay" and cell["chips"] == 1
    assert os.path.exists(os.path.join(harness.BENCH_DIR, "drivers", "train_lm_sambay.py"))
    names = {m["name"] for m in cell["per_layer"]}
    assert {"selective_scan_time_pct.train", "diff_attention_time_pct.train",
            "window_keys_visited_pct.train", "sambay_matmul_roofline.train"} <= names
    assert not {"loop_kernels_roofline.train", "lm_matmul_roofline.train",
                "moe_routed_time_pct.train", "ssd_scan_time_pct.train"} & names
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    assert set(cell["limits"]) == set(NUMBERS)
    table = cell["config_file"]["bench"]["route_kernels"]
    assert cmp.kernels_fit({}, table)[0]
    assert not cmp.kernels_fit({"ragged-dot-none": 1.0}, table)[0]
    assert not cmp.kernels_fit({"ffw_fwd": 1.0}, table)[0]
    other = harness.load_cell("nemotron3super.train")
    assert not {"selective_scan_time_pct.train", "sambay_matmul_roofline.train"} & {
        m["name"] for m in other["per_layer"]}
