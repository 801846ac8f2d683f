"""The Laguna cell at a size a CPU test can hold (`tiny_laguna.py`):
everything of a run but the look for a chip. Sound: `correct` true, every
number beside its limit. With the timed path broken underneath (no gate, a
window that forgets its lower edge, a router that forgets its scaling, steps
that lose their update), or the reference put in the program's place in
float8: false. And the cell's files against each other and against the
catalog's published numbers."""
import dataclasses
import json
import os

import pytest

from benchmark import control_laguna, control_sambay, harness
from benchmark import correct as cmp
from tiny import drive, on_cpu
from tiny_laguna import tiny_laguna_cell

CELL = "lagunaxs2.train"
NUMBERS = ["loss_gap", "first_grad_norm_gap", "first_grad_diff", "param_delta_norm_gap"]


def test_sound_run_is_correct(capsys):
    line, out = drive(tiny_laguna_cell(), capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"train_col_iters_per_s_per_chip", "setup_s"}
    assert "compiles in window 0" in out
    compared = line["compared"]
    assert list(compared)[:5] == NUMBERS + ["routing_agreement"]
    assert compared["routing_agreement"]["value"] == 1.0       # float32 against float32
    assert compared["route"] == {"value": "lm_xla", "limit": "lm_xla", "ok": True}
    assert compared["records_vjp_path"]["ok"] and compared["spans_with_nonfinite_loss"]["ok"]
    assert "'attn_key_blocks_window': 3.0" in out and "'moe_pairs_here'" in out
    assert "window's records, moe_rows_full_share: " in out
    assert "(FD SE SE SE FE, published 0-4)" in out


def test_a_program_without_the_gate_is_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp

    from glom_tpu.models import laguna

    monkeypatch.setattr(laguna, "head_gate", lambda p, u, dtype: jnp.ones(
        u.shape[:-1] + (p["gate"].shape[1],), u.dtype))
    line, out = drive(tiny_laguna_cell(), capsys)
    assert line["correct"] is False and "OVER" in out


def test_a_window_without_its_lower_edge_is_not_correct(capsys, monkeypatch):
    """The sliding layers attend to every key before them: full attention
    where the model has a window of 16."""
    from glom_tpu.models import laguna

    real = laguna.blocked_attention
    monkeypatch.setattr(laguna, "blocked_attention",
                        lambda q, k, v, window=None: real(q, k, v, None))
    line, out = drive(tiny_laguna_cell(), capsys)
    assert line["correct"] is False and "OVER" in out


def test_a_router_without_its_scaling_is_not_correct(capsys):
    cell = tiny_laguna_cell()
    cell["config_file"]["model"]["moe_routed_scaling_factor"] = 1.0
    from benchmark.drivers import train_lm_laguna as drv   # the reference keeps the 2.5

    model_of = drv.model_of
    with pytest.MonkeyPatch.context() as m:
        m.setattr(drv, "model_of", lambda cfg: dict(model_of(cfg), moe_routed_scaling_factor=2.5))
        line, out = drive(cell, capsys)
    assert line["correct"] is False and "OVER" in out


@pytest.mark.parametrize("fault", list(control_sambay.FAULTS))
def test_steps_that_lose_their_update_are_not_correct(capsys, fault):
    """The fast variant alone, which is step 2 of the first three and two of
    every three in the window: the parameters' change reads a third. Both
    variants: a state left unchanged reads 1."""
    with control_sambay.losing_updates(*control_sambay.FAULTS[fault]):
        line, out = drive(tiny_laguna_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    change = line["compared"]["param_delta_norm_gap"]
    assert not change["ok"]
    if fault == "state_unchanged":
        # not 1.0 to the bit: the seeded weights are made again inside the program that takes
        # the norms (`first_three_steps`), an ulp off the installed ones where the compiler
        # fuses the draw otherwise
        assert change["value"] == pytest.approx(1.0, abs=1e-5)
    else:
        assert 0.2 < change["value"] < 0.4


def test_float8_reference_fails_the_cells_limits(capsys):
    cell = tiny_laguna_cell()
    with on_cpu():
        rows = control_laguna.readings(cell, [11, 2**31 + 7], "float8", faults=1)
    capsys.readouterr()
    limits = dict(cell["limits"])
    floor = limits.pop("routing_agreement")
    for r in rows:
        sound, control = dict(r["sound"]), dict(r["control"])
        assert set(sound) == set(NUMBERS) | {"routing_agreement"} == set(cell["limits"])
        assert sound.pop("routing_agreement") >= floor and cmp.judge(sound, limits)["ok"], r
        agreed = control.pop("routing_agreement")
        assert agreed < floor or not cmp.judge(control, limits)["ok"], r
    for fault in ("fast_update_lost", control_laguna.HALF_BATCH):
        assert not cmp.judge({k: v for k, v in rows[0][fault].items()
                              if k != "routing_agreement"}, limits)["ok"], fault
    # half of the batch left out: the first gradient is one sequence's where the reference's
    # is the mean of two, which are all but orthogonal: the difference's norm says it first
    half = rows[0][control_laguna.HALF_BATCH]
    assert half["first_grad_diff"] > 2 * limits["first_grad_diff"]
    summary = control_laguna.summarise(rows)
    assert summary["first_grad_diff"][control_laguna.HALF_BATCH + "_best"] == (
        half["first_grad_diff"])
    assert summary["first_grad_diff"]["sound_worst"] < summary["first_grad_diff"]["control_best"]
    assert summary["routing_agreement"]["sound_worst"] > summary["routing_agreement"][
        "control_best"]


# ------------------------------------------------------------ the cell's files


def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs", "laguna-xs2-ep8vp8.json")) as fh:
        return json.load(fh)


def test_the_configuration_file_is_the_catalog_row_but_for_what_it_lists_as_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Laguna-XS.2")
    cf = _config()
    assert cf["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cf[k] != v}
    assert differs == set(cf["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert cf["published"] == {k: row["config"][k] for k in cf["reduced"]}
    assert (cf["num_key_value_heads"], cf["head_dim"], cf["intermediate_size"],
            cf["sliding_window"], cf["hidden_size"], cf["moe_intermediate_size"],
            cf["num_experts_per_tok"]) == (8, 128, 8192, 512, 2048, 512, 8)


def test_the_model_group_is_what_the_top_level_says():
    from glom_tpu.models import laguna
    from glom_tpu.utils.presets import get_preset

    cf = _config()
    model = cf["model"]
    for key, value in model.items():
        if key in cf and not isinstance(cf[key], list):
            assert cf[key] == value, key
    letters = lambda names, table: "".join(table[n] for n in names)
    assert model["layer_types"] == letters(cf["layer_types"],
                                           {"full_attention": "F", "sliding_attention": "S"})
    assert model["mlp_layer_types"] == letters(cf["mlp_layer_types"], {"dense": "D", "sparse": "E"})
    assert cf["num_attention_heads_per_layer"] == [
        model["num_attention_heads" if kind == "F" else "num_sliding_attention_heads"]
        for kind in model["layer_types"]]
    full, sliding = (cf["rope_parameters"][k] for k in ("full_attention", "sliding_attention"))
    assert (model["rope_theta_full"], model["yarn_factor"], model["yarn_beta_fast"],
            model["yarn_beta_slow"], model["yarn_original_max_position_embeddings"],
            model["yarn_attention_factor"], model["partial_rotary_factor"]) == (
        full["rope_theta"], full["factor"], full["beta_fast"], full["beta_slow"],
        full["original_max_position_embeddings"], full["attention_factor"],
        full["partial_rotary_factor"])
    assert model["rope_theta_sliding"] == sliding["rope_theta"] and (
        sliding["partial_rotary_factor"] == 1)
    assert model["num_hidden_layers_total"] == cf["published"]["num_hidden_layers"]
    assert model["num_experts_total"] == cf["published"]["num_experts"]
    assert 8 * model["vocab_size"] == cf["published"]["vocab_size"]
    assert 8 * model["num_experts"] == cf["published"]["num_experts"]
    assert (model["layer_offset"], model["num_hidden_layers"], model["expert_offset"]) == (0, 5, 96)
    for item in ("gating", "router", "qk_norm", "rotation", "yarn", "window_edge", "packing",
                 "init"):
        assert cf["assumed"][item], item
    assert "8 chips sharing each layer" in cf["deployment"] and "96-127" in cf["deployment"]
    preset = get_preset(cf["preset"]).model
    assert dataclasses.asdict(preset) == model
    held = laguna.param_count(preset)
    assert held == 691_623_936 and "691,623,936" in cf["deployment"] and (
        "11.07 GB" in cf["deployment"])
    assert "2 of 5" in cf["why"] and "10 of 40" in cf["why"]


def test_the_cell_finds_its_files_and_its_readers():
    cell = harness.load_cell(CELL)
    assert cell["traffic_file"]["kind"] == "train_lm_laguna" and cell["chips"] == 1
    assert cell["config_file"]["train"]["batch_per_chip"] == 2
    assert os.path.exists(os.path.join(harness.BENCH_DIR, "drivers", "train_lm_laguna.py"))
    names = {m["name"] for m in cell["per_layer"]}
    own = {"mixed_attention_time_pct.train", "attn_flash_roofline.train",
           "laguna_matmul_roofline.train"}
    # the routed part is one layer with one pair of readers for both families' cells
    assert own | {"moe_routed_time_pct.train", "moe_expert_rows_fill_pct.train"} <= names
    assert not {"loop_kernels_roofline.train", "lm_matmul_roofline.train",
                "ssd_scan_time_pct.train", "sambay_matmul_roofline.train",
                "diff_attention_time_pct.train", "window_keys_visited_pct.train"} & names
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    assert set(cell["limits"]) == set(NUMBERS) | {"routing_agreement"}
    table = cell["config_file"]["bench"]["route_kernels"]
    sound = {"attn_flash_fwd": 1.0, "attn_flash_bwd_onesweep": 1.0, "ragged-dot-none": 1.0}
    assert cmp.kernels_fit(sound, table)[0]
    assert not cmp.kernels_fit({"ragged-dot-none": 1.0}, table)[0]          # the XLA loop
    assert not cmp.kernels_fit({k: v for k, v in sound.items() if "ragged" not in k}, table)[0]
    assert not cmp.kernels_fit(dict(sound, ffw_fwd=1.0), table)[0]
    for other in ("nemotron3super.train", "phi4flash.train"):
        assert not own & {m["name"] for m in harness.load_cell(other)["per_layer"]}


def test_a_checkout_without_the_family_stops_at_once(monkeypatch):
    """What the parent commit does with this cell's files laid over it: no
    look for a chip, a plain message, a non-zero exit."""
    import sys

    from benchmark.drivers import train_lm_laguna as drv

    monkeypatch.setitem(sys.modules, "glom_tpu.models.laguna", None)
    monkeypatch.setattr(harness, "start_jax", lambda chips: pytest.fail("reached for the chip"))
    with pytest.raises(SystemExit, match="cannot run the Laguna cell"):
        drv.run(tiny_laguna_cell(), None, None)
