"""The Kimi Linear cell at a size a CPU test can hold (`tiny_kimi.py`):
everything of a run but the look for a chip. Sound: `correct` true, every
number beside its limit. With the timed path broken underneath (no decay, a
delta rule without its in-chunk solve, a router that forgets its scaling,
steps that lose their update), or the reference put in the program's place in
float8: false. The control's faults of the delta rule's own types. And the
cell's files against each other and against the catalog's published numbers."""
import dataclasses
import json
import os

import pytest

from benchmark import control_kimi, control_sambay, harness
from benchmark import correct as cmp
from tiny import drive, on_cpu
from tiny_kimi import tiny_kimi_cell

CELL = "kimilinear.train"
NUMBERS = ["loss_gap", "first_grad_norm_gap", "first_grad_diff", "param_delta_norm_gap",
           "mixer_grad_diff", "kda_scan_diff"]


def test_sound_run_is_correct(capsys):
    line, out = drive(tiny_kimi_cell(), capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"train_col_iters_per_s_per_chip", "setup_s"}
    assert "compiles in window 0" in out
    compared = line["compared"]
    assert list(compared)[:7] == NUMBERS + ["routing_agreement"]
    assert compared["kda_scan_diff"]["value"] < 1e-5 and "'dbeta': " in out
    assert 0 < compared["mixer_grad_diff"]["value"] <= compared["first_grad_diff"]["value"]
    assert compared["routing_agreement"]["value"] == 1.0       # float32 against float32
    assert compared["route"] == {"value": "lm_xla", "limit": "lm_xla", "ok": True}
    assert compared["records_vjp_path"]["ok"] and compared["spans_with_nonfinite_loss"]["ok"]
    assert "'kda_chunks': 16.0" in out and "'moe_pairs_here'" in out
    assert "window's records, moe_rows_full_share: " in out
    assert "window's records, kda_log_decay_min: -" in out
    assert "(KD KE KE AE KE, published 1-5)" in out


def test_a_delta_rule_without_its_decay_is_not_correct(capsys, monkeypatch):
    from glom_tpu.models import kimi_linear

    real = kimi_linear.kda_chunked
    monkeypatch.setattr(kimi_linear, "kda_chunked",
                        lambda q, k, v, g, beta: real(q, k, v, 0 * g, beta))
    line, out = drive(tiny_kimi_cell(), capsys)
    assert line["correct"] is False and "OVER" in out


def test_a_delta_rule_without_its_in_chunk_solve_is_not_correct(capsys, monkeypatch):
    """The chunk's updates applied as if each saw the state the chunk entered
    with: T = Diag(beta), a gated linear attention inside the chunk."""
    import jax
    import jax.numpy as jnp

    from glom_tpu.models import kimi_linear

    monkeypatch.setattr(kimi_linear, "unit_lower_inverse", lambda strict: jnp.broadcast_to(
        jnp.eye(strict.shape[-1], dtype=strict.dtype), strict.shape))
    jax.clear_caches()
    try:
        line, out = drive(tiny_kimi_cell(), capsys)
    finally:
        jax.clear_caches()
    assert line["correct"] is False and "OVER" in out


@pytest.mark.parametrize("fault", list(control_kimi.SCAN_FAULTS))
def test_a_delta_rule_in_bfloat16_is_not_correct(capsys, fault):
    """The carried state, or the in-chunk solve, in bfloat16 where the
    configuration states float32: the delta rule's own number reads over its
    limit, whatever the step's other roundings hide."""
    with control_kimi.scan_in_bfloat16(control_kimi.SCAN_FAULTS[fault]):
        line, out = drive(tiny_kimi_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    scan = line["compared"]["kda_scan_diff"]
    assert not scan["ok"] and scan["value"] > 1.5 * scan["limit"]


def test_a_router_without_its_scaling_is_not_correct(capsys):
    cell = tiny_kimi_cell()
    cell["config_file"]["model"]["routed_scaling_factor"] = 1.0
    from benchmark.drivers import train_lm_kimi as drv   # the reference keeps the 2.446

    model_of = drv.model_of
    with pytest.MonkeyPatch.context() as m:
        m.setattr(drv, "model_of", lambda cfg: dict(model_of(cfg), routed_scaling_factor=2.446))
        line, out = drive(cell, capsys)
    assert line["correct"] is False and "OVER" in out


@pytest.mark.parametrize("fault", list(control_sambay.FAULTS))
def test_steps_that_lose_their_update_are_not_correct(capsys, fault):
    """The fast variant alone, which is step 2 of the first three and two of
    every three in the window: the parameters' change reads a third. Both
    variants: a state left unchanged reads 1."""
    with control_sambay.losing_updates(*control_sambay.FAULTS[fault]):
        line, out = drive(tiny_kimi_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    change = line["compared"]["param_delta_norm_gap"]
    assert not change["ok"]
    if fault == "state_unchanged":
        assert change["value"] == pytest.approx(1.0, abs=1e-5)
    else:
        assert 0.2 < change["value"] < 0.4


def test_the_control_and_the_faults_read_worse_than_a_sound_run(capsys):
    """`control_kimi.readings` on two seeds at the tiny size: sound within the
    cell's limits; the float8 reference outside them; a lost update and half
    of the row left out outside them; and the delta rule's state or solve in
    bfloat16 over the limit of the delta rule's own number, and further from
    the reference than the sound float32 program is in the first gradient (at
    this size the whole program is float32, so the fault is the only rounding
    there is)."""
    cell = tiny_kimi_cell()
    with on_cpu():
        rows = control_kimi.readings(cell, [11, 2**31 + 7], "float8", faults=1, scan_faults=1)
    capsys.readouterr()
    limits = dict(cell["limits"])
    floor = limits.pop("routing_agreement")
    for r in rows:
        sound, control = dict(r["sound"]), dict(r["control"])
        assert set(sound) == set(NUMBERS) | {"routing_agreement"} == set(cell["limits"])
        assert sound.pop("routing_agreement") >= floor and cmp.judge(sound, limits)["ok"], r
        agreed = control.pop("routing_agreement")
        assert agreed < floor or not cmp.judge(control, limits)["ok"], r
        assert control["mixer_grad_diff"] > limits["mixer_grad_diff"], r
        assert control["kda_scan_diff"] == sound["kda_scan_diff"]    # the recurrence is no product
    first = rows[0]
    for fault in ("fast_update_lost", "state_unchanged", control_kimi.HALF_ROW):
        assert not cmp.judge({k: v for k, v in first[fault].items()
                              if k != "routing_agreement"}, limits)["ok"], fault
    assert first[control_kimi.HALF_ROW]["first_grad_diff"] > 2 * limits["first_grad_diff"]
    assert first[control_kimi.HALF_ROW]["mixer_grad_diff"] > 2 * limits["mixer_grad_diff"]
    for fault in control_kimi.SCAN_FAULTS:     # step 1 alone: the gradient's numbers and the scan's
        assert set(first[fault]) == {"first_grad_norm_gap", "first_grad_diff", "mixer_grad_diff",
                                     "kda_scan_diff"}
        assert first[fault]["kda_scan_diff"] > 1.5 * limits["kda_scan_diff"], fault
        assert first[fault]["mixer_grad_diff"] > 20 * first["sound"]["mixer_grad_diff"], fault
    assert set(rows[1]) == {"seed", "seconds", "sound", "control"}      # faults on the first only
    summary = control_kimi.summarise(rows)
    assert summary["first_grad_diff"]["sound_worst"] < summary["first_grad_diff"]["control_best"]
    assert summary["first_grad_diff"][control_kimi.HALF_ROW + "_best"] == (
        first[control_kimi.HALF_ROW]["first_grad_diff"])
    assert summary["kda_scan_diff"]["sound_worst"] < limits["kda_scan_diff"] < summary[
        "kda_scan_diff"]["scan_state_bfloat16_best"]
    assert "scan_state_bfloat16_best" not in summary["loss_gap"]
    assert summary["routing_agreement"]["sound_worst"] > summary["routing_agreement"][
        "control_best"]
    from glom_tpu.models import kimi_linear
    import jax.numpy as jnp

    assert kimi_linear.SCAN_STATE_DTYPE == kimi_linear.SCAN_SOLVE_DTYPE == jnp.float32


# ------------------------------------------------------------ the cell's files


def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs", "kimi-linear-ep32vp8.json")) as fh:
        return json.load(fh)


def test_the_configuration_file_is_the_catalog_row_but_for_what_it_lists_as_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    cf = _config()
    assert cf["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cf[k] != v}
    assert differs == set(cf["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert cf["published"] == {k: row["config"][k] for k in cf["reduced"]}
    assert cf["linear_attn_config"] == row["config"]["linear_attn_config"]     # whole, as published
    assert (cf["hidden_size"], cf["intermediate_size"], cf["moe_intermediate_size"],
            cf["kv_lora_rank"], cf["qk_nope_head_dim"], cf["qk_rope_head_dim"], cf["v_head_dim"],
            cf["num_experts_per_token"], cf["num_attention_heads"]) == (
        2304, 9216, 1024, 512, 128, 64, 128, 8, 32)


def test_the_files_widths_are_the_presets():
    """The `model` group against the top level, the published lists and the
    preset: every width and both mixers' 32 heads whole, layers 1-5, 8 of 256
    experts, 20,480 of 163,840 rows, and the count written in the file."""
    from glom_tpu.models import kimi_linear
    from glom_tpu.utils.presets import get_preset

    cf = _config()
    model = cf["model"]
    for key, value in model.items():
        if key in cf and not isinstance(cf[key], (list, dict)):
            assert cf[key] == value, key
    linear = cf["linear_attn_config"]
    assert (model["linear_num_heads"], model["linear_head_dim"],
            model["short_conv_kernel_size"]) == (
        linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]) == (32, 128, 4)
    letters = model["layer_types"]
    assert [i + 1 for i, m in enumerate(letters) if m == "K"] == linear["kda_layers"]
    assert [i + 1 for i, m in enumerate(letters) if m == "A"] == linear["full_attn_layers"]
    assert model["num_hidden_layers_total"] == cf["published"]["num_hidden_layers"] == 27
    assert model["num_experts_total"] == cf["published"]["num_experts"] == 256
    assert 8 * model["vocab_size"] == cf["published"]["vocab_size"]
    assert 32 * model["num_experts"] == cf["published"]["num_experts"]
    assert (model["layer_offset"], model["num_hidden_layers"], model["expert_offset"]) == (0, 5, 88)
    assert model["first_k_dense_replace"] == cf["first_k_dense_replace"] == 1
    assert (cf["mla_use_nope"], cf["q_lora_rank"], cf["moe_renormalize"],
            cf["moe_router_activation_func"], cf["num_nextn_predict_layers"]) == (
        True, None, True, "sigmoid", 0)
    for item in ("kda_equations", "kda_low_rank", "kda_recurrence_parameters", "convolutions",
                 "latent_attention", "router", "packing", "init", "moe_rung_loads"):
        assert cf["assumed"][item], item
    assert "32 chips sharing each layer" in cf["deployment"] and "88-95" in cf["deployment"]
    preset = get_preset(cf["preset"]).model
    assert dataclasses.asdict(preset) == model
    held = kimi_linear.param_count(preset)
    assert held == 602_433_408 and "602,433,408" in cf["deployment"] and (
        "9.64 GB" in cf["deployment"])
    assert "three to one" in cf["why"] and "four to one" in cf["why"]
    assert cf["train"] == {"batch_per_chip": 1, "learning_rate": 0.0003,
                           "compute_dtype": "bfloat16", "remat": True}


def test_the_cell_finds_its_files_and_its_readers():
    cell = harness.load_cell(CELL)
    assert cell["traffic_file"]["kind"] == "train_lm_kimi" and cell["chips"] == 1
    assert (cell["traffic_file"]["seq_len"], cell["traffic_file"]["pool_batches"],
            cell["traffic_file"]["prefetch"]) == (16384, 6, 2)
    assert os.path.exists(os.path.join(harness.BENCH_DIR, "drivers", "train_lm_kimi.py"))
    names = {m["name"] for m in cell["per_layer"]}
    own = {"kda_scan_time_pct.train", "linear_attention_time_pct.train",
           "latent_attention_time_pct.train", "mla_flash_roofline.train",
           "kimi_matmul_roofline.train"}
    # the routed part is one layer with one pair of readers for three families' cells
    assert own | {"moe_routed_time_pct.train", "moe_expert_rows_fill_pct.train"} <= names
    assert not {"loop_kernels_roofline.train", "lm_matmul_roofline.train",
                "ssd_scan_time_pct.train", "sambay_matmul_roofline.train",
                "attn_flash_roofline.train", "laguna_matmul_roofline.train",
                "mixed_attention_time_pct.train"} & names
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
    for other in ("nemotron3super.train", "phi4flash.train", "lagunaxs2.train"):
        assert not own & {m["name"] for m in harness.load_cell(other)["per_layer"]}


def test_a_checkout_without_the_family_stops_at_once(monkeypatch):
    """What the parent commit does with this cell's files laid over it: no
    look for a chip, a plain message, a non-zero exit."""
    import sys

    from benchmark.drivers import train_lm_kimi as drv

    monkeypatch.setitem(sys.modules, "glom_tpu.models.kimi_linear", None)
    monkeypatch.setattr(harness, "start_jax", lambda chips: pytest.fail("reached for the chip"))
    with pytest.raises(SystemExit, match="cannot run the Kimi Linear cell"):
        drv.run(tiny_kimi_cell(), None, None)
