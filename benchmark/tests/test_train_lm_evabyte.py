"""The EvaByte cell at a size a CPU test can hold (`tiny_evabyte.py`):
everything of a run but the look for a chip. Sound: `correct` true, every
number beside its limit. With the timed path broken underneath (the
summaries left out, a sliding window in the aligned one's place, only one
head in the loss, steps that lose their update), or the reference put in the
program's place in float8 or with a wrong reading of the equations: false.
And the cell's files against each other and against the catalog's published
numbers."""
import dataclasses
import json
import os

import pytest

from benchmark import control_evabyte, control_sambay, harness
from benchmark import correct as cmp
from benchmark.reference import evabyte_ref
from tiny import drive, on_cpu
from tiny_evabyte import tiny_evabyte_cell

CELL = "evabyte.train"
NUMBERS = ["loss_gap", "first_grad_norm_gap", "first_grad_diff", "param_delta_norm_gap",
           "eva_attention_diff"]


def test_sound_run_is_correct(capsys):
    line, out = drive(tiny_evabyte_cell(), capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"train_col_iters_per_s_per_chip", "setup_s"}
    assert "compiles in window 0" in out
    compared = line["compared"]
    assert list(compared)[:5] == NUMBERS
    assert compared["eva_attention_diff"]["value"] < 1e-5 and "'dmu': " in out
    assert compared["route"] == {"value": "lm_xla", "limit": "lm_xla", "ok": True}
    assert compared["records_vjp_path"]["ok"] and compared["spans_with_nonfinite_loss"]["ok"]
    assert "attn_forward_kept" not in compared      # the tiny cell drops the chip's requirement
    assert "'attn_key_blocks_local': 9.0" in out and "window's records, lm_pred_heads: 3" in out
    assert "heads held=2 of 4 window=32 chunk=4 pred heads=3" in out


def test_a_run_that_fell_back_to_the_xla_loop_is_not_correct(capsys):
    """The cell's own requirement left standing (`bench.attention_on_kernels`):
    on the CPU every layer's attention is the XLA loop, `attn_forward_kept`
    reads 0 in step 1 and in every record, and a run with five sound numbers
    and no trace is not correct."""
    line, out = drive(tiny_evabyte_cell(on_kernels=True), capsys)
    compared = line["compared"]
    assert all(compared[n]["ok"] for n in NUMBERS) and compared["records_vjp_path"]["ok"]
    assert compared["attn_forward_kept"] == {"value": "0", "limit": "only 3", "ok": False}
    assert line["correct"] is False and "FAILED" in out


def test_a_model_of_windows_alone_is_not_correct(capsys, monkeypatch):
    """The program without its summaries: every query sees its window's keys
    and nothing before them."""
    from glom_tpu.models import evabyte

    real = evabyte.eva_attention
    monkeypatch.setattr(evabyte, "eva_attention",
                        lambda q, k, v, khat, vhat, cfg: real(q, k, v, khat - 1e9, vhat, cfg))
    line, out = drive(tiny_evabyte_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    assert not line["compared"]["eva_attention_diff"]["ok"]


def test_a_sliding_window_in_the_aligned_ones_place_is_not_correct(capsys, monkeypatch):
    from glom_tpu.models import evabyte, hybrid_lm

    def sliding(q, k, v, khat, vhat, cfg):
        out, blocks, _ = hybrid_lm.blocked_attention(q[:, :, :, None], k, v, cfg.window_size)
        return out[:, :, :, 0], blocks, 0, 0

    monkeypatch.setattr(evabyte, "eva_attention", sliding)
    line, out = drive(tiny_evabyte_cell(), capsys)
    assert line["correct"] is False and not line["compared"]["eva_attention_diff"]["ok"]


def test_a_loss_of_the_first_head_alone_is_not_correct(capsys, monkeypatch):
    from glom_tpu.models import evabyte, hybrid_lm

    monkeypatch.setattr(evabyte, "next_token_loss", lambda h, head, ids, k: (
        hybrid_lm.next_token_loss(h, head[:, :head.shape[1] // k], ids)))
    line, out = drive(tiny_evabyte_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    assert line["compared"]["eva_attention_diff"]["ok"]       # the attention is sound


@pytest.mark.parametrize("fault", list(control_sambay.FAULTS))
def test_steps_that_lose_their_update_are_not_correct(capsys, fault):
    with control_sambay.losing_updates(*control_sambay.FAULTS[fault]):
        line, out = drive(tiny_evabyte_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    change = line["compared"]["param_delta_norm_gap"]
    assert not change["ok"]
    if fault == "state_unchanged":
        assert change["value"] == pytest.approx(1.0, abs=1e-5)


def test_the_control_and_the_faults_read_worse_than_a_sound_run(capsys):
    """`control_evabyte.readings` on two seeds at the tiny size: sound within
    the cell's limits; the float8 reference far outside a sound run; every wrong reading
    of the equations outside them (the stream's adds in bfloat16 apart: this
    size's float32 program leaves it the only rounding there is, so here it
    reads, and it is logged for the real size, where the file says what tells
    it); a lost update and half of the row left out outside them."""
    cell = tiny_evabyte_cell()
    with on_cpu():
        rows = control_evabyte.readings(cell, [11, 2**31 + 7], "float8", equation_faults=1,
                                        faults=1)
    capsys.readouterr()
    limits = cell["limits"]
    for r in rows:
        assert set(r["sound"]) == set(NUMBERS) == set(limits)
        assert cmp.judge(r["sound"], limits)["ok"], r
        # at this size the program is float32, so the control is told by its distance from a
        # sound run; the limits are the real size's, where the program's bfloat16 lies between
        assert r["control"]["first_grad_diff"] > 1000 * r["sound"]["first_grad_diff"], r
    first = rows[0]
    for fault in evabyte_ref.FAULTS:
        read = first[fault]
        assert {"loss_gap", "first_grad_norm_gap", "first_grad_diff"} <= set(read)
        assert ("eva_attention_diff" in read) == (fault in control_evabyte.ATTENTION_FAULTS)
        if fault != "bfloat16_stream":
            assert not cmp.judge(read, {k: limits[k] for k in read})["ok"], (fault, read)
    for fault in control_evabyte.ATTENTION_FAULTS:
        assert first[fault]["eva_attention_diff"] > 3 * limits["eva_attention_diff"], fault
    for fault in ("fast_update_lost", "state_unchanged", control_evabyte.HALF_ROW):
        assert not cmp.judge(first[fault], limits)["ok"], fault
    assert set(rows[1]) == {"seed", "seconds", "sound", "control"}      # faults on the first only
    summary = control_evabyte.summarise(rows)
    assert summary["first_grad_diff"]["sound_worst"] < summary["first_grad_diff"]["control_best"]
    assert summary["eva_attention_diff"]["sound_worst"] < limits["eva_attention_diff"] < summary[
        "eva_attention_diff"]["no_summaries_best"]
    assert "head0_only_best" not in summary["eva_attention_diff"]


# ------------------------------------------------------------ the cell's files


def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs", "evabyte-stage4tp4.json")) as fh:
        return json.load(fh)


def test_the_configuration_file_is_the_catalog_row_but_for_what_it_lists_as_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "EvaByte")
    cf = _config()
    assert cf["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cf[k] != v}
    assert differs == set(cf["reduced"]) == {"num_hidden_layers", "num_attention_heads",
                                             "num_key_value_heads"}
    assert cf["published"] == {k: row["config"][k] for k in cf["reduced"]}
    assert (cf["hidden_size"], cf["intermediate_size"], cf["window_size"], cf["chunk_size"],
            cf["num_pred_heads"], cf["vocab_size"], cf["rope_theta"]) == (
        4096, 11008, 2048, 16, 8, 320, 100000)


def test_the_files_widths_are_the_presets():
    from glom_tpu.models import evabyte
    from glom_tpu.utils.presets import get_preset

    cf = _config()
    model = cf["model"]
    for key, value in model.items():
        if key in cf and not isinstance(cf[key], (list, dict)):
            assert cf[key] == value, key
    assert model["head_dim"] * cf["published"]["num_attention_heads"] == model["hidden_size"]
    assert 4 * model["num_attention_heads"] == model["num_attention_heads_total"] == 32
    assert model["num_hidden_layers_total"] == cf["published"]["num_hidden_layers"] == 32
    assert (cf["fp32_skip_add"], cf["fp32_logits"], cf["norm_add_unit_offset"],
            cf["attention_class"], cf["model_type"]) == (True, True, True, "eva", "evabyte")
    for item in ("source_code", "summariser", "summaries_of_rotated_keys", "own_window",
                 "rotation", "stream", "head", "init", "packing", "head_dim"):
        assert cf["assumed"][item], item
    assert "four chips sharing each layer by heads" in cf["deployment"]
    preset = get_preset(cf["preset"]).model
    assert dataclasses.asdict(preset) == model
    held = evabyte.param_count(preset)
    assert held == 620_015_616 and "620,015,616" in cf["deployment"] and (
        "9.92 GB" in cf["deployment"]) and "7.44 GB" in cf["deployment"]
    assert cf["train"] == {"batch_per_chip": 1, "learning_rate": 0.0003,
                           "compute_dtype": "bfloat16", "remat": True}


def test_the_cell_finds_its_files_and_its_readers():
    cell = harness.load_cell(CELL)
    assert cell["traffic_file"]["kind"] == "train_lm_evabyte" and cell["chips"] == 1
    assert (cell["traffic_file"]["seq_len"], cell["traffic_file"]["pool_batches"],
            cell["traffic_file"]["prefetch"]) == (16384, 6, 2)
    names = {m["name"] for m in cell["per_layer"]}
    own = {"eva_attention_time_pct.train", "eva_summary_time_pct.train",
           "eva_flash_roofline.train", "eva_key_blocks_visited_pct.train",
           "evabyte_matmul_roofline.train"}
    assert own <= names
    assert not {"loop_kernels_roofline.train", "moe_routed_time_pct.train",
                "kimi_matmul_roofline.train", "attn_flash_roofline.train"} & names
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    assert set(cell["limits"]) == set(NUMBERS)
    table = cell["config_file"]["bench"]["route_kernels"]
    sound = {"attn_flash_fwd": 1.0, "attn_flash_bwd_onesweep": 1.0}
    assert cmp.kernels_fit(sound, table)[0]
    assert not cmp.kernels_fit({}, table)[0]                                 # the XLA loop
    assert not cmp.kernels_fit(dict(sound, **{"ragged-dot-none": 1.0}), table)[0]
    assert not cmp.kernels_fit(dict(sound, ffw_fwd=1.0), table)[0]
    for other in ("nemotron3super.train", "phi4flash.train", "lagunaxs2.train",
                  "kimilinear.train"):
        assert not own & {m["name"] for m in harness.load_cell(other)["per_layer"]}


def test_a_checkout_without_the_family_stops_at_once(monkeypatch):
    """What the parent commit does with this cell's files laid over it: no
    look for a chip, a plain message, a non-zero exit."""
    import sys

    from benchmark.drivers import train_lm_evabyte as drv

    monkeypatch.setitem(sys.modules, "glom_tpu.models.evabyte", None)
    monkeypatch.setattr(harness, "start_jax", lambda chips: pytest.fail("reached for the chip"))
    with pytest.raises(SystemExit, match="cannot run the EvaByte cell"):
        drv.run(tiny_evabyte_cell(), None, None)
