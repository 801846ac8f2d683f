"""The Ouro cell at a size a CPU test can hold (`tiny_ouro.py`): everything
of a run but the look for a chip. Sound: `correct` true, every number beside
its limit. With the timed path broken underneath (a pass fewer, the closing
norm left out between passes, the last pass's loss alone, a looped weight's
gradient from its last use alone, steps that lose their update), or the
reference put in the program's place in float8 or with a wrong reading of the
loop: false. And the cell's files against each other and against the
catalog's published numbers."""
import dataclasses
import json
import os

import pytest

from benchmark import control_ouro, control_sambay, harness
from benchmark import correct as cmp
from benchmark.reference import ouro_ref
from tiny import drive, on_cpu
from tiny_ouro import tiny_ouro_cell

CELL = "ouro26b.train"
NUMBERS = ["first_grad_norm_gap", "first_grad_diff", "param_delta_norm_gap", "first_loss_gap"]


def unrolled_stack(*, carry_closed: bool = True, last_use_alone: bool = False):
    """`run_stack`'s loop written out, for the broken timed paths: the passes
    a plain Python loop; `carry_closed` False starts the next pass from the
    stream before the closing norm; `last_use_alone` stops the gradient of
    the weights every pass but the last reads."""
    import jax

    from glom_tpu.models import hybrid_lm

    def run_stack(params, ids, layers, *, compute_dtype=None, remat=True, side=None, passes=1,
                  close=None):
        x = hybrid_lm._cast(params["embed"][ids], compute_dtype)
        closed, aux = [], []
        for t in range(passes):
            held = params["layers"]
            if last_use_alone and t < passes - 1:
                held = jax.lax.stop_gradient(held)
            for f, p in zip(layers, held):
                x, side, a = jax.checkpoint(f)(p, x, side)
                aux.append(a)
            closed.append(close(x))
            if carry_closed:
                x = closed[-1]
        return jax.numpy.stack(closed), aux

    return run_stack


def test_sound_run_is_correct(capsys):
    line, out = drive(tiny_ouro_cell(), capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"train_col_iters_per_s_per_chip", "setup_s"}
    assert "compiles in window 0" in out
    compared = line["compared"]
    assert list(compared)[:4] == NUMBERS and "loss_gap" not in compared
    assert "logged and not judged: loss_gap = " in out     # steps 1 and 3: the limits' file says why
    assert compared["route"] == {"value": "lm_xla", "limit": "lm_xla", "ok": True}
    assert compared["records_vjp_path"]["ok"] and compared["spans_with_nonfinite_loss"]["ok"]
    assert compared["ut_steps"] == {"value": "4", "limit": "only 4", "ok": True}
    assert "attn_forward_kept" not in compared      # the tiny cell drops the chip's requirement
    assert "'layer_applications': 8.0" in out and "window's records, ut_steps: 4" in out
    assert "layers held=2 of 2 passes=4" in out and "rows x layers x passes/s/chip" in out


def test_a_run_that_fell_back_to_the_xla_loop_is_not_correct(capsys):
    """The cell's own requirement left standing (`bench.attention_on_kernels`):
    on the CPU every application's attention is the XLA loop,
    `attn_forward_kept` reads 0 in step 1 and in every record, and a run with
    four sound numbers and no trace is not correct."""
    line, out = drive(tiny_ouro_cell(on_kernels=True), capsys)
    compared = line["compared"]
    assert all(compared[n]["ok"] for n in NUMBERS) and compared["ut_steps"]["ok"]
    assert compared["attn_forward_kept"] == {"value": "0", "limit": "only 8", "ok": False}
    assert line["correct"] is False and "FAILED" in out


def test_three_passes_for_four_are_not_correct(capsys, monkeypatch):
    """The timed path a pass short (the configuration's count kept for the
    verdict): the loss, the gradients and the step's own count all say so."""
    from glom_tpu.models import ouro

    real = ouro.run_stack
    monkeypatch.setattr(ouro, "run_stack",
                        lambda *a, passes, **kw: real(*a, passes=passes - 1, **kw))
    line, out = drive(tiny_ouro_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    assert line["compared"]["ut_steps"] == {"value": "3", "limit": "only 4", "ok": False}
    assert not line["compared"]["first_grad_diff"]["ok"]


def test_the_closing_norm_left_out_between_passes_is_not_correct(capsys, monkeypatch):
    """The head and the gate still read the normed state; the next pass starts
    from the stream before the norm."""
    from glom_tpu.models import ouro

    monkeypatch.setattr(ouro, "run_stack", unrolled_stack(carry_closed=False))
    line, out = drive(tiny_ouro_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    assert line["compared"]["ut_steps"]["ok"] and not line["compared"]["first_grad_diff"]["ok"]


def test_the_last_passs_loss_alone_is_not_correct(capsys, monkeypatch):
    from glom_tpu.models import hybrid_lm, ouro

    def last_alone(params, closed, ids, cfg, compute_dtype=None):
        h = closed[-1].reshape(-1, closed.shape[-1])
        loss = hybrid_lm.next_token_loss(h, hybrid_lm._cast(params["head"], compute_dtype), ids)
        return loss, 0.0 * loss, 1.0 + 0.0 * loss

    monkeypatch.setattr(ouro, "exit_weighed_loss", last_alone)
    line, out = drive(tiny_ouro_cell(), capsys)
    assert line["correct"] is False and "OVER" in out


def test_a_looped_weights_gradient_from_its_last_use_alone_is_not_correct(capsys, monkeypatch):
    """`stop_gradient` on the weights the first three passes read: step 1's
    loss is the sound one, the looped leaves' first gradient is not."""
    from glom_tpu.models import ouro

    monkeypatch.setattr(ouro, "run_stack", unrolled_stack(last_use_alone=True))
    line, out = drive(tiny_ouro_cell(), capsys)
    compared = line["compared"]
    assert line["correct"] is False and compared["ut_steps"]["ok"]
    assert not compared["first_grad_diff"]["ok"] and compared["first_grad_diff"]["value"] > 0.3


@pytest.mark.parametrize("fault", list(control_sambay.FAULTS))
def test_steps_that_lose_their_update_are_not_correct(capsys, fault):
    with control_sambay.losing_updates(*control_sambay.FAULTS[fault]):
        line, out = drive(tiny_ouro_cell(), capsys)
    assert line["correct"] is False and "OVER" in out
    change = line["compared"]["param_delta_norm_gap"]
    assert not change["ok"]
    if fault == "state_unchanged":
        assert change["value"] == pytest.approx(1.0, abs=1e-5)


def test_the_control_and_the_faults_read_worse_than_a_sound_run(capsys):
    """`control_ouro.readings` on two seeds at the tiny size: sound within the
    cell's limits; the float8 reference far outside a sound run; every wrong
    reading of the loop outside the limits by at least one number; a lost
    update outside them."""
    cell = tiny_ouro_cell()
    with on_cpu():
        rows = control_ouro.readings(cell, [11, 2**31 + 7], "float8", loop_faults=1, faults=1)
    capsys.readouterr()
    limits = cell["limits"]
    for r in rows:
        assert set(r["sound"]) == set(NUMBERS) | {"loss_gap"} and set(NUMBERS) == set(limits)
        assert cmp.judge({k: r["sound"][k] for k in limits}, limits)["ok"], r
        # at this size the program is float32, so the control is told by its distance from a
        # sound run; the limits are the real size's, where the program's bfloat16 lies between
        assert r["control"]["first_grad_diff"] > 1000 * r["sound"]["first_grad_diff"], r
    first = rows[0]
    for fault in ouro_ref.FAULTS:
        read = first[fault]
        assert {"first_loss_gap", "first_grad_norm_gap", "first_grad_diff"} <= set(read)
        assert ("param_delta_norm_gap" in read) == (fault in control_ouro.THREE_STEPS)
        judged = {k: v for k, v in read.items() if k in limits}
        assert not cmp.judge(judged, limits)["ok"], (fault, read)
    for fault in ("fast_update_lost", "state_unchanged"):
        assert not cmp.judge({k: first[fault][k] for k in limits}, limits)["ok"], fault
    assert set(rows[1]) == {"seed", "seconds", "sound", "control"}      # faults on the first only
    summary = control_ouro.summarise(rows)
    assert summary["first_grad_diff"]["sound_worst"] < summary["first_grad_diff"]["control_best"]
    assert summary["first_grad_diff"]["sound_worst"] < limits["first_grad_diff"] < summary[
        "first_grad_diff"]["last_use_gradient_best"]


# ------------------------------------------------------------ the cell's files


def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs", "ouro-2.6b-stage8.json")) as fh:
        return json.load(fh)


def test_the_configuration_file_is_the_catalog_row_but_for_what_it_lists_as_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Ouro-2.6B")
    cf = _config()
    assert cf["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cf[k] != v}
    assert differs == set(cf["reduced"]) == {"num_hidden_layers"}
    assert cf["published"] == {k: row["config"][k] for k in cf["reduced"]} == {
        "num_hidden_layers": 48}
    assert (cf["hidden_size"], cf["intermediate_size"], cf["num_attention_heads"],
            cf["num_key_value_heads"], cf["head_dim"], cf["vocab_size"], cf["total_ut_steps"],
            cf["rope_theta"], cf["early_exit_threshold"]) == (
        2048, 5632, 16, 16, 128, 49152, 4, 1000000, 1)


def test_the_files_widths_are_the_presets():
    from glom_tpu.models import ouro
    from glom_tpu.utils.presets import get_preset

    cf = _config()
    model = cf["model"]
    for key, value in model.items():
        if key in cf and not isinstance(cf[key], (list, dict)):
            assert cf[key] == value, key
    assert model["num_hidden_layers_total"] == cf["published"]["num_hidden_layers"] == 48
    assert model["num_hidden_layers_total"] == 6 * model["num_hidden_layers"]
    for item in ("source_code", "sandwich_norms", "closing_norm", "weights_shared", "positions",
                 "attention", "exit_gate", "exit_distribution", "loss", "early_exit_threshold",
                 "init", "packing"):
        assert cf["assumed"][item], item
    assert "6 stages of 8 layers" in cf["deployment"] and "four times" in cf["deployment"]
    preset = get_preset(cf["preset"]).model
    assert dataclasses.asdict(preset) == model
    held = ouro.param_count(preset)
    assert held == 612_438_017 and "612,438,017" in cf["deployment"] and (
        "9.80 GB" in cf["deployment"]) and "7.35 GB" in cf["deployment"]
    assert cf["train"] == {"batch_per_chip": 2, "learning_rate": 0.0003,
                           "compute_dtype": "bfloat16", "remat": True}


def test_the_cell_finds_its_files_and_its_readers():
    cell = harness.load_cell(CELL)
    assert cell["traffic_file"]["kind"] == "train_lm_ouro" and cell["chips"] == 1
    assert (cell["traffic_file"]["seq_len"], cell["traffic_file"]["pool_batches"],
            cell["traffic_file"]["prefetch"]) == (4096, 6, 2)
    names = {m["name"] for m in cell["per_layer"]}
    own = {"ouro_attention_time_pct.train", "ouro_exit_loss_time_pct.train",
           "ouro_sandwich_norm_time_pct.train", "ouro_flash_roofline.train",
           "ouro_matmul_roofline.train", "ouro_applications_kept_pct.train"}
    assert own <= names
    assert not {"loop_kernels_roofline.train", "moe_routed_time_pct.train",
                "evabyte_matmul_roofline.train", "attn_flash_roofline.train"} & names
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    assert set(cell["limits"]) == set(NUMBERS)
    table = cell["config_file"]["bench"]["route_kernels"]
    sound = {"attn_flash_fwd": 1.0, "attn_flash_bwd_onesweep": 1.0}
    assert cmp.kernels_fit(sound, table)[0]
    assert not cmp.kernels_fit({}, table)[0]                                 # the XLA loop
    assert not cmp.kernels_fit(dict(sound, **{"ragged-dot-none": 1.0}), table)[0]
    assert not cmp.kernels_fit(dict(sound, selective_scan_fwd=1.0), table)[0]
    for other in ("nemotron3super.train", "phi4flash.train", "lagunaxs2.train",
                  "kimilinear.train", "evabyte.train"):
        assert not own & {m["name"] for m in harness.load_cell(other)["per_layer"]}


def test_a_checkout_without_the_family_stops_at_once(monkeypatch):
    """What the parent commit does with this cell's files laid over it: no
    look for a chip, a plain message, a non-zero exit."""
    import sys

    from benchmark.drivers import train_lm_ouro as drv

    monkeypatch.setitem(sys.modules, "glom_tpu.models.ouro", None)
    monkeypatch.setattr(harness, "start_jax", lambda chips: pytest.fail("reached for the chip"))
    with pytest.raises(SystemExit, match="cannot run the Ouro cell"):
        drv.run(tiny_ouro_cell(), None, None)
