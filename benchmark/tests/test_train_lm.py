"""The language-model cell at a size a CPU test can hold (`tiny_lm.py`):
everything of a run but the look for a chip. Sound: `correct` true, every
number beside its limit. With the timed path broken underneath, or the
reference put in the program's place in float8: false. And the cell's files
against each other and against the catalog's published numbers."""
import json
import os

import pytest

from benchmark import control_lm, harness
from benchmark import correct as cmp
from tiny import drive, on_cpu
from tiny_lm import tiny_lm_cell

CELL = "nemotron3super.train"


def test_sound_run_is_correct(capsys):
    line, out = drive(tiny_lm_cell(), capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"train_col_iters_per_s_per_chip", "setup_s"}
    assert "compiles in window 0" in out
    compared = line["compared"]
    assert list(compared)[:5] == ["loss_gap", "first_grad_norm_gap", "first_grad_diff",
                                  "param_delta_norm_gap", "routing_agreement"]
    assert compared["routing_agreement"]["value"] == 1.0
    assert compared["route"] == {"value": "lm_xla", "limit": "lm_xla", "ok": True}
    assert compared["records_vjp_path"]["ok"] and compared["spans_with_nonfinite_loss"]["ok"]


def test_pairs_dropped_by_the_dispatch_are_not_correct(capsys, monkeypatch):
    """Room for half the pairs only: the rows past it fall off."""
    from glom_tpu.models import hybrid_lm

    real = hybrid_lm.dispatch

    def short(top_i, cfg):
        pair, valid, sizes = real(top_i, cfg)
        import jax.numpy as jnp

        keep = jnp.arange(pair.shape[0]) < jnp.cumsum(sizes)[0]  # the first expert's rows only
        return pair, valid & keep, sizes

    monkeypatch.setattr(hybrid_lm, "dispatch", short)
    line, out = drive(tiny_lm_cell(), capsys)
    assert line["correct"] is False and "OVER" in out


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    import jax

    from glom_tpu.train.trainer import Trainer

    real = Trainer.step_fast

    def frozen(self, batch):
        before = self.state
        metrics = real(self, batch)
        self.state = before._replace(step=self.state.step)  # the update is lost
        return metrics

    monkeypatch.setattr(Trainer, "step_fast", frozen)
    real_jit = jax.jit  # the state is donated to the real step: stop that
    monkeypatch.setattr(jax, "jit", lambda f=None, **kw: real_jit(
        f, **{k: v for k, v in kw.items() if k != "donate_argnums"}) if f is not None
        else (lambda g: real_jit(g, **{k: v for k, v in kw.items() if k != "donate_argnums"})))
    line, out = drive(tiny_lm_cell(), capsys)
    assert line["correct"] is False and "OVER" in out


def test_float8_reference_fails_the_cells_limits(capsys):
    cell = tiny_lm_cell()
    with on_cpu():
        rows = control_lm.readings(cell, [11, 2**31 + 7], "float8")
    capsys.readouterr()
    limits = dict(cell["limits"])
    floor = limits.pop("routing_agreement")
    for r in rows:
        sound, control = dict(r["sound"]), dict(r["control"])
        assert sound.pop("routing_agreement") >= floor, r
        control.pop("routing_agreement")
        assert cmp.judge(sound, limits)["ok"], r
        assert not cmp.judge(control, limits)["ok"], r
    summary = control_lm.summarise(rows)
    assert summary["first_grad_diff"]["sound_worst"] < summary["first_grad_diff"]["control_best"]


def test_routing_agreement_counts_shared_choices():
    import numpy as np

    from benchmark.drivers.train_lm import routing_agreement

    program = np.array([[[1, 2, 3, 4], [5, 6, 7, 8]]])
    same = np.array([[[[4, 3, 2, 1], [8, 7, 6, 5]]]])      # the order does not matter
    half = np.array([[[[1, 2, 9, 9], [5, 6, 0, 0]]]])
    assert routing_agreement(program, same) == 1.0
    assert routing_agreement(program, half) == 0.5


# ------------------------------------------------------------ the cell's files


def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs", "nemotron3-super-ep64tp8.json")) as fh:
        return json.load(fh)


def test_the_configuration_file_is_the_catalog_row_but_for_what_it_lists_as_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    cf = _config()
    assert cf["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cf[k] != v}
    assert differs == set(cf["reduced"])
    assert cf["published"] == {k: row["config"][k] for k in cf["reduced"]}
    widths = {"hidden_size", "moe_latent_size", "moe_intermediate_size", "intermediate_size",
              "moe_shared_expert_intermediate_size", "head_dim", "mamba_head_dim",
              "ssm_state_size", "chunk_size", "conv_kernel", "expand", "num_experts_per_tok"}
    assert not widths & set(cf["reduced"])


def test_the_model_group_is_what_the_top_level_says():
    cf = _config()
    for key, value in cf["model"].items():
        if key in cf and key != "hybrid_override_pattern":
            assert cf[key] == value, key
    assert cf["model"]["hybrid_override_pattern"] == cf["hybrid_override_pattern"]
    assert cf["model"]["n_routed_experts_total"] == cf["published"]["n_routed_experts"]
    assert cf["model"]["num_hidden_layers_total"] == cf["published"]["num_hidden_layers"]
    for item in ("positions", "router", "latent", "init", "norms", "packing",
                 "multi_token_prediction"):
        assert cf["assumed"][item]
    assert "64 chips" in cf["deployment"]


def test_the_cell_finds_its_files_and_its_readers():
    cell = harness.load_cell(CELL)
    assert cell["traffic_file"]["kind"] == "train_lm" and cell["chips"] == 1
    assert os.path.exists(os.path.join(harness.BENCH_DIR, "drivers", "train_lm.py"))
    names = {m["name"] for m in cell["per_layer"]}
    assert {"moe_routed_time_pct.train", "ssd_scan_time_pct.train", "lm_matmul_roofline.train",
            "moe_expert_rows_fill_pct.train"} <= names
    assert "loop_kernels_roofline.train" not in names
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    assert set(cell["limits"]) == {"loss_gap", "first_grad_norm_gap", "first_grad_diff",
                                   "param_delta_norm_gap", "routing_agreement"}
    table = cell["config_file"]["bench"]["route_kernels"]
    assert cmp.kernels_fit({"ragged-dot-none": 1.0, "ragged-dot-metadata": 0.1}, table)[0]
    assert not cmp.kernels_fit({"ragged-dot-none": 1.0, "ffw_fwd": 1.0}, table)[0]
    assert not cmp.kernels_fit({}, table)[0]
