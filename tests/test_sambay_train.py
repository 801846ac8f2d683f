"""The SambaY family through the one trainer: the objective by the
configuration's type, `Trainer.fit` on the tiny preset (falling loss, the new
device scopes in the step, the new counters in the records), the CLI by the
same command, the full preset's shapes.

CPU only: what is checked is behaviour and metadata, never a time.
"""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glom_tpu.data import prefetch_to_device, token_dataset
from glom_tpu.models import sambay
from glom_tpu.tracing.spans import DEVICE_PHASES, LM_DEVICE_PHASES, SAMBAY_DEVICE_PHASES
from glom_tpu.train import Objective, Trainer, objective_for
from glom_tpu.train.trainer import default_optimizer, make_train_step
from glom_tpu.utils.config import SambaYConfig
from glom_tpu.utils.presets import LM_PRESETS, get_preset


class Collector:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)


@pytest.fixture(scope="module")
def tiny():
    p = get_preset("sambay-tiny")
    return p.model, p.train


@pytest.fixture(scope="module")
def fitted(tiny):
    """One trainer on the tiny preset, three steps through fit over a
    prefetched feed that repeats one batch (so that the loss has to fall),
    logging every step, at a learning rate that shows in three steps."""
    cfg, tcfg = tiny
    tcfg = dataclasses.replace(tcfg, learning_rate=3e-3)
    writer = Collector()
    trainer = Trainer(cfg, tcfg, metrics_writer=writer)
    batch = next(token_dataset(tcfg.batch_size, cfg.seq_len, cfg.vocab_size, seed=1))
    data = prefetch_to_device(iter([batch] * 3), size=2, metrics_writer=writer)
    history = trainer.fit(data, num_steps=3, log_every=1)
    return trainer, history, writer.records


def test_the_objective_is_the_language_models_by_the_configs_type(tiny):
    cfg, tcfg = tiny
    obj = objective_for(cfg, tcfg)
    assert isinstance(obj, Objective)
    assert (obj.vjp_path, obj.grad_accum, obj.has_aux) == ("lm_xla", 1, True)
    assert obj.batch_shape == (cfg.seq_len,) and obj.batch_dtype == jnp.int32
    with pytest.raises(ValueError):
        objective_for(cfg, dataclasses.replace(tcfg, grad_accum=2))
    with pytest.raises(ValueError, match="GLOM"):
        objective_for(cfg, tcfg, consensus_fn=lambda *a: None)


def test_fit_trains_the_tiny_preset_for_three_steps(fitted, tiny):
    trainer, history, records = fitted
    losses = [h["loss"] for h in history]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert abs(losses[0] - np.log(tiny[0].vocab_size)) < 0.1      # near ln(128) at the start
    assert losses[0] > losses[1] > losses[2]
    assert trainer.vjp_path == "lm_xla" and int(trainer.state.step) == 3
    assert history[0]["params_bytes_per_replica"] == 4 * sambay.param_count(tiny[0])


def test_the_records_carry_the_step_counters(fitted, tiny):
    cfg = tiny[0]
    _, history, records = fitted
    steps = [r for r in records if r.get("kind") == "train_step"]
    assert len(steps) == 3 and all(r["vjp_path"] == "lm_xla" for r in steps)
    n_window, n_full = cfg.kinds.count("W"), cfg.kinds.count("F") + cfg.kinds.count("X")
    for r in steps:
        # 80 tokens are one query block here: ceil(80 / 128) = 1 key block a layer
        assert r["attn_key_blocks_window"] == n_window and r["attn_key_blocks_full"] == n_full
        assert r["scan_chunks"] == 1                                # 80 positions: one chunk
        assert r["attn_forward_kept"] == 0     # the XLA loop names nothing for the recomputation
        assert r["mlp_backward_staged"] == cfg.num_hidden_layers   # an MLP a layer, each on the rule
    assert set(sambay.COUNTERS) <= set(history[-1])
    from glom_tpu.telemetry import schema

    assert all(r["schema_version"] == schema.SCHEMA_VERSION for r in records)


def test_the_lowered_step_carries_every_scope_of_the_vocabulary(tiny):
    cfg, tcfg = tiny
    opt = default_optimizer(tcfg)
    from glom_tpu.train.trainer import create_train_state

    state, _ = create_train_state(jax.random.PRNGKey(0), cfg, tcfg, opt)
    ids = jnp.zeros((tcfg.batch_size, cfg.seq_len), jnp.int32)
    compiled = jax.jit(make_train_step(cfg, tcfg, opt)).lower(
        state, ids, jax.random.PRNGKey(0)).compile()
    op_names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    words = {w for name in op_names for w in re.findall(r"[A-Za-z0-9_]+", name)}
    assert set(SAMBAY_DEVICE_PHASES) <= words and {"optimizer", "step_metrics"} <= words
    assert not set(SAMBAY_DEVICE_PHASES) & set(DEVICE_PHASES)
    # what the two language models' vocabularies share means the same in both
    assert set(SAMBAY_DEVICE_PHASES) & set(LM_DEVICE_PHASES) == {
        "embed", "mamba_in", "mamba_out", "lm_head_loss"}
    # next to nothing of the step's instructions lies outside every scope (a
    # name without a path is a reduction's own little computation, not an
    # instruction of the step)
    scoped = set(SAMBAY_DEVICE_PHASES) | {"optimizer", "step_metrics"}
    placed = [n for n in op_names if n.startswith("jit(")]
    inside = sum(any(w in scoped for w in re.findall(r"[A-Za-z0-9_]+", n)) for n in placed)
    assert len(placed) > 10_000 and inside / len(placed) > 0.95


def test_the_presets_of_the_family():
    assert {"phi4-mini-flash-stage6vp8", "sambay-tiny"} <= set(LM_PRESETS)
    full = get_preset("phi4-mini-flash-stage6vp8")
    assert isinstance(full.model, SambaYConfig)
    assert (full.train.batch_size, full.train.compute_dtype, full.train.remat,
            full.train.learning_rate) == (1, "bfloat16", True, 3e-4)
    published = SambaYConfig()
    for width in ("hidden_size", "intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "sliding_window", "mamba_expand", "mamba_d_state",
                  "mamba_d_conv", "mb_per_layer", "num_hidden_layers_total"):
        assert getattr(full.model, width) == getattr(published, width), width
    assert (full.model.layer_offset, full.model.num_hidden_layers, full.model.vocab_size,
            full.model.seq_len) == (14, 6, 25008, 8192)
    assert 8 * full.model.vocab_size == published.vocab_size
    assert (full.model.head_dim, full.model.mamba_inner, full.model.mamba_dt_rank) == (64, 5120, 160)


def test_the_cli_trains_the_tiny_preset_by_the_same_command(tmp_path):
    from glom_tpu.train.cli import main

    out = tmp_path / "m.jsonl"
    assert main(["--preset", "sambay-tiny", "--steps", "4", "--log-every", "2",
                 "--prefetch", "2", "--metrics-file", str(out)]) == 0
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    steps = [r for r in recs if r.get("kind") == "train_step"]
    assert len(steps) == 2 and all(r["vjp_path"] == "lm_xla" for r in steps)
    assert all(set(sambay.COUNTERS) <= set(r) for r in steps)


@pytest.mark.parametrize("flag", [["--distributed"], ["--check-parity"], ["--data-dir", "x"]])
def test_the_cli_refuses_gloms_options_on_the_preset(flag):
    from glom_tpu.train.cli import main

    with pytest.raises(SystemExit, match="GLOM"):
        main(["--preset", "sambay-tiny", "--steps", "1", *flag])


def test_python_m_glom_tpu_train_is_the_cli(monkeypatch, capsys):
    import runpy
    import sys

    monkeypatch.setattr(sys, "argv", ["glom_tpu.train", "--help"])
    with pytest.raises(SystemExit) as done:
        runpy.run_module("glom_tpu.train", run_name="__main__")
    assert done.value.code == 0 and "--preset" in capsys.readouterr().out
