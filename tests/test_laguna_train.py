"""The Laguna family through the one trainer: the objective by the
configuration's type, `Trainer.fit` on the tiny preset (falling loss, the
family's device scopes in the step, the routed part's and the attentions'
counters in the records), the CLI by the same command, the full preset's
shapes.

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
from glom_tpu.models import hybrid_lm, laguna
from glom_tpu.tracing.spans import (
    DEVICE_PHASES,
    LAGUNA_DEVICE_PHASES,
    LAGUNA_INNER_SCOPES,
    LM_DEVICE_PHASES,
    SAMBAY_DEVICE_PHASES,
)
from glom_tpu.train import Objective, Trainer, objective_for
from glom_tpu.train.trainer import default_optimizer, make_train_step
from glom_tpu.utils.config import LagunaConfig
from glom_tpu.utils.presets import LM_PRESETS, get_preset


class Collector:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)


@pytest.fixture(scope="module")
def tiny():
    p = get_preset("laguna-tiny")
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
    assert history[0]["params_bytes_per_replica"] == 4 * laguna.param_count(tiny[0])


def test_the_records_carry_the_counters(fitted, tiny):
    cfg = tiny[0]
    _, history, records = fitted
    steps = [r for r in records if r.get("kind") == "train_step"]
    assert len(steps) == 3 and all(r["vjp_path"] == "lm_xla" for r in steps)
    n, k, e = 2 * cfg.seq_len, cfg.num_experts_per_tok, cfg.num_experts
    rungs = hybrid_lm.row_rungs(n, cfg, (cfg.moe_rung_loads,))
    for r in steps:
        # 80 tokens are one query block here: ceil(80 / 128) = 1 key block a layer
        assert (r["attn_key_blocks_window"], r["attn_key_blocks_full"]) == (3, 2)
        assert r["attn_forward_kept"] == 0     # the XLA loop names nothing for the recomputation
        assert r["swiglu_backward_staged"] == 5    # one dense layer's MLP, four shared experts
        assert r["moe_pairs_here"] + e <= r["moe_rows_computed"] <= rungs[-1]
        assert 0 < r["moe_pairs_here"] <= n * min(k, e)
        assert 0.0 <= r["moe_rows_full_share"] <= 1.0 and r["moe_max_expert_load"] <= n
    assert set(laguna.COUNTERS) <= set(history[-1])
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
    assert set(LAGUNA_DEVICE_PHASES) <= words and {"optimizer", "step_metrics"} <= words
    assert set(LAGUNA_INNER_SCOPES) <= words
    assert not set(LAGUNA_DEVICE_PHASES + LAGUNA_INNER_SCOPES) & set(DEVICE_PHASES)
    # an inner scope is opened inside an attention scope and nowhere else
    for inner in LAGUNA_INNER_SCOPES:
        paths = [n for n in op_names if re.search(rf"\b{inner}\b", n)]
        assert paths and all(re.search(r"\b(window|full)_attention\b.*\b" + inner + r"\b", n)
                             for n in paths), inner
    # what the families' vocabularies share means the same in each
    assert set(LAGUNA_DEVICE_PHASES) & set(LM_DEVICE_PHASES) == {
        "embed", "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
        "lm_head_loss"}
    assert set(LAGUNA_DEVICE_PHASES) & set(SAMBAY_DEVICE_PHASES) == {
        "embed", "window_attention", "full_attention", "lm_head_loss"}
    # next to nothing of the step's instructions lies outside every scope
    scoped = set(LAGUNA_DEVICE_PHASES) | {"optimizer", "step_metrics"}
    placed = [n for n in op_names if n.startswith("jit(")]
    inside = sum(any(w in scoped for w in re.findall(r"[A-Za-z0-9_]+", n)) for n in placed)
    assert len(placed) > 3_000 and inside / len(placed) > 0.95


def test_the_presets_of_the_family():
    assert {"laguna-xs2-ep8vp8", "laguna-tiny"} <= set(LM_PRESETS)
    full = get_preset("laguna-xs2-ep8vp8")
    assert isinstance(full.model, LagunaConfig)
    assert (full.train.batch_size, full.train.compute_dtype, full.train.remat,
            full.train.learning_rate) == (2, "bfloat16", True, 3e-4)
    published = LagunaConfig()
    for width in ("hidden_size", "intermediate_size", "num_attention_heads",
                  "num_sliding_attention_heads", "num_key_value_heads", "head_dim",
                  "sliding_window", "partial_rotary_factor", "num_experts_per_tok",
                  "num_experts_total", "moe_intermediate_size", "shared_expert_intermediate_size",
                  "moe_routed_scaling_factor", "num_hidden_layers_total", "layer_types",
                  "mlp_layer_types"):
        assert getattr(full.model, width) == getattr(published, width), width
    assert (full.model.layer_offset, full.model.num_hidden_layers, full.model.vocab_size,
            full.model.num_experts, full.model.seq_len) == (0, 5, 12544, 32, 8192)
    # two sequences of 8,192: balanced, 16,384 pairs here. The cell's preset asks for a
    # small rung of four times that (its traffic's router is out of balance: three of a
    # layer's 8 chosen experts held here fit it), then every pair; the family's default
    # is the other family's two loads
    assert full.model.moe_rung_loads == 4 and (published.moe_rung_loads,) == hybrid_lm.RUNG_LOADS
    assert hybrid_lm.row_rungs(2 * 8192, full.model, (full.model.moe_rung_loads,)) == (
        65536, 132096)
    assert hybrid_lm.row_rungs(2 * 8192, full.model) == (32768, 132096)
    assert hybrid_lm.row_rungs(8192, get_preset("nemotron3-super-ep64tp8").model) == (
        6144, 66560)                                            # the other family's, as they were


def test_the_cli_trains_the_tiny_preset_by_the_same_command(tmp_path):
    from glom_tpu.train.cli import main

    out = tmp_path / "m.jsonl"
    assert main(["--preset", "laguna-tiny", "--steps", "4", "--log-every", "2",
                 "--prefetch", "2", "--metrics-file", str(out)]) == 0
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    steps = [r for r in recs if r.get("kind") == "train_step"]
    assert len(steps) == 2 and all(r["vjp_path"] == "lm_xla" for r in steps)
    assert all(set(laguna.COUNTERS) <= set(r) for r in steps)
    assert not any("shared_backward_staged" in r for r in steps)   # `hybrid_lm.relu2_mlp`'s


@pytest.mark.parametrize("flag", [["--distributed"], ["--check-parity"], ["--data-dir", "x"]])
def test_the_cli_refuses_gloms_options_on_the_preset(flag):
    from glom_tpu.train.cli import main

    with pytest.raises(SystemExit, match="GLOM"):
        main(["--preset", "laguna-tiny", "--steps", "1", *flag])
