"""The Ouro family through the one trainer: the objective by the
configuration's type, `Trainer.fit` on the tiny preset (falling loss, the
family's device scopes in the step, its counters in the records), three Adam
steps of `fit` against the plain reference from seeded weights, the CLI by the
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

from benchmark import weights_ouro as wo
from benchmark.reference import ouro_ref
from glom_tpu.data import prefetch_to_device, token_dataset
from glom_tpu.models import ouro
from glom_tpu.tracing.spans import DEVICE_PHASES, OURO_DEVICE_PHASES
from glom_tpu.train import Objective, Trainer, objective_for, objectives
from glom_tpu.train.trainer import TrainState, default_optimizer, make_train_step
from glom_tpu.utils.config import OuroConfig
from glom_tpu.utils.presets import LM_PRESETS, get_preset


class Collector:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)


@pytest.fixture(scope="module")
def tiny():
    p = get_preset("ouro-tiny")
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
    assert objectives._lm_family(cfg) == (ouro.init_ouro, ouro.lm_loss)
    with pytest.raises(ValueError):
        objective_for(cfg, dataclasses.replace(tcfg, grad_accum=2))


def test_fit_trains_the_tiny_preset_for_three_steps(fitted, tiny):
    trainer, history, records = fitted
    losses = [h["loss"] for h in history]
    assert len(losses) == 3 and all(np.isfinite(losses))
    # ln(128) less beta times the entropy of about (1/2, 1/4, 1/8, 1/8)
    assert abs(losses[0] - (np.log(tiny[0].vocab_size) - 0.05 * 1.21)) < 0.1
    assert losses[0] > losses[1] > losses[2]
    assert trainer.vjp_path == "lm_xla" and int(trainer.state.step) == 3
    assert history[0]["params_bytes_per_replica"] == 4 * ouro.param_count(tiny[0])


def test_the_records_carry_the_counters(fitted, tiny):
    cfg = tiny[0]
    _, history, records = fitted
    steps = [r for r in records if r.get("kind") == "train_step"]
    assert len(steps) == 3 and all(r["vjp_path"] == "lm_xla" for r in steps)
    applications = cfg.total_ut_steps * cfg.num_hidden_layers
    for r in steps:
        assert r["ut_steps"] == cfg.total_ut_steps == 4
        assert r["layer_applications"] == applications == 8
        assert r["attn_key_blocks_full"] == applications        # 80 tokens: one block a call
        assert r["attn_forward_kept"] == 0     # the XLA loop names nothing for the recomputation
        assert r["swiglu_backward_staged"] == applications      # a dense MLP an application
        assert 0.0 < r["exit_entropy"] <= np.log(4) + 1e-6 and 0.0 < r["exit_mass_last"] < 1.0
    assert set(ouro.COUNTERS) <= set(history[-1])


@pytest.mark.parametrize("seed", [5, 2**31 + 77])
def test_three_adam_steps_through_fit_are_the_references(tiny, seed):
    """From the benchmark's seeded weights, installed as a checkpoint resume
    would: the losses of the three steps and every leaf's change over the
    three (the looped leaves' and the gate's among them), against
    `ouro_ref.train_reference` on the same batches."""
    cfg, tcfg = tiny
    model = dataclasses.asdict(cfg)
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, cfg.vocab_size, (tcfg.batch_size, cfg.seq_len), dtype=np.int32)
               for _ in range(3)]
    trainer = Trainer(cfg, tcfg, metrics_writer=Collector())
    w0 = wo.make_weights(seed, model)
    params = wo.to_program_params(w0)
    trainer.state = TrainState(params=params, opt_state=trainer.optimizer.init(params),
                               step=jnp.zeros((), jnp.int32))
    with jax.default_matmul_precision("highest"):
        history = trainer.fit(prefetch_to_device(iter(batches), size=2), num_steps=3, log_every=1)
        want = ouro_ref.train_reference(lambda: wo.make_weights(seed, model), batches, model,
                                        lr=tcfg.learning_rate)
    assert np.allclose([h["loss"] for h in history], want["losses"], rtol=2e-6)
    got = wo.from_program_params(trainer.state.params)
    w0 = wo.make_weights(seed, model)
    for leaf, norm in want["delta_norms"].items():
        change = float(jnp.linalg.norm(got[leaf] - w0[leaf]))
        assert abs(change - norm) < 2e-3 * norm, leaf      # Adam's sign at a gradient near zero
    assert len(want["delta_norms"]) == 2 * 11 + 5


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
    assert set(OURO_DEVICE_PHASES) <= words and {"optimizer", "step_metrics"} <= words
    assert not set(OURO_DEVICE_PHASES) & set(DEVICE_PHASES)         # no word shared with GLOM's
    # the attention's scope holds the scores and nothing of the projections or the norms
    scores = [n for n in op_names if re.search(r"\bfull_attention\b", n)]
    assert scores and not any(re.search(r"\b(ouro_in|ouro_out|sandwich_norm)\b", n)
                              for n in scores)
    scoped = set(OURO_DEVICE_PHASES) | {"optimizer", "step_metrics"}
    placed = [n for n in op_names if n.startswith("jit(")]
    inside = sum(any(w in scoped for w in re.findall(r"[A-Za-z0-9_]+", n)) for n in placed)
    assert len(placed) > 500 and inside / len(placed) > 0.95


def test_the_presets_of_the_family():
    assert {"ouro-2.6b-stage8", "ouro-tiny"} <= set(LM_PRESETS)
    full = get_preset("ouro-2.6b-stage8")
    assert isinstance(full.model, OuroConfig)
    assert (full.train.batch_size, full.train.compute_dtype, full.train.remat,
            full.train.learning_rate) == (2, "bfloat16", True, 3e-4)
    published = OuroConfig()
    for width in ("hidden_size", "intermediate_size", "head_dim", "num_attention_heads",
                  "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps",
                  "num_hidden_layers_total", "total_ut_steps"):
        assert getattr(full.model, width) == getattr(published, width), width
    assert (published.hidden_size, published.intermediate_size, published.head_dim,
            published.num_attention_heads, published.num_key_value_heads, published.vocab_size,
            published.num_hidden_layers, published.total_ut_steps, published.rope_theta,
            published.rms_norm_eps) == (2048, 5632, 128, 16, 16, 49152, 48, 4, 1e6, 1e-6)
    assert (full.model.num_hidden_layers, full.model.seq_len) == (8, 4096)
    # ISSUE.md's arithmetic: a layer 51,388,416; 612,438,017 held with the gate's 2,049
    assert ouro.count_shapes(ouro.layer_shapes(full.model)) == 51_388_416
    assert ouro.param_count(full.model) == 612_438_017
    assert ouro.param_count(published) == 48 * 51_388_416 + 2 * 100_663_296 + 2_048 + 2_049
    with pytest.raises(ValueError):
        OuroConfig(num_hidden_layers=49)
    with pytest.raises(ValueError):
        OuroConfig(total_ut_steps=0)
    with pytest.raises(ValueError):
        OuroConfig(num_attention_heads=16, num_key_value_heads=5)


def test_the_cli_trains_the_tiny_preset_by_the_same_command(tmp_path):
    from glom_tpu.train.cli import main

    out = tmp_path / "m.jsonl"
    assert main(["--preset", "ouro-tiny", "--steps", "4", "--log-every", "2",
                 "--prefetch", "2", "--metrics-file", str(out)]) == 0
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    steps = [r for r in recs if r.get("kind") == "train_step"]
    assert len(steps) == 2 and all(r["vjp_path"] == "lm_xla" for r in steps)
    assert all(set(ouro.COUNTERS) <= set(r) for r in steps)


@pytest.mark.parametrize("flag", [["--distributed"], ["--check-parity"], ["--data-dir", "x"]])
def test_the_cli_refuses_gloms_options_on_the_preset(flag):
    from glom_tpu.train.cli import main

    with pytest.raises(SystemExit, match="GLOM's"):
        main(["--preset", "ouro-tiny", "--steps", "1"] + flag)
