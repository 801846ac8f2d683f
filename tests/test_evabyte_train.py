"""The EvaByte family through the one trainer: the objective by the
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

from benchmark import weights_evabyte as we
from benchmark.reference import evabyte_ref
from glom_tpu.data import prefetch_to_device, token_dataset
from glom_tpu.models import evabyte
from glom_tpu.tracing.spans import DEVICE_PHASES, EVABYTE_DEVICE_PHASES
from glom_tpu.train import Objective, Trainer, objective_for, objectives
from glom_tpu.train.trainer import TrainState, default_optimizer, make_train_step
from glom_tpu.utils.config import EvaByteConfig
from glom_tpu.utils.presets import LM_PRESETS, get_preset


class Collector:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)


@pytest.fixture(scope="module")
def tiny():
    p = get_preset("evabyte-tiny")
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
    assert objectives._lm_family(cfg) == (evabyte.init_evabyte, evabyte.lm_loss)
    with pytest.raises(ValueError):
        objective_for(cfg, dataclasses.replace(tcfg, grad_accum=2))
    # the float32 stream is the family's own: the trainer's configuration has no word for it
    assert not [f.name for f in dataclasses.fields(tcfg) if "stream" in f.name or "skip" in f.name]


def test_fit_trains_the_tiny_preset_for_three_steps(fitted, tiny):
    trainer, history, records = fitted
    losses = [h["loss"] for h in history]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert abs(losses[0] - np.log(tiny[0].vocab_size)) < 0.1      # near ln(40) at the start
    assert losses[0] > losses[1] > losses[2]
    assert trainer.vjp_path == "lm_xla" and int(trainer.state.step) == 3
    assert history[0]["params_bytes_per_replica"] == 4 * evabyte.param_count(tiny[0])


def test_the_records_carry_the_counters(fitted, tiny):
    cfg = tiny[0]
    _, history, records = fitted
    steps = [r for r in records if r.get("kind") == "train_step"]
    assert len(steps) == 3 and all(r["vjp_path"] == "lm_xla" for r in steps)
    for r in steps:
        # 80 bytes are three windows of 32 a row: a query block a window, each one key block of
        # own keys; the second and the third also one block of summaries (8 and 16 of them)
        assert (r["attn_key_blocks_local"], r["attn_key_blocks_summary"]) == (3 * 3, 3 * 2)
        assert r["eva_summary_keys"] == 3 * 2 * (80 // cfg.chunk_size)
        assert r["lm_pred_heads"] == cfg.num_pred_heads == 3
        assert r["attn_forward_kept"] == 0     # the XLA loop names nothing for the recomputation
        assert r["swiglu_backward_staged"] == cfg.num_hidden_layers == 3   # a dense MLP a layer
    assert set(evabyte.COUNTERS) <= set(history[-1])


@pytest.mark.parametrize("seed", [5, 2**31 + 77])
def test_three_adam_steps_through_fit_are_the_references(tiny, seed):
    """From the benchmark's seeded weights, installed as a checkpoint resume
    would: the losses of the three steps, the first gradient (Adam's first
    moment after one step) and every leaf's change over the three, against
    `evabyte_ref.train_reference` on the same batches."""
    cfg, tcfg = tiny
    model = dataclasses.asdict(cfg)
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, cfg.vocab_size, (tcfg.batch_size, cfg.seq_len), dtype=np.int32)
               for _ in range(3)]
    trainer = Trainer(cfg, tcfg, metrics_writer=Collector())
    w0 = we.make_weights(seed, model)
    params = we.to_program_params(w0)
    trainer.state = TrainState(params=params, opt_state=trainer.optimizer.init(params),
                               step=jnp.zeros((), jnp.int32))
    with jax.default_matmul_precision("highest"):
        history = trainer.fit(prefetch_to_device(iter(batches), size=2), num_steps=3, log_every=1)
        want = evabyte_ref.train_reference(lambda: we.make_weights(seed, model), batches, model,
                                           lr=tcfg.learning_rate)
    assert np.allclose([h["loss"] for h in history], want["losses"], rtol=2e-6)
    got = we.from_program_params(trainer.state.params)
    w0 = we.make_weights(seed, model)
    for leaf, norm in want["delta_norms"].items():
        change = float(jnp.linalg.norm(got[leaf] - w0[leaf]))
        assert abs(change - norm) < 2e-3 * norm, leaf      # Adam's sign at a gradient near zero
    assert len(want["delta_norms"]) == 3 * 11 + 3


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
    assert set(EVABYTE_DEVICE_PHASES) <= words and {"optimizer", "step_metrics"} <= words
    assert not set(EVABYTE_DEVICE_PHASES) & set(DEVICE_PHASES)      # no word shared with GLOM's
    # the attention's scope holds the scores and nothing of the projections or the summariser
    scores = [n for n in op_names if re.search(r"\beva_attention\b", n)]
    assert scores and not any(re.search(r"\beva_(in|summary|out)\b", n) for n in scores)
    scoped = set(EVABYTE_DEVICE_PHASES) | {"optimizer", "step_metrics"}
    placed = [n for n in op_names if n.startswith("jit(")]
    inside = sum(any(w in scoped for w in re.findall(r"[A-Za-z0-9_]+", n)) for n in placed)
    assert len(placed) > 500 and inside / len(placed) > 0.95


def test_the_presets_of_the_family():
    assert {"evabyte-stage4tp4", "evabyte-tiny"} <= set(LM_PRESETS)
    full = get_preset("evabyte-stage4tp4")
    assert isinstance(full.model, EvaByteConfig)
    assert (full.train.batch_size, full.train.compute_dtype, full.train.remat,
            full.train.learning_rate) == (1, "bfloat16", True, 3e-4)
    published = EvaByteConfig()
    for width in ("hidden_size", "intermediate_size", "head_dim", "window_size", "chunk_size",
                  "num_pred_heads", "vocab_size", "rope_theta", "rms_norm_eps",
                  "num_hidden_layers_total", "num_attention_heads_total"):
        assert getattr(full.model, width) == getattr(published, width), width
    assert (published.hidden_size, published.intermediate_size, published.head_dim,
            published.window_size, published.chunk_size, published.num_pred_heads,
            published.vocab_size, published.num_hidden_layers,
            published.num_attention_heads) == (4096, 11008, 128, 2048, 16, 8, 320, 32, 32)
    assert (full.model.num_hidden_layers, full.model.num_attention_heads,
            full.model.seq_len) == (4, 8, 16384)
    # ISSUE.md's arithmetic: a layer 152,053,760 with 8 of 32 heads, 620,015,616 held
    assert evabyte.count_shapes(evabyte.layer_shapes(full.model)) == 152_053_760
    assert evabyte.param_count(full.model) == 620_015_616
    assert evabyte.count_shapes(evabyte.layer_shapes(published)) == 202_391_552
    with pytest.raises(ValueError):
        EvaByteConfig(num_attention_heads=33)
    with pytest.raises(ValueError):
        EvaByteConfig(window_size=2048, chunk_size=24)


def test_the_cli_trains_the_tiny_preset_by_the_same_command(tmp_path):
    from glom_tpu.train.cli import main

    out = tmp_path / "m.jsonl"
    assert main(["--preset", "evabyte-tiny", "--steps", "4", "--log-every", "2",
                 "--prefetch", "2", "--metrics-file", str(out)]) == 0
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    steps = [r for r in recs if r.get("kind") == "train_step"]
    assert len(steps) == 2 and all(r["vjp_path"] == "lm_xla" for r in steps)
    assert all(set(evabyte.COUNTERS) <= set(r) for r in steps)


@pytest.mark.parametrize("flag", [["--distributed"], ["--check-parity"], ["--data-dir", "x"]])
def test_the_cli_refuses_gloms_options_on_the_preset(flag):
    from glom_tpu.train.cli import main

    with pytest.raises(SystemExit, match="GLOM's"):
        main(["--preset", "evabyte-tiny", "--steps", "1"] + flag)
