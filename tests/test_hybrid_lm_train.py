"""The second family through the one trainer: the objective seam
(train/objectives.Objective), `Trainer.fit` on the tiny preset (host spans,
device scopes, counters), the CLI, the token feed; and GLOM's step as it was
before the seam.

CPU only: what is checked is behaviour and metadata, never a time.
"""

import dataclasses
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glom_tpu.data import prefetch_to_device, token_dataset
from glom_tpu.tracing.spans import DEVICE_PHASES, HOST_PHASES, LM_DEVICE_PHASES
from glom_tpu.train import Objective, Trainer, objective_for
from glom_tpu.train.trainer import (
    TrainState,
    default_optimizer,
    make_train_step,
    resolve_training_route,
)
from glom_tpu.utils.config import GlomConfig, TrainConfig
from glom_tpu.utils.presets import LM_PRESETS, PRESETS, get_preset


class Collector:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)


@pytest.fixture(scope="module")
def tiny():
    p = get_preset("hybrid-lm-tiny")
    return p.model, p.train


@pytest.fixture(scope="module")
def fitted(tiny):
    """One trainer on the tiny preset, six steps through fit over a
    prefetched token feed, logging every other step."""
    cfg, tcfg = tiny
    writer = Collector()
    trainer = Trainer(cfg, tcfg, metrics_writer=writer)
    data = prefetch_to_device(
        token_dataset(tcfg.batch_size, cfg.seq_len, cfg.vocab_size, seed=1), size=2,
        metrics_writer=writer)
    history = trainer.fit(data, num_steps=6, log_every=2)
    return trainer, history, writer.records


# ------------------------------------------------------------------- the seam


def test_the_objective_is_picked_by_the_configs_type(tiny):
    cfg, tcfg = tiny
    lm_obj = objective_for(cfg, tcfg)
    assert isinstance(lm_obj, Objective)
    assert (lm_obj.vjp_path, lm_obj.grad_accum, lm_obj.has_aux) == ("lm_xla", 1, True)
    assert lm_obj.batch_shape == (cfg.seq_len,) and lm_obj.batch_dtype == jnp.int32
    gcfg = GlomConfig(dim=32, levels=3, image_size=16, patch_size=4)
    gtcfg = TrainConfig(batch_size=4)
    glom = objective_for(gcfg, gtcfg)
    assert (glom.grad_accum, glom.vjp_path) == resolve_training_route(gcfg, gtcfg)
    assert glom.batch_shape == (3, 16, 16) and not glom.has_aux


def test_gloms_objective_draws_the_noise_the_trainer_always_drew():
    cfg = GlomConfig(dim=32, levels=3, image_size=16, patch_size=4)
    tcfg = TrainConfig(batch_size=4, noise_std=0.5)
    img = jnp.zeros((4, 3, 16, 16))
    rng, step = jax.random.PRNGKey(3), jnp.asarray(7, jnp.int32)
    want = 0.5 * jax.random.normal(jax.random.fold_in(rng, step), img.shape, img.dtype)
    assert (objective_for(cfg, tcfg).draw(rng, step, img) == want).all()


@pytest.mark.parametrize("bad", [dict(grad_accum=2), dict(compute_dtype="float16")])
def test_the_language_model_objective_refuses_what_it_has_not(tiny, bad):
    with pytest.raises(ValueError):
        objective_for(tiny[0], dataclasses.replace(tiny[1], **bad))


def test_a_consensus_fn_belongs_to_glom(tiny):
    with pytest.raises(ValueError, match="GLOM"):
        objective_for(tiny[0], tiny[1], consensus_fn=lambda *a: None)


def _pre_seam_step(cfg, tcfg, optimizer, with_grad_norm):
    """GLOM's single-pass step as `make_train_step` wrote it before the
    objective seam (telemetry off, no accumulation, one device)."""
    from glom_tpu.train.objectives import denoise_loss

    def loss_of(params, img, noise):
        return denoise_loss(params, img, noise, cfg, recon_index=tcfg.recon_iter_index,
                            iters=tcfg.iters, remat=tcfg.remat, compute_dtype=None,
                            consensus_fn=None, use_pallas=tcfg.use_pallas,
                            unroll=tcfg.scan_unroll, with_diagnostics=False)

    def train_step(state, batch, rng):  # the argument's name is in the program's metadata
        with jax.named_scope("noise"):
            noise_rng = jax.random.fold_in(rng, state.step)
            noise = tcfg.noise_std * jax.random.normal(noise_rng, batch.shape, batch.dtype)
        loss, grads = jax.value_and_grad(loss_of)(state.params, batch, noise)
        metrics = {}
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        metrics.update({"loss": loss, "step": state.step})
        with jax.named_scope("step_metrics"):
            if with_grad_norm:
                metrics["grad_norm"] = optax.global_norm(grads)
        return TrainState(params, opt_state, state.step + 1), metrics

    return train_step


@pytest.mark.parametrize("with_grad_norm", [True, False], ids=["logging", "fast"])
def test_gloms_step_is_the_program_it_was_before_the_seam(with_grad_norm):
    """Both variants lower to the same program text, instruction for
    instruction, as the step written out by hand, and compile to
    instructions under the same scopes."""
    from glom_tpu.train.trainer import create_train_state

    cfg = GlomConfig(dim=32, levels=3, image_size=16, patch_size=4)
    tcfg = TrainConfig(batch_size=4, noise_std=0.5)
    state, opt = create_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    img, rng = jnp.zeros((4, 3, 16, 16)), jax.random.PRNGKey(1)
    lower = lambda f: jax.jit(f).lower(state, img, rng)
    new = lower(make_train_step(cfg, tcfg, opt, with_grad_norm=with_grad_norm))
    old = lower(_pre_seam_step(cfg, tcfg, opt, with_grad_norm))
    assert len(new.as_text()) > 10_000 and new.as_text() == old.as_text()
    op_names = lambda low: sorted(re.findall(r'op_name="([^"]*)"', low.compile().as_text()))
    assert op_names(new) == op_names(old)


def test_gloms_trainer_reports_the_route_it_always_did():
    cfg = GlomConfig(dim=32, levels=3, image_size=16, patch_size=4)
    tcfg = TrainConfig(batch_size=4)
    trainer = Trainer(cfg, tcfg)
    assert (trainer.grad_accum, trainer.vjp_path) == resolve_training_route(cfg, tcfg)
    rec = trainer.step(jnp.zeros((4, 3, 16, 16)))
    assert rec["vjp_path"] == "scan_dense" and np.isfinite(float(rec["loss"]))


# ------------------------------------------------------- Trainer.fit, tiny preset


def test_fit_trains_the_tiny_preset_through_the_one_loop(fitted):
    trainer, history, records = fitted
    assert trainer.vjp_path == "lm_xla" and trainer.grad_accum == 1
    assert [r["step"] for r in history] == [1.0, 3.0, 5.0]
    assert all(r["kind"] == "train_step" and np.isfinite(r["loss"]) for r in history)
    assert all(r["vjp_path"] == "lm_xla" for r in history)
    # random ids: the loss starts near ln(vocabulary)
    assert abs(history[0]["loss"] - np.log(128)) < 0.5


def test_fit_emits_the_three_host_spans_and_the_prefetch_workers(fitted):
    _, _, records = fitted
    names = {r["name"] for r in records if r.get("kind") == "span"}
    assert names >= {"host_data_next", "host_step_dispatch", "host_log_fetch"}
    assert names <= set(HOST_PHASES)


def test_the_logging_records_carry_the_routing_counters(fitted, tiny):
    cfg = tiny[0]
    _, history, _ = fitted
    from glom_tpu.models.hybrid_lm import ROW_TILE, row_rungs

    n, k, e = 2 * cfg.seq_len, cfg.num_experts_per_tok, cfg.n_routed_experts
    layers = cfg.pattern.count("E")
    rungs = row_rungs(n, cfg)
    assert rungs[-1] == -(-(n * min(k, e) + e) // ROW_TILE) * ROW_TILE   # whole row tiles
    for r in history:
        # the mean over the expert layers of the rung each ran, which holds
        # its pairs and the experts' rows of room
        assert any(sum(ran) == r["moe_rows_computed"] * layers
                   for ran in itertools.combinations_with_replacement(rungs, layers))
        assert r["moe_pairs_here"] + e <= r["moe_rows_computed"] <= rungs[-1]
        assert 0.0 <= r["moe_rows_full_share"] <= 1.0
        assert (r["moe_rows_full_share"] == 1.0) == (r["moe_rows_computed"] == rungs[-1])
        assert 0 < r["moe_pairs_here"] <= n * min(k, e)
        assert r["moe_pairs_here"] / layers <= r["moe_max_expert_load"] * e
        assert r["moe_max_expert_load"] <= n
        assert r["attn_forward_kept"] == 0     # the XLA loop names nothing for the recomputation


def test_the_logging_records_count_the_staged_shared_experts(fitted, tiny):
    """`shared_backward_staged`: the `E` layers of the step, each of whose
    shared expert's backward reads staged operands (`hybrid_lm.relu2_mlp`)."""
    _, history, _ = fitted
    assert len(history) == 3
    assert all(r["shared_backward_staged"] == tiny[0].pattern.count("E") == 2 for r in history)


@pytest.mark.parametrize("preset", ["laguna-tiny", "kimi-linear-tiny"])
def test_the_counter_does_not_reach_the_families_that_run_swiglu(preset):
    """Laguna's and Kimi Linear's counters are built from
    `hybrid_lm.STACK_COUNTERS`, and their shared expert is `laguna.swiglu`
    under their own `moe_shared` scope: what their objective hands the
    trainer for a record (the loss's aux) does not carry the name."""
    from glom_tpu.models import hybrid_lm, kimi_linear, laguna
    from glom_tpu.train.objectives import init_params

    p = get_preset(preset)
    family = {"laguna-tiny": laguna, "kimi-linear-tiny": kimi_linear}[preset]
    objective = objective_for(p.model, p.train)
    params = jax.eval_shape(lambda k: init_params(k, p.model), jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((p.train.batch_size, p.model.seq_len), jnp.int32)
    _, aux = jax.eval_shape(lambda w, i: objective.loss(w, i, ()), params, ids)
    assert set(aux) == set(family.COUNTERS) >= set(hybrid_lm.STACK_COUNTERS)
    assert "shared_backward_staged" not in aux


def test_the_records_pass_the_telemetry_schema(fitted):
    from glom_tpu.telemetry import schema

    _, _, records = fitted
    for r in records:
        assert r["schema_version"] == schema.SCHEMA_VERSION


def test_the_lowered_step_carries_every_scope_of_the_vocabulary(tiny):
    cfg, tcfg = tiny
    opt = default_optimizer(tcfg)
    step = make_train_step(cfg, tcfg, opt)
    from glom_tpu.train.trainer import create_train_state

    state, _ = create_train_state(jax.random.PRNGKey(0), cfg, tcfg, opt)
    ids = jnp.zeros((tcfg.batch_size, cfg.seq_len), jnp.int32)
    compiled = jax.jit(step).lower(state, ids, jax.random.PRNGKey(0)).compile()
    op_names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    words = {w for name in op_names for w in re.findall(r"[A-Za-z0-9_]+", name)}
    assert set(LM_DEVICE_PHASES) <= words
    assert {"optimizer", "step_metrics"} <= words
    assert not set(LM_DEVICE_PHASES) & set(DEVICE_PHASES)
    # next to nothing of the step's instructions lies outside every scope
    scoped = set(LM_DEVICE_PHASES) | {"optimizer", "step_metrics"}
    inside = sum(any(w in scoped for w in re.findall(r"[A-Za-z0-9_]+", n)) for n in op_names)
    assert inside / len(op_names) > 0.9


def test_the_trainers_static_record_counts_the_language_models_bytes(fitted, tiny):
    from glom_tpu.models.hybrid_lm import param_count

    trainer, history, _ = fitted
    assert history[0]["params_bytes_per_replica"] == 4 * param_count(tiny[0])
    assert history[0]["opt_bytes_per_replica"] >= 8 * param_count(tiny[0])


# ------------------------------------------------------ presets, data, the CLI


def test_the_language_model_presets_have_a_table_of_their_own():
    assert set(LM_PRESETS) == {"nemotron3-super-ep64tp8", "hybrid-lm-tiny",
                               "phi4-mini-flash-stage6vp8", "sambay-tiny",
                               "laguna-xs2-ep8vp8", "laguna-tiny",
                               "kimi-linear-ep32vp8", "kimi-linear-tiny",
                               "evabyte-stage4tp4", "evabyte-tiny",
                               "ouro-2.6b-stage8", "ouro-tiny"}
    assert not set(LM_PRESETS) & set(PRESETS)
    assert all(isinstance(p.model, GlomConfig) for p in PRESETS.values())
    full = get_preset("nemotron3-super-ep64tp8")
    assert (full.train.batch_size, full.train.compute_dtype, full.train.remat) == (
        1, "bfloat16", True)
    assert full.model.seq_len == 8192 and full.model.vocab_size == 16384


def test_token_dataset_is_seeded_and_in_range():
    a = next(token_dataset(3, 17, 128, seed=5))
    b = next(token_dataset(3, 17, 128, seed=5))
    c = next(token_dataset(3, 17, 128, seed=6))
    assert a.shape == (3, 17) and a.dtype == np.int32
    assert (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 128


def test_the_cli_trains_the_tiny_preset_by_the_same_command(tmp_path):
    import json

    from glom_tpu.train.cli import main

    out = tmp_path / "m.jsonl"
    assert main(["--preset", "hybrid-lm-tiny", "--steps", "4", "--log-every", "2",
                 "--prefetch", "2", "--metrics-file", str(out)]) == 0
    recs = [json.loads(l) for l in out.read_text().splitlines()]
    steps = [r for r in recs if r.get("kind") == "train_step"]
    assert len(steps) == 2 and all(r["vjp_path"] == "lm_xla" for r in steps)
    assert all("moe_pairs_here" in r for r in steps)


@pytest.mark.parametrize("flag", [["--distributed"], ["--check-parity"], ["--data-dir", "x"]])
def test_the_cli_refuses_gloms_options_on_a_language_model_preset(flag):
    from glom_tpu.train.cli import main

    with pytest.raises(SystemExit, match="GLOM"):
        main(["--preset", "hybrid-lm-tiny", "--steps", "1", *flag])
