"""Everything of a run but the look for a chip, with the timed path broken
underneath: `correct` has to come out false. And unbroken: true."""
import numpy as np
import pytest

from tiny import drive, tiny_cell


def test_sound_training_run_is_correct(capsys):
    line, out = drive(tiny_cell("flagship.train"), capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_col_iters_per_s_per_chip", "setup_s"}
    assert "compiles in window 0" in out
    assert line["compared"]["route"] == {"value": "scan_dense", "limit": "scan_dense",
                                         "ok": True}


@pytest.mark.parametrize("expect, correct", [(None, True), ("fused_loop", False)])
def test_the_configuration_may_leave_the_route_open_or_pin_it(capsys, expect, correct):
    """Without `bench.expect_vjp_path` a sound run is correct on whatever
    route the trainer resolved (on the CPU: scan_dense, with no kernel); with
    it, a program on another route is not."""
    cell = tiny_cell("flagship.train")
    if expect is None:
        del cell["config_file"]["bench"]["expect_vjp_path"]
    else:
        cell["config_file"]["bench"]["expect_vjp_path"] = expect
    line, out = drive(cell, capsys)
    assert line["correct"] is correct
    assert line["compared"]["route"] == {"value": "scan_dense", "limit": expect or "any",
                                         "ok": correct}
    assert line["compared"]["records_vjp_path"]["ok"]
    assert all(c["ok"] for name, c in line["compared"].items() if name != "route")


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    from glom_tpu.train.trainer import Trainer

    real = Trainer.step_fast

    def frozen(self, batch):
        before = self.state
        metrics = real(self, batch)
        self.state = before._replace(step=self.state.step)  # the update is lost
        return metrics

    # the state is donated to the real step: stop that, so `before` survives
    import jax

    monkeypatch.setattr(Trainer, "step_fast", frozen)
    real_jit = jax.jit
    monkeypatch.setattr(jax, "jit", lambda f, **kw: real_jit(
        f, **{k: v for k, v in kw.items() if k != "donate_argnums"}))
    line, out = drive(tiny_cell("flagship.train"), capsys)
    assert line["correct"] is False
    assert "OVER" in out


def test_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    from glom_tpu.train.trainer import Trainer

    real = Trainer.step

    def half(self, batch):
        b = batch.shape[0] // 2
        import jax.numpy as jnp

        return real(self, jnp.concatenate([batch[:b], batch[:b]]))

    monkeypatch.setattr(Trainer, "step", half)
    line, out = drive(tiny_cell("flagship.train"), capsys)
    assert line["correct"] is False


def test_sound_serving_run_is_correct(capsys):
    line, out = drive(tiny_cell("flagship.serve-steady"), capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_images_per_s", "serve_p50_ms", "serve_p95_ms",
                                    "setup_s"}
    assert line["attempted"] == 60


def test_an_answer_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    from glom_tpu.serve.engine import InferenceEngine

    real = InferenceEngine.infer

    def altered(self, imgs, *a, **kw):
        res = real(self, imgs, *a, **kw)
        return res._replace(levels=res.levels * 1.05)

    monkeypatch.setattr(InferenceEngine, "infer", altered)
    line, out = drive(tiny_cell("flagship.serve-steady"), capsys)
    assert line["correct"] is False
    assert "OVER" in out


def test_without_a_chip_the_command_prints_no_result(tmp_path):
    import os
    import subprocess
    import sys

    from benchmark import harness

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "flagship.train",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=harness.ROOT, env=env, capture_output=True, text=True)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
