"""Process start-up and device selection: the compile cache's placement,
the no-fallback gates (bench_bootstrap, detect_chip, chip_smoke.py,
tpu_validate.py), the in-process watchdog probe, and one engine replica
per device."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace
from unittest import mock

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


class TestCompileCache:
    def test_env_placement_is_left_alone(self, monkeypatch, restore_cache_dir):
        from glom_tpu.utils.startup import enable_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert enable_compile_cache() == "/somewhere/else"
        # JAX reads the variable itself; the function set nothing.
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_resolves_in_checkout_from_any_cwd(
        self, monkeypatch, tmp_path, restore_cache_dir
    ):
        from glom_tpu.utils.startup import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        seen = []
        for cwd in (tmp_path, REPO / "tests"):
            monkeypatch.chdir(cwd)
            seen.append(enable_compile_cache())
            assert jax.config.jax_compilation_cache_dir == seen[-1]
        assert seen[0] == seen[1] == str(REPO / ".jax_cache")
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored


class TestNoHiddenPlatform:
    def test_unknown_tpu_kind_raises_and_cpu_has_no_peak(self):
        from glom_tpu.utils.config import GlomConfig
        from glom_tpu.utils.metrics import PEAK_FLOPS, detect_chip, mfu

        assert "cpu" not in PEAK_FLOPS
        v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        assert detect_chip(v5e) == "v5e"
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            detect_chip(SimpleNamespace(platform="tpu", device_kind="TPU v9x"))
        # Off-TPU the name is a label only: it buys no utilization figure.
        assert detect_chip() == "cpu"
        cfg = GlomConfig(dim=16, levels=3, image_size=8, patch_size=4)
        with pytest.raises(ValueError, match="device metric"):
            mfu(cfg, 100.0, chip="cpu")

    @pytest.mark.parametrize("probe", [8, None])
    def test_bench_bootstrap_never_writes_jax_platforms(
        self, probe, monkeypatch, capsys, restore_cache_dir
    ):
        """Backend up or down, the gate leaves the caller's platform
        choice exactly as it found it — set or unset."""
        from glom_tpu.telemetry import sinks, watchdog

        real = watchdog.BackendWatchdog
        for env in ({"JAX_PLATFORMS": "cpu"}, {}):
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
            for k, v in env.items():
                monkeypatch.setenv(k, v)
            with mock.patch.object(
                watchdog, "BackendWatchdog",
                lambda **kw: real(probe=lambda t: probe, **kw),
            ):
                try:
                    ok = sinks.bench_bootstrap("my_metric", "u")
                finally:
                    watchdog.set_global_watchdog(None)
            assert ok is (probe is not None)
            assert os.environ.get("JAX_PLATFORMS") == env.get("JAX_PLATFORMS")
        capsys.readouterr()

    def test_bench_bootstrap_refuses_the_cpu_jax_fell_back_to(
        self, monkeypatch, capsys, restore_cache_dir
    ):
        """No chip and no explicit JAX_PLATFORMS=cpu: the backend answers
        (it is the CPU) but the gate reports UNMEASURED, not a toy run."""
        from glom_tpu.telemetry import sinks, watchdog
        from glom_tpu.utils import startup

        monkeypatch.setattr(startup, "cpu_requested", lambda: False)
        try:
            assert sinks.bench_bootstrap("my_metric", "u") is False
        finally:
            watchdog.set_global_watchdog(None)
        row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert row["kind"] == "error" and row["value"] is None
        assert row["error"] == "no-accelerator"
        assert row["metric"] == "my_metric"

    def test_cpu_requested_reads_the_first_platform(self):
        """The chip machine runs under JAX_PLATFORMS=tpu,cpu: a trailing
        host platform is not a request for the CPU."""
        from glom_tpu.utils import startup

        for platforms, want in (
            ("cpu", True), ("tpu,cpu", False), ("", False), (None, False),
        ):
            with mock.patch.object(
                type(jax.config), "jax_platforms", platforms, create=True
            ):
                assert startup.cpu_requested() is want

    def test_chip_scripts_fail_without_a_chip(self):
        """chip_smoke.py and tpu_validate.py under JAX_PLATFORMS=cpu: a
        non-zero exit and a reason in seconds, before anything compiles,
        and no result line."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for script in ("chip_smoke.py", "tpu_validate.py"):
            proc = subprocess.run(
                [sys.executable, str(REPO / script)], env=env, cwd=REPO,
                capture_output=True, text=True, timeout=120,
            )
            assert proc.returncode != 0, script
            assert "needs a TPU" in proc.stderr, (script, proc.stderr[-500:])
            assert '"ok"' not in proc.stdout, script

    def test_scripts_the_documents_name_exist(self):
        """Every `name.py` / `name.sh` that README.md or docs/*.md names
        without a directory is a file at the root of the tree, or the
        bare name of a module under glom_tpu/, benchmark/ or tests/."""
        modules = {
            p.name
            for d in ("glom_tpu", "benchmark", "tests")
            for p in (REPO / d).rglob("*.py")
        }
        bare = re.compile(r"(?<![\w/.*-])[A-Za-z_]\w*\.(?:py|sh)\b")
        missing = sorted(
            f"{doc.relative_to(REPO)}: {name}"
            for doc in [REPO / "README.md", *sorted((REPO / "docs").glob("*.md"))]
            for name in set(bare.findall(doc.read_text()))
            if name not in modules and not (REPO / name).is_file()
        )
        assert not missing, missing


class TestInProcessProbe:
    def test_default_probe_asks_this_process_and_starts_no_child(
        self, monkeypatch
    ):
        from glom_tpu.telemetry.watchdog import BackendWatchdog

        def no_children(*a, **kw):
            raise AssertionError("the watchdog probe started a process")

        monkeypatch.setattr(subprocess, "run", no_children)
        monkeypatch.setattr(subprocess, "Popen", no_children)
        wd = BackendWatchdog(probe_timeout=60.0)
        assert wd.probe_once() == "up"
        assert wd.record()["backend_devices"] == len(jax.local_devices())

    def test_probe_that_raises_or_hangs_reads_down(self, monkeypatch):
        import threading

        from glom_tpu.telemetry import watchdog

        monkeypatch.setattr(
            jax, "local_devices", lambda: (_ for _ in ()).throw(RuntimeError("x"))
        )
        assert watchdog._default_probe(5.0) is None
        release = threading.Event()
        monkeypatch.setattr(jax, "local_devices", lambda: release.wait(30) and [])
        assert watchdog._default_probe(0.05) is None  # join timeout, no hang
        release.set()


class TestOneEnginePerDevice:
    def test_engines_flag_pins_four_engines_to_four_devices(self, tmp_path):
        """`--engines 4` on the 8-device virtual platform: each engine's
        warmup events report where its params actually live."""
        from glom_tpu.serve.cli import main

        out = tmp_path / "serve.jsonl"
        rc = main(
            ["--preset", "mnist", "--synthetic", "4", "--engines", "4",
             "--buckets", "1", "--max-batch", "1", "--iters", "1",
             "--out", str(out)]
        )
        assert rc == 0
        recs = [json.loads(line) for line in out.read_text().splitlines()]
        placed = {
            r["engine"]: tuple(r["devices"])
            for r in recs if r.get("event") == "warmup"
        }
        assert sorted(placed) == ["engine0", "engine1", "engine2", "engine3"]
        assert len(set(placed.values())) == 4, placed
        assert all(len(d) == 1 for d in placed.values())

    def test_pinned_engine_keeps_params_pool_and_output_on_its_device(self):
        import numpy as np

        from glom_tpu.serve.engine import InferenceEngine
        from glom_tpu.utils.config import GlomConfig, ServeConfig

        dev = jax.devices()[3]
        cfg = GlomConfig(dim=16, levels=3, image_size=8, patch_size=4)
        scfg = ServeConfig(
            buckets=(1,), max_batch=1, iters=1,
            page_pool_pages=8, page_tokens=2,
        )
        eng = InferenceEngine(cfg, scfg, device=dev)
        for leaf in jax.tree_util.tree_leaves(eng.params):
            assert leaf.devices() == {dev}
        assert eng.pool.buffer().devices() == {dev}
        res = eng.infer(np.zeros((1, 3, 8, 8), np.float32))
        assert res.levels.devices() == {dev}
        with pytest.raises(ValueError, match="serve mesh"):
            InferenceEngine(
                cfg, ServeConfig(buckets=(2,), max_batch=2, mesh_data=2),
                device=dev,
            )
