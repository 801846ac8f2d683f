"""glom-lint (glom_tpu/analysis): every checker catches its seeded
violation with file:line, passes a clean snippet, and the pass self-hosts
clean on the repo with the reviewed baseline.

Pure AST tests — no jax import, no compiles; they stay in tier-1.
"""

import json
from pathlib import Path

import pytest

from glom_tpu.analysis import run
from glom_tpu.analysis import baseline as baseline_mod
from glom_tpu.analysis.__main__ import main

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "fixtures"


def lint(tmp_path, source, name="snippet.py", select=None):
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(source)
    return run([str(path)], select=select)


def by_checker(findings, checker):
    return [f for f in findings if f.checker == checker]


# ---------------------------------------------------------------------------
# collective-coverage
# ---------------------------------------------------------------------------


class TestCollectiveCoverage:
    def test_unknown_axis_literal_flagged(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'bogus_axis')\n"
        )
        fs = by_checker(lint(tmp_path, src), "collective-coverage")
        assert len(fs) == 1
        assert fs[0].line == 3
        assert "bogus_axis" in fs[0].message

    def test_declared_axis_constant_clean(self, tmp_path):
        src = (
            "from jax import lax\n"
            "DATA_AXIS = 'data'\n"
            "def f(x):\n"
            "    return lax.psum(x, DATA_AXIS)\n"
        )
        assert by_checker(lint(tmp_path, src), "collective-coverage") == []

    def test_axis_param_threading_clean(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def shard_body(x, axis_name):\n"
            "    return lax.ppermute(x, axis_name, [(0, 1)])\n"
        )
        assert by_checker(lint(tmp_path, src), "collective-coverage") == []

    def test_non_axis_param_flagged(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def f(x, which):\n"
            "    return lax.pmean(x, which)\n"
        )
        fs = by_checker(lint(tmp_path, src), "collective-coverage")
        assert len(fs) == 1 and "which" in fs[0].message

    def test_unregistered_collective_in_wire_module(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def grads(g):\n"
            "    return lax.psum_scatter(g, 'data', scatter_dimension=0)\n"
        )
        fs = by_checker(
            lint(tmp_path, src, name="parallel/manual.py"),
            "collective-coverage",
        )
        assert len(fs) == 1
        assert fs[0].line == 3 and "record_collective" in fs[0].message

    def test_registered_collective_clean(self, tmp_path):
        src = (
            "from jax import lax\n"
            "from glom_tpu.telemetry import counters as tele_counters\n"
            "def grads(g):\n"
            "    tele_counters.record_collective('reduce', 8)\n"
            "    return lax.psum_scatter(g, 'data', scatter_dimension=0)\n"
        )
        assert (
            by_checker(
                lint(tmp_path, src, name="parallel/manual.py"),
                "collective-coverage",
            )
            == []
        )

    def test_registration_not_required_outside_wire_modules(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'data')\n"
        )
        assert by_checker(lint(tmp_path, src), "collective-coverage") == []


# ---------------------------------------------------------------------------
# trace-purity
# ---------------------------------------------------------------------------


class TestTracePurity:
    def test_host_clock_in_jitted_body(self, tmp_path):
        src = (
            "import time\n"
            "import jax\n"
            "def step(x):\n"
            "    t0 = time.perf_counter()\n"
            "    return x + t0\n"
            "fast = jax.jit(step)\n"
        )
        fs = by_checker(lint(tmp_path, src), "trace-purity")
        assert len(fs) == 1 and fs[0].line == 4
        assert "trace time" in fs[0].message

    def test_print_reachable_through_helper(self, tmp_path):
        src = (
            "import jax\n"
            "def helper(x):\n"
            "    print('loss', x)\n"
            "    return x\n"
            "def step(x):\n"
            "    return helper(x) * 2\n"
            "fast = jax.jit(step)\n"
        )
        fs = by_checker(lint(tmp_path, src), "trace-purity")
        assert len(fs) == 1 and fs[0].line == 3
        assert "jax.debug.print" in fs[0].message

    def test_numpy_on_parameter_in_shard_map_body(self, tmp_path):
        src = (
            "import numpy as np\n"
            "from jax import shard_map\n"
            "def build(mesh):\n"
            "    def body(params, x):\n"
            "        return np.asarray(x).sum()\n"
            "    return shard_map(body, mesh=mesh, in_specs=(), out_specs=())\n"
        )
        fs = by_checker(lint(tmp_path, src), "trace-purity")
        assert len(fs) == 1 and "numpy cannot consume tracers" in fs[0].message

    def test_metadata_reads_are_pure(self, tmp_path):
        src = (
            "import numpy as np\n"
            "import jax\n"
            "def step(x):\n"
            "    b = x.shape[0]\n"
            "    scale = np.float32(1.0 / b)\n"
            "    dt = np.dtype(x.dtype).itemsize\n"
            "    return x * scale + dt\n"
            "fast = jax.jit(step)\n"
        )
        assert by_checker(lint(tmp_path, src), "trace-purity") == []

    def test_branch_on_tracer_value(self, tmp_path):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def body(c, x):\n"
            "    s = jnp.sum(x)\n"
            "    if s > 0:\n"
            "        return c, x\n"
            "    return c, -x\n"
            "def outer(xs):\n"
            "    return jax.lax.scan(body, 0, xs)\n"
        )
        fs = by_checker(lint(tmp_path, src), "trace-purity")
        assert len(fs) == 1 and fs[0].line == 5
        assert "lax.cond" in fs[0].message

    def test_while_loop_cond_and_config_branch_clean(self, tmp_path):
        src = (
            "import jax.numpy as jnp\n"
            "from jax import lax\n"
            "def run(x0, remat):\n"
            "    def cond(c):\n"
            "        return jnp.max(jnp.abs(c)) > 1e-3\n"
            "    def body(c):\n"
            "        if remat:\n"
            "            return c * 0.5\n"
            "        return c * 0.9\n"
            "    return lax.while_loop(cond, body, x0)\n"
        )
        assert by_checker(lint(tmp_path, src), "trace-purity") == []

    def test_host_code_not_flagged(self, tmp_path):
        src = (
            "import time\n"
            "def bench(step):\n"
            "    t0 = time.perf_counter()\n"
            "    step()\n"
            "    print(time.perf_counter() - t0)\n"
        )
        assert by_checker(lint(tmp_path, src), "trace-purity") == []


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------


class TestDonationSafety:
    def test_use_after_donated_dispatch(self, tmp_path):
        src = (
            "import jax\n"
            "def serve(params, imgs):\n"
            "    fn = jax.jit(lambda p, x: x * 2, donate_argnums=(1,))\n"
            "    out = fn(params, imgs)\n"
            "    return out, imgs.mean()\n"
        )
        fs = by_checker(lint(tmp_path, src), "donation-safety")
        assert len(fs) == 1 and fs[0].line == 5
        assert "imgs" in fs[0].message and "donated" in fs[0].message

    def test_non_donated_position_clean(self, tmp_path):
        src = (
            "import jax\n"
            "def serve(params, imgs):\n"
            "    fn = jax.jit(lambda p, x: x * 2, donate_argnums=(1,))\n"
            "    out = fn(params, imgs)\n"
            "    return out, params\n"
        )
        assert by_checker(lint(tmp_path, src), "donation-safety") == []

    def test_rebind_revives_the_name(self, tmp_path):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def serve(imgs):\n"
            "    fn = jax.jit(lambda x: x * 2, donate_argnums=(0,))\n"
            "    out = fn(imgs)\n"
            "    imgs = jnp.zeros((4,))\n"
            "    return out, imgs\n"
        )
        assert by_checker(lint(tmp_path, src), "donation-safety") == []

    def test_decorated_empty_argnums_means_no_donation(self, tmp_path):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, donate_argnums=())\n"
            "def fwd(x):\n"
            "    return x * 2\n"
            "def serve(imgs):\n"
            "    out = fwd(imgs)\n"
            "    return out, imgs.mean()\n"
        )
        assert by_checker(lint(tmp_path, src), "donation-safety") == []

    def test_decorated_donating_function_flagged(self, tmp_path):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, donate_argnums=(0,))\n"
            "def fwd(x):\n"
            "    return x * 2\n"
            "def serve(imgs):\n"
            "    out = fwd(imgs)\n"
            "    return out, imgs.mean()\n"
        )
        fs = by_checker(lint(tmp_path, src), "donation-safety")
        assert len(fs) == 1 and fs[0].line == 8

    def test_lowered_compile_chain_conservative(self, tmp_path):
        src = (
            "import jax\n"
            "def serve(donate, abstract, params, imgs):\n"
            "    fn = jax.jit(lambda p, x: x, donate_argnums=donate)"
            ".lower(abstract, abstract).compile()\n"
            "    out = fn(params, imgs)\n"
            "    return imgs.sum()\n"
        )
        # unresolvable argnums spec -> every positional arg is treated as
        # donated, so the later read of imgs is flagged
        fs = by_checker(lint(tmp_path, src), "donation-safety")
        assert len(fs) == 1 and "imgs" in fs[0].message

    # -- memoized-handle taint (the PR 5 blind spot, closed) ---------------

    def test_memoized_handle_via_provider_method_flagged(self, tmp_path):
        """The engine's real shape: the donating compiled handle is
        stored in self._compiled by one method, fetched through a
        provider method by another, and the donated batch is read after
        the dispatch — invisible to the intra-function pass, caught by
        the class-level taint."""
        src = (
            "import jax\n"
            "class Engine:\n"
            "    def _compile(self, sig, abstract):\n"
            "        lowered = jax.jit(\n"
            "            lambda p, x: x, donate_argnums=(1,)\n"
            "        ).lower(abstract, abstract)\n"
            "        compiled = lowered.compile()\n"
            "        self._compiled[sig] = compiled\n"
            "        return compiled\n"
            "    def infer(self, sig, abstract, params, imgs):\n"
            "        fn = self._compile(sig, abstract)\n"
            "        out = fn(params, imgs)\n"
            "        return out, imgs.mean()\n"
        )
        fs = by_checker(lint(tmp_path, src), "donation-safety")
        assert len(fs) == 1 and fs[0].line == 13
        assert "imgs" in fs[0].message

    def test_memoized_handle_direct_subscript_call_flagged(self, tmp_path):
        src = (
            "import jax\n"
            "class Engine:\n"
            "    def _compile(self, sig):\n"
            "        self._compiled[sig] = jax.jit(\n"
            "            lambda p, x: x, donate_argnums=(1,)\n"
            "        )\n"
            "    def infer(self, sig, params, imgs):\n"
            "        out = self._compiled[sig](params, imgs)\n"
            "        return out, imgs.sum()\n"
        )
        fs = by_checker(lint(tmp_path, src), "donation-safety")
        assert len(fs) == 1 and fs[0].line == 9
        assert "self._compiled" in fs[0].message

    def test_memoized_handle_splat_kwargs_conservative(self, tmp_path):
        """`jax.jit(fn, **jit_kw)` hides the donation inside the dict —
        on the HANDLE path every position is conservatively donated (the
        direct intra-function rule is unchanged: no class, no handle, no
        finding)."""
        src = (
            "import jax\n"
            "class Engine:\n"
            "    def _compile(self, sig, jit_kw):\n"
            "        self._compiled[sig] = jax.jit(lambda x: x, **jit_kw)\n"
            "    def infer(self, sig, imgs):\n"
            "        out = self._compiled[sig](imgs)\n"
            "        return out, imgs.sum()\n"
        )
        fs = by_checker(lint(tmp_path, src), "donation-safety")
        assert len(fs) == 1 and "imgs" in fs[0].message

    def test_memoized_handle_rebind_clears_the_taint(self, tmp_path):
        """Rebinding the handle name to a NON-donating callable clears
        the taint: the plain callable's call sites must not inherit the
        memoized handle's donation spec (review-caught false positive)."""
        src = (
            "import jax\n"
            "class Engine:\n"
            "    def _compile(self, sig):\n"
            "        self._compiled[sig] = jax.jit(\n"
            "            lambda p, x: x, donate_argnums=(1,)\n"
            "        )\n"
            "        return self._compiled[sig]\n"
            "    def infer(self, sig, plain_fn, params, imgs):\n"
            "        fn = self._compile(sig)\n"
            "        fn = plain_fn\n"
            "        out = fn(params, imgs)\n"
            "        return out, imgs.mean()\n"
        )
        assert by_checker(lint(tmp_path, src), "donation-safety") == []

    def test_memoized_handle_non_donated_position_clean(self, tmp_path):
        src = (
            "import jax\n"
            "class Engine:\n"
            "    def _compile(self, sig):\n"
            "        self._compiled[sig] = jax.jit(\n"
            "            lambda p, x: x, donate_argnums=(1,)\n"
            "        )\n"
            "    def infer(self, sig, params, imgs):\n"
            "        out = self._compiled[sig](params, imgs)\n"
            "        return out, params\n"
        )
        assert by_checker(lint(tmp_path, src), "donation-safety") == []

    def test_memoized_handle_fixture_pair(self):
        """The seeded acceptance pair (tests/fixtures/donation_memo.py):
        both leaky dispatch shapes flagged, the host-copy twin clean."""
        from glom_tpu.analysis import run

        fs = by_checker(
            run([str(FIXTURES / "donation_memo.py")]), "donation-safety"
        )
        symbols = {f.symbol for f in fs}
        assert symbols == {
            "LeakyMemoEngine.infer",
            "LeakyMemoEngine.infer_direct",
        }, fs
        assert all("Safe" not in f.symbol for f in fs)

    def test_alias_unpinned_dispatch_flagged(self, tmp_path):
        """A bare pool.buffer() flowing into a donating dispatch is an
        alias-unpinned-dispatch finding (ISSUE 16) — the pool's donated
        write-back can invalidate the buffer mid-dispatch."""
        src = (
            "import jax\n"
            "class Engine:\n"
            "    def _compile(self, sig):\n"
            "        self._compiled[sig] = jax.jit(\n"
            "            lambda p, b: b, donate_argnums=(0,)\n"
            "        )\n"
            "        return self._compiled[sig]\n"
            "    def infer(self, sig, params):\n"
            "        fn = self._compile(sig)\n"
            "        buf = self.pool.buffer()\n"
            "        return fn(params, buf)\n"
        )
        fs = by_checker(lint(tmp_path, src), "donation-safety")
        assert len(fs) == 1
        assert fs[0].key == "alias-unpinned-dispatch"
        assert "acquire_read" in fs[0].message

    def test_alias_pinned_rebind_clean(self, tmp_path):
        """Rebinding the name through acquire_read() before the dispatch
        clears the hazard — the latest binding decides."""
        src = (
            "import jax\n"
            "class Engine:\n"
            "    def _compile(self, sig):\n"
            "        self._compiled[sig] = jax.jit(\n"
            "            lambda p, b: b, donate_argnums=(0,)\n"
            "        )\n"
            "        return self._compiled[sig]\n"
            "    def infer(self, sig, params):\n"
            "        fn = self._compile(sig)\n"
            "        buf = self.pool.buffer()\n"
            "        buf = self.pool.acquire_read()\n"
            "        try:\n"
            "            return fn(params, buf)\n"
            "        finally:\n"
            "            self.pool.release_read()\n"
        )
        assert by_checker(lint(tmp_path, src), "donation-safety") == []

    def test_alias_compile_time_probe_clean(self, tmp_path):
        """A bare buffer() read that never reaches a dispatch (the
        engine's compile-time dtype probe) stays clean."""
        src = (
            "import jax\n"
            "class Engine:\n"
            "    def _compile(self, sig):\n"
            "        dt = self.pool.buffer().dtype\n"
            "        self._compiled[sig] = jax.jit(\n"
            "            lambda p, b: b, donate_argnums=(0,)\n"
            "        )\n"
            "        return self._compiled[sig]\n"
        )
        assert by_checker(lint(tmp_path, src), "donation-safety") == []

    def test_alias_fixture_pair(self):
        """The seeded acceptance pair (tests/fixtures/alias_pool.py):
        both unpinned dispatch shapes flagged, the pinned twin and its
        compile-time probe clean."""
        from glom_tpu.analysis import run

        fs = by_checker(
            run([str(FIXTURES / "alias_pool.py")]), "donation-safety"
        )
        alias = [f for f in fs if f.key == "alias-unpinned-dispatch"]
        symbols = {f.symbol for f in alias}
        assert symbols == {
            "LeakyPoolEngine.infer",
            "LeakyPoolEngine.infer_inline",
        }, fs
        assert all("Safe" not in f.symbol for f in fs)


# ---------------------------------------------------------------------------
# schema-emit
# ---------------------------------------------------------------------------


class TestSchemaEmit:
    def test_unknown_kind_flagged(self, tmp_path):
        src = (
            "from glom_tpu.telemetry.sinks import emit\n"
            "emit({'metric': 'x', 'value': 1.0, 'unit': 'u'}, kind='benhc')\n"
        )
        fs = by_checker(lint(tmp_path, src), "schema-emit")
        assert len(fs) == 1 and "benhc" in fs[0].message

    def test_registered_kind_clean(self, tmp_path):
        src = (
            "from glom_tpu.telemetry.sinks import emit\n"
            "emit({'metric': 'x', 'value': 1.0, 'unit': 'u'}, kind='bench')\n"
            "emit({'event': 'dispatch', 'trace_ids': ids}, kind='serve')\n"
        )
        assert by_checker(lint(tmp_path, src), "schema-emit") == []

    def test_request_scoped_event_without_trace_context_flagged(
        self, tmp_path
    ):
        src = (
            "from glom_tpu.serve.events import emit_serve\n"
            "emit_serve(w, {'event': 'dispatch', 'engine': 'e0'})\n"
        )
        fs = by_checker(lint(tmp_path, src), "schema-emit")
        assert len(fs) == 1 and fs[0].key == "trace-context"
        assert "trace_id" in fs[0].message

    def test_trace_context_rule_accepts_null_and_splat(self, tmp_path):
        src = (
            "from glom_tpu.serve.events import emit_serve\n"
            "emit_serve(w, {'event': 'resolve', 'trace_id': None,\n"
            "               'slo_class': None})\n"
            "emit_serve(w, {'event': 'shed', **fields})\n"
            "emit_serve(w, {'event': 'warmup', 'bucket': 4})\n"
        )
        assert by_checker(lint(tmp_path, src), "schema-emit") == []

    def test_trace_context_rule_skips_non_serve_kinds(self, tmp_path):
        # A "fault" record whose site context happens to name an event
        # from the serve vocabulary is out of scope for the rule.
        src = (
            "from glom_tpu.telemetry.sinks import emit\n"
            "emit({'fault': 'x', 'event': 'dispatch'}, kind='fault')\n"
        )
        assert by_checker(lint(tmp_path, src), "schema-emit") == []

    def test_trace_emit_fixture_pair(self):
        """The seeded acceptance pair (tests/fixtures/trace_emit.py): the
        context-less dispatch emit flagged, the four good shapes clean."""
        from glom_tpu.analysis import run

        fs = by_checker(
            run([str(FIXTURES / "trace_emit.py")]), "schema-emit"
        )
        assert len(fs) == 1, fs
        assert fs[0].key == "trace-context"
        assert fs[0].symbol == "bad_dispatch_emit"
        src_lines = (FIXTURES / "trace_emit.py").read_text().splitlines()
        assert "dispatch" in src_lines[fs[0].line - 1]

    def test_tenant_scoped_event_without_class_flagged(self, tmp_path):
        src = (
            "from glom_tpu.serve.events import emit_serve\n"
            "emit_serve(w, {'event': 'admit', 'request_id': rid})\n"
        )
        fs = by_checker(lint(tmp_path, src), "schema-emit")
        assert len(fs) == 1 and fs[0].key == "class-context"
        assert "slo_class" in fs[0].message

    def test_class_context_rule_accepts_null_and_splat(self, tmp_path):
        src = (
            "from glom_tpu.serve.events import emit_serve\n"
            "emit_serve(w, {'event': 'admit', 'slo_class': None})\n"
            "emit_serve(w, {'event': 'settle', **fields})\n"
            "emit_serve(w, {'event': 'ladder', 'rung': 'shed'})\n"
        )
        assert by_checker(lint(tmp_path, src), "schema-emit") == []

    def test_class_emit_fixture_pair(self):
        """The seeded acceptance pair (tests/fixtures/class_emit.py): the
        class-less admit emit flagged, the three good shapes clean."""
        from glom_tpu.analysis import run

        fs = by_checker(
            run([str(FIXTURES / "class_emit.py")]), "schema-emit"
        )
        assert len(fs) == 1, fs
        assert fs[0].key == "class-context"
        assert fs[0].symbol == "bad_admit_emit"
        src_lines = (FIXTURES / "class_emit.py").read_text().splitlines()
        assert "admit" in src_lines[fs[0].line - 1]

    def test_dead_zero_unmeasured_flagged(self, tmp_path):
        src = (
            "from glom_tpu.telemetry.sinks import emit\n"
            "emit({'metric': 'x', 'value': 0.0, 'unit': 'u',\n"
            "      'error': 'backend-down'}, kind='error')\n"
        )
        fs = by_checker(lint(tmp_path, src), "schema-emit")
        assert len(fs) == 1 and "must be None" in fs[0].message

    def test_null_unmeasured_clean(self, tmp_path):
        src = (
            "from glom_tpu.telemetry.sinks import emit\n"
            "emit({'metric': 'x', 'value': None, 'unit': 'u',\n"
            "      'error': 'backend-down'}, kind='error')\n"
        )
        assert by_checker(lint(tmp_path, src), "schema-emit") == []

    def test_error_kind_requires_error_field(self, tmp_path):
        src = (
            "from glom_tpu.telemetry import schema\n"
            "rec = schema.stamp({'metric': 'x', 'value': None}, kind='error')\n"
        )
        fs = by_checker(lint(tmp_path, src), "schema-emit")
        assert len(fs) == 1 and "no 'error' field" in fs[0].message

    def test_writer_write_with_inline_kind(self, tmp_path):
        src = "writer.write({'kind': 'not_a_kind', 'note': 'x'})\n"
        fs = by_checker(lint(tmp_path, src), "schema-emit")
        assert len(fs) == 1 and "not_a_kind" in fs[0].message


# ---------------------------------------------------------------------------
# lockset
# ---------------------------------------------------------------------------

RACY = '''
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self._thread = threading.Thread(target=self._run)

    def _run(self):
        with self._lock:
            self.count += 1

    def read(self):
        return self.count
'''

CLEAN = '''
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self._thread = threading.Thread(target=self._run)

    def _run(self):
        with self._lock:
            self.count += 1

    def read(self):
        with self._lock:
            return self.count
'''


class TestLockset:
    def test_unguarded_read_flagged(self, tmp_path):
        fs = by_checker(lint(tmp_path, RACY), "lockset")
        assert len(fs) == 1 and fs[0].line == 15
        assert "count" in fs[0].message and "read" in fs[0].message

    def test_guarded_everywhere_clean(self, tmp_path):
        assert by_checker(lint(tmp_path, CLEAN), "lockset") == []

    def test_unlocked_shared_write_from_thread(self, tmp_path):
        src = (
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.log = []\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        self.log.append(1)\n"
            "    def snapshot(self):\n"
            "        return list(self.log)\n"
        )
        fs = by_checker(lint(tmp_path, src), "lockset")
        assert len(fs) == 1 and "unsynchronized" in fs[0].message

    def test_held_context_inherits_transitively(self, tmp_path):
        """A private method called only from a held method (which is
        itself only called from lexically-held sites) inherits heldness
        through the fixpoint — the watchdog's _record_transition ->
        _write_event chain must not false-positive."""
        src = (
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.count = 0\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        with self._lock:\n"
            "            self._bump()\n"
            "    def _bump(self):\n"
            "        self._write()\n"
            "    def _write(self):\n"
            "        self.count += 1\n"
            "    def read(self):\n"
            "        with self._lock:\n"
            "            return self.count\n"
        )
        assert by_checker(lint(tmp_path, src), "lockset") == []

    def test_mutator_call_is_one_finding_not_two(self, tmp_path):
        """self.buf.clear() is ONE access (a write): the walk must not
        also count the inner self.buf read, or the baseline needs
        count=2 for one site."""
        src = (
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.buf = []\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        with self._lock:\n"
            "            self.buf.append(1)\n"
            "    def reset(self):\n"
            "        self.buf.clear()\n"
        )
        fs = by_checker(lint(tmp_path, src), "lockset")
        assert len(fs) == 1 and fs[0].line == 11

    def test_config_and_queue_attrs_exempt(self, tmp_path):
        src = (
            "import queue\n"
            "import threading\n"
            "class W:\n"
            "    def __init__(self, depth):\n"
            "        self._lock = threading.Lock()\n"
            "        self.depth = depth\n"
            "        self._q = queue.Queue(maxsize=depth)\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        self._q.put(self.depth)\n"
            "    def submit(self):\n"
            "        self._q.put(self.depth)\n"
        )
        assert by_checker(lint(tmp_path, src), "lockset") == []

    def test_regression_fixture_racy_flagged_locked_clean(self):
        """THE acceptance pair: the deliberately-unlocked DynamicBatcher
        queue mutation in the checked-in fixture is flagged at its line;
        the locked twin in the same file is not."""
        findings = by_checker(
            run([str(FIXTURES / "racy_batcher.py")]), "lockset"
        )
        assert findings, "lockset checker missed the seeded race"
        assert all("RacyBatcher" in f.message for f in findings)
        src_lines = (FIXTURES / "racy_batcher.py").read_text().splitlines()
        for f in findings:
            assert "LockedBatcher" not in f.message
        # the finding anchors the unlocked append itself
        assert any(
            "pending.append" in src_lines[f.line - 1] for f in findings
        )


# ---------------------------------------------------------------------------
# framework: pragmas, baseline, CLI, self-hosting
# ---------------------------------------------------------------------------


class TestFramework:
    def test_pragma_suppresses_same_line(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'bogus')  "
            "# glom-lint: ok[collective-coverage] seeded test axis\n"
        )
        assert by_checker(lint(tmp_path, src), "collective-coverage") == []

    def test_pragma_on_own_line_suppresses_next(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def f(x):\n"
            "    # glom-lint: ok[collective-coverage] seeded test axis\n"
            "    return lax.psum(x, 'bogus')\n"
        )
        assert by_checker(lint(tmp_path, src), "collective-coverage") == []

    def test_pragma_without_reason_is_a_finding(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'bogus')  "
            "# glom-lint: ok[collective-coverage]\n"
        )
        fs = lint(tmp_path, src)
        assert by_checker(fs, "collective-coverage") == []
        assert len(by_checker(fs, "pragma")) == 1

    def test_pragma_wrong_checker_does_not_suppress(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'bogus')  "
            "# glom-lint: ok[lockset] wrong checker\n"
        )
        assert len(by_checker(lint(tmp_path, src), "collective-coverage")) == 1

    def test_pragma_in_docstring_is_not_a_suppression(self, tmp_path):
        """The framework documents its own syntax in docstrings; those
        examples must neither suppress nor warn as unused."""
        src = (
            '"""Docs: write  # glom-lint: ok[lockset] reason  inline."""\n'
            "x = 1\n"
        )
        path = tmp_path / "m.py"
        path.write_text(src)
        warnings = []
        assert run([str(path)], warnings=warnings) == []
        assert warnings == []

    def test_unused_pragma_warns(self, tmp_path):
        src = (
            "def f(x):\n"
            "    return x  # glom-lint: ok[lockset] nothing fires here\n"
        )
        path = tmp_path / "m.py"
        path.write_text(src)
        warnings = []
        assert run([str(path)], warnings=warnings) == []
        assert len(warnings) == 1 and "unused pragma" in warnings[0]
        # a USED pragma does not warn
        used = (
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'bogus')  "
            "# glom-lint: ok[collective-coverage] seeded\n"
        )
        path.write_text(used)
        warnings = []
        assert run([str(path)], warnings=warnings) == []
        assert warnings == []
        # a partial --select cannot judge unusedness: no warning
        path.write_text(src)
        warnings = []
        run([str(path)], select=["schema-emit"], warnings=warnings)
        assert warnings == []

    def test_select_unknown_checker_raises(self, tmp_path):
        with pytest.raises(ValueError, match="unknown checkers"):
            lint(tmp_path, "x = 1\n", select=["nope"])

    def test_parse_error_is_a_finding(self, tmp_path):
        fs = lint(tmp_path, "def broken(:\n")
        assert len(by_checker(fs, "parse")) == 1

    def test_baseline_roundtrip_and_ratchet(self, tmp_path, capsys):
        bad = tmp_path / "mod.py"
        bad.write_text(
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'bogus')\n"
        )
        b = tmp_path / "baseline.json"
        # 1. unbaselined run fails
        assert main([str(bad), "--no-baseline"]) == 1
        # 2. write + annotate the baseline
        assert main([str(bad), "--write-baseline", str(b)]) == 0
        data = json.loads(b.read_text())
        assert len(data["suppressions"]) == 1
        # 3. unreviewed entries refuse to gate
        assert main([str(bad), "--baseline", str(b)]) == 1
        for entry in data["suppressions"].values():
            entry["reviewed"] = "seeded test suppression"
        b.write_text(json.dumps(data))
        # 4. reviewed baseline gates green
        assert main([str(bad), "--baseline", str(b)]) == 0
        # 5. a NEW finding beyond the baselined count fails
        bad.write_text(
            bad.read_text()
            + "def g(x):\n    return lax.pmean(x, 'bogus2')\n"
        )
        assert main([str(bad), "--baseline", str(b)]) == 1
        # 6. fixing everything leaves the stale entry as a warning only
        bad.write_text("def f(x):\n    return x\n")
        assert main([str(bad), "--baseline", str(b)]) == 0
        assert "stale baseline entry" in capsys.readouterr().out

    def test_baseline_fingerprints_are_line_free(self, tmp_path):
        src = (
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'bogus')\n"
        )
        shifted = "# a comment pushing everything down\n\n\n" + src
        fp1 = [f.fingerprint for f in lint(tmp_path, src, name="a/m.py")]
        fp2 = [f.fingerprint for f in lint(tmp_path, shifted, name="a/m.py")]
        assert fp1 == fp2

    def test_list_checkers(self, capsys):
        assert main(["--list-checkers"]) == 0
        out = capsys.readouterr().out
        for name in (
            "collective-coverage", "trace-purity", "donation-safety",
            "schema-emit", "lockset",
        ):
            assert name in out

    def test_self_host_repo_is_clean_with_baseline(self, monkeypatch):
        """The acceptance gate: the merged tree + the checked-in reviewed
        baseline (<= 10 suppressions) lints clean."""
        monkeypatch.chdir(REPO)
        findings = run(["glom_tpu"])
        data = baseline_mod.load(str(REPO / "analysis_baseline.json"))
        assert len(data["suppressions"]) <= 10
        assert baseline_mod.unreviewed(data) == []
        new, _stale = baseline_mod.apply(findings, data)
        assert new == [], "\n".join(f.render() for f in new)


class TestLockOrder:
    def test_fixture_pair_flags_only_the_deadlocky_class(self):
        """The seeded acceptance pair (tests/fixtures/lock_order.py):
        DeadlockyCoordinator's AB/BA cycle is flagged at file:line on
        BOTH edges (including the one formed transitively through
        _tally), OrderedCoordinator scans clean."""
        fs = by_checker(
            run([str(FIXTURES / "lock_order.py")]), "lock-order"
        )
        assert len(fs) == 2
        assert all("DeadlockyCoordinator" in f.symbol for f in fs)
        keys = {f.key for f in fs}
        assert keys == {
            "lock-order-_ledger_lock-_stats_lock",
            "lock-order-_stats_lock-_ledger_lock",
        }
        assert all(f.line > 0 for f in fs)

    def test_nested_two_locks_one_order_clean(self, tmp_path):
        src = (
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "        self.x = 0\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                self.x += 1\n"
            "    def two(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                return self.x\n"
        )
        assert by_checker(lint(tmp_path, src), "lock-order") == []

    def test_reverse_nesting_flagged(self, tmp_path):
        src = (
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "        self.x = 0\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                self.x += 1\n"
            "    def two(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                return self.x\n"
        )
        fs = by_checker(lint(tmp_path, src), "lock-order")
        assert len(fs) == 2
        assert {f.line for f in fs} == {9, 13}

    def test_sequential_acquisition_is_not_an_order(self, tmp_path):
        """Taking A, releasing it, then taking B imposes no order — only
        NESTED holds build edges."""
        src = (
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "        self.x = 0\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            self.x += 1\n"
            "        with self._b:\n"
            "            self.x += 1\n"
            "    def two(self):\n"
            "        with self._b:\n"
            "            self.x += 1\n"
            "        with self._a:\n"
            "            return self.x\n"
        )
        assert by_checker(lint(tmp_path, src), "lock-order") == []

    def test_transitive_cycle_through_call_flagged(self, tmp_path):
        src = (
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "        self.x = 0\n"
            "    def _take_b(self):\n"
            "        with self._b:\n"
            "            self.x += 1\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            self._take_b()\n"
            "    def two(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                return self.x\n"
        )
        fs = by_checker(lint(tmp_path, src), "lock-order")
        assert len(fs) == 2

    def test_single_lock_class_has_no_order_contract(self, tmp_path):
        src = (
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self.x = 0\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            with self._a:\n"
            "                self.x += 1\n"
        )
        assert by_checker(lint(tmp_path, src), "lock-order") == []

    def test_shipped_batcher_two_lock_pattern_is_acyclic(self):
        """The multi-engine DynamicBatcher's documented order
        (_engine_lock -> _counter_lock) scans clean — the target this
        checker ships alongside."""
        import glom_tpu.serve.batcher as batcher_mod

        fs = by_checker(run([batcher_mod.__file__]), "lock-order")
        assert fs == []


# ---------------------------------------------------------------------------
# signal-safety
# ---------------------------------------------------------------------------


class TestSignalSafety:
    """Code reachable from a signal.signal-registered handler must not
    acquire non-reentrant Locks or call the blocking-IO denylist — the
    PR 6 'sharing the loop's manager deadlocks' lesson, made static."""

    def test_fixture_pair_flags_only_the_deadlocky_class(self):
        fs = by_checker(
            run([str(FIXTURES / "signal_fixture.py")]), "signal-safety"
        )
        assert fs and all("Deadlocky" in f.symbol for f in fs), fs
        keys = {f.key for f in fs}
        assert keys == {
            "handler-lock-self._lock",
            "handler-join-unbounded",
            "handler-blocking-time.sleep",
            "handler-blocking-queue-get",
        }, keys
        assert all(f.line > 0 for f in fs)

    def test_nested_handler_lock_flagged(self, tmp_path):
        """The flight.py registration shape: a NESTED def handed to
        signal.signal, reaching a module-level helper that takes a plain
        Lock."""
        src = (
            "import signal\n"
            "import threading\n"
            "LOCK = threading.Lock()\n"
            "def flush():\n"
            "    with LOCK:\n"
            "        pass\n"
            "def install():\n"
            "    def _handler(signum, frame):\n"
            "        flush()\n"
            "    signal.signal(signal.SIGTERM, _handler)\n"
        )
        fs = by_checker(lint(tmp_path, src), "signal-safety")
        assert len(fs) == 1 and fs[0].line == 5
        assert "LOCK" in fs[0].message

    def test_rlock_and_bounded_join_exempt(self, tmp_path):
        """The shipped mitigations are NOT findings: RLock reacquisition
        succeeds for the paused owner, and a bounded join is the
        grace-window form."""
        src = (
            "import signal\n"
            "import threading\n"
            "LOCK = threading.RLock()\n"
            "def handler(signum, frame):\n"
            "    with LOCK:\n"
            "        w = threading.Thread(target=print)\n"
            "        w.start()\n"
            "        w.join(timeout=5.0)\n"
            "signal.signal(signal.SIGTERM, handler)\n"
        )
        assert by_checker(lint(tmp_path, src), "signal-safety") == []

    def test_unregistered_code_never_flagged(self, tmp_path):
        """The same hazardous shapes OUTSIDE a handler path are some
        other checker's business (lockset), not this one's."""
        src = (
            "import threading\n"
            "LOCK = threading.Lock()\n"
            "def flush():\n"
            "    with LOCK:\n"
            "        pass\n"
        )
        assert by_checker(lint(tmp_path, src), "signal-safety") == []

    def test_thread_target_is_not_handler_context(self, tmp_path):
        """Work moved to a spawned thread is the sanctioned escape hatch
        (the PR 6 fix): the target's body is not handler-reachable."""
        src = (
            "import signal\n"
            "import threading\n"
            "LOCK = threading.Lock()\n"
            "def worker():\n"
            "    with LOCK:\n"
            "        pass\n"
            "def handler(signum, frame):\n"
            "    t = threading.Thread(target=worker)\n"
            "    t.start()\n"
            "    t.join(timeout=3.0)\n"
            "signal.signal(signal.SIGTERM, handler)\n"
        )
        assert by_checker(lint(tmp_path, src), "signal-safety") == []

    def test_shipped_flight_recorder_handler_path_is_clean(self):
        """The self-host acceptance the satellite names: flight.py's
        SIGTERM path (RLock ring + bounded daemon-thread join) and the
        new pod coordinator's handler-side save both scan clean."""
        import glom_tpu.resilience.coordinator as coord_mod
        import glom_tpu.tracing.flight as flight_mod

        fs = by_checker(
            run([flight_mod.__file__, coord_mod.__file__]), "signal-safety"
        )
        assert fs == [], fs


class TestAxisEnvironment:
    def test_seeded_fixture_pair(self):
        """The seeded acceptance pair (tests/fixtures/axis_env.py): the
        leaky body's psum over MODEL_AXIS — vocabulary-legal but absent
        from ITS shard_map's ('data','seq') MeshConfig — is flagged both
        at the direct lax.psum site and through the _psum_wire threaded
        axis; the clean twin (every collective on a declared axis) scans
        clean."""
        fs = by_checker(
            run([str(FIXTURES / "axis_env.py")]), "axis-environment"
        )
        assert len(fs) == 2, fs
        assert all("'model'" in f.message for f in fs)
        src_lines = (FIXTURES / "axis_env.py").read_text().splitlines()
        for f in fs:
            assert "leaky" in f.symbol or "MODEL" in src_lines[f.line - 1]

    def test_mesh_attested_env_flags_foreign_axis(self, tmp_path):
        src = (
            "from jax import lax\n"
            "from glom_tpu.utils.config import MeshConfig\n"
            "from jax import shard_map\n"
            "DATA_AXIS = 'data'\n"
            "MODEL_AXIS = 'model'\n"
            "def build(make_mesh, P):\n"
            "    mesh = make_mesh(MeshConfig(data=8))\n"
            "    def body(x):\n"
            "        return lax.psum(x, MODEL_AXIS)\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P(DATA_AXIS),), out_specs=P())\n"
        )
        fs = by_checker(lint(tmp_path, src), "axis-environment")
        assert len(fs) == 1
        assert "'model'" in fs[0].message

    def test_opaque_param_caller_attestation_pair(self):
        """The seeded pair for the opaque-mesh blind spot
        (tests/fixtures/axis_env_param.py): the module ALSO builds a
        'model'-carrying training mesh, so the module-wide union would
        attest the wrong environment — the checker must follow the
        intra-module CALLER's MeshConfig(data, seq) instead and flag
        the psum over 'model' (direct site + threaded wrapper), plus
        the hop-forwarded leaky body whose MeshConfig is one more
        caller up. The clean twins and the caller-less opaque helper
        (module-union fallback) scan clean."""
        fs = by_checker(
            run([str(FIXTURES / "axis_env_param.py")]), "axis-environment"
        )
        assert len(fs) == 3, fs
        assert all("'model'" in f.message for f in fs)
        assert sum("_serve_shard_leaky" in f.symbol for f in fs) == 2
        assert sum("_hop_leaky" in f.symbol for f in fs) == 1

    def test_caller_attestation_beats_module_union(self, tmp_path):
        """A file that builds BOTH a (data, seq) serve mesh (passed to
        the opaque-param helper) and a model-carrying training mesh:
        the union alone would hide the bug."""
        src = (
            "from jax import lax\n"
            "from glom_tpu.utils.config import MeshConfig\n"
            "from jax import shard_map\n"
            "DATA_AXIS = 'data'\n"
            "MODEL_AXIS = 'model'\n"
            "def train_mesh(make_mesh):\n"
            "    return make_mesh(MeshConfig(data=2, model=2))\n"
            "def helper(mesh, P):\n"
            "    def body(x):\n"
            "        return lax.psum(x, MODEL_AXIS)\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P(DATA_AXIS),), out_specs=P())\n"
            "def build(make_mesh, P):\n"
            "    mesh = make_mesh(MeshConfig(data=8))\n"
            "    return helper(mesh, P)\n"
        )
        fs = by_checker(lint(tmp_path, src), "axis-environment")
        assert len(fs) == 1
        assert "'model'" in fs[0].message

    def test_one_unattested_caller_poisons_attestation(self, tmp_path):
        """Two callers, one of which binds the mesh param opaquely: the
        checker must not guess — it falls back to the module union
        (which carries 'model' here), so nothing flags."""
        src = (
            "from jax import lax\n"
            "from glom_tpu.utils.config import MeshConfig\n"
            "from jax import shard_map\n"
            "DATA_AXIS = 'data'\n"
            "MODEL_AXIS = 'model'\n"
            "def train_mesh(make_mesh):\n"
            "    return make_mesh(MeshConfig(data=2, model=2))\n"
            "def helper(mesh, P):\n"
            "    def body(x):\n"
            "        return lax.psum(x, MODEL_AXIS)\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P(DATA_AXIS),), out_specs=P())\n"
            "def build(make_mesh, P):\n"
            "    mesh = make_mesh(MeshConfig(data=8))\n"
            "    return helper(mesh, P)\n"
            "def build_opaque(mesh, P):\n"
            "    return helper(mesh, P)\n"
        )
        assert by_checker(lint(tmp_path, src), "axis-environment") == []

    def test_opaque_mesh_skips(self, tmp_path):
        """No MeshConfig anywhere (the training shard bodies' shape:
        mesh arrives from config) -> the environment is unattested and
        the checker never guesses."""
        src = (
            "from jax import lax\n"
            "from jax import shard_map\n"
            "DATA_AXIS = 'data'\n"
            "MODEL_AXIS = 'model'\n"
            "def build(mesh, P):\n"
            "    def body(x):\n"
            "        return lax.psum(x, MODEL_AXIS)\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P(DATA_AXIS),), out_specs=P())\n"
        )
        assert by_checker(lint(tmp_path, src), "axis-environment") == []

    def test_module_wide_meshconfig_attests(self, tmp_path):
        """A module that builds meshes SOMEWHERE attests its axis set
        even when a given site's mesh is a parameter — the serve-mesh
        shape (make_serve_mesh builds (data, seq); every shard_map in
        the file inherits that environment)."""
        src = (
            "from jax import lax\n"
            "from glom_tpu.utils.config import MeshConfig\n"
            "from jax import shard_map\n"
            "DATA_AXIS = 'data'\n"
            "SEQ_AXIS = 'seq'\n"
            "MODEL_AXIS = 'model'\n"
            "def make_my_mesh(make_mesh, scfg):\n"
            "    return make_mesh(MeshConfig(data=scfg.d, seq=scfg.s))\n"
            "def build(mesh, P):\n"
            "    def body(x):\n"
            "        y = lax.psum(x, SEQ_AXIS)\n"
            "        return lax.psum(y, MODEL_AXIS)\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P(DATA_AXIS),), out_specs=P())\n"
        )
        fs = by_checker(lint(tmp_path, src), "axis-environment")
        assert len(fs) == 1
        assert "'model'" in fs[0].message

    def test_spec_axes_union_into_env(self, tmp_path):
        """An axis visible only in the specs (via a local spec variable,
        one level of indirection) is part of the environment — spec axes
        never false-positive even when the MeshConfig kwargs are
        narrower than the specs."""
        src = (
            "from jax import lax\n"
            "from glom_tpu.utils.config import MeshConfig\n"
            "from jax import shard_map\n"
            "DATA_AXIS = 'data'\n"
            "SEQ_AXIS = 'seq'\n"
            "def build(make_mesh, P):\n"
            "    mesh = make_mesh(MeshConfig(data=4))\n"
            "    lv_spec = P(DATA_AXIS, SEQ_AXIS)\n"
            "    def body(x):\n"
            "        return lax.psum(x, SEQ_AXIS)\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(lv_spec,), out_specs=lv_spec)\n"
        )
        assert by_checker(lint(tmp_path, src), "axis-environment") == []

    def test_serve_mesh_paged_gather_is_clean(self):
        """The site the ISSUE names: parallel/serve_mesh.py's paged
        gather collectives (all_gather over 'data', witness psums over
        'seq'/'data') all live inside the (data, seq) environment."""
        import glom_tpu.parallel.serve_mesh as sm

        assert by_checker(run([sm.__file__]), "axis-environment") == []


class TestHandRolledCollectiveTiming:
    """ISSUE 13: a registered site that hand-rolls its own clock/callback
    harness around a wire-moving collective must route through the ONE
    shared timing wrapper (counters.timed_collective)."""

    def test_fixture_pair(self, tmp_path):
        """The seeded acceptance pair (tests/fixtures/collective_timing
        .py), linted under a registration-scope path: the leaky twin's
        psum is flagged hand-rolled-timing, the wrapper-routed twin is
        clean."""
        src = (FIXTURES / "collective_timing.py").read_text()
        fs = by_checker(
            lint(tmp_path, src, name="parallel/manual.py"),
            "collective-coverage",
        )
        timing = [f for f in fs if "hand-rolled" in f.message]
        assert len(timing) == 1
        src_lines = src.splitlines()
        assert "lax.psum(g, DATA_AXIS)" in src_lines[timing[0].line - 1]
        assert "leaky_timed_reduce" in timing[0].symbol
        assert "timed_collective" in timing[0].message
        # Neither twin trips the registration rule (record_collective and
        # timed_collective both register), and the clean twin trips
        # NOTHING.
        assert not any("not registered" in f.message for f in fs)
        assert not any("clean_timed_reduce" in (f.symbol or "")
                       for f in fs)

    def test_wrapper_lambda_counts_as_registered(self, tmp_path):
        """The wrapper takes the collective as a LAMBDA: the coverage
        rule must walk the enclosing-scope chain, not just the innermost
        scope, or every wrapper-routed site reads unregistered."""
        src = (
            "from jax import lax\n"
            "from glom_tpu.telemetry import counters as tele_counters\n"
            "DATA_AXIS = 'data'\n"
            "def grads(g):\n"
            "    return tele_counters.timed_collective(\n"
            "        's', DATA_AXIS, 'reduce', 8,\n"
            "        lambda x: lax.psum(x, DATA_AXIS), g,\n"
            "        collective='psum',\n"
            "    )\n"
        )
        assert (
            by_checker(
                lint(tmp_path, src, name="parallel/manual.py"),
                "collective-coverage",
            )
            == []
        )

    def test_timing_primitive_without_collective_is_fine(self, tmp_path):
        """A clock in a registration-scope module that never touches a
        collective (a host-side stats helper) is not this rule's
        business — trace-purity owns reachability from traced entries."""
        src = (
            "import time\n"
            "def stats():\n"
            "    return time.perf_counter()\n"
        )
        assert (
            by_checker(
                lint(tmp_path, src, name="parallel/manual.py"),
                "collective-coverage",
            )
            == []
        )

    def test_hand_rolled_clock_next_to_collective_flagged(self, tmp_path):
        src = (
            "import time\n"
            "from jax import lax\n"
            "from glom_tpu.telemetry import counters as tele_counters\n"
            "DATA_AXIS = 'data'\n"
            "def grads(g):\n"
            "    tele_counters.record_collective('reduce', 8)\n"
            "    t0 = time.perf_counter()\n"
            "    out = lax.psum(g, DATA_AXIS)\n"
            "    dt = time.perf_counter() - t0\n"
            "    return out, dt\n"
        )
        fs = by_checker(
            lint(tmp_path, src, name="parallel/manual.py"),
            "collective-coverage",
        )
        assert len(fs) == 1
        assert "hand-rolled" in fs[0].message and fs[0].line == 8


# ---------------------------------------------------------------------------
# whole-program pass: cross-module fixture pairs (ISSUE 20)
# ---------------------------------------------------------------------------


def run_pair(*names, select=None, scratch=None):
    """Lint a seeded cross-module fixture pair as one analyzed set."""
    return run(
        [str(FIXTURES / n) for n in names], select=select, scratch=scratch
    )


class TestCrossModulePairs:
    def test_purity_reaches_through_import(self):
        """tests/fixtures/xmod_purity.py: the jit entry lives in one
        module, the host print one import away — flagged AT the print's
        own file:line in the util module; the pure twin stays green."""
        fs = by_checker(
            run_pair("xmod_purity.py", "xmod_purity_util.py"),
            "trace-purity",
        )
        assert len(fs) == 1, fs
        assert fs[0].path.endswith("xmod_purity_util.py")
        assert fs[0].key == "host-print"
        src_lines = (
            (FIXTURES / "xmod_purity_util.py").read_text().splitlines()
        )
        assert "print(" in src_lines[fs[0].line - 1]

    def test_purity_pair_needs_both_files(self):
        """The same leaky module linted ALONE is silent — the evidence
        is unreachable without the companion, which is exactly the
        blind spot the project graph closes."""
        assert (
            by_checker(run_pair("xmod_purity_util.py"), "trace-purity")
            == []
        )

    def test_donation_handle_flows_through_typed_receiver(self):
        """tests/fixtures/xmod_donation.py: the donating handle lives on
        Engine in the companion module; the typed-receiver dispatches
        here must taint it — direct handle-attr load, provider-method
        return, and the *args splat (previously skipped silently)."""
        fs = by_checker(
            run_pair("xmod_donation.py", "xmod_donation_engine.py"),
            "donation-safety",
        )
        assert all(f.path.endswith("xmod_donation.py") for f in fs)
        by_key = sorted(f.key for f in fs)
        assert by_key == [
            "splat-at-donating-call",
            "use-after-donate-imgs",
            "use-after-donate-imgs",
        ], fs
        leaky = sorted(f.symbol for f in fs)
        assert leaky == ["provider_leaky", "serve_leaky", "splat_leaky"]

    def test_lock_order_cycle_across_classes_and_modules(self):
        """tests/fixtures/xmod_lock_order.py: each class is single-lock
        and locally consistent; the deadlock exists only in the global
        (class, lock) graph. Both halves of the cycle are flagged, each
        in its OWN module, and the recorded edges name both classes."""
        scratch = {}
        fs = by_checker(
            run_pair(
                "xmod_lock_order.py",
                "xmod_lock_order_pool.py",
                scratch=scratch,
            ),
            "lock-order",
        )
        assert len(fs) == 2, fs
        paths = sorted(f.path for f in fs)
        assert paths[0].endswith("xmod_lock_order.py")
        assert paths[1].endswith("xmod_lock_order_pool.py")
        edges = scratch["lock-order:edges"]
        assert ("Cache._lock", "Pool._lock") in edges
        assert ("Pool._lock", "Cache._lock") in edges
        # the clean twins contribute no edges
        assert not any("Quiet" in a or "Quiet" in b for a, b in edges)

    def test_mesh_flow_attested_through_import(self):
        """tests/fixtures/xmod_mesh_flow.py: the builder module owns no
        MeshConfig at all. The serve caller's (data, seq) ctor intent
        attests the leaky/clean sites through the import boundary; the
        annotated-MeshConfig train parameter attests the FULL axis
        tuple, so its 'model' psum is legal."""
        scratch = {}
        fs = by_checker(
            run_pair(
                "xmod_mesh_flow.py",
                "xmod_mesh_flow_runtime.py",
                scratch=scratch,
            ),
            "axis-environment",
        )
        assert len(fs) == 1, fs
        assert fs[0].path.endswith("xmod_mesh_flow.py")
        assert fs[0].key == "axis-env-model"
        assert fs[0].symbol.startswith("build_leaky")
        trail = {
            (row[0].rsplit("/", 1)[-1], row[2], row[3])
            for row in scratch["axis-environment:attested"]
        }
        assert ("xmod_mesh_flow.py", "flow", ("data", "seq")) in trail
        assert (
            "xmod_mesh_flow.py",
            "flow",
            ("data", "model", "seq"),
        ) in trail
        # single-module run: no caller evidence, every site skips
        solo = {}
        assert (
            by_checker(
                run_pair("xmod_mesh_flow.py", scratch=solo),
                "axis-environment",
            )
            == []
        )
        assert all(
            row[2] == "unattested"
            for row in solo["axis-environment:attested"]
        )

    def test_real_repo_project_evidence(self, monkeypatch):
        """Pins this PR's upgrades against the real tree: the attested
        cross-object lock edges include the serve cache->pool order, and
        the training shard_map sites in parallel/manual.py attest the
        full axis tuple through the runtime's MeshConfig — the sites
        that were skipped before the project graph existed."""
        monkeypatch.chdir(REPO)
        scratch = {}
        run(["glom_tpu"], scratch=scratch)
        edges = scratch["lock-order:edges"]
        assert ("ColumnCache._lock", "PagedColumnPool._lock") in edges
        path, line = edges[("ColumnCache._lock", "PagedColumnPool._lock")]
        assert path == "glom_tpu/serve/column_cache.py" and line > 0
        trail = scratch["axis-environment:attested"]
        manual = {
            row[1]: (row[2], row[3])
            for row in trail
            if row[0] == "glom_tpu/parallel/manual.py"
        }
        assert manual, trail
        assert all(
            how == "flow" and axes == ("data", "model", "seq")
            for how, axes in manual.values()
        ), manual


# ---------------------------------------------------------------------------
# analysis cache (--cache): fingerprint reuse + cross-module invalidation
# ---------------------------------------------------------------------------


class TestAnalysisCache:
    UTIL = "def helper(x):\n    print('x', x)\n    return x\n"
    APP = (
        "import jax\n"
        "from util import helper\n"
        "def step(x):\n"
        "    return helper(x)\n"
        "fast = jax.jit(step)\n"
    )
    LONE = "def f(x):\n    return x\n"

    def _tree(self, tmp_path):
        (tmp_path / "util.py").write_text(self.UTIL)
        (tmp_path / "app.py").write_text(self.APP)
        (tmp_path / "lone.py").write_text(self.LONE)
        return [str(tmp_path / n) for n in ("util.py", "app.py", "lone.py")]

    def _cached_run(self, tmp_path, paths):
        from glom_tpu.analysis.cache import AnalysisCache

        cache = AnalysisCache(str(tmp_path / "cache.json"))
        findings = run(paths, cache=cache)
        return cache, findings

    def test_warm_cache_replays_findings(self, tmp_path):
        paths = self._tree(tmp_path)
        cache, cold = self._cached_run(tmp_path, paths)
        assert cache.stats() == "cache: 0/3 files reused (cold)"
        # the cross-module purity finding is part of what gets stored
        assert [f.key for f in cold] == ["host-print"]
        cache, warm = self._cached_run(tmp_path, paths)
        assert cache.stats() == "cache: 3/3 files reused (warm)"
        assert [(f.fingerprint, f.line) for f in warm] == [
            (f.fingerprint, f.line) for f in cold
        ]

    def test_cross_module_invalidation_both_directions(self, tmp_path):
        """An import edge couples the PAIR: editing the callee must
        re-analyze its importers (their findings read its body), and
        editing the importer must re-analyze the callee (project-wide
        checkers place findings in the callee that the importer's entry
        points cause — the fixture's print is exactly that). The
        unrelated module stays reused either way."""
        paths = self._tree(tmp_path)
        self._cached_run(tmp_path, paths)
        (tmp_path / "util.py").write_text(self.UTIL.replace("'x'", "'y'"))
        cache, _ = self._cached_run(tmp_path, paths)
        assert cache.stats() == "cache: 1/3 files reused (mixed)"
        assert [Path(p).name for p in cache.reused_files] == ["lone.py"]
        self._cached_run(tmp_path, paths)  # re-warm
        (tmp_path / "app.py").write_text(
            self.APP + "def extra(y):\n    return y\n"
        )
        cache, findings = self._cached_run(tmp_path, paths)
        assert cache.stats() == "cache: 1/3 files reused (mixed)"
        assert [Path(p).name for p in cache.reused_files] == ["lone.py"]
        assert [f.key for f in findings] == ["host-print"]

    def test_corruption_falls_back_loudly(self, tmp_path, capsys):
        paths = self._tree(tmp_path)
        _, cold = self._cached_run(tmp_path, paths)
        (tmp_path / "cache.json").write_text("{ not json")
        cache, findings = self._cached_run(tmp_path, paths)
        err = capsys.readouterr().err
        assert "unreadable" in err and "FULL pass" in err
        assert cache.stats() == "cache: 0/3 files reused (cold)"
        assert [f.fingerprint for f in findings] == [
            f.fingerprint for f in cold
        ]
        # ... and the rewritten cache warms right back up
        cache, _ = self._cached_run(tmp_path, paths)
        assert cache.stats() == "cache: 3/3 files reused (warm)"

    def test_select_runs_never_cache(self, tmp_path):
        from glom_tpu.analysis.cache import AnalysisCache

        paths = self._tree(tmp_path)
        cache = AnalysisCache(str(tmp_path / "cache.json"))
        run(paths, cache=cache, select=["trace-purity"])
        assert "disabled" in cache.stats()
        assert not (tmp_path / "cache.json").exists()


# ---------------------------------------------------------------------------
# --prune-baseline
# ---------------------------------------------------------------------------


class TestPruneBaseline:
    def _seed(self, tmp_path):
        bad = tmp_path / "mod.py"
        bad.write_text(
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'bogus')\n"
            "def g(x):\n"
            "    return lax.pmean(x, 'bogus2')\n"
        )
        b = tmp_path / "baseline.json"
        assert main([str(bad), "--write-baseline", str(b)]) == 0
        data = json.loads(b.read_text())
        assert len(data["suppressions"]) == 2
        for entry in data["suppressions"].values():
            entry["reviewed"] = "seeded test suppression"
        b.write_text(json.dumps(data))
        # fix ONE of the two findings -> one stale entry
        bad.write_text(
            "from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'bogus')\n"
        )
        return bad, b

    def test_dry_run_default_reports_without_writing(
        self, tmp_path, capsys
    ):
        bad, b = self._seed(tmp_path)
        before = b.read_text()
        assert main([str(bad), "--baseline", str(b), "--prune-baseline"]) == 0
        out = capsys.readouterr().out
        assert "dry run" in out and "stale:" in out
        assert b.read_text() == before
        assert not Path(str(b) + ".removed.json").exists()

    def test_apply_rewrites_and_stamps_removal_list(self, tmp_path, capsys):
        bad, b = self._seed(tmp_path)
        assert (
            main(
                [
                    str(bad),
                    "--baseline",
                    str(b),
                    "--prune-baseline",
                    "--apply",
                ]
            )
            == 0
        )
        out = capsys.readouterr().out
        assert "pruned 1 entry" in out
        data = json.loads(b.read_text())
        assert len(data["suppressions"]) == 1
        removal = json.loads(Path(str(b) + ".removed.json").read_text())
        assert removal["pruned_at"] and removal["baseline"] == str(b)
        [(fp, entry)] = removal["removed"].items()
        assert "bogus2" in fp or "pmean" in entry["message"]
        assert entry["reviewed"] == "seeded test suppression"
        # the pruned baseline still gates the remaining finding green
        assert main([str(bad), "--baseline", str(b)]) == 0

    def test_nothing_stale_is_a_no_op(self, tmp_path, capsys):
        bad, b = self._seed(tmp_path)
        assert (
            main(
                [str(bad), "--baseline", str(b), "--prune-baseline", "--apply"]
            )
            == 0
        )
        capsys.readouterr()
        assert (
            main(
                [str(bad), "--baseline", str(b), "--prune-baseline", "--apply"]
            )
            == 0
        )
        assert "nothing to prune" in capsys.readouterr().out

    def test_partial_select_refuses_to_prune(self, tmp_path, capsys):
        bad, b = self._seed(tmp_path)
        assert (
            main(
                [
                    str(bad),
                    "--baseline",
                    str(b),
                    "--select",
                    "collective-coverage",
                    "--prune-baseline",
                ]
            )
            == 2
        )
        assert "full run" in capsys.readouterr().err
