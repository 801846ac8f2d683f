"""collective-coverage: every manual-path collective is axis-sound and
wire-accounted.

Two rules, both static mirrors of runtime invariants PR 1-2 established:

  1. AXIS NAMES (all scanned files): the axis argument of every
     psum / psum_scatter / pmean / all_gather / ppermute / all_to_all /
     axis_index call must resolve to a declared mesh axis — a module-level
     `*_AXIS` string constant, a literal in the mesh vocabulary
     (MeshConfig.axis_names), or an `axis`-named parameter threaded in by
     the caller (the ring/halo/ulysses bodies). A typo'd axis name fails
     at runtime only when that exact mesh shape is exercised — EQuARX and
     the Automatic Cross-Replica Sharding work both show manual collective
     schedules are where silent mismatches creep in, so the lint catches
     it on CPU.

  2. REGISTRATION (wire-accounted modules only — parallel/manual.py and
     parallel/serve_mesh.py): every wire-moving collective
     (psum/psum_scatter/pmean/all_gather) call site must sit in a function
     that also calls telemetry.counters.record_collective — the static
     mirror of the runtime comm_model_drift reconciliation, which only
     catches an unregistered site when a live mesh traces the step.
     Scalar loss/metric collectives that are deliberately outside the
     wire model carry reviewed suppressions (see analysis_baseline.json).
"""

from __future__ import annotations

import ast
from typing import List, Optional

from glom_tpu.analysis.astutil import (
    call_name,
    enclosing_function,
    imported_collective_aliases,
    qualname_at,
)
from glom_tpu.analysis.core import Checker, Context, Finding, SourceModule

# collective -> positional index of the axis-name argument
AXIS_ARG = {
    "psum": 1,
    "pmean": 1,
    "pmax": 1,
    "pmin": 1,
    "psum_scatter": 1,
    "all_gather": 1,
    "all_to_all": 1,
    "ppermute": 1,
    "axis_index": 0,
}
# the wire-moving subset that must be record_collective-registered in the
# wire-accounted modules
WIRE_MOVING = {"psum", "psum_scatter", "pmean", "all_gather", "all_to_all"}
# Functions that count as registering the enclosing site with the measured
# counters: record_collective (byte-only, the PR 2 form) or the shared
# timing wrapper timed_collective (bytes + site registry + optional
# io_callback brackets — the capacity observatory's sanctioned route).
_REGISTERING = {"record_collective", "timed_collective"}
# Host clocks and callback primitives that mark a HAND-ROLLED timing
# harness when they share a function chain with a wire-moving collective:
# the trace-purity checker already bans bare host clocks in traced code,
# and the per-collective wall-time contract requires every timed site to
# route through counters.timed_collective (one wrapper = one clock
# discipline, one record shape, one lint surface).
_TIMING_PRIMITIVES = {
    "perf_counter", "monotonic", "perf_counter_ns", "monotonic_ns",
    "io_callback", "pure_callback", "callback",
}


def _collective_of(call: ast.Call, aliases: dict) -> Optional[str]:
    name = call_name(call)
    if name is None:
        return None
    parts = name.split(".")
    leaf = parts[-1]
    if leaf not in AXIS_ARG:
        return None
    if len(parts) == 1:
        # bare call: only a collective if imported from jax.lax
        return leaf if aliases.get(leaf) == leaf else None
    base = parts[-2]
    if base == "lax" or aliases.get(parts[0]) == "<laxmod>":
        return leaf
    return None


class CollectiveCoverage(Checker):
    name = "collective-coverage"
    description = (
        "manual-path collectives use declared mesh axes and are "
        "registered with telemetry.counters"
    )

    def check(self, module: SourceModule, ctx: Context) -> List[Finding]:
        aliases = imported_collective_aliases(module.tree)
        findings: List[Finding] = []
        registered_scope = any(
            module.relpath.endswith(suffix)
            for suffix in ctx.registration_modules
        )
        # Pre-collect: per function node, does it call a registering
        # function (record_collective / timed_collective), a timing
        # primitive, or the shared wrapper specifically? The wrapper takes
        # the collective as a LAMBDA, so membership checks walk the whole
        # enclosing-scope CHAIN (lambda -> function -> ...), not just the
        # innermost scope.
        records_in: set = set()
        timing_in: set = set()
        wrapper_in: set = set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                name = call_name(node)
                leaf = name.split(".")[-1] if name else None
                if leaf in _REGISTERING:
                    fn = enclosing_function(module.parents, node)
                    records_in.add(id(fn))
                    if leaf == "timed_collective":
                        wrapper_in.add(id(fn))
                if leaf in _TIMING_PRIMITIVES:
                    fn = enclosing_function(module.parents, node)
                    timing_in.add(id(fn))

        def scope_chain(node):
            """Every enclosing function/lambda of `node`, innermost
            first (module level terminates the chain)."""
            fn = enclosing_function(module.parents, node)
            while fn is not None:
                yield fn
                fn = enclosing_function(module.parents, fn)

        # Module-level string constants (for axis-arg resolution).
        consts = {}
        for node in module.tree.body:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if (
                        isinstance(t, ast.Name)
                        and isinstance(node.value, ast.Constant)
                        and isinstance(node.value.value, str)
                    ):
                        consts[t.id] = node.value.value

        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            coll = _collective_of(node, aliases)
            if coll is None:
                continue
            symbol = qualname_at(module.parents, module.index, node)
            findings.extend(
                self._check_axis(module, ctx, node, coll, consts, symbol)
            )
            if registered_scope and coll in WIRE_MOVING:
                chain = list(scope_chain(node))
                chain_ids = {id(fn) for fn in chain}
                if not chain_ids & records_in:
                    findings.append(
                        Finding(
                            checker=self.name,
                            path=module.relpath,
                            line=node.lineno,
                            col=node.col_offset,
                            message=(
                                f"lax.{coll} site is not registered with "
                                "telemetry.counters.record_collective — "
                                "the measured wire bytes (and "
                                "comm_model_drift) silently omit it"
                            ),
                            symbol=symbol,
                            key=f"unregistered-{coll}",
                        )
                    )
                if chain_ids & timing_in and not chain_ids & wrapper_in:
                    # A registered site that hand-rolls its own clock or
                    # callback harness around the collective: the
                    # per-collective wall-time contract requires the ONE
                    # shared wrapper (counters.timed_collective), so
                    # every timed site shares a clock discipline, record
                    # shape, and purity audit — and the trace-purity
                    # checker's host-clock ban stays meaningful.
                    findings.append(
                        Finding(
                            checker=self.name,
                            path=module.relpath,
                            line=node.lineno,
                            col=node.col_offset,
                            message=(
                                f"lax.{coll} site is timed with a "
                                "hand-rolled clock/callback harness — "
                                "route the timing through "
                                "counters.timed_collective (the shared "
                                "timing wrapper; docs/OBSERVABILITY.md, "
                                "Capacity observatory)"
                            ),
                            symbol=symbol,
                            key=f"hand-rolled-timing-{coll}",
                        )
                    )
        return findings

    # -- axis resolution ----------------------------------------------------

    def _axis_node(self, call: ast.Call, coll: str) -> Optional[ast.AST]:
        for kw in call.keywords:
            if kw.arg == "axis_name":
                return kw.value
        idx = AXIS_ARG[coll]
        if len(call.args) > idx:
            return call.args[idx]
        return None

    def _axis_ok(
        self,
        node: ast.AST,
        ctx: Context,
        consts: dict,
        call: ast.Call,
        module: SourceModule,
    ) -> Optional[str]:
        """None when the axis resolves to a declared name; else a short
        reason string for the finding."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if node.value in ctx.axis_vocab:
                return None
            return (
                f"axis {node.value!r} is not a declared mesh axis "
                f"{sorted(ctx.axis_vocab)}"
            )
        if isinstance(node, (ast.Tuple, ast.List)):
            for elt in node.elts:
                reason = self._axis_ok(elt, ctx, consts, call, module)
                if reason:
                    return reason
            return None
        if isinstance(node, ast.Name):
            if node.id in consts:
                if consts[node.id] in ctx.axis_vocab:
                    return None
                return (
                    f"axis constant {node.id}={consts[node.id]!r} is not a "
                    f"declared mesh axis {sorted(ctx.axis_vocab)}"
                )
            # An axis threaded in by the caller: accept parameters whose
            # name says so (axis_name=SEQ_AXIS at the call sites is what
            # the vocabulary rule already checked).
            fn = enclosing_function(module.parents, node)
            while fn is not None:
                info = module.index.info_for(fn)
                if info is not None and node.id in info.params:
                    if "axis" in node.id:
                        return None
                    return (
                        f"axis comes from parameter {node.id!r} — rename it "
                        "to carry 'axis' so call sites are checkable, or "
                        "pass a declared axis constant"
                    )
                fn = enclosing_function(module.parents, fn)
            return f"axis name {node.id!r} is not statically resolvable"
        return "axis argument is not statically resolvable"

    def _check_axis(
        self,
        module: SourceModule,
        ctx: Context,
        call: ast.Call,
        coll: str,
        consts: dict,
        symbol: str,
    ) -> List[Finding]:
        axis = self._axis_node(call, coll)
        if axis is None:
            reason = f"lax.{coll} call has no axis argument"
        else:
            reason = self._axis_ok(axis, ctx, consts, call, module)
        if reason is None:
            return []
        return [
            Finding(
                checker=self.name,
                path=module.relpath,
                line=call.lineno,
                col=call.col_offset,
                message=f"lax.{coll}: {reason}",
                symbol=symbol,
                key=f"axis-{coll}",
            )
        ]
