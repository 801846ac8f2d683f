"""Pod-coordinated preemption: the save barrier, gang supervision, and
the cross-host plumbing they share (docs/RESILIENCE.md).

The SIGTERM grace-window checkpoint (tracing/flight.set_checkpoint_hook →
utils/checkpoint.preemption_save) saves SINGLE-HOST state. On a
multi-process pod — exactly the topology the ZeRO-sharded trainer exists
for — an uncoordinated grace save leaves hosts committed at different
steps, and the resume is silently inconsistent: each host restores its
own newest step and the gang trains from a state no single step ever
described. This module closes that gap with three pieces:

  * TWO-PHASE PREEMPTION SAVE BARRIER (PodCoordinator.preemption_barrier)
    — on SIGTERM every host proposes its highest dispatchable step (the
    step its live state can commit), the round commits the MIN over
    proposals, and every host then lands exactly that step inside the
    grace deadline: the host AT the min grace-saves its live state; a
    host already PAST it proves the step is still retained on disk. A
    host that misses the deadline — or whose save fails — aborts the
    round loudly (stamped "barrier" abort, no pod commit marker), so a
    partial pod checkpoint can never masquerade as complete. Every
    phase of every round is a stamped schema "barrier" event.

  * CROSS-HOST RESTORE RECONCILIATION — utils/checkpoint.CheckpointManager
    grows a pod mode (`pod_peers=[...]`): restore(None) walks this
    host's steps newest-first and only hands out a step whose per-host
    manifests are ALL valid; a half-committed step (torn, missing, or
    checksum-failed on any host) is quarantined on EVERY host — the
    multi-host twin of the PR 6 torn-step path — with the decision
    stamped (recovery action "quarantine-half-step").

  * GANG SUPERVISION (signal_gang_stop / gang_stop_requested /
    gang_barrier, wired through train/supervise.fit_supervised's `gang=`
    seam) — one host's crash signals a gang-wide stop; every member
    raises GangRestart at its next checkpoint-span boundary, the gang
    rendezvous at the restart barrier, and every member resumes from the
    reconciled common step.

TRANSPORT: rendezvous rides a SHARED DIRECTORY (DirectoryTransport — one
atomically-written JSON message file per host per phase), so the whole
layer runs in CPU tier-1 with plain subprocesses or threads; real pods
swap in JaxDistributedTransport (the jax.distributed key-value store)
behind the same three-method interface. Message posts carry a fault-hook
seam (resilience/faults.message_loss / barrier_delay) so barrier-message
loss and deadline overrun are injectable, deterministic, and stamped.

Step-drift contract: "highest dispatchable step" is the step a host's
live state can commit RIGHT NOW. In a real lockstep pod the collectives
bound drift to the one in-flight step; in the chaos harness (independent
subprocesses) drift is bounded by per-step checkpointing + retention —
a host past the committed min that no longer RETAINS that step cannot
satisfy the round and aborts it loudly (raise --checkpoint-keep).
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

from glom_tpu.telemetry import schema


# The persistent per-lifetime "this member finished every step" flag:
# gang-restart barriers excuse done hosts from arrival (a finished member
# never rendezvous again). A relaunched host's own stale flag is purged
# by DirectoryTransport's construction-time cleanup.
GANG_DONE_ROUND = "gang-done"


class BarrierAbort(RuntimeError):
    """A coordination round could not complete: deadline passed with
    hosts missing, a peer aborted, or this host's own save failed. The
    abort is stamped BEFORE this raises — a silent abort would be the
    exact partial-pod-checkpoint hazard the barrier exists to prevent."""

    def __init__(self, message: str, **detail):
        super().__init__(message)
        self.detail = detail


class GangRestart(RuntimeError):
    """Raised inside a gang member's training loop when a peer signaled a
    gang-wide stop: the supervisor treats it like any failure (restart +
    backoff), so the whole gang falls back to the restart barrier and
    resumes from the reconciled common step together."""


def _emit_barrier(writer, rec: dict) -> dict:
    """Stamp one "barrier" event and deliver writer-else-flight — the
    same routing as emit_fault/emit_recovery, for the new kind."""
    from glom_tpu.tracing.flight import write_or_observe

    stamped = schema.stamp(rec, kind="barrier")
    write_or_observe(writer, stamped)
    return stamped


class DirectoryTransport:
    """Rendezvous over a shared directory: one message = one atomically
    renamed JSON file `<root>/rounds/<round>/<phase>_<host>.json`.

    This is the CPU-tier-1 transport (subprocesses or threads on one
    filesystem) AND the degraded-mode transport for real pods whose
    checkpoint storage is already shared. Posts are atomic (temp + fsync
    + rename, via utils.checkpoint.atomic_write_json) so a reader never
    sees a torn message; reads are lock-free directory scans. The
    `fault_hook` seam is how the chaos harness injects barrier-message
    loss (hook returns True → the message is silently dropped) and
    deadline overrun (hook stalls before the write)."""

    def __init__(
        self,
        root,
        host: int,
        n_hosts: int,
        *,
        fault_hook: Optional[Callable[[dict], bool]] = None,
    ):
        if n_hosts < 1:
            raise ValueError(f"n_hosts {n_hosts} must be >= 1")
        if not 0 <= host < n_hosts:
            raise ValueError(f"host {host} outside 0..{n_hosts - 1}")
        self.root = Path(root)
        self.host = host
        self.n_hosts = n_hosts
        self.fault_hook = fault_hook
        (self.root / "rounds").mkdir(parents=True, exist_ok=True)
        # Round ids are derived from the RESUME step — the one value
        # hosts agree on without communicating — so a relaunch after an
        # aborted (or zero-progress) round reuses the id. A fresh
        # process must therefore never own stale messages: a leftover
        # abort would poison every future round with this id, and a
        # leftover propose/saved could complete one without us. Each
        # host deletes ITS OWN messages at construction (= process
        # start, before any round); peers' files are theirs to clean.
        # Durable pod_commit markers live at the root, not under
        # rounds/, and are deliberately kept.
        for stale in (self.root / "rounds").glob(f"*/*_{host}.json"):
            try:
                stale.unlink()
            except OSError:
                pass

    def _round_dir(self, round_id: str) -> Path:
        return self.root / "rounds" / round_id

    def post(self, round_id: str, phase: str, payload: dict) -> bool:
        """Post this host's message for (round, phase); returns False when
        the fault hook dropped it (simulated message loss — the poster,
        like a real sender over a lossy link, is not told)."""
        if self.fault_hook is not None and self.fault_hook(
            {"op": "post", "round": round_id, "phase": phase, "host": self.host}
        ):
            return False
        from glom_tpu.utils.checkpoint import atomic_write_json

        rdir = self._round_dir(round_id)
        rdir.mkdir(parents=True, exist_ok=True)
        atomic_write_json(
            rdir / f"{phase}_{self.host}.json",
            {"host": self.host, **payload},
        )
        return True

    def read_all(self, round_id: str, phase: str) -> Dict[int, dict]:
        """{host: payload} for every message posted so far — a partially
        torn directory scan never raises (a message mid-rename simply
        isn't there yet)."""
        out: Dict[int, dict] = {}
        rdir = self._round_dir(round_id)
        if not rdir.is_dir():
            return out
        for p in rdir.glob(f"{phase}_*.json"):
            try:
                host = int(p.stem.rsplit("_", 1)[1])
                with open(p) as fh:
                    out[host] = json.load(fh)
            except (ValueError, OSError, json.JSONDecodeError):
                continue
        return out


class JaxDistributedTransport:
    """The same three-method interface over jax.distributed's key-value
    store — the transport for REAL pods (no shared filesystem needed:
    the TPU coordinator service carries the messages). Construction
    requires jax.distributed.initialize() to have run; the CPU tier-1
    suite never touches this class (DirectoryTransport covers the
    protocol), and the first multi-process chip run is where it earns
    its keep."""

    def __init__(self, *, timeout_ms: int = 60_000):
        import jax

        state = getattr(
            getattr(jax, "_src", None), "distributed", None
        )
        client = getattr(getattr(state, "global_state", None), "client", None)
        if client is None:  # pragma: no cover — real-pod only
            raise RuntimeError(
                "JaxDistributedTransport requires jax.distributed."
                "initialize() (the multi-process pod runtime); use "
                "DirectoryTransport for single-machine rendezvous"
            )
        self._client = client
        self._timeout_ms = timeout_ms
        self.host = jax.process_index()
        self.n_hosts = jax.process_count()
        self.fault_hook = None

    def post(self, round_id: str, phase: str, payload: dict) -> bool:  # pragma: no cover
        self._client.key_value_set(
            f"glom/{round_id}/{phase}_{self.host}",
            json.dumps({"host": self.host, **payload}),
        )
        return True

    def read_all(self, round_id: str, phase: str) -> Dict[int, dict]:  # pragma: no cover
        out: Dict[int, dict] = {}
        for h in range(self.n_hosts):
            try:
                raw = self._client.key_value_try_get(
                    f"glom/{round_id}/{phase}_{h}"
                )
            except Exception:  # noqa: BLE001 — absent key
                continue
            try:
                out[h] = json.loads(raw)
            except (TypeError, json.JSONDecodeError):
                continue
        return out


class PodCoordinator:
    """Host-side coordination over a transport: the preemption save
    barrier plus the gang-stop/rendezvous primitives fit_supervised's
    gang mode rides. Every decision is a stamped schema event ("barrier"
    for round phases, "recovery" for gang stops), delivered
    writer-else-flight so a dying process still leaves the round's story
    in its flight dump."""

    def __init__(
        self,
        transport,
        *,
        writer=None,
        poll_s: float = 0.05,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
    ):
        if poll_s <= 0:
            raise ValueError(f"poll_s {poll_s} must be > 0")
        self.transport = transport
        self.host = transport.host
        self.n_hosts = transport.n_hosts
        self.writer = writer
        self.poll_s = poll_s
        self._clock = clock
        self._sleep = sleep

    # -- stamping ----------------------------------------------------------

    def _emit(self, phase: str, round_id: str, **detail) -> dict:
        return _emit_barrier(
            self.writer,
            {
                "phase": phase,
                "round": round_id,
                "host": self.host,
                "n_hosts": self.n_hosts,
                "wall_time_s": round(time.time(), 3),
                **detail,
            },
        )

    # -- barrier plumbing --------------------------------------------------

    def _abort(self, round_id: str, reason: str, **detail) -> BarrierAbort:
        """Post + stamp the abort, return the exception for the caller to
        raise. The post is best-effort (the transport may be the thing
        that failed); the stamp always lands locally."""
        try:
            self.transport.post(round_id, "abort", {"reason": reason, **detail})
        except Exception:  # noqa: BLE001 — the stamp still records it
            pass
        self._emit("abort", round_id, reason=reason, **detail)
        return BarrierAbort(
            f"barrier round {round_id} aborted on host {self.host}: {reason}",
            round=round_id, reason=reason, **detail,
        )

    def _wait_all(
        self,
        round_id: str,
        phase: str,
        deadline: float,
        *,
        honor_done: bool = False,
    ) -> Dict[int, dict]:
        """Block until all n_hosts posted (round, phase); raise
        BarrierAbort on a peer abort or on the deadline — stamping which
        hosts were missing, because 'who never answered' is the first
        postmortem question. With honor_done (the gang-restart barriers),
        a host that posted the persistent gang-done flag counts as
        arrived: a member that already finished every step will never
        rendezvous again, and waiting for it would deadlock the
        survivors' recovery."""
        while True:
            # Aborts are read FIRST: a host that limped in late must not
            # declare a round complete that a peer already aborted (the
            # pod commit marker — written only after host 0's own full
            # wait — stays the one completeness authority either way).
            aborts = self.transport.read_all(round_id, "abort")
            peer_aborts = {h: a for h, a in aborts.items() if h != self.host}
            msgs = self.transport.read_all(round_id, phase)
            required = set(range(self.n_hosts))
            if honor_done:
                required -= set(
                    self.transport.read_all(GANG_DONE_ROUND, "done")
                )
                required.add(self.host)  # our own arrival is never excused
            if not peer_aborts and required <= set(msgs):
                return msgs
            if peer_aborts:
                h, a = sorted(peer_aborts.items())[0]
                raise self._abort(
                    round_id,
                    f"peer host {h} aborted: {a.get('reason', '?')}",
                    peer=h, waiting_for=phase,
                )
            if self._clock() >= deadline:
                missing = sorted(required - set(msgs))
                raise self._abort(
                    round_id,
                    f"deadline passed waiting for {phase}",
                    waiting_for=phase, missing=missing,
                )
            self._sleep(self.poll_s)

    # -- the two-phase preemption save barrier -----------------------------

    def preemption_barrier(
        self,
        round_id: str,
        proposal_step: int,
        save_fn: Callable[[int], Any],
        *,
        deadline_s: float = 30.0,
    ) -> int:
        """Run one coordinated grace-save round; returns the committed
        common step. Phase 1: propose `proposal_step` (this host's
        highest dispatchable step) and wait for every host's proposal;
        the round commits the MIN. Phase 2: `save_fn(commit)` must land
        exactly that step on this host (save now, or prove it is still
        retained), then every host acks and — on full acknowledgment —
        host 0 writes the pod commit marker `pod_commit_<step>.json`.
        Any miss (deadline, peer abort, failed save) raises BarrierAbort
        with the abort already stamped and NO commit marker written."""
        deadline = self._clock() + deadline_s
        proposal_step = int(proposal_step)
        self.transport.post(round_id, "propose", {"step": proposal_step})
        self._emit(
            "propose", round_id, step=proposal_step, deadline_s=deadline_s
        )
        proposals = self._wait_all(round_id, "propose", deadline)
        commit = min(int(p["step"]) for p in proposals.values())
        self._emit(
            "commit", round_id, step=commit,
            proposals={str(h): int(p["step"]) for h, p in sorted(proposals.items())},
        )
        try:
            note = save_fn(commit)
        except BaseException as e:  # noqa: BLE001 — aborts the round loudly
            raise self._abort(
                round_id,
                f"save of committed step {commit} failed: "
                f"{type(e).__name__}: {e}"[:300],
                step=commit,
            ) from e
        self.transport.post(round_id, "saved", {"step": commit})
        self._emit("saved", round_id, step=commit, note=str(note or "saved"))
        self._wait_all(round_id, "saved", deadline)
        if self.host == 0:
            marker = {
                "step": commit,
                "round": round_id,
                "n_hosts": self.n_hosts,
                "proposals": {
                    str(h): int(p["step"])
                    for h, p in sorted(proposals.items())
                },
                "wall_time_s": round(time.time(), 3),
            }
            root = getattr(self.transport, "root", None)
            if root is not None:
                from glom_tpu.utils.checkpoint import atomic_write_json

                atomic_write_json(
                    Path(root) / f"pod_commit_{commit}.json", marker
                )
            else:
                # Rootless transports (the jax.distributed KV store)
                # carry the marker as a round message instead; peers
                # read it with read_all(round, "pod-commit").
                self.transport.post(round_id, "pod-commit", marker)
        self._emit("complete", round_id, step=commit)
        return commit

    # -- gang supervision --------------------------------------------------

    def _gang_round(self, epoch: int) -> str:
        return f"gang-e{int(epoch)}"

    def signal_gang_stop(self, epoch: int, reason: str) -> None:
        """One host's failure becomes the gang's restart: post the stop
        flag for this epoch (peers poll it between checkpoint spans) and
        stamp the decision as a recovery event."""
        from glom_tpu.resilience.faults import emit_recovery

        self.transport.post(
            self._gang_round(epoch), "stop", {"reason": str(reason)[:300]}
        )
        emit_recovery(
            self.writer,
            {
                "action": "gang-stop",
                "epoch": int(epoch),
                "host": self.host,
                "reason": str(reason)[:300],
            },
        )

    def gang_stop_requested(self, epoch: int) -> bool:
        return bool(self.transport.read_all(self._gang_round(epoch), "stop"))

    def signal_gang_done(self, steps: int) -> None:
        """This member finished every step and is leaving the gang:
        post the persistent done flag so restart barriers stop waiting
        for a host that will never rendezvous again."""
        self.transport.post(GANG_DONE_ROUND, "done", {"steps": int(steps)})
        self._emit("done", GANG_DONE_ROUND, steps=int(steps))

    def gang_barrier(
        self, name: str, epoch: int, *, deadline_s: float = 30.0
    ) -> None:
        """Rendezvous: every gang member posts arrival for (name, epoch)
        and blocks until all arrived — messages persist, so a late member
        (deeper backoff) sails through an already-full barrier, and a
        member that posted gang-done (finished all its steps) is excused.
        A member that never arrives inside the deadline aborts the round
        loudly (the supervisor's restart budget then decides what
        happens)."""
        round_id = f"{name}-e{int(epoch)}"
        deadline = self._clock() + deadline_s
        self.transport.post(round_id, "arrive", {})
        self._emit("arrive", round_id, epoch=int(epoch))
        self._wait_all(round_id, "arrive", deadline, honor_done=True)
        self._emit("complete", round_id, epoch=int(epoch))


# -- pod helpers -------------------------------------------------------------


def peer_host_dirs(checkpoint_dir, host: int, n_hosts: int) -> List[str]:
    """Sibling host checkpoint dirs under the pod layout convention
    `<root>/host_<k>`: the one naming contract the CLI, the chaos driver,
    and restore reconciliation all share. Loud on a mismatch — a pod run
    whose dirs don't follow the convention would silently reconcile
    against nothing."""
    checkpoint_dir = Path(checkpoint_dir)
    if checkpoint_dir.name != f"host_{host}":
        raise ValueError(
            f"pod checkpoint dir {checkpoint_dir} must be named "
            f"host_{host} (the <root>/host_<k> pod layout, "
            "docs/RESILIENCE.md)"
        )
    return [
        str(checkpoint_dir.parent / f"host_{k}")
        for k in range(n_hosts)
        if k != host
    ]


def read_pod_commit(coord_root) -> Optional[dict]:
    """Newest pod commit marker under the coordination root (None when no
    round ever completed) — the chaos driver's one-file answer to 'did
    the gang commit a common step, and which'."""
    markers = []
    for p in Path(coord_root).glob("pod_commit_*.json"):
        try:
            with open(p) as fh:
                markers.append(json.load(fh))
        except (OSError, json.JSONDecodeError):
            continue
    if not markers:
        return None
    return max(markers, key=lambda m: m.get("step", -1))


def pod_preemption_save(
    coordinator: PodCoordinator,
    checkpoint_dir,
    state: Any,
    step: int,
    *,
    deadline_s: float = 30.0,
    round_id: str = "preempt-g0",
    metrics_writer=None,
) -> dict:
    """THE pod-mode SIGTERM checkpoint hook body (train/cli.py plugs this
    into tracing/flight.set_checkpoint_hook instead of the single-host
    preemption_save): propose this host's current step, let the barrier
    commit the gang min, and land exactly that step — by grace-saving the
    live state when this host IS the min, or by verifying the committed
    step is still retained when this host ran past it (per-step
    checkpointing + retention bound that window; a miss aborts the round
    loudly). Returns the dict the flight recorder merges into the
    stamped "preemption-checkpoint" recovery record."""
    step = int(step)

    def save_fn(commit: int) -> str:
        if commit >= step:
            # This host IS the min (commit == step by construction: the
            # min can never exceed our own proposal): grace-save the live
            # state through the throwaway sync manager.
            from glom_tpu.utils.checkpoint import preemption_save

            preemption_save(
                checkpoint_dir, state, commit, metrics_writer=metrics_writer
            )
            return "grace-saved"
        # Past the committed step: the round is satisfiable only if the
        # committed step is on disk and verifies. "On disk" is a MOVING
        # target at SIGTERM time — the loop's ASYNC save of that very
        # step may still be in flight, and its commit thread is NOT
        # paused by the signal handler (only the main thread is), so the
        # step can land while we watch. Poll for a bounded slice of the
        # grace budget before declaring the round unsatisfiable.
        from glom_tpu.utils.checkpoint import step_valid_in_dir

        wait_until = time.monotonic() + max(1.0, deadline_s * 0.25)
        while not step_valid_in_dir(checkpoint_dir, commit):
            if time.monotonic() >= wait_until:
                raise RuntimeError(
                    f"host {coordinator.host} is at step {step}, past the "
                    f"committed step {commit}, and does not retain it — "
                    "the pod round cannot complete (raise "
                    "--checkpoint-keep or lower --checkpoint-every)"
                )
            time.sleep(0.1)
        return "already-committed"

    commit = coordinator.preemption_barrier(
        round_id, step, save_fn, deadline_s=deadline_s
    )
    return {
        "step": commit,
        "pod": True,
        "round": round_id,
        "n_hosts": coordinator.n_hosts,
        "proposed_step": step,
    }
