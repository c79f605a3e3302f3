"""Seeded chaos soak: a real multi-host local job under a fault schedule.

Stands up the full managed-mode stack in one process — Store, controller,
N HostAgents launching real OS processes over loopback gloo — submits a
checkpointing LM training job, arms a :class:`ChaosInjector`, and watches
the recovery invariants the whole subsystem exists to guarantee:

1. **Completion** — the job reaches Succeeded despite every scheduled
   fault.
2. **Gang atomicity** — no *persistent* partial gang: at no point does a
   strict, nonempty subset of the gang exist for longer than the grace
   window (transient partials during sequential create/delete are
   physics; a partial gang that sticks is the bug the atomic scheduler
   forecloses).
3. **Warm restarts** — every post-fault incarnation carries a
   ``TPUJOB_RESUME_STEP`` > 0 (it resumes, not retrains), and the declared
   resume steps never decrease across incarnations.
4. **Backoff exemption** — preemption restarts increment
   ``preemption_count``, never ``restart_count``, so they cannot exhaust
   ``backoff_limit``.
5. **Reproducibility** — the applied fault sequence matches the schedule,
   and the schedule is a pure function of the seed.
6. **Bounded recovery downtime, from the trace** — every preemption
   restart span in the job's timeline (obs/: opened when the controller
   tears the gang down, closed when the recreated gang reports RUNNING)
   is closed, and its width — the measured gang downtime — stays under
   ``downtime_bound_s``. Previously recovery latency could only be
   inferred indirectly; now it is read off the same trace ``tpujob
   trace`` exports.
7. **Zero duplicate gang-member creates** — distinct incarnations
   (uids) per gang name never exceed 1 + restart_count +
   preemption_count: no sync — least of all a RESTARTED controller's
   first — ever re-created a child it should have re-adopted.
8. **Control-plane crash recovery** (``--operator-crash``) — the rig
   becomes the real multi-process topology: a RestartableOperator
   (durable store via runtime/persist.py + controller + HTTP API) with
   agents and the injector on RemoteStore. A scheduled OPERATOR_CRASH
   kills and recovers the whole control plane mid-run; the job must
   still satisfy every invariant above, and the outage must be VISIBLE
   as a ``controller-restart`` span in the job's trace.
9. **Peer warm restore** (``--p2p``) — agents run host-lifetime shard
   depots (rendezvous/statechannel.py); at least one post-fault
   incarnation must restore from a PEER (its restore span carries
   ``source=peer``), proving the depot survived the gang teardown and
   the controller's ``TPUJOB_RESTORE_PEERS`` hint reached the workload.
   Recovery downtime is additionally measured as EFFECTIVE downtime —
   restart-span start to the matching restore span's end — because the
   restart span closes at gang RUNNING, before the workload's restore
   (and its modeled slow-store read, ``--disk-restore-delay``) runs.

``--compare-restore`` runs the SAME seed twice — disk-only baseline,
then p2p — and asserts the p2p effective-downtime p50 cuts the disk
baseline by more than 2x (the acceptance receipt; JSON artifact under
``--workdir``).

Runnable standalone (the CI ``chaos-soak`` / ``crash-soak`` /
``ckpt-soak`` stages)::

    python -m tf_operator_tpu.chaos.soak --seed 7 --steps 8
    python -m tf_operator_tpu.chaos.soak --seed 11 --steps 8 --operator-crash
    python -m tf_operator_tpu.chaos.soak --seed 13 --steps 6 --p2p \\
        --disk-restore-delay 8 --compare-restore

Exits nonzero when any invariant is violated.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from tf_operator_tpu.api.types import (
    KIND_PROCESS,
    ConditionType,
    ObjectMeta,
    ProcessTemplate,
    ReplicaSpec,
    ReplicaType,
    TopologySpec,
    TPUJob,
    TPUJobSpec,
)
from tf_operator_tpu.chaos.faults import FaultKind, FaultSchedule
from tf_operator_tpu.chaos.injector import ChaosInjector
from tf_operator_tpu.controller import TPUJobController
from tf_operator_tpu.controller.status import has_condition, is_finished
from tf_operator_tpu.obs.export import derive_timings
from tf_operator_tpu.obs.spans import job_trace
from tf_operator_tpu.rendezvous.env import ENV_RESUME_STEP
from tf_operator_tpu.runtime import (
    FakeProcessControl,
    HostAgent,
    LocalProcessControl,
    RemoteStore,
    Store,
)
from tf_operator_tpu.runtime.store import (
    NotFoundError,
    TransientStoreError,
    WatchEventType,
)

log = logging.getLogger("tpujob.soak")

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Data-plane env for launched gang members: CPU jax with loopback gloo
# collectives (mirrors the e2e tests).
DATAPLANE_ENV = {
    "JAX_PLATFORMS": "cpu",
    "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
    "XLA_FLAGS": "",
}


def default_schedule(seed: int, operator_crash: bool = False) -> FaultSchedule:
    """The acceptance recipe: one mid-run crash (after the first
    checkpoint exists, so recovery is warm) then one preemption notice
    delivered to the post-restart gang. With ``operator_crash``, the
    control plane itself is killed+recovered between the two — so the
    preemption drain is executed by the RESTARTED controller over
    re-adopted state. Pure function of the seed."""
    return FaultSchedule.generate(
        seed, crashes=1, preemptions=1,
        operator_crashes=1 if operator_crash else 0,
        first_step=2, spread_s=0.0,
    )


class RestartableOperator:
    """The OPERATOR_CRASH target: a full in-process operator — durable
    store (``runtime/persist.py`` WAL + snapshots under ``data_dir``),
    reconciling controller, and the HTTP API server agents connect to —
    that can be killed and brought back on the SAME port mid-soak.

    ``restart()`` is the crash: the API server dies first (agents'
    RemoteStore calls start failing and their watches drop), then the
    controller threads, and the store object is simply dropped — nothing
    is flushed or handed over beyond what the WAL already captured per
    mutation, which is exactly the SIGKILL contract. The new incarnation
    recovers from disk, re-runs informers, and executes the controller's
    re-adoption pass (record_recovery)."""

    def __init__(
        self,
        data_dir: str,
        heartbeat_ttl: float,
        resync_period: float = 0.5,
        snapshot_every: int = 50,
        ledger_dir: Optional[str] = None,
    ) -> None:
        self.data_dir = data_dir
        self.heartbeat_ttl = heartbeat_ttl
        self.resync_period = resync_period
        self.snapshot_every = snapshot_every
        self.ledger_dir = ledger_dir
        self.port = 0  # first start picks an ephemeral port, then pins it
        self.restarts = 0
        # One FakeProcessControl per incarnation: in managed mode every
        # gang member is host-bound, so ANY create through a controller's
        # own backend — any incarnation's — is a leak the soak reports.
        self.fakes: List[FakeProcessControl] = []
        self.store: Optional[Store] = None
        self.controller = None
        self.dashboard = None
        self.ledger = None

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def start(self) -> None:
        from tf_operator_tpu.dashboard import DashboardServer
        from tf_operator_tpu.runtime.persist import open_store

        store, info = open_store(
            self.data_dir, snapshot_every=self.snapshot_every
        )
        fake = FakeProcessControl()
        ctl = TPUJobController(store, fake, resync_period=self.resync_period)
        ctl.scheduler.heartbeat_ttl = self.heartbeat_ttl
        ledger = None
        if self.ledger_dir is not None:
            from tf_operator_tpu.obs.ledger import FleetLedger

            # Re-opened every incarnation: recovery is rollup + segment
            # replay, and attach_ledger's sweep folds any terminal the
            # dead incarnation observed but never folded.
            ledger = FleetLedger(self.ledger_dir)
            ctl.attach_ledger(ledger)
        dashboard = DashboardServer(
            store, host="127.0.0.1", port=self.port, ledger=ledger
        )
        dashboard.start()
        self.port = dashboard.port
        ctl.api_url = dashboard.url
        ctl.run(workers=2)
        if info.recovered:
            ctl.record_recovery(info)
        self.store, self.controller, self.dashboard = store, ctl, dashboard
        self.ledger = ledger
        self.fakes.append(fake)
        log.warning(
            "operator up on %s (recovered=%s objects=%d rv=%d)",
            self.url, info.recovered, info.objects, info.resource_version,
        )

    def crash(self) -> None:
        """Tear the control plane down ungracefully-in-spirit: no drain,
        no handoff — durability must come from the WAL alone."""
        self.dashboard.stop()
        self.controller.stop()
        if self.ledger is not None:
            # fold() flushes per record, so close() adds no durability —
            # it only releases the segment handle (the SIGKILL contract
            # holds either way; this just avoids two writers post-restart).
            self.ledger.close()
            self.ledger = None
        self.store = None

    def restart(self) -> None:
        self.restarts += 1
        log.warning("chaos: killing the operator (restart #%d)", self.restarts)
        self.crash()
        self.start()

    def created_through_controller(self) -> List[str]:
        """Process names any incarnation's controller launched through its
        OWN backend — must be empty in managed mode."""
        return [
            p.metadata.name for fake in self.fakes for p in fake.created
        ]


@dataclass
class SoakResult:
    succeeded: bool = False
    restart_count: int = 0
    preemption_count: int = 0
    last_restart_cause: str = ""
    conditions: List[tuple] = field(default_factory=list)
    # Controller-declared resume steps, one per created gang process
    # INCARNATION (deduped by uid — remote watch replays redeliver), in
    # first-observed (creation) order.
    resume_steps: List[int] = field(default_factory=list)
    partial_gang_violations: List[str] = field(default_factory=list)
    applied: List[dict] = field(default_factory=list)
    schedule: Optional[FaultSchedule] = None
    # Trace-derived restart windows (obs.export.derive_timings "restarts"
    # rows: cause / start / end / downtime_s) and the bound invariant 6
    # checks them against.
    restart_windows: List[dict] = field(default_factory=list)
    downtime_bound_s: float = 60.0
    # Distinct uids created per gang-member name (watch ADDED, deduped):
    # invariant 7 pins this to 1 + restart_count + preemption_count —
    # an operator restart that double-created gang members would exceed it.
    gang_incarnations: Dict[str, int] = field(default_factory=dict)
    # Control-plane crash bookkeeping (invariant 8): how many times the
    # operator was killed+recovered, and every span op in the job's trace
    # (the restart must be VISIBLE as a controller-restart span).
    operator_restarts: int = 0
    trace_ops: List[str] = field(default_factory=list)
    # Peer warm-restore bookkeeping (invariant 9): whether the rig ran
    # with shard depots, the source of every restore span in the trace
    # (chronological), and the EFFECTIVE recovery downtime per restart —
    # restart-span start to the matching restore span's end. The plain
    # restart window closes at gang RUNNING, BEFORE the workload's
    # restore (and any slow-store read) runs; effective downtime is what
    # an operator actually waits for training to resume.
    p2p: bool = False
    restore_sources: List[str] = field(default_factory=list)
    effective_downtimes_s: List[Optional[float]] = field(default_factory=list)
    # Goodput attribution (invariant 10, r13): the controller's per-cause
    # tpujob_lost_seconds_total counters, scraped before teardown.
    # goodput_scraped=False (crash mode: counters reset with the operator)
    # skips the invariant.
    goodput_scraped: bool = False
    lost_seconds: Dict[str, float] = field(default_factory=dict)
    # Goodput-autopilot receipts (r16, the A/B soak's raw material): the
    # full goodput decomposition (same function the reconciler folds at
    # terminal), the job's autopilot status mirror + cadence directive,
    # every autopilot-decision span (attrs carry the justifying
    # numbers), and per-op closed-span width sums for the cause-ledger
    # cross-check (restart/resize/hang must each equal their own spans'
    # widths, however the families interleave).
    goodput: Dict[str, Any] = field(default_factory=dict)
    autopilot_status: Dict[str, Any] = field(default_factory=dict)
    cadence_directive: Dict[str, Any] = field(default_factory=dict)
    decision_spans: List[dict] = field(default_factory=list)
    span_widths_by_op: Dict[str, float] = field(default_factory=dict)
    downtime_spans: List[dict] = field(default_factory=list)

    def check(self) -> List[str]:
        """Invariant failures, empty when the soak passed."""
        errs = []
        if not self.succeeded:
            errs.append(f"job did not succeed: {self.conditions}")
        if self.partial_gang_violations:
            errs.append(f"partial gang persisted: {self.partial_gang_violations}")
        if self.resume_steps != sorted(self.resume_steps):
            errs.append(f"resume steps not monotonic: {self.resume_steps}")
        if not any(s > 0 for s in self.resume_steps):
            errs.append(
                f"no warm restart observed (resume steps {self.resume_steps})"
            )
        sched_kinds = [f.kind.value for f in (self.schedule.faults if self.schedule else ())]
        applied_kinds = [a["kind"] for a in self.applied]
        if applied_kinds != sched_kinds:
            errs.append(
                f"applied fault sequence {applied_kinds} != schedule {sched_kinds}"
            )
        if any(a["kind"] == "preempt" for a in self.applied) and (
            self.preemption_count < 1
        ):
            errs.append("preemption applied but preemption_count is 0")
        # Invariant 6: recovery downtime measured FROM THE TRACE. Every
        # preemption restart span must have closed (the gang came back
        # RUNNING) within the bound.
        preempt_windows = [
            w for w in self.restart_windows if w.get("cause") == "preemption"
        ]
        if any(a["kind"] == "preempt" for a in self.applied):
            if not preempt_windows:
                errs.append(
                    "preemption applied but the trace has no preemption "
                    f"restart span (windows: {self.restart_windows})"
                )
        for w in preempt_windows:
            if w.get("downtime_s") is None:
                errs.append(
                    f"preemption restart span never closed (gang did not "
                    f"return to RUNNING): {w}"
                )
            elif w["downtime_s"] > self.downtime_bound_s:
                errs.append(
                    f"preemption recovery downtime {w['downtime_s']:.1f}s "
                    f"exceeds bound {self.downtime_bound_s:.0f}s: {w}"
                )
        # Invariant 7: zero duplicate gang-member creates. Every create of
        # a gang name is accounted for by exactly one fault-driven gang
        # restart (+1 for the original) — a controller that restarted and
        # re-created children it should have re-adopted shows up here.
        expected_incarnations = 1 + self.restart_count + self.preemption_count
        for name, n in sorted(self.gang_incarnations.items()):
            if n > expected_incarnations:
                errs.append(
                    f"duplicate gang-member creates: {name} created {n}x "
                    f"but only {expected_incarnations} incarnations are "
                    f"accounted for ({self.restart_count} restarts + "
                    f"{self.preemption_count} preemptions + the original)"
                )
        # Invariant 8: an operator crash actually happened when scheduled,
        # and the restart is visible in the job trace as a
        # controller-restart span (the recovery pass records one per live
        # job — obs/ is how an SRE sees the control-plane outage inline
        # with the job's own timeline).
        if any(a["kind"] == "operator-crash" for a in self.applied):
            if self.operator_restarts < 1:
                errs.append("operator-crash applied but the operator never restarted")
            if "controller-restart" not in self.trace_ops:
                errs.append(
                    "operator crashed+recovered but the job trace has no "
                    f"controller-restart span (ops: {sorted(set(self.trace_ops))})"
                )
        # Invariant 9: with shard depots armed, at least one post-fault
        # incarnation restored from a PEER — the depot outlived the gang
        # teardown and the TPUJOB_RESTORE_PEERS hint closed the loop. The
        # effective downtimes (restart start -> restore end, the number
        # that includes the workload's restore) also honor the bound —
        # the TIGHTENED check the plain RUNNING-closed window can't see.
        if self.p2p:
            if "peer" not in self.restore_sources:
                errs.append(
                    "p2p soak: no restart restored from a peer (restore "
                    f"sources: {self.restore_sources})"
                )
            for d in self.effective_downtimes_s:
                if d is not None and d > self.downtime_bound_s:
                    errs.append(
                        f"effective recovery downtime {d:.1f}s (restart -> "
                        f"restore committed) exceeds bound "
                        f"{self.downtime_bound_s:.0f}s"
                    )
        # Invariant 10 (r13): goodput attribution. Every closed restart
        # window's downtime must land under lost_seconds{cause="restart"}
        # — the counter is incremented at the same span-close point as the
        # downtime histogram, so the sums must agree — and NONE of it may
        # leak into cause="resize" (no resizes happen here; the two span
        # families must never double-count one outage).
        if self.goodput_scraped:
            expected = sum(
                w["downtime_s"] for w in self.restart_windows
                if w.get("downtime_s") is not None
            )
            got = self.lost_seconds.get("restart", 0.0)
            if expected > 0 and abs(got - expected) > max(0.5, 0.05 * expected):
                errs.append(
                    f"lost_seconds{{cause=restart}} {got:.2f}s != closed "
                    f"restart-window downtime {expected:.2f}s"
                )
            if self.lost_seconds.get("resize", 0.0) > 0:
                errs.append(
                    "restart downtime leaked into cause=resize: "
                    f"{self.lost_seconds}"
                )
        return errs


class _InvariantWatcher:
    """Watches gang-atomicity and warm-restart invariants live.

    Partial-gang detection is persistence-based: sequential store
    creates/deletes make instantaneous strict subsets unavoidable, so a
    violation is a strict nonempty subset that survives ``grace_s``
    continuously — the steady state the atomic scheduler must foreclose.

    Works against a local Store OR a RemoteStore (the operator-crash
    rig): remote watches reconnect and REPLAY existing objects, so every
    observation dedupes by uid — a replayed ADDED is the same
    incarnation, not a new create. List polls during an operator outage
    raise TransientStoreError; the poll loop skips those ticks (the
    partial-gang clock also resets: with the store dark there is no
    evidence either way)."""

    def __init__(self, store: Any, job_name: str, gang_names: List[str],
                 grace_s: float = 10.0, allowed_subset_fn=None) -> None:
        self.store = store
        self.job_name = job_name
        self.gang_names = set(gang_names)
        self.grace_s = grace_s
        # Elastic soak: a DELIBERATE shrink is a sanctioned strict subset
        # — the callback returns the set of member names the job's live
        # resize directive currently blesses (or None for "full gang
        # only"). A subset that matches neither is still a violation.
        self.allowed_subset_fn = allowed_subset_fn
        self.violations: List[str] = []
        self.resume_steps: List[int] = []
        # name -> set of uids observed for it (distinct incarnations
        # actually created; the duplicate-create oracle).
        self.created_uids: Dict[str, set] = {}
        self._seen_uids: set = set()
        self._partial_since: Optional[float] = None
        self._stop = threading.Event()
        self._watch = store.watch(kinds=[KIND_PROCESS])
        self._threads = [
            threading.Thread(target=self._watch_loop, daemon=True,
                             name="soak-watch"),
            threading.Thread(target=self._poll_loop, daemon=True,
                             name="soak-invariant"),
        ]

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def stop(self) -> None:
        self._stop.set()
        self._watch.stop()
        for t in self._threads:
            t.join(timeout=5)

    def _watch_loop(self) -> None:
        for ev in self._watch:
            if self._stop.is_set():
                return
            if ev.type is not WatchEventType.ADDED or ev.obj is None:
                continue
            p = ev.obj
            if p.metadata.name not in self.gang_names:
                continue
            if p.metadata.uid in self._seen_uids:
                continue  # watch-reconnect replay of a known incarnation
            self._seen_uids.add(p.metadata.uid)
            self.created_uids.setdefault(p.metadata.name, set()).add(
                p.metadata.uid
            )
            self.resume_steps.append(
                int(p.spec.env.get(ENV_RESUME_STEP, "0") or 0)
            )

    def _poll_loop(self) -> None:
        from tf_operator_tpu.runtime.store import TransientStoreError

        while not self._stop.wait(0.2):
            try:
                live = {
                    p.metadata.name
                    for p in self.store.list(KIND_PROCESS, namespace="default")
                    if p.metadata.name in self.gang_names and not p.is_finished()
                }
            except TransientStoreError:
                self._partial_since = None  # store dark (operator outage)
                continue
            if live and live != self.gang_names and self.allowed_subset_fn:
                try:
                    allowed = self.allowed_subset_fn()
                except Exception:
                    allowed = None
                if allowed is not None and live == allowed:
                    self._partial_since = None
                    continue
            if live and live != self.gang_names:
                now = time.monotonic()
                if self._partial_since is None:
                    self._partial_since = now
                elif now - self._partial_since > self.grace_s:
                    self.violations.append(
                        f"members {sorted(live)} of {sorted(self.gang_names)} "
                        f"alone for > {self.grace_s}s"
                    )
                    self._partial_since = now  # one report per episode
            else:
                self._partial_since = None


def _soak_job(
    name: str,
    workers: int,
    num_hosts: int,
    ckpt_dir: str,
    steps: int,
    checkpoint_every: int,
    backoff_limit: int,
    heartbeat_ttl: Optional[float],
    data_plane: str = "light",
    step_sleep_s: float = 1.0,
    disk_restore_delay_s: float = 0.0,
    workload_extra: Optional[Dict[str, Any]] = None,
    autopilot: Optional[Dict[str, Any]] = None,
) -> TPUJob:
    """``data_plane='light'`` (default) runs workloads/soak.py — real
    checkpoint subsystem, no cross-process collectives, so the soak works
    in containers whose jax cannot do multi-process CPU SPMD (where ALL
    real-gang e2es fail). ``'lm'`` runs the full gloo-collectives LM
    trainer for environments that support it."""
    env = dict(DATAPLANE_ENV)
    env["PYTHONPATH"] = _ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    if data_plane == "lm":
        entrypoint = "tf_operator_tpu.workloads.lm:main"
        workload = {
            "preset": "tiny",
            "steps": steps,
            "batch_size": 4,
            "seq_len": 32,
            "checkpoint_dir": ckpt_dir,
            "checkpoint_every": checkpoint_every,
        }
    else:
        entrypoint = "tf_operator_tpu.workloads.soak:main"
        workload = {
            "steps": steps,
            "step_sleep_s": step_sleep_s,
            "checkpoint_dir": ckpt_dir,
            "checkpoint_every": checkpoint_every,
            # The chunked async npy pipeline is the one under test — it
            # is also the backend whose commit hook feeds the shard
            # depots (docs/design.md §4.9), which invariant 9 needs.
            "checkpoint_backend": "npy",
            # Models the flagship slow-store read: a resumed chief whose
            # restore source is DISK sleeps this long; the peer path
            # skips it (workloads/soak.py).
            "disk_restore_delay_s": disk_restore_delay_s,
        }
    job = TPUJob(
        metadata=ObjectMeta(name=name),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=workers,
                    template=ProcessTemplate(
                        entrypoint=entrypoint,
                        env=env,
                        chips_per_process=1,
                    ),
                )
            },
            topology=TopologySpec(num_hosts=num_hosts, chips_per_host=1),
        ),
    )
    if workload_extra:
        workload.update(workload_extra)
    job.spec.run_policy.backoff_limit = backoff_limit
    job.spec.run_policy.heartbeat_ttl_seconds = heartbeat_ttl
    if autopilot is not None:
        job.spec.run_policy.autopilot = dict(autopilot)
    job.spec.workload = workload
    return job


def run_soak(
    seed: int = 0,
    schedule: Optional[FaultSchedule] = None,
    hosts: int = 3,
    num_hosts: int = 2,
    workers: int = 2,
    steps: int = 8,
    checkpoint_every: int = 2,
    backoff_limit: int = 2,
    timeout: float = 420.0,
    workdir: Optional[str] = None,
    heartbeat_ttl: float = 3.0,
    data_plane: str = "light",
    step_sleep_s: float = 1.0,
    downtime_bound_s: float = 60.0,
    operator_crash: bool = False,
    p2p_restore: bool = False,
    disk_restore_delay_s: float = 0.0,
    workload_extra: Optional[Dict[str, Any]] = None,
    autopilot: Optional[Dict[str, Any]] = None,
) -> SoakResult:
    """Run one seeded soak; returns the observations (see SoakResult.check).

    ``hosts`` > ``num_hosts`` leaves spare capacity so a preempted gang has
    somewhere to move — a drained host is not schedulable.

    ``p2p_restore`` arms the peer warm-restore path: every agent runs a
    host-lifetime shard depot, the controller stamps
    ``TPUJOB_RESTORE_PEERS``, and invariant 9 requires at least one
    post-fault incarnation to restore from a peer.
    ``disk_restore_delay_s`` is the modeled slow-store read a DISK
    restore pays (and a peer restore skips) in the light data plane.

    ``operator_crash`` (or a schedule containing OPERATOR_CRASH) switches
    the rig to the crash-recovery topology: the operator is a
    :class:`RestartableOperator` (durable store under ``workdir/store`` +
    controller + HTTP API), agents and the injector talk to it over
    RemoteStore, and the scheduled fault kills+recovers the whole control
    plane mid-run while the data plane keeps training."""
    schedule = (
        schedule if schedule is not None
        else default_schedule(seed, operator_crash=operator_crash)
    )
    crash_mode = any(
        f.kind is FaultKind.OPERATOR_CRASH for f in schedule.faults
    )
    tmp = workdir or tempfile.mkdtemp(prefix="tpujob-soak-")
    ckpt_dir = os.path.join(tmp, "ckpt")
    job_name = "soak-lm"

    operator: Optional[RestartableOperator] = None
    if crash_mode:
        # Operator downtime must never masquerade as host loss: the
        # recovered Host records carry pre-crash heartbeats, and agents
        # need a beat to reconnect before the TTL reaper runs — a
        # NodeLost fence during the outage would gang-restart a healthy
        # gang and fail the duplicate-create invariant for the wrong
        # reason.
        heartbeat_ttl = max(heartbeat_ttl, 10.0)
        operator = RestartableOperator(
            os.path.join(tmp, "store"), heartbeat_ttl=heartbeat_ttl
        )
        operator.start()
        store: Any = RemoteStore(operator.url, timeout=5.0)
    else:
        store = Store()
    injector = ChaosInjector(
        schedule, store, job_name=job_name, checkpoint_dir=ckpt_dir,
        operator=operator,
    )
    agents = [
        HostAgent(
            injector.wrap(),
            f"soak-h{i}",
            total_chips=workers,  # any single host could hold the full gang
            heartbeat_interval=0.25,
            backend=LocalProcessControl(
                injector.wrap(), log_dir=os.path.join(tmp, "logs")
            ),
            # p2p mode: host-lifetime shard depots — they outlive every
            # gang teardown, which is what invariant 9 exercises.
            depot=p2p_restore,
        )
        for i in range(hosts)
    ]
    injector.agents = {a.name: a for a in agents}
    if crash_mode:
        ctl = None
        fake = None
        dashboard = None
    else:
        # The controller's own process control must stay idle in managed
        # mode (every gang member is host-bound); a fake makes a leak loud.
        fake = FakeProcessControl()
        ctl = TPUJobController(store, fake, resync_period=0.5)
        ctl.scheduler.heartbeat_ttl = heartbeat_ttl
        # Workload-side spans (restore-source, save-stall) travel through
        # the operator API (ENV_API_SERVER); without one they drop
        # silently and invariant 9 is blind. Crash mode gets this from
        # RestartableOperator; managed mode needs its own.
        from tf_operator_tpu.dashboard import DashboardServer

        dashboard = DashboardServer(store, host="127.0.0.1", port=0)
        dashboard.start()
        ctl.api_url = dashboard.url

    gang_names = [f"{job_name}-worker-{i}" for i in range(workers)]
    watcher = _InvariantWatcher(store, job_name, gang_names)
    result = SoakResult(schedule=schedule)
    for a in agents:
        a.start()
    if ctl is not None:
        ctl.run(workers=2)
    watcher.start()
    try:
        store.create(
            _soak_job(job_name, workers, num_hosts, ckpt_dir, steps,
                      checkpoint_every, backoff_limit, heartbeat_ttl,
                      data_plane=data_plane, step_sleep_s=step_sleep_s,
                      disk_restore_delay_s=disk_restore_delay_s,
                      workload_extra=workload_extra, autopilot=autopilot)
        )
        injector.arm()
        deadline = time.monotonic() + timeout
        st = None
        while time.monotonic() < deadline:
            try:
                st = store.get("TPUJob", "default", job_name).status
            except TransientStoreError:
                time.sleep(0.25)  # operator mid-restart
                continue
            if is_finished(st) and injector.done:
                break
            time.sleep(0.25)
        st = store.get("TPUJob", "default", job_name).status
        result.succeeded = has_condition(st, ConditionType.SUCCEEDED)
        result.restart_count = st.restart_count
        result.preemption_count = st.preemption_count
        result.last_restart_cause = st.last_restart_cause
        result.conditions = [
            (c.type.value, c.reason, c.message) for c in st.conditions
        ]
        # Invariant 6/8 input: the trace — read while the store is still
        # up. Same spans `tpujob trace` exports, not log inference.
        trace = job_trace(store, "default", job_name)
        result.restart_windows = derive_timings(trace).get("restarts", [])
        result.trace_ops = [s.op for s in trace]
        # Restore-source spans + effective downtime (invariant 9): each
        # restart window is matched to the earliest CLOSED restore span
        # starting at/after the window opened — effective = restore end -
        # restart start. A window with no matching restore (the gang came
        # back but never reported one) falls back to the RUNNING-closed
        # width so the bound still sees it.
        restore_spans = sorted(
            (s for s in trace if s.op == "restore" and s.end_time),
            key=lambda s: s.start_time,
        )
        result.restore_sources = [
            s.attrs.get("source", "disk") for s in restore_spans
        ]
        windows = sorted(result.restart_windows, key=lambda w: w["start"])
        starts = [w["start"] for w in windows]
        for i, w in enumerate(windows):
            nxt = starts[i + 1] if i + 1 < len(starts) else float("inf")
            match = next(
                (s for s in restore_spans
                 if w["start"] <= s.start_time < nxt),
                None,
            )
            if match is not None:
                result.effective_downtimes_s.append(
                    max(0.0, match.end_time - w["start"])
                )
            else:
                result.effective_downtimes_s.append(w.get("downtime_s"))
        if ctl is not None:
            result.lost_seconds = _scrape_lost_seconds(ctl.metrics)
            result.goodput_scraped = True
            # Autopilot receipts (r16): the goodput decomposition (the
            # SAME pure function the reconciler folds at terminal, over
            # the same trace + telemetry — the A/B gate's numerator),
            # the status-mirrored decisions, and per-op closed-span
            # width sums for the cause-ledger cross-check.
            from tf_operator_tpu.obs.telemetry import (
                goodput_decomposition,
                job_telemetry,
            )

            job_obj = store.get("TPUJob", "default", job_name)
            end = st.completion_time or time.time()
            result.goodput = goodput_decomposition(
                trace, job_telemetry(store, "default", job_name),
                job_obj.metadata.creation_timestamp, end,
            )
            result.autopilot_status = dict(job_obj.status.autopilot or {})
            result.cadence_directive = dict(
                job_obj.status.checkpoint_cadence_directive or {}
            )
            result.decision_spans = [
                {"name": s.metadata.name, "attrs": dict(s.attrs or {})}
                for s in trace if s.op == "autopilot-decision"
            ]
            result.span_widths_by_op = {
                op: sum(
                    max(0.0, s.end_time - s.start_time)
                    for s in trace if s.op == op and s.end_time
                )
                for op in ("restart", "resize", "hang")
            }
            result.downtime_spans = [
                {
                    "name": s.metadata.name, "op": s.op,
                    "attrs": dict(s.attrs or {}),
                    "width_s": round(max(0.0, s.end_time - s.start_time), 6),
                }
                for s in trace
                if s.op in ("restart", "resize", "hang") and s.end_time
            ]
    finally:
        injector.stop()
        watcher.stop()
        if ctl is not None:
            ctl.stop()
        for a in agents:
            a.stop()
        if dashboard is not None:
            dashboard.stop()
        if operator is not None:
            operator.crash()  # agents stopped; tear the API down last
        if fake is not None:
            fake.clear()
    result.resume_steps = list(watcher.resume_steps)
    result.partial_gang_violations = list(watcher.violations)
    result.applied = list(injector.applied)
    result.downtime_bound_s = downtime_bound_s
    result.p2p = p2p_restore
    result.gang_incarnations = {
        name: len(uids) for name, uids in watcher.created_uids.items()
    }
    if operator is not None:
        result.operator_restarts = operator.restarts
        leaked = operator.created_through_controller()
    else:
        leaked = [p.metadata.name for p in fake.created]
    if leaked:
        result.partial_gang_violations.append(
            "controller launched through its own backend in managed mode: "
            f"{leaked}"
        )
    return result


def default_autopilot_schedule(seed: int) -> FaultSchedule:
    """The autopilot A/B recipe: ONE mid-run crash (after checkpoint
    progress, so recovery is warm). The crash is what gives the ON
    lane's Young/Daly policy a finite measured MTBF — before it the
    cadence stretches on the zero-failure clamp, after it the interval
    re-derives from δ and the observed failure rate. Pure function of
    the seed, shared verbatim by both lanes."""
    return FaultSchedule.generate(
        seed, crashes=1, preemptions=0, first_step=2, spread_s=0.0
    )


@dataclass
class AutopilotSoakResult:
    """Two same-seed, same-fault-schedule soak lanes: ``run_policy.
    autopilot`` off then on. ``check()`` gates the goodput gain and the
    receipt discipline (every executed decision present as an
    autopilot-decision span carrying its justifying numbers), and
    extends the r13 cause-attribution invariant to both lanes: each of
    restart/resize/hang's ledger lost-seconds must equal the sum of its
    OWN closed spans' widths — however autopilot-triggered resizes and
    watchdog windows interleave, nothing double-counts."""

    off: SoakResult
    on: SoakResult
    min_gain: float = 1.10

    # Every numeric attr a cadence decision span must justify itself with.
    CADENCE_RECEIPT_KEYS = (
        "save_stall_s", "mtbf_s", "step_time_s", "tau_s",
        "from_every", "to_every", "epoch",
    )

    def gain(self) -> Optional[float]:
        off_r = self.off.goodput.get("goodput_ratio", 0.0)
        on_r = self.on.goodput.get("goodput_ratio", 0.0)
        return (on_r / off_r) if off_r else None

    def check(self) -> List[str]:
        errs: List[str] = []
        for tag, lane in (("off", self.off), ("on", self.on)):
            errs.extend(f"[{tag}] {e}" for e in lane.check())
            # Satellite 6 (extends invariant 10): per-cause single-source
            # attribution. The restart/resize/hang counters increment
            # ONLY at their own span closes, so each must match its own
            # spans' summed widths — an autopilot resize interleaving
            # with a watchdog hang in one incarnation must not leak
            # either window into the other's cause.
            if lane.goodput_scraped:
                for cause in ("restart", "resize", "hang"):
                    got = lane.lost_seconds.get(cause, 0.0)
                    want = lane.span_widths_by_op.get(cause, 0.0)
                    if abs(got - want) > max(0.5, 0.05 * want):
                        errs.append(
                            f"[{tag}] lost_seconds{{cause={cause}}} "
                            f"{got:.2f}s != closed {cause}-span widths "
                            f"{want:.2f}s"
                        )
        # The off lane must be autopilot-silent: no decisions, no spans.
        if self.off.decision_spans or self.off.autopilot_status:
            errs.append(
                "autopilot-off lane recorded autopilot activity: "
                f"spans={len(self.off.decision_spans)} "
                f"status={self.off.autopilot_status}"
            )
        # The on lane acted, and every action is receipted.
        decisions_total = int(
            self.on.autopilot_status.get("decisions_total", 0)
        )
        if decisions_total < 1:
            errs.append("autopilot-on lane executed no decisions")
        if len(self.on.decision_spans) != decisions_total:
            errs.append(
                f"autopilot receipt mismatch: {len(self.on.decision_spans)} "
                f"decision spans != decisions_total {decisions_total}"
            )
        cadence = [
            d for d in self.on.decision_spans
            if d["attrs"].get("kind") == "cadence"
        ]
        if not cadence:
            errs.append(
                "autopilot-on lane never retuned the checkpoint cadence "
                f"(decisions: {self.on.decision_spans})"
            )
        for d in cadence:
            for key in self.CADENCE_RECEIPT_KEYS:
                v = d["attrs"].get(key)
                try:
                    valid = v is not None and (v == "inf" or float(v) >= 0)
                except ValueError:
                    valid = False
                if not valid:
                    errs.append(
                        f"cadence decision span {d['name']} missing "
                        f"justifying number {key!r}: attrs={d['attrs']}"
                    )
        # The directive round-tripped. The controller only authors epoch
        # N+1 after the chief acked N, so the ack may trail the final
        # epoch by at most one (a directive issued in the run's last
        # poll interval is legitimately still in flight at completion) —
        # but at least one epoch must have been applied.
        cd = self.on.cadence_directive
        applied = int(cd.get("applied_epoch", 0))
        epoch = int(cd.get("epoch", 0))
        if applied < 1 or applied < epoch - 1:
            errs.append(
                f"cadence directive never round-tripped: epoch {epoch}, "
                f"applied_epoch={cd.get('applied_epoch')}"
            )
        # The mechanism receipt: the retune actually cut save-stall loss.
        off_stall = self.off.goodput.get("lost_s", {}).get("ckpt-stall", 0.0)
        on_stall = self.on.goodput.get("lost_s", {}).get("ckpt-stall", 0.0)
        if not on_stall < off_stall:
            errs.append(
                f"autopilot did not cut ckpt-stall loss: on {on_stall:.2f}s "
                f">= off {off_stall:.2f}s"
            )
        # THE gate: autopilot-on goodput >= min_gain x the off lane.
        off_r = self.off.goodput.get("goodput_ratio", 0.0)
        on_r = self.on.goodput.get("goodput_ratio", 0.0)
        if not (off_r > 0 and on_r >= self.min_gain * off_r):
            errs.append(
                f"goodput gain gate failed: on {on_r:.4f} < "
                f"{self.min_gain:.2f}x off {off_r:.4f}"
            )
        return errs


def run_autopilot_soak(
    seed: int = 0,
    steps: int = 20,
    step_sleep_s: float = 0.2,
    save_stall_extra_s: float = 0.8,
    timeout: float = 180.0,
    workdir: Optional[str] = None,
    min_gain: float = 1.10,
    max_checkpoint_every: int = 8,
) -> AutopilotSoakResult:
    """The A/B autopilot soak: the SAME seed and fault schedule, run
    twice — ``run_policy.autopilot`` off, then on. Identical workload in
    both lanes: ``checkpoint_every=1`` with a modeled per-save blocking
    stall (``save_stall_extra_s``), so the off lane pays the stall on
    every step while the on lane's measured-δ/measured-MTBF retune
    stretches the interval and recovers the difference as goodput.

    A single worker keeps the A/B clean: the telemetry-averaged
    ckpt-stall loss is then exactly the chief's stall seconds, so the
    gate measures the cadence policy, not rank-dilution artifacts.

    Sizing: steps x save_stall_extra_s is the off lane's stall loss —
    the A/B signal. It must dwarf the lanes' uncontrolled noise
    (process startup / compile-init varies by a couple of seconds run
    to run), or the 1.10x gate flakes. The defaults put ~16 s of
    recoverable stall against ~2 s of noise."""
    root = workdir or tempfile.mkdtemp(prefix="tpujob-autopilot-soak-")
    workload_extra = {
        # The modeled flagship save cost the retune amortizes.
        "save_stall_extra_s": save_stall_extra_s,
        # One telemetry window per step: the autopilot needs fresh
        # step-time medians at test timescales.
        "telemetry_every": 1,
        # Per-step directive polling (no throttle): a retune must land
        # at the very next step boundary.
        "cadence_poll_s": 0.0,
    }

    def lane(tag: str, autopilot: Optional[Dict[str, Any]]) -> SoakResult:
        return run_soak(
            seed=seed,
            # Re-derived per lane from the seed: pure function, so both
            # lanes see byte-identical fault schedules.
            schedule=default_autopilot_schedule(seed),
            hosts=2, num_hosts=1, workers=1, steps=steps,
            checkpoint_every=1, backoff_limit=2, timeout=timeout,
            workdir=os.path.join(root, tag), heartbeat_ttl=3.0,
            step_sleep_s=step_sleep_s, workload_extra=workload_extra,
            autopilot=autopilot,
        )

    off = lane("off", None)
    on = lane("on", {
        "enabled": True,
        # Test-timescale hysteresis: still >= the straggler tracker's
        # flag_windows (the no-flap contract), just with a short cooldown.
        "cooldown_s": 1.0,
        "confirm_ticks": 2,
        "max_checkpoint_every": max_checkpoint_every,
    })
    return AutopilotSoakResult(off=off, on=on, min_gain=min_gain)


def autopilot_artifact(
    result: AutopilotSoakResult, seed: int
) -> Dict[str, Any]:
    """The checked-in A/B receipt (artifacts/autopilotbench_r16.json)."""
    errors = result.check()

    def lane(r: SoakResult) -> Dict[str, Any]:
        return {
            "succeeded": r.succeeded,
            "restarts": r.restart_count,
            "goodput": r.goodput,
            "lost_seconds": r.lost_seconds,
            "span_widths_by_op": r.span_widths_by_op,
            "downtime_spans": r.downtime_spans,
            "resume_steps": r.resume_steps,
            "applied": [a["kind"] for a in r.applied],
        }

    return {
        "bench": "autopilot-ab-soak",
        "seed": seed,
        "gate_min_gain": result.min_gain,
        "off": lane(result.off),
        "on": {
            **lane(result.on),
            "decisions_total": result.on.autopilot_status.get(
                "decisions_total", 0
            ),
            "active_checkpoint_every": result.on.autopilot_status.get(
                "active_checkpoint_every", 0
            ),
            "cadence_directive": result.on.cadence_directive,
            "decisions": result.on.decision_spans,
        },
        "goodput_gain": result.gain(),
        "errors": errors,
        "pass": not errors,
    }


@dataclass
class FleetLedgerSoakResult:
    """Observations from the fleet-ledger soak (r18): durable cross-job
    memory under operator death, job GC, and the prior-fed first cadence
    decision of a fresh job. See run_fleet_ledger_soak."""

    history: List[Dict[str, Any]] = field(default_factory=list)
    prior_mtbf_s: float = 0.0
    prior_failures: int = 0
    prior_jobs: int = 0
    summary_before: bytes = b""
    summary_after: bytes = b""
    operator_restarts: int = 0
    gc_uid_present: bool = False
    gc_jobs_folded_before: int = 0
    gc_jobs_folded_after: int = 0
    wal_stats: Dict[str, Any] = field(default_factory=dict)
    on: Dict[str, Any] = field(default_factory=dict)
    off: Dict[str, Any] = field(default_factory=dict)
    max_checkpoint_every: int = 24
    within: float = 1.5

    @staticmethod
    def first_decision(lane: Dict[str, Any]) -> Dict[str, Any]:
        ds = lane.get("cadence_decisions") or []
        return dict(ds[0]) if ds else {}

    def converged_every(self) -> Optional[int]:
        """The Young/Daly optimum the prior-fed first decision is gated
        against: the ON lane's own measured stall and step time, but the
        LEDGER's converged MTBF instead of the lane's (nonexistent) own
        failure history."""
        first = self.first_decision(self.on)
        try:
            stall = float(first["save_stall_s"])
            step = float(first["step_time_s"])
        except (KeyError, ValueError):
            return None
        if self.prior_mtbf_s <= 0:
            return None
        from tf_operator_tpu.autopilot.policy import optimal_checkpoint_every

        return optimal_checkpoint_every(
            stall, self.prior_mtbf_s, step,
            min_every=1, max_every=self.max_checkpoint_every,
        ).every

    def check(self) -> List[str]:
        errs: List[str] = []
        for obs in self.history:
            name = obs.get("name")
            if not obs.get("succeeded"):
                errs.append(f"history job {name} did not succeed")
            if int(obs.get("restarts") or 0) < 1:
                errs.append(f"history job {name} saw no crash restart")
            if not obs.get("folded"):
                errs.append(f"history job {name} never folded into the ledger")
        if not (0 < self.prior_mtbf_s < float("inf")):
            errs.append(
                f"ledger prior MTBF not finite-positive: {self.prior_mtbf_s}"
            )
        if self.prior_failures < len(self.history):
            errs.append(
                f"ledger prior failures {self.prior_failures} < history "
                f"incidents {len(self.history)}"
            )
        if self.operator_restarts < 1:
            errs.append("operator was never killed+restarted")
        if not self.summary_before or self.summary_before != self.summary_after:
            errs.append(
                "fleet summary not byte-identical across operator restart "
                f"({len(self.summary_before)}B vs {len(self.summary_after)}B)"
            )
        if not self.gc_uid_present:
            errs.append("job GC removed the ledger record (must survive)")
        if self.gc_jobs_folded_after != self.gc_jobs_folded_before:
            errs.append(
                f"ledger jobs-folded count changed across GC: "
                f"{self.gc_jobs_folded_before} -> {self.gc_jobs_folded_after}"
            )
        # OFF lane: a fresh job with no fleet prior has infinite own MTBF,
        # so its first retune must sit at the clamp edge, receipt-free.
        off1 = self.first_decision(self.off)
        if not off1:
            errs.append("off lane made no cadence decision")
        else:
            if int(off1.get("to_every") or -1) != self.max_checkpoint_every:
                errs.append(
                    f"off lane first decision not at clamp edge "
                    f"{self.max_checkpoint_every}: {off1}"
                )
            if off1.get("mtbf_s") != "inf":
                errs.append(
                    f"off lane first decision has finite MTBF (fleet prior "
                    f"leaked?): {off1}"
                )
            if "prior_mtbf_s" in off1:
                errs.append(
                    f"off lane decision carries a fleet-prior receipt: {off1}"
                )
        # ON lane: the first decision must be prior-receipted and land
        # within `within`x of the converged optimum.
        on1 = self.first_decision(self.on)
        opt = self.converged_every()
        if not on1:
            errs.append("on lane made no cadence decision")
        elif opt is None:
            errs.append(f"on lane first decision missing its numbers: {on1}")
        else:
            for k in ("prior_mtbf_s", "prior_samples", "prior_weight"):
                if k not in on1:
                    errs.append(
                        f"on lane first decision missing receipt attr "
                        f"{k}: {on1}"
                    )
            to = int(on1.get("to_every") or -1)
            if not (to <= self.within * opt and opt <= self.within * to):
                errs.append(
                    f"on lane first cadence {to} not within {self.within}x "
                    f"of converged optimum {opt} "
                    f"(prior mtbf {self.prior_mtbf_s:.2f}s)"
                )
            # Distinguishability: the clamp edge must NOT satisfy the ON
            # gate, or the A/B proves nothing.
            if not self.max_checkpoint_every > self.within * opt:
                errs.append(
                    f"A/B not distinguishable: clamp edge "
                    f"{self.max_checkpoint_every} <= {self.within}x "
                    f"optimum {opt}"
                )
        # Telemetry-heavy run, coalesced WAL: zero Telemetry bytes, the
        # skip counter proves the traffic existed, and control-plane
        # kinds carry all the durable bytes.
        tel = self.wal_stats.get("Telemetry", {})
        if tel.get("bytes", -1) != 0 or tel.get("skipped", 0) <= 0:
            errs.append(f"telemetry WAL not coalesced: {tel}")
        control = sum(
            (v or {}).get("bytes", 0)
            for k, v in self.wal_stats.items()
            if k in ("TPUJob", KIND_PROCESS)
        )
        if not control > 0:
            errs.append(
                f"no TPUJob/Process WAL bytes recorded: {self.wal_stats}"
            )
        return errs


def _run_ledger_job(
    operator: RestartableOperator,
    root: str,
    name: str,
    schedule: Optional[FaultSchedule],
    steps: int,
    step_sleep_s: float,
    save_stall_extra_s: float,
    autopilot: Optional[Dict[str, Any]],
    timeout: float,
    heartbeat_ttl: float = 10.0,
) -> Dict[str, Any]:
    """Run ONE job through the standing operator — its own agents (per-job
    host names, so no host accumulates enough incidents to trip the
    reputation threshold mid-soak) and an optional per-job injector over
    RemoteStore — wait for terminal AND the ledger fold, and return the
    observation dict the fleet-ledger gates consume."""
    ckpt_dir = os.path.join(root, name, "ckpt")
    store = RemoteStore(operator.url, timeout=5.0)
    injector = (
        ChaosInjector(schedule, store, job_name=name, checkpoint_dir=ckpt_dir)
        if schedule is not None
        else None
    )

    def client() -> Any:
        return (
            injector.wrap()
            if injector is not None
            else RemoteStore(operator.url, timeout=5.0)
        )

    agents = [
        HostAgent(
            client(), f"{name}-h{i}", total_chips=1,
            heartbeat_interval=0.25,
            backend=LocalProcessControl(
                client(), log_dir=os.path.join(root, name, "logs")
            ),
        )
        for i in range(2)
    ]
    if injector is not None:
        injector.agents = {a.name: a for a in agents}
    obs: Dict[str, Any] = {"name": name}
    for a in agents:
        a.start()
    try:
        store.create(
            _soak_job(
                name, 1, 1, ckpt_dir, steps,
                checkpoint_every=1, backoff_limit=2,
                heartbeat_ttl=heartbeat_ttl, data_plane="light",
                step_sleep_s=step_sleep_s,
                workload_extra={
                    # Same geometry as the autopilot A/B: a modeled
                    # per-save blocking stall worth retuning away, fresh
                    # telemetry every step, unthrottled directive polls.
                    "save_stall_extra_s": save_stall_extra_s,
                    "telemetry_every": 1,
                    "cadence_poll_s": 0.0,
                },
                autopilot=autopilot,
            )
        )
        if injector is not None:
            injector.arm()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                st = store.get("TPUJob", "default", name).status
            except TransientStoreError:
                time.sleep(0.25)
                continue
            if is_finished(st) and (injector is None or injector.done):
                break
            time.sleep(0.25)
        job_obj = store.get("TPUJob", "default", name)
        st = job_obj.status
        obs["uid"] = job_obj.metadata.uid
        obs["succeeded"] = has_condition(st, ConditionType.SUCCEEDED)
        obs["restarts"] = st.restart_count
        obs["preemptions"] = st.preemption_count
        # The tentpole contract: terminal observed => the record is IN
        # the ledger (durably) before the soak moves on.
        fold_deadline = time.monotonic() + 15.0
        folded = False
        while time.monotonic() < fold_deadline:
            led = operator.ledger
            if led is not None and led.has(obs["uid"]):
                folded = True
                break
            time.sleep(0.1)
        obs["folded"] = folded
        trace = job_trace(store, "default", name)
        obs["cadence_decisions"] = [
            dict(s.attrs or {})
            for s in sorted(
                (s for s in trace
                 if s.op == "autopilot-decision"
                 and (s.attrs or {}).get("kind") == "cadence"),
                key=lambda s: s.start_time,
            )
        ]
        obs["applied"] = (
            [a["kind"] for a in injector.applied] if injector is not None
            else []
        )
    finally:
        if injector is not None:
            injector.stop()
        for a in agents:
            a.stop()
    return obs


def run_fleet_ledger_soak(
    seed: int = 0,
    history_jobs: int = 2,
    history_steps: int = 6,
    fresh_steps: int = 16,
    step_sleep_s: float = 0.2,
    save_stall_extra_s: float = 0.8,
    max_checkpoint_every: int = 24,
    within: float = 1.5,
    timeout: float = 120.0,
    workdir: Optional[str] = None,
) -> FleetLedgerSoakResult:
    """The fleet-ledger acceptance soak (r18), four phases against ONE
    standing operator with a durable FleetLedger:

    1. **History** — seeded crash-faulted jobs run to Succeeded; each
       terminal folds exactly once, leaving the ledger a finite fleet
       MTBF (the prior the fresh job will consume).
    2. **Operator death** — the operator is killed and restarted;
       ``GET /api/fleet/summary`` must be byte-identical across the
       bounce (rollup + segment replay + dedup re-sweep).
    3. **Prior A/B** — two identical fresh fault-free jobs, autopilot on
       in both, differing ONLY in ``use_fleet_priors``. The OFF lane has
       no failure history, so its first retune clamps to
       ``max_checkpoint_every`` with ``mtbf_s=inf``; the ON lane's first
       decision must carry the prior receipt attrs and land within
       ``within``x of the Young/Daly optimum at the LEDGER's MTBF. The
       clamp edge is sized to fail the ON gate (distinguishability).
       ON runs first so neither lane's own fold can perturb the other's
       prior (the OFF lane never consults the ledger at all).
    4. **Job GC** — a history job is deleted from the store; the ledger
       record must survive (jobs-folded count unchanged).

    Also captures first-incarnation ``wal_stats()``: with per-step
    telemetry from every job, Telemetry WAL bytes must be ZERO (skipped
    counter positive) while TPUJob/Process kinds carry the durable bytes
    — the coalescing satellite's receipt."""
    import urllib.request

    root = workdir or tempfile.mkdtemp(prefix="tpujob-fleet-ledger-")
    operator = RestartableOperator(
        os.path.join(root, "store"),
        # Operator downtime must not masquerade as host loss (same
        # reasoning as crash mode in run_soak).
        heartbeat_ttl=10.0,
        ledger_dir=os.path.join(root, "ledger"),
    )
    operator.start()
    result = FleetLedgerSoakResult(
        max_checkpoint_every=max_checkpoint_every, within=within
    )

    def fetch(path: str) -> bytes:
        with urllib.request.urlopen(operator.url + path, timeout=5.0) as r:
            return r.read()

    try:
        # Phase 1: build fleet history.
        for i in range(history_jobs):
            result.history.append(
                _run_ledger_job(
                    operator, root, f"fleet-hist-{i}",
                    schedule=FaultSchedule.generate(
                        seed + i, crashes=1, preemptions=0,
                        first_step=2, spread_s=0.0,
                    ),
                    steps=history_steps, step_sleep_s=step_sleep_s,
                    save_stall_extra_s=save_stall_extra_s,
                    autopilot=None, timeout=timeout,
                )
            )
        led = operator.ledger
        prior = led.cadence_inputs("", "") if led is not None else {}
        result.prior_mtbf_s = float(prior.get("mtbf_s") or 0.0)
        result.prior_failures = int(prior.get("failures") or 0)
        result.prior_jobs = int(prior.get("jobs") or 0)
        # First-incarnation WAL accounting, before the restart resets the
        # in-memory counters.
        result.wal_stats = operator.store.wal_stats()
        result.summary_before = fetch("/api/fleet/summary")
        # Phase 2: kill + recover the whole control plane.
        operator.restart()
        result.operator_restarts = operator.restarts
        result.summary_after = fetch("/api/fleet/summary")
        # Phase 3: the prior A/B (ON first — see docstring).
        base = {
            "enabled": True,
            "cooldown_s": 1.0,
            "confirm_ticks": 2,
            "max_checkpoint_every": max_checkpoint_every,
        }
        result.on = _run_ledger_job(
            operator, root, "fleet-fresh-on", schedule=None,
            steps=fresh_steps, step_sleep_s=step_sleep_s,
            save_stall_extra_s=save_stall_extra_s,
            autopilot={**base, "use_fleet_priors": True}, timeout=timeout,
        )
        result.off = _run_ledger_job(
            operator, root, "fleet-fresh-off", schedule=None,
            steps=fresh_steps, step_sleep_s=step_sleep_s,
            save_stall_extra_s=save_stall_extra_s,
            autopilot={**base, "use_fleet_priors": False}, timeout=timeout,
        )
        # Phase 4: GC a history job; its ledger record must survive.
        led = operator.ledger
        victim = result.history[0]
        result.gc_jobs_folded_before = len(led) if led is not None else 0
        store = RemoteStore(operator.url, timeout=5.0)
        store.delete("TPUJob", "default", victim["name"])
        gc_deadline = time.monotonic() + 15.0
        while time.monotonic() < gc_deadline:
            try:
                store.get("TPUJob", "default", victim["name"])
            except NotFoundError:
                break
            except TransientStoreError:
                pass
            time.sleep(0.25)
        # Let the controller's GC sync (informer-cached None) run its
        # gauge sweep before we assert.
        time.sleep(1.5)
        result.gc_uid_present = bool(
            victim.get("uid")
            and led is not None
            and led.has(victim["uid"])
        )
        result.gc_jobs_folded_after = len(led) if led is not None else 0
    finally:
        operator.crash()
    return result


def fleetledger_artifact(
    result: FleetLedgerSoakResult, seed: int
) -> Dict[str, Any]:
    """The checked-in receipt (artifacts/fleetledger_r18.json)."""
    import json as _json

    errors = result.check()

    def lane(obs: Dict[str, Any]) -> Dict[str, Any]:
        return {
            "name": obs.get("name"),
            "succeeded": obs.get("succeeded"),
            "restarts": obs.get("restarts"),
            "folded": obs.get("folded"),
            "applied": obs.get("applied"),
            "first_cadence_decision": result.first_decision(obs),
            "cadence_decisions": obs.get("cadence_decisions"),
        }

    try:
        summary = _json.loads(result.summary_after.decode("utf-8"))
    except (ValueError, UnicodeDecodeError):
        summary = None
    return {
        "bench": "fleet-ledger-soak",
        "seed": seed,
        "history": [lane(o) for o in result.history],
        "prior": {
            "mtbf_s": result.prior_mtbf_s,
            "failures": result.prior_failures,
            "jobs": result.prior_jobs,
        },
        "operator_restarts": result.operator_restarts,
        "summary_byte_identical_across_restart": bool(
            result.summary_before
            and result.summary_before == result.summary_after
        ),
        "gc": {
            "record_survived": result.gc_uid_present,
            "jobs_folded_before": result.gc_jobs_folded_before,
            "jobs_folded_after": result.gc_jobs_folded_after,
        },
        "wal_stats": result.wal_stats,
        "gate_within": result.within,
        "max_checkpoint_every": result.max_checkpoint_every,
        "converged_optimum_every": result.converged_every(),
        "on": lane(result.on),
        "off": lane(result.off),
        "fleet_summary": summary,
        "errors": errors,
        "pass": not errors,
    }


def default_elastic_schedule(
    seed: int, kills: int = 2, spread_s: float = 6.0
) -> FaultSchedule:
    """The elastic acceptance recipe: ``kills`` kill/return faults against
    non-chief members, each returning 3-6s later. Pure function of the
    seed."""
    return FaultSchedule.generate_elastic(
        seed, kills=kills, first_step=1, spread_s=spread_s,
        return_after_s=(3.0, 6.0),
    )


@dataclass
class ElasticSoakResult:
    """Observations of one elastic soak (see check for the gates)."""

    succeeded: bool = False
    restart_count: int = 0
    preemption_count: int = 0
    resize_count: int = 0
    resize_epoch: int = 0
    world_size: int = 0
    last_restart_cause: str = ""
    resize_history: List[dict] = field(default_factory=list)
    conditions: List[tuple] = field(default_factory=list)
    applied: List[dict] = field(default_factory=list)
    schedule: Optional[FaultSchedule] = None
    partial_gang_violations: List[str] = field(default_factory=list)
    # Eval digests: the faulted run's (from workdir/gang/done.json) vs the
    # uninterrupted stream's (position-ordered canonical consumption) —
    # equality IS the bit-identical gate.
    digest: str = ""
    expected_digest: str = ""
    # Controller resize spans from the trace: direction + downtime_s
    # (None = never closed).
    resize_windows: List[dict] = field(default_factory=list)
    restore_sources: List[str] = field(default_factory=list)
    # Consumption rate (positions/s) before the first shrink, while
    # shrunk, and after the first re-grow.
    tokens_per_s: Dict[str, Optional[float]] = field(default_factory=dict)
    downtime_bound_s: float = 60.0
    # Goodput attribution (r13): per-cause lost-seconds counters scraped
    # from the live controller before teardown.
    goodput_scraped: bool = False
    lost_seconds: Dict[str, float] = field(default_factory=dict)
    # Device-state mode (r19, tentpole leg a): the chief's final params
    # digest vs the uninterrupted run's (the SAME jitted row update over
    # the canonical order), and the chief's merged ReshardPlan counters —
    # at least one row must have been re-laid-out device-to-device AND at
    # least one re-fetched, or the re-shard never actually ran.
    device_state: bool = False
    params_digest: str = ""
    expected_params_digest: str = ""
    reshard_plan: Dict[str, Any] = field(default_factory=dict)
    # Resize x preemption composition (r19, tentpole leg b): a fleet
    # preemption annotation stamped MID-SHRINK (the directive published,
    # the barrier not yet). The reconciler must defer the drain to the
    # post-resize epoch: the stamped shrink span closes BEFORE the
    # preemption restart span opens.
    preempt_during_resize: bool = False
    preempt_stamp_time: float = 0.0
    preempt_stamped_epoch: int = 0
    restart_windows: List[dict] = field(default_factory=list)
    # Store-observed quota oracle: live gang chips of every job in the
    # soak's Queue, polled continuously, must never exceed the quota —
    # held-for-regrow and mid-drain chips included (no double-count).
    quota_violations: List[str] = field(default_factory=list)

    @property
    def params_bit_identical(self) -> bool:
        return bool(self.params_digest) and (
            self.params_digest == self.expected_params_digest
        )

    @property
    def bit_identical(self) -> bool:
        return bool(self.digest) and self.digest == self.expected_digest

    @property
    def peer_restores(self) -> int:
        return sum(1 for s in self.restore_sources if s == "peer")

    def check(self) -> List[str]:
        errs = []
        if not self.succeeded:
            errs.append(f"job did not succeed: {self.conditions}")
        # THE tentpole gate: member loss + return handled entirely by
        # shrink/re-grow — zero full gang restarts of any flavor. The
        # composed drain-during-shrink schedule (r19) sanctions exactly
        # ONE gang teardown: the deliberately injected fleet preemption,
        # which must land as a preemption (never a counted restart).
        allowed_preempts = 1 if self.preempt_during_resize else 0
        if self.restart_count or self.preemption_count != allowed_preempts:
            errs.append(
                f"unexpected gang restarts (restarts="
                f"{self.restart_count} preemptions={self.preemption_count} "
                f"want 0/{allowed_preempts} "
                f"last_cause={self.last_restart_cause!r}) — member loss "
                "must resize, not restart"
            )
        kills = sum(
            1 for f in (self.schedule.faults if self.schedule else ())
            if f.kind is FaultKind.KILL_RETURN
        )
        if self.resize_count < 2 * kills:
            errs.append(
                f"resize_count {self.resize_count} < {2 * kills} "
                f"(each of {kills} kill/returns must shrink AND re-grow)"
            )
        directions = [h.get("direction") for h in self.resize_history]
        if "shrink" not in directions or "grow" not in directions:
            errs.append(f"resize history lacks a direction: {directions}")
        if self.partial_gang_violations:
            errs.append(
                f"unsanctioned partial gang: {self.partial_gang_violations}"
            )
        sched_kinds = [
            f.kind.value for f in (self.schedule.faults if self.schedule else ())
        ]
        applied_kinds = [a["kind"] for a in self.applied]
        if applied_kinds != sched_kinds:
            errs.append(
                f"applied fault sequence {applied_kinds} != schedule "
                f"{sched_kinds}"
            )
        if not self.bit_identical:
            errs.append(
                f"eval digest mismatch after resizes: got "
                f"{self.digest[:16] or '<none>'} want "
                f"{self.expected_digest[:16]} — a token was dropped, "
                "duplicated, or reordered"
            )
        if self.peer_restores < 1:
            errs.append(
                "no resize restored from a peer depot (restore sources: "
                f"{self.restore_sources}) — the re-grown member must pull "
                "missing shards from survivors, disk is last resort"
            )
        for w in self.resize_windows:
            if w.get("downtime_s") is None:
                errs.append(f"resize span never closed: {w}")
            elif w["downtime_s"] > self.downtime_bound_s:
                errs.append(
                    f"resize downtime {w['downtime_s']:.1f}s exceeds bound "
                    f"{self.downtime_bound_s:.0f}s: {w}"
                )
        # Goodput attribution (r13): resize downtime lands under
        # lost_seconds{cause="resize"} (same span-close point as the
        # downtime histogram) and never doubles into cause="restart" —
        # the elastic gate above already demands zero full restarts.
        if self.goodput_scraped:
            expected = sum(
                w["downtime_s"] for w in self.resize_windows
                if w.get("downtime_s") is not None
            )
            got = self.lost_seconds.get("resize", 0.0)
            if expected > 0 and abs(got - expected) > max(0.5, 0.05 * expected):
                errs.append(
                    f"lost_seconds{{cause=resize}} {got:.2f}s != closed "
                    f"resize-window downtime {expected:.2f}s"
                )
            if self.lost_seconds.get("restart", 0.0) > 0:
                errs.append(
                    "resize downtime leaked into cause=restart: "
                    f"{self.lost_seconds}"
                )
            # Satellite (r19): in the composed schedule the preemption's
            # own downtime lands under cause=preemption and equals its
            # own restart-span widths — resize and preemption never
            # double-count one outage, however they interleave.
            if self.preempt_during_resize:
                p_expected = sum(
                    w["downtime_s"] for w in self.restart_windows
                    if w.get("cause") == "preemption"
                    and w.get("downtime_s") is not None
                )
                p_got = self.lost_seconds.get("preemption", 0.0)
                if p_expected > 0 and abs(p_got - p_expected) > max(
                    0.5, 0.05 * p_expected
                ):
                    errs.append(
                        f"lost_seconds{{cause=preemption}} {p_got:.2f}s != "
                        f"closed preemption-window downtime "
                        f"{p_expected:.2f}s"
                    )
        # Device-state gates (r19 tentpole leg a): final params
        # bit-identical to the uninterrupted run, and the chief's merged
        # plan proves the re-shard both re-laid-out device rows AND
        # re-fetched rows other members advanced.
        if self.device_state:
            if not self.params_bit_identical:
                errs.append(
                    f"device-state params NOT bit-identical: got "
                    f"{self.params_digest[:16] or '<none>'} want "
                    f"{self.expected_params_digest[:16]} — a row was "
                    "lost, duplicated, or mis-sourced across a resize"
                )
            # A full restart (preemption drain) wipes every member's
            # device state, so the new chief's merged plan starts from
            # scratch and may legitimately contain zero device-to-device
            # re-layouts — the store re-fetch gate below still applies
            # (that is exactly how a wiped gang recovers the rows).
            if int(self.reshard_plan.get("relaid", 0) or 0) < 1 and not (
                self.restart_count or self.preemption_count
            ):
                errs.append(
                    f"re-shard never re-laid-out a device row: "
                    f"{self.reshard_plan}"
                )
            if int(self.reshard_plan.get("refetched", 0) or 0) < 1:
                errs.append(
                    f"re-shard never re-fetched a row from the store: "
                    f"{self.reshard_plan}"
                )
        # Composition gates (r19 tentpole leg b): the annotation landed
        # mid-shrink, and the drain was DEFERRED — the in-flight shrink
        # span closed before the preemption restart span opened.
        if self.preempt_during_resize:
            if not self.preempt_stamp_time:
                errs.append(
                    "composition probe never caught a shrink mid-flight "
                    "to stamp the preempt annotation"
                )
            preempts = [
                w for w in self.restart_windows
                if w.get("cause") == "preemption"
            ]
            if len(preempts) != 1:
                errs.append(
                    f"expected exactly one preemption restart window: "
                    f"{self.restart_windows}"
                )
            elif self.preempt_stamp_time:
                w = preempts[0]
                if w.get("downtime_s") is None:
                    errs.append(f"preemption restart span never closed: {w}")
                shrink = next(
                    (z for z in self.resize_windows
                     if z.get("direction") == "shrink"
                     and str(z.get("epoch")) == str(self.preempt_stamped_epoch)),
                    None,
                )
                if shrink is None or shrink.get("end") is None:
                    errs.append(
                        f"stamped shrink epoch {self.preempt_stamped_epoch} "
                        f"has no closed resize span: {self.resize_windows}"
                    )
                elif w["start"] < shrink["end"] - 1e-6:
                    errs.append(
                        f"drain NOT deferred: preemption restart opened at "
                        f"{w['start']:.3f} before the in-flight shrink "
                        f"closed at {shrink['end']:.3f}"
                    )
        if self.quota_violations:
            errs.append(
                f"store-observed quota violations "
                f"({len(self.quota_violations)}): {self.quota_violations[:3]}"
            )
        return errs


def _scrape_lost_seconds(metrics) -> Dict[str, float]:
    """{cause: seconds} from a live ControllerMetrics'
    ``tpujob_lost_seconds_total`` counters (parsed from exposition text so
    the soak reads the same surface Prometheus would)."""
    import re

    out: Dict[str, float] = {}
    for line in metrics.render().splitlines():
        m = re.match(
            r'tpujob_lost_seconds_total\{[^}]*cause="([^"]+)"[^}]*\} (\S+)',
            line,
        )
        if m:
            out[m.group(1)] = out.get(m.group(1), 0.0) + float(m.group(2))
    return out


def _percentile(xs: List[float], q: float) -> Optional[float]:
    vals = sorted(xs)
    if not vals:
        return None
    idx = min(len(vals) - 1, int(round(q * (len(vals) - 1))))
    return vals[idx]


def _elastic_phase_rates(
    records: List[dict], history: List[dict]
) -> Dict[str, Optional[float]]:
    """Positions/s before the first shrink, while shrunk, and after the
    first re-grow — from the durable consumption records' timestamps
    against the resize history's wall-clock marks."""
    ts = sorted(float(r["t"]) for r in records if "t" in r)
    shrinks = [float(h["time"]) for h in history
               if h.get("direction") == "shrink" and h.get("time")]
    if not ts or not shrinks:
        return {}
    s1 = shrinks[0]
    g1 = next((float(h["time"]) for h in history
               if h.get("direction") == "grow"
               and float(h.get("time", 0) or 0) > s1), None)

    def rate(a: float, b: Optional[float]) -> Optional[float]:
        if b is None or b <= a:
            return None
        n = sum(1 for t in ts if a <= t < b)
        return round(n / (b - a), 2)

    return {
        "before": rate(ts[0], s1),
        "during_shrink": rate(s1, g1),
        "after_regrow": rate(g1, ts[-1] + 1e-9) if g1 else None,
    }


class _QuotaOracle(threading.Thread):
    """Store-observed quota auditor (r19): at no sampled instant may the
    summed live chips of a queue's jobs exceed its ``quota_chips``.
    Over-spec loans are charged to the queue (grow-beyond-spec worlds
    must still fit inside it), so this single invariant covers normal
    admission, the composed resize×preemption schedule, AND the
    grow/reclaim probe. Reads the store like an external auditor —
    nothing the controller could fudge."""

    def __init__(
        self, store, queue_name: str, quota: int, poll_s: float = 0.15
    ):
        super().__init__(daemon=True)
        self.store = store
        self.queue_name = queue_name
        self.quota = int(quota)
        self.poll_s = poll_s
        self.violations: List[str] = []
        self._halt = threading.Event()

    def _sample(self) -> int:
        from tf_operator_tpu.api.types import LABEL_JOB_NAME

        used = 0
        for j in self.store.list("TPUJob", namespace="default"):
            if getattr(j.spec.scheduling, "queue", "") != self.queue_name:
                continue
            used += sum(
                max(p.spec.chips, 0)
                for p in self.store.list(
                    KIND_PROCESS,
                    namespace="default",
                    label_selector={LABEL_JOB_NAME: j.metadata.name},
                )
                if not p.is_finished()
            )
        return used

    def run(self) -> None:
        while not self._halt.is_set():
            try:
                used = self._sample()
                if used > self.quota and len(self.violations) < 32:
                    msg = (
                        f"queue {self.queue_name} quota {self.quota} "
                        f"exceeded: live chips = {used}"
                    )
                    if not self.violations or self.violations[-1] != msg:
                        self.violations.append(msg)
            except Exception:
                pass
            self._halt.wait(self.poll_s)

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=5)


def run_elastic_soak(
    seed: int = 0,
    schedule: Optional[FaultSchedule] = None,
    kills: int = 2,
    workers: int = 3,
    total_windows: int = 900,
    step_sleep_s: float = 0.06,
    checkpoint_every: int = 10,
    backoff_limit: int = 2,
    timeout: float = 150.0,
    workdir: Optional[str] = None,
    heartbeat_ttl: float = 2.0,
    downtime_bound_s: float = 60.0,
    device_state: bool = False,
    preempt_during_resize: bool = False,
    queue_quota: int = 0,
) -> ElasticSoakResult:
    """Seeded kill/return soak over an ELASTIC job (run_policy.elastic):
    every member loss must be absorbed by a shrink directive and every
    host return by a symmetric re-grow — zero full gang restarts, the
    consumed stream bit-identical to an uninterrupted run, and the
    re-grown member restoring from a surviving peer's shard depot.

    One member per host (each agent holds exactly one chip), so a killed
    member IS a lost host; agents run host-lifetime shard depots.

    r19 knobs:

    - ``device_state``: the workload carries a real params/opt pytree on
      device through every resize (train/reshard.py); the gate hardens
      to *bit-identical final params* vs the uninterrupted run.
    - ``preempt_during_resize``: a probe thread stamps the fleet preempt
      annotation the instant a shrink directive is mid-flight; the
      reconciler must DEFER the drain until the resize epoch closes
      (exactly one preemption restart, opening strictly after the
      stamped shrink span ends).
    - ``queue_quota``: creates a Queue with that many chips, binds the
      job to it, and runs a store-polling quota oracle for the whole
      soak — any sampled exceedance fails the run."""
    from tf_operator_tpu.train.data import elastic_global_order
    from tf_operator_tpu.workloads.elastic import _digest, _read_records

    schedule = (
        schedule if schedule is not None
        else default_elastic_schedule(seed, kills=kills)
    )
    tmp = workdir or tempfile.mkdtemp(prefix="tpujob-elastic-soak-")
    ckpt_dir = os.path.join(tmp, "ckpt")
    gang_dir = os.path.join(tmp, "gang")
    os.makedirs(gang_dir, exist_ok=True)
    job_name = "soak-elastic"

    store = Store()
    injector = ChaosInjector(
        schedule, store, job_name=job_name, checkpoint_dir=ckpt_dir,
    )
    agents = [
        HostAgent(
            injector.wrap(),
            f"soak-h{i}",
            total_chips=1,  # one member per host: a kill IS a host loss
            heartbeat_interval=0.25,
            backend=LocalProcessControl(
                injector.wrap(), log_dir=os.path.join(tmp, "logs")
            ),
            depot=True,  # survivors' depots are the re-grow restore source
        )
        for i in range(workers)
    ]
    injector.agents = {a.name: a for a in agents}
    fake = FakeProcessControl()
    ctl = TPUJobController(store, fake, resync_period=0.5)
    ctl.scheduler.heartbeat_ttl = heartbeat_ttl
    from tf_operator_tpu.dashboard import DashboardServer

    dashboard = DashboardServer(store, host="127.0.0.1", port=0)
    dashboard.start()
    ctl.api_url = dashboard.url

    env = dict(DATAPLANE_ENV)
    env["PYTHONPATH"] = _ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    job = TPUJob(
        metadata=ObjectMeta(name=job_name),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=workers,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.elastic:main",
                        env=env,
                        chips_per_process=1,
                    ),
                )
            },
            topology=TopologySpec(num_hosts=workers, chips_per_host=1),
        ),
    )
    job.spec.run_policy.backoff_limit = backoff_limit
    job.spec.run_policy.heartbeat_ttl_seconds = heartbeat_ttl
    job.spec.run_policy.elastic = True
    job.spec.workload = {
        "workdir": gang_dir,
        "total_windows": total_windows,
        "step_sleep_s": step_sleep_s,
        "data_seed": seed,
        "checkpoint_dir": ckpt_dir,
        "checkpoint_every": checkpoint_every,
        "checkpoint_backend": "npy",
        "elastic": True,
    }
    if device_state:
        job.spec.workload["device_state"] = True

    oracle: Optional[_QuotaOracle] = None
    queue_name = "elastic-soak-q"
    if queue_quota > 0:
        from tf_operator_tpu.sched.objects import Queue, QueueSpec

        store.create(
            Queue(
                metadata=ObjectMeta(name=queue_name, namespace="default"),
                spec=QueueSpec(quota_chips=queue_quota),
            )
        )
        job.spec.scheduling.queue = queue_name
        oracle = _QuotaOracle(store, queue_name, queue_quota)

    gang_names = [f"{job_name}-worker-{i}" for i in range(workers)]

    def sanctioned_subset() -> Optional[set]:
        """The member set the live shrink directive blesses, if any."""
        try:
            st = store.get("TPUJob", "default", job_name).status
        except Exception:
            return None
        d = st.resize_directive or {}
        if d.get("direction") == "shrink" and d.get("members"):
            return set(d["members"])
        return None

    watcher = _InvariantWatcher(
        store, job_name, gang_names, allowed_subset_fn=sanctioned_subset
    )
    result = ElasticSoakResult(
        schedule=schedule, downtime_bound_s=downtime_bound_s,
        device_state=device_state,
        preempt_during_resize=preempt_during_resize,
    )

    stamp_halt = threading.Event()

    def _stamp_preempt_mid_shrink() -> None:
        # Composition probe (r19 leg b): the instant a shrink directive
        # is in flight (published, barrier not yet closed), stamp the
        # fleet preempt annotation. The reconciler must defer the drain
        # to the post-resize epoch — check() verifies the preemption
        # restart span opens only after the stamped shrink span closed.
        from tf_operator_tpu.controller.reconciler import ANNOTATION_PREEMPT

        while not stamp_halt.is_set():
            try:
                j = store.get("TPUJob", "default", job_name)
                d = j.status.resize_directive or {}
                if (
                    d.get("direction") == "shrink"
                    and "boundary_remaining" not in d
                ):
                    epoch = int(d.get("epoch", 0) or 0)

                    def _stamp(fresh):
                        if fresh.metadata.annotations.get(ANNOTATION_PREEMPT):
                            return False
                        fresh.metadata.annotations[ANNOTATION_PREEMPT] = (
                            "chaos-soak/fleet-pressure"
                        )

                    if store.update_with_retry(
                        "TPUJob", "default", job_name, _stamp
                    ) is not None:
                        result.preempt_stamp_time = time.monotonic()
                        result.preempt_stamped_epoch = epoch
                    return
            except Exception:
                pass
            stamp_halt.wait(0.02)

    stamper = (
        threading.Thread(target=_stamp_preempt_mid_shrink, daemon=True)
        if preempt_during_resize else None
    )
    for a in agents:
        a.start()
    ctl.run(workers=2)
    watcher.start()
    if oracle is not None:
        oracle.start()
    try:
        store.create(job)
        injector.arm()
        if stamper is not None:
            stamper.start()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            st = store.get("TPUJob", "default", job_name).status
            if is_finished(st) and injector.done:
                break
            time.sleep(0.25)
        st = store.get("TPUJob", "default", job_name).status
        result.succeeded = has_condition(st, ConditionType.SUCCEEDED)
        result.restart_count = st.restart_count
        result.preemption_count = st.preemption_count
        result.resize_count = st.resize_count
        result.resize_epoch = st.resize_epoch
        result.world_size = st.world_size
        result.last_restart_cause = st.last_restart_cause
        result.resize_history = list(st.resize_history or [])
        result.conditions = [
            (c.type.value, c.reason, c.message) for c in st.conditions
        ]
        trace = job_trace(store, "default", job_name)
        result.resize_windows = [
            {
                "direction": s.attrs.get("direction", ""),
                "epoch": s.attrs.get("epoch", ""),
                "start": s.start_time,
                "end": s.end_time or None,
                "downtime_s": (
                    round(s.end_time - s.start_time, 3) if s.end_time else None
                ),
            }
            for s in trace if s.op == "resize"
        ]
        result.restart_windows = derive_timings(trace).get("restarts", [])
        result.restore_sources = [
            s.attrs.get("source", "disk")
            for s in sorted(
                (s for s in trace if s.op == "restore" and s.end_time),
                key=lambda s: s.start_time,
            )
        ]
        records = _read_records(gang_dir)
        result.tokens_per_s = _elastic_phase_rates(
            records, result.resize_history
        )
        digest_path = os.path.join(gang_dir, "eval_digest.txt")
        if os.path.exists(digest_path):
            with open(digest_path) as f:
                result.digest = f.read().strip()
        order = elastic_global_order(total_windows, seed=seed)
        result.expected_digest = _digest(
            [{"p": p, "w": int(order[p])} for p in range(total_windows)],
            total_windows,
        )
        if device_state:
            # Device-state receipts: the chief's done.json carries the
            # assembled-params digest and the merged re-shard plan; the
            # expected digest re-derives the uninterrupted run through
            # the SAME jitted update the members ran.
            import json as _json

            from tf_operator_tpu.train import reshard as _reshard

            done_path = os.path.join(gang_dir, "done.json")
            if os.path.exists(done_path):
                with open(done_path) as f:
                    done = _json.load(f)
                result.params_digest = done.get("params_digest", "")
                result.reshard_plan = dict(done.get("reshard", {}))
            result.expected_params_digest = _reshard.params_digest(
                _reshard.expected_params(
                    total_windows, _reshard.PARAM_DIM, seed, order
                )
            )
        result.lost_seconds = _scrape_lost_seconds(ctl.metrics)
        result.goodput_scraped = True
    finally:
        injector.stop()
        stamp_halt.set()
        if oracle is not None:
            oracle.stop()
            result.quota_violations = list(oracle.violations)
        watcher.stop()
        ctl.stop()
        for a in agents:
            a.stop()
        dashboard.stop()
        fake.clear()
    result.applied = list(injector.applied)
    result.partial_gang_violations = list(watcher.violations)
    leaked = [p.metadata.name for p in fake.created]
    if leaked:
        result.partial_gang_violations.append(
            "controller launched through its own backend in managed mode: "
            f"{leaked}"
        )
    return result


def elastic_artifact(result: ElasticSoakResult, seed: int) -> Dict[str, Any]:
    """The elasticbench receipt (one JSON object; CI writes it to
    ``artifacts/elasticbench_r12.json`` and ``genjob --bench-elastic``
    prints it on one line)."""
    downtimes = [
        w["downtime_s"] for w in result.resize_windows
        if w.get("downtime_s") is not None
    ]
    return {
        "bench": "elastic-soak",
        "seed": seed,
        "resize_count": result.resize_count,
        "resize_epoch": result.resize_epoch,
        "resizes": result.resize_windows,
        "resize_downtime_p50_s": _percentile(downtimes, 0.5),
        "resize_downtime_p99_s": _percentile(downtimes, 0.99),
        "tokens_per_s": result.tokens_per_s,
        "zero_full_restarts": (
            result.restart_count == 0
            and result.preemption_count
            == (1 if result.preempt_during_resize else 0)
        ),
        "restart_count": result.restart_count,
        "preemption_count": result.preemption_count,
        "digest": result.digest,
        "expected_digest": result.expected_digest,
        "bit_identical": result.bit_identical,
        "peer_restores": result.peer_restores,
        "restore_sources": result.restore_sources,
        "applied": result.applied,
        "lost_seconds": {
            k: round(v, 3) for k, v in sorted(result.lost_seconds.items())
        },
        **(
            {
                "params_digest": result.params_digest,
                "expected_params_digest": result.expected_params_digest,
                "params_bit_identical": result.params_bit_identical,
                "reshard": result.reshard_plan,
            }
            if result.device_state else {}
        ),
        **(
            {
                "preempt_stamped_epoch": result.preempt_stamped_epoch,
                "restart_windows": result.restart_windows,
                "quota_violations": result.quota_violations,
            }
            if result.preempt_during_resize else {}
        ),
        "pass": not result.check(),
    }


@dataclass
class GrowBeyondSpecResult:
    """Observations of one grow-beyond-spec probe (r19 tentpole leg c):
    a running elastic job with ``scheduling.elastic_max_world`` above its
    spec must borrow idle in-quota chips and grow past spec, then shrink
    cleanly back when a queued admission applies quota pressure — no
    restart, no backoff charge, and the queue never over quota."""

    spec_world: int = 0
    max_world: int = 0
    # Largest world_size ever observed on the primary job, and the
    # largest status.overspec_workers alongside it.
    grew_to: int = 0
    overspec_seen: int = 0
    primary_succeeded: bool = False
    pressure_succeeded: bool = False
    restart_count: int = 0
    preemption_count: int = 0
    final_overspec: int = 0
    resize_history: List[dict] = field(default_factory=list)
    conditions: List[tuple] = field(default_factory=list)
    pressure_conditions: List[tuple] = field(default_factory=list)
    quota_violations: List[str] = field(default_factory=list)

    def check(self) -> List[str]:
        errs = []
        if not self.primary_succeeded:
            errs.append(
                f"primary elastic job did not succeed: {self.conditions}"
            )
        if not self.pressure_succeeded:
            errs.append(
                f"pressure job did not succeed (reclaim never freed its "
                f"chips?): {self.pressure_conditions}"
            )
        if self.grew_to <= self.spec_world:
            errs.append(
                f"never grew beyond spec: world peaked at {self.grew_to} "
                f"(spec {self.spec_world}, elastic_max_world "
                f"{self.max_world})"
            )
        if self.overspec_seen < 1:
            errs.append("status.overspec_workers never went positive")
        if self.restart_count or self.preemption_count:
            errs.append(
                f"reclaim charged a restart (restarts={self.restart_count} "
                f"preemptions={self.preemption_count}) — over-spec "
                "reclaim must shrink, not tear down"
            )
        causes = {h.get("cause") for h in self.resize_history}
        if "grow-beyond-spec" not in causes:
            errs.append(
                f"resize history lacks a grow-beyond-spec entry: "
                f"{self.resize_history}"
            )
        if "overspec-reclaim" not in causes:
            errs.append(
                f"resize history lacks an overspec-reclaim entry: "
                f"{self.resize_history}"
            )
        if self.final_overspec:
            errs.append(
                f"job ended still holding an over-spec loan: "
                f"{self.final_overspec} member(s)"
            )
        if self.quota_violations:
            errs.append(
                f"store-observed quota violations "
                f"({len(self.quota_violations)}): {self.quota_violations[:3]}"
            )
        return errs


def run_grow_beyond_spec_probe(
    seed: int = 0,
    workers: int = 2,
    max_world: int = 3,
    total_windows: int = 600,
    step_sleep_s: float = 0.05,
    timeout: float = 120.0,
    workdir: Optional[str] = None,
) -> GrowBeyondSpecResult:
    """Grow-beyond-spec probe (r19 tentpole leg c). ``max_world`` hosts
    with one chip each, a Queue whose quota covers all of them, and an
    elastic job specced at ``workers`` with ``elastic_max_world`` =
    ``max_world``: the fleet must offer the idle in-quota chips and the
    job grow past spec. Then a 1-chip pressure job joins the queue —
    quota pressure must reclaim the loan FIRST (the job shrinks back to
    spec with no restart and no backoff charge) and the pressure job run
    to completion on the freed chip. A store-polling quota oracle audits
    the whole composition."""
    tmp = workdir or tempfile.mkdtemp(prefix="tpujob-grow-spec-")
    gang_dir = os.path.join(tmp, "gang")
    ckpt_dir = os.path.join(tmp, "ckpt")
    os.makedirs(gang_dir, exist_ok=True)
    primary, pressure = "grow-primary", "grow-pressure"
    queue_name = "grow-q"

    from tf_operator_tpu.sched.objects import Queue, QueueSpec

    store = Store()
    agents = [
        HostAgent(
            store,
            f"grow-h{i}",
            total_chips=1,
            heartbeat_interval=0.25,
            backend=LocalProcessControl(
                store, log_dir=os.path.join(tmp, "logs")
            ),
            depot=True,
        )
        for i in range(max_world)
    ]
    fake = FakeProcessControl()
    ctl = TPUJobController(store, fake, resync_period=0.5)
    from tf_operator_tpu.dashboard import DashboardServer

    dashboard = DashboardServer(store, host="127.0.0.1", port=0)
    dashboard.start()
    ctl.api_url = dashboard.url

    env = dict(DATAPLANE_ENV)
    env["PYTHONPATH"] = _ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    job = TPUJob(
        metadata=ObjectMeta(name=primary),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=workers,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.elastic:main",
                        env=env,
                        chips_per_process=1,
                    ),
                )
            },
            topology=TopologySpec(num_hosts=workers, chips_per_host=1),
        ),
    )
    job.spec.run_policy.elastic = True
    job.spec.run_policy.heartbeat_ttl_seconds = 2.0
    job.spec.scheduling.queue = queue_name
    job.spec.scheduling.elastic_max_world = max_world
    job.spec.workload = {
        "workdir": gang_dir,
        "total_windows": total_windows,
        "step_sleep_s": step_sleep_s,
        "data_seed": seed,
        "checkpoint_dir": ckpt_dir,
        "checkpoint_every": 10,
        "checkpoint_backend": "npy",
        "elastic": True,
    }
    presser = TPUJob(
        metadata=ObjectMeta(name=pressure),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=1,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.noop:main",
                        env=env,
                        chips_per_process=1,
                    ),
                )
            },
            topology=TopologySpec(num_hosts=1, chips_per_host=1),
        ),
    )
    presser.spec.scheduling.queue = queue_name
    presser.spec.workload = {"sleep_s": 2.0}

    store.create(
        Queue(
            metadata=ObjectMeta(name=queue_name, namespace="default"),
            spec=QueueSpec(quota_chips=max_world),
        )
    )
    oracle = _QuotaOracle(store, queue_name, max_world)
    result = GrowBeyondSpecResult(spec_world=workers, max_world=max_world)
    for a in agents:
        a.start()
    ctl.run(workers=2)
    oracle.start()
    try:
        store.create(job)
        deadline = time.monotonic() + timeout
        injected = False
        while time.monotonic() < deadline:
            st = store.get("TPUJob", "default", primary).status
            result.grew_to = max(result.grew_to, st.world_size)
            result.overspec_seen = max(
                result.overspec_seen, st.overspec_workers
            )
            if is_finished(st):
                if injected:
                    pst = store.get("TPUJob", "default", pressure).status
                    if is_finished(pst):
                        break
                else:
                    break  # finished before the pressure landed: probe fails
            if not injected and st.world_size >= max_world:
                # Beyond spec on loaned chips: now apply quota pressure.
                store.create(presser)
                injected = True
            time.sleep(0.1)
        st = store.get("TPUJob", "default", primary).status
        result.primary_succeeded = has_condition(st, ConditionType.SUCCEEDED)
        result.restart_count = st.restart_count
        result.preemption_count = st.preemption_count
        result.final_overspec = st.overspec_workers
        result.resize_history = list(st.resize_history or [])
        result.conditions = [
            (c.type.value, c.reason, c.message) for c in st.conditions
        ]
        if injected:
            pst = store.get("TPUJob", "default", pressure).status
            result.pressure_succeeded = has_condition(
                pst, ConditionType.SUCCEEDED
            )
            result.pressure_conditions = [
                (c.type.value, c.reason, c.message) for c in pst.conditions
            ]
    finally:
        oracle.stop()
        result.quota_violations = list(oracle.violations)
        ctl.stop()
        for a in agents:
            a.stop()
        dashboard.stop()
        fake.clear()
    return result


def run_elastic_general_soak(
    seed: int = 0, workdir: Optional[str] = None, timeout: float = 150.0
) -> Tuple[ElasticSoakResult, ElasticSoakResult, GrowBeyondSpecResult]:
    """The r19 acceptance composition (CI ``elastic-general-soak``):

    1. **device-state soak** — the r12 kill/return schedule with a real
       device param/opt pytree carried through every resize; gate is
       bit-identical final params + eval digest vs the uninterrupted
       run, with >=1 peer-depot shard restore.
    2. **drain-during-shrink** — one kill/return overlapped with a fleet
       preemption stamped mid-shrink, under a store-audited Queue; gate
       is the deferred drain (exactly one preemption restart, opening
       after the stamped shrink closed), zero quota violations, and the
       same bit-identity.
    3. **grow-beyond-spec probe** — world_size past spec on loaned
       in-quota chips, first-reclaimed cleanly under injected pressure.
    """
    base = workdir or tempfile.mkdtemp(prefix="tpujob-elastic-general-")
    device = run_elastic_soak(
        seed=seed, kills=2, workers=3, total_windows=900,
        step_sleep_s=0.06, device_state=True, timeout=timeout,
        workdir=os.path.join(base, "device"),
    )
    # Slow, short windows: each step is a wide stamp-landing target, so
    # the probe reliably catches the shrink between directive publish
    # and barrier completion.
    drain = run_elastic_soak(
        seed=seed + 1, kills=1, workers=3, total_windows=90,
        step_sleep_s=0.4, device_state=True, preempt_during_resize=True,
        queue_quota=3, timeout=timeout,
        workdir=os.path.join(base, "drain"),
    )
    grow = run_grow_beyond_spec_probe(
        seed=seed + 2, workdir=os.path.join(base, "grow"),
        timeout=timeout,
    )
    return device, drain, grow


def elastic_general_artifact(
    device: ElasticSoakResult,
    drain: ElasticSoakResult,
    grow: GrowBeyondSpecResult,
    seed: int,
) -> Dict[str, Any]:
    """The elasticbench receipt for the composed r19 acceptance (CI
    writes it to ``artifacts/elasticbench_r19.json``)."""
    return {
        "bench": "elastic-general-soak",
        "seed": seed,
        "device_state_soak": elastic_artifact(device, seed),
        "drain_during_shrink": elastic_artifact(drain, seed + 1),
        "grow_beyond_spec": {
            "spec_world": grow.spec_world,
            "elastic_max_world": grow.max_world,
            "grew_to": grow.grew_to,
            "overspec_seen": grow.overspec_seen,
            "restart_count": grow.restart_count,
            "preemption_count": grow.preemption_count,
            "resize_history": grow.resize_history,
            "quota_violations": grow.quota_violations,
            "pass": not grow.check(),
        },
        "pass": not (device.check() or drain.check() or grow.check()),
    }


def default_hang_schedule(seed: int) -> FaultSchedule:
    """The hang acceptance recipe: ONE whole-gang wedge, gated on the
    first checkpoint (warm recovery + at least one telemetry flush per
    rank before progress freezes). Pure function of the seed."""
    return FaultSchedule.generate_hang(seed, first_step=2, spread_s=0.0)


@dataclass
class HangSoakResult:
    """Observations of one hang soak (see check for the gates)."""

    succeeded: bool = False
    hang_count: int = 0
    restart_count: int = 0
    preemption_count: int = 0
    last_restart_cause: str = ""
    conditions: List[tuple] = field(default_factory=list)
    applied: List[dict] = field(default_factory=list)
    schedule: Optional[FaultSchedule] = None
    resume_steps: List[int] = field(default_factory=list)
    partial_gang_violations: List[str] = field(default_factory=list)
    # Hang spans from the trace: stuck step + measured downtime (span
    # start is BACKDATED to when progress stopped; close is gang-RUNNING
    # again — the span width IS the wedge window as charged to goodput).
    hang_windows: List[dict] = field(default_factory=list)
    # Declaration latency: stackdump_directive["time"] (when the
    # reconciler declared HUNG) minus the hang span's backdated start
    # (when progress actually stopped). >= hang_timeout by construction;
    # the gate bounds the slack above it.
    detect_latency_s: Optional[float] = None
    directive_epoch: int = 0
    ack_ranks: List[str] = field(default_factory=list)
    # The frozen bundle's payload (None = never frozen) and the shipped
    # per-rank stack dumps.
    bundle: Optional[Dict[str, Any]] = None
    bundle_reason: str = ""
    stackdumps: List[dict] = field(default_factory=list)
    goodput_scraped: bool = False
    lost_seconds: Dict[str, float] = field(default_factory=dict)
    workers: int = 0
    hang_timeout_s: float = 0.0
    detect_bound_s: float = 10.0
    downtime_bound_s: float = 60.0

    WEDGE_FRAME = "_fake_collective_all_reduce"

    def check(self) -> List[str]:
        errs = []
        if not self.succeeded:
            errs.append(f"job did not succeed: {self.conditions}")
        sched_kinds = [
            f.kind.value for f in (self.schedule.faults if self.schedule else ())
        ]
        applied_kinds = [a["kind"] for a in self.applied]
        if applied_kinds != sched_kinds:
            errs.append(
                f"applied fault sequence {applied_kinds} != schedule "
                f"{sched_kinds}"
            )
        if self.hang_count != 1:
            errs.append(
                f"hang_count {self.hang_count} != 1 (one wedge must yield "
                "exactly one declaration — the verdict latch failed)"
            )
        # Cause attribution: a hang restart is charged to restart_count
        # under ON_FAILURE (it consumes backoff budget) with the hang
        # cause, and it never reads as a preemption.
        if self.restart_count != 1 or self.last_restart_cause != "hang":
            errs.append(
                f"hang recovery miscounted: restart_count="
                f"{self.restart_count} last_restart_cause="
                f"{self.last_restart_cause!r} (want 1 / 'hang')"
            )
        if self.preemption_count:
            errs.append(
                f"hang leaked into preemption_count={self.preemption_count}"
            )
        if self.partial_gang_violations:
            errs.append(f"partial gang persisted: {self.partial_gang_violations}")
        # Detection bound: declared within hang_timeout + slack of the
        # moment progress stopped.
        if self.detect_latency_s is None:
            errs.append("no detection latency measurable (no declaration)")
        elif not (
            self.hang_timeout_s - 0.5
            <= self.detect_latency_s
            <= self.hang_timeout_s + self.detect_bound_s
        ):
            errs.append(
                f"detection latency {self.detect_latency_s:.2f}s outside "
                f"[{self.hang_timeout_s:.1f}, "
                f"{self.hang_timeout_s + self.detect_bound_s:.1f}]s"
            )
        # The wedge window, from the trace: exactly one hang span, closed
        # (the gang came back RUNNING), at least the timeout wide, under
        # the bound.
        if len(self.hang_windows) != 1:
            errs.append(f"expected exactly one hang span: {self.hang_windows}")
        for w in self.hang_windows:
            if w.get("downtime_s") is None:
                errs.append(f"hang span never closed: {w}")
            elif w["downtime_s"] > self.downtime_bound_s:
                errs.append(
                    f"hang downtime {w['downtime_s']:.1f}s exceeds bound "
                    f"{self.downtime_bound_s:.0f}s: {w}"
                )
            elif w["downtime_s"] < self.hang_timeout_s - 0.5:
                errs.append(
                    f"hang span {w['downtime_s']:.1f}s narrower than the "
                    f"timeout {self.hang_timeout_s:.1f}s — the start was "
                    "not backdated to when progress stopped"
                )
        # Warm recovery: the post-hang incarnation resumed, not retrained.
        if not any(s > 0 for s in self.resume_steps):
            errs.append(
                f"no warm restart observed (resume steps {self.resume_steps})"
            )
        # Bundle completeness: frozen with reason=hang, every rank's
        # stack present and naming the wedged frame, last telemetry
        # windows and the open hang span captured in the scene.
        if self.bundle is None:
            errs.append("no postmortem bundle was frozen")
        else:
            if self.bundle_reason != "hang":
                errs.append(f"bundle reason {self.bundle_reason!r} != 'hang'")
            stacks = self.bundle.get("stackdumps", [])
            got_ranks = sorted(int(s.get("rank", -1)) for s in stacks)
            if got_ranks != list(range(self.workers)):
                errs.append(
                    f"bundle stack ranks {got_ranks} != all ranks "
                    f"{list(range(self.workers))}"
                )
            for s in stacks:
                if self.WEDGE_FRAME not in s.get("text", ""):
                    errs.append(
                        f"rank {s.get('rank')} stack does not name the "
                        f"wedged frame {self.WEDGE_FRAME!r}"
                    )
            if not self.bundle.get("telemetry"):
                errs.append("bundle has no last-telemetry windows")
            if not any(
                sp.get("op") == "hang" and sp.get("open")
                for sp in self.bundle.get("spans", [])
            ):
                errs.append(
                    "bundle spans do not include the open hang span "
                    "(the scene was frozen after recovery, not before)"
                )
        # One hang ⇒ one stack sweep: every shipped dump belongs to the
        # single directive epoch, exactly one per rank.
        epochs = sorted({d["epoch"] for d in self.stackdumps})
        if self.stackdumps and epochs != [self.directive_epoch]:
            errs.append(
                f"stack dumps span sweep epochs {epochs} "
                f"(directive epoch {self.directive_epoch}) — sweep dedup "
                "failed"
            )
        if len(self.stackdumps) != self.workers:
            errs.append(
                f"{len(self.stackdumps)} stack dumps shipped for "
                f"{self.workers} ranks"
            )
        # Goodput attribution: the wedge window lands under
        # lost_seconds{cause="hang"} within 5%, with ZERO leakage into
        # the restart/resize causes (a hang recovery opens no restart
        # span).
        if self.goodput_scraped:
            expected = sum(
                w["downtime_s"] for w in self.hang_windows
                if w.get("downtime_s") is not None
            )
            got = self.lost_seconds.get("hang", 0.0)
            if expected > 0 and abs(got - expected) > max(0.5, 0.05 * expected):
                errs.append(
                    f"lost_seconds{{cause=hang}} {got:.2f}s != hang-window "
                    f"downtime {expected:.2f}s (±5%)"
                )
            for leak in ("restart", "preemption", "resize", "resize-shrink",
                         "resize-grow"):
                if self.lost_seconds.get(leak, 0.0) > 0:
                    errs.append(
                        f"hang downtime leaked into cause={leak}: "
                        f"{self.lost_seconds}"
                    )
        return errs


def run_hang_soak(
    seed: int = 0,
    schedule: Optional[FaultSchedule] = None,
    hosts: int = 2,
    num_hosts: int = 2,
    workers: int = 2,
    steps: int = 10,
    checkpoint_every: int = 2,
    backoff_limit: int = 2,
    hang_timeout: float = 4.0,
    timeout: float = 150.0,
    workdir: Optional[str] = None,
    heartbeat_ttl: float = 3.0,
    step_sleep_s: float = 0.4,
    detect_bound_s: float = 10.0,
    downtime_bound_s: float = 60.0,
) -> HangSoakResult:
    """Seeded whole-gang-wedge soak (the r15 acceptance rig).

    A HANG fault wedges every rank inside a named fake collective while
    heartbeats stay live. The gates: the watchdog declares within bound,
    the SIGUSR2 sweep ships every rank's stack naming the wedged frame,
    the bundle freezes the scene BEFORE recovery destroys it, the victim
    warm-resumes to Succeeded with ``last_restart_cause=hang`` and the
    restart charged per ON_FAILURE, and goodput attributes the wedge
    window to ``cause="hang"`` with zero leakage into restart/resize."""
    from tf_operator_tpu.obs.blackbox import job_stackdumps, load_postmortem

    schedule = (
        schedule if schedule is not None else default_hang_schedule(seed)
    )
    tmp = workdir or tempfile.mkdtemp(prefix="tpujob-hang-soak-")
    ckpt_dir = os.path.join(tmp, "ckpt")
    job_name = "soak-hang"

    store = Store()
    injector = ChaosInjector(
        schedule, store, job_name=job_name, checkpoint_dir=ckpt_dir,
    )
    agents = [
        HostAgent(
            injector.wrap(),
            f"soak-h{i}",
            total_chips=workers,
            heartbeat_interval=0.25,
            backend=LocalProcessControl(
                injector.wrap(), log_dir=os.path.join(tmp, "logs")
            ),
            stackdump_dir=os.path.join(tmp, "stackdumps", f"soak-h{i}"),
        )
        for i in range(hosts)
    ]
    injector.agents = {a.name: a for a in agents}
    fake = FakeProcessControl()
    ctl = TPUJobController(store, fake, resync_period=0.5)
    ctl.scheduler.heartbeat_ttl = heartbeat_ttl
    from tf_operator_tpu.dashboard import DashboardServer

    dashboard = DashboardServer(store, host="127.0.0.1", port=0)
    dashboard.start()
    ctl.api_url = dashboard.url

    job = _soak_job(
        job_name, workers, num_hosts, ckpt_dir, steps, checkpoint_every,
        backoff_limit, heartbeat_ttl, data_plane="light",
        step_sleep_s=step_sleep_s,
    )
    job.spec.run_policy.hang_timeout_seconds = hang_timeout

    gang_names = [f"{job_name}-worker-{i}" for i in range(workers)]
    watcher = _InvariantWatcher(store, job_name, gang_names)
    result = HangSoakResult(
        schedule=schedule, workers=workers, hang_timeout_s=hang_timeout,
        detect_bound_s=detect_bound_s, downtime_bound_s=downtime_bound_s,
    )
    for a in agents:
        a.start()
    ctl.run(workers=2)
    watcher.start()
    try:
        store.create(job)
        injector.arm()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            st = store.get("TPUJob", "default", job_name).status
            if is_finished(st) and injector.done:
                break
            time.sleep(0.25)
        st = store.get("TPUJob", "default", job_name).status
        result.succeeded = has_condition(st, ConditionType.SUCCEEDED)
        result.hang_count = st.hang_count
        result.restart_count = st.restart_count
        result.preemption_count = st.preemption_count
        result.last_restart_cause = st.last_restart_cause
        result.conditions = [
            (c.type.value, c.reason, c.message) for c in st.conditions
        ]
        directive = st.stackdump_directive or {}
        result.directive_epoch = int(directive.get("epoch", 0) or 0)
        result.ack_ranks = sorted((directive.get("acks") or {}).keys())
        trace = job_trace(store, "default", job_name)
        result.hang_windows = [
            {
                "stuck_step": s.attrs.get("stuck_step", ""),
                "start": s.start_time,
                "downtime_s": (
                    round(s.end_time - s.start_time, 3) if s.end_time else None
                ),
            }
            for s in trace if s.op == "hang"
        ]
        declared_at = float(directive.get("time", 0.0) or 0.0)
        hang_starts = [s.start_time for s in trace if s.op == "hang"]
        if declared_at and hang_starts:
            result.detect_latency_s = round(declared_at - min(hang_starts), 3)
        bundle = load_postmortem(store, "default", job_name)
        if bundle is not None:
            result.bundle = bundle.payload
            result.bundle_reason = bundle.reason
        result.stackdumps = [
            {
                "rank": d.rank, "epoch": d.epoch,
                "host": d.payload.get("host", ""),
                "names_wedge_frame": (
                    HangSoakResult.WEDGE_FRAME in d.payload.get("text", "")
                ),
            }
            for d in job_stackdumps(store, "default", job_name)
        ]
        result.lost_seconds = _scrape_lost_seconds(ctl.metrics)
        result.goodput_scraped = True
    finally:
        injector.stop()
        watcher.stop()
        ctl.stop()
        for a in agents:
            a.stop()
        dashboard.stop()
        fake.clear()
    result.resume_steps = list(watcher.resume_steps)
    result.partial_gang_violations = list(watcher.violations)
    result.applied = list(injector.applied)
    leaked = [p.metadata.name for p in fake.created]
    if leaked:
        result.partial_gang_violations.append(
            "controller launched through its own backend in managed mode: "
            f"{leaked}"
        )
    return result


def hang_artifact(result: HangSoakResult, seed: int) -> Dict[str, Any]:
    """The hangbench receipt (one JSON object; CI writes it to
    ``artifacts/hangbench_r15.json``)."""
    downtimes = [
        w["downtime_s"] for w in result.hang_windows
        if w.get("downtime_s") is not None
    ]
    return {
        "bench": "hang-soak",
        "seed": seed,
        "hang_timeout_s": result.hang_timeout_s,
        "hangs_total": result.hang_count,
        "detect_latency_s": result.detect_latency_s,
        "hang_windows": result.hang_windows,
        "hang_downtime_p50_s": _percentile(downtimes, 0.5),
        "wedge_frame": HangSoakResult.WEDGE_FRAME,
        "stackdumps": result.stackdumps,
        "all_ranks_named_wedge_frame": (
            len(result.stackdumps) == result.workers
            and all(d["names_wedge_frame"] for d in result.stackdumps)
        ),
        "bundle_frozen": result.bundle is not None,
        "bundle_reason": result.bundle_reason,
        "resume_steps": result.resume_steps,
        "restart_count": result.restart_count,
        "last_restart_cause": result.last_restart_cause,
        "lost_seconds": {
            k: round(v, 3) for k, v in sorted(result.lost_seconds.items())
        },
        "applied": result.applied,
        "pass": not result.check(),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tpujob-soak", description="seeded chaos soak runner"
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--hosts", type=int, default=3)
    p.add_argument("--num-hosts", type=int, default=2)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--checkpoint-every", type=int, default=2)
    p.add_argument("--backoff-limit", type=int, default=2)
    p.add_argument("--timeout", type=float, default=420.0)
    p.add_argument("--workdir", default=None)
    p.add_argument("--data-plane", choices=("light", "lm"), default="light",
                   help="'light' = real checkpoints, no cross-process "
                        "collectives (works everywhere); 'lm' = full gloo "
                        "LM trainer (needs multi-process-capable jax)")
    p.add_argument("--step-sleep", type=float, default=1.0,
                   help="light data plane: seconds per step (the fault "
                        "landing window)")
    p.add_argument("--downtime-bound", type=float, default=60.0,
                   help="max allowed preemption recovery downtime "
                        "(seconds), asserted from the trace's restart "
                        "spans (invariant 6)")
    p.add_argument("--operator-crash", action="store_true",
                   help="crash-recovery mode: the operator (durable store "
                        "+ controller + API) is killed and restarted "
                        "mid-run by a scheduled OPERATOR_CRASH fault while "
                        "agents ride RemoteStore retries; adds the "
                        "zero-duplicate-creates and restart-in-trace "
                        "invariants")
    p.add_argument("--p2p", action="store_true",
                   help="peer warm-restore mode: agents run host-lifetime "
                        "shard depots; invariant 9 requires >=1 restart to "
                        "restore from a peer, and recovery downtime is "
                        "measured through the restore span (effective)")
    p.add_argument("--disk-restore-delay", type=float, default=0.0,
                   help="modeled slow-store read (seconds) a DISK restore "
                        "pays in the light data plane; the peer path "
                        "skips it")
    p.add_argument("--compare-restore", action="store_true",
                   help="run the same seed twice (disk-only baseline, then "
                        "p2p) and assert the p2p effective-downtime p50 "
                        "cuts the disk baseline by >2x; writes "
                        "restore-compare.json under --workdir")
    p.add_argument("--elastic", action="store_true",
                   help="elastic soak: seeded kill/return schedule over an "
                        "elastic job — member loss must shrink (never full "
                        "restart), host return must re-grow, the consumed "
                        "stream must be bit-identical to an uninterrupted "
                        "run, and >=1 resize must restore from a peer depot")
    p.add_argument("--hang", action="store_true",
                   help="hang soak: a HANG fault wedges every rank inside "
                        "a fake collective (heartbeats stay live); gates "
                        "watchdog detection latency, the SIGUSR2 stack "
                        "sweep naming the wedged frame on every rank, the "
                        "frozen postmortem bundle, warm recovery with "
                        "last_restart_cause=hang, and goodput attribution "
                        "of the wedge window to cause=hang")
    p.add_argument("--hang-timeout", type=float, default=4.0,
                   help="hang soak: run_policy.hang_timeout_seconds")
    p.add_argument("--detect-bound", type=float, default=10.0,
                   help="hang soak: max allowed slack (seconds) of the "
                        "declaration past the hang timeout")
    p.add_argument("--autopilot-ab", action="store_true",
                   help="goodput-autopilot A/B soak: the same seed and "
                        "fault schedule run twice (run_policy.autopilot "
                        "off, then on); gates autopilot-on goodput_ratio "
                        ">= --min-goodput-gain x the off lane, the "
                        "per-decision span receipts, and the per-cause "
                        "lost-seconds == own-span-widths ledger invariant")
    p.add_argument("--min-goodput-gain", type=float, default=1.10,
                   help="autopilot A/B: required on/off goodput_ratio "
                        "multiple")
    p.add_argument("--save-stall-extra", type=float, default=0.8,
                   help="autopilot A/B: modeled per-save blocking stall "
                        "(seconds) the cadence retune amortizes")
    p.add_argument("--fleet-ledger", action="store_true",
                   help="fleet-ledger soak (r18): seeded crash-faulted "
                        "history jobs fold into a durable FleetLedger; "
                        "gates byte-identical /api/fleet/summary across an "
                        "operator kill+restart, record survival across job "
                        "GC, telemetry-coalesced WAL accounting, and the "
                        "prior A/B — a fresh job with use_fleet_priors "
                        "must make its FIRST cadence decision within 1.5x "
                        "of the converged Young/Daly optimum (receipted "
                        "with the prior numbers) while the no-prior lane "
                        "sits at the clamp edge")
    p.add_argument("--elastic-general", action="store_true",
                   help="composed r19 elastic acceptance: (1) the "
                        "kill/return soak with a REAL device param/opt "
                        "pytree re-sharded through every resize "
                        "(bit-identical final params), (2) a fleet "
                        "preemption stamped mid-shrink under a "
                        "store-audited Queue (drain deferred, zero quota "
                        "violations), (3) the grow-beyond-spec probe "
                        "(world past spec on loaned chips, clean "
                        "first-reclaim under pressure)")
    p.add_argument("--kills", type=int, default=2,
                   help="elastic soak: number of kill/return faults")
    p.add_argument("--total-windows", type=int, default=900,
                   help="elastic soak: corpus positions to consume")
    p.add_argument("--artifact", default=None,
                   help="elastic soak: also write the bench receipt JSON "
                        "to this path")
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s [%(levelname)s] %(message)s",
        stream=sys.stderr,
    )

    def one(p2p: bool, workdir: Optional[str]) -> SoakResult:
        return run_soak(
            seed=args.seed, steps=args.steps, hosts=args.hosts,
            num_hosts=args.num_hosts, workers=args.workers,
            checkpoint_every=args.checkpoint_every,
            backoff_limit=args.backoff_limit, timeout=args.timeout,
            workdir=workdir, data_plane=args.data_plane,
            step_sleep_s=args.step_sleep,
            downtime_bound_s=args.downtime_bound,
            operator_crash=args.operator_crash,
            p2p_restore=p2p, disk_restore_delay_s=args.disk_restore_delay,
        )

    def report(result: SoakResult, tag: str = "") -> List[str]:
        downtimes = [
            round(w["downtime_s"], 2) if w.get("downtime_s") is not None
            else None
            for w in result.restart_windows
        ]
        effective = [
            round(d, 2) if d is not None else None
            for d in result.effective_downtimes_s
        ]
        print(
            f"soak{tag} seed={args.seed}: succeeded={result.succeeded} "
            f"restarts={result.restart_count} "
            f"preemptions={result.preemption_count} "
            f"last_cause={result.last_restart_cause!r} "
            f"resume_steps={result.resume_steps} applied={result.applied} "
            f"trace_downtimes_s={downtimes} "
            f"effective_downtimes_s={effective} "
            f"restore_sources={result.restore_sources} "
            f"operator_restarts={result.operator_restarts} "
            f"gang_incarnations={result.gang_incarnations}"
        )
        errors = result.check()
        for e in errors:
            print(f"INVARIANT VIOLATED{tag}: {e}", file=sys.stderr)
        return errors

    if args.autopilot_ab:
        import json as _json

        # Deliberately NOT forwarding --steps/--step-sleep: the A/B's
        # lane geometry is sized so the recoverable stall dwarfs startup
        # noise (see run_autopilot_soak); the generic soak defaults
        # would shrink the signal into the noise floor.
        aresult = run_autopilot_soak(
            seed=args.seed,
            save_stall_extra_s=args.save_stall_extra,
            timeout=args.timeout, workdir=args.workdir,
            min_gain=args.min_goodput_gain,
        )
        artifact = autopilot_artifact(aresult, args.seed)
        print(_json.dumps(artifact))
        if args.artifact:
            os.makedirs(
                os.path.dirname(os.path.abspath(args.artifact)), exist_ok=True
            )
            with open(args.artifact, "w") as f:
                _json.dump(artifact, f, indent=2)
            print(f"autopilot A/B receipt -> {args.artifact}")
        errors = aresult.check()
        for e in errors:
            print(f"AUTOPILOT INVARIANT VIOLATED: {e}", file=sys.stderr)
        return 1 if errors else 0

    if args.fleet_ledger:
        import json as _json

        # Like --autopilot-ab, the lane geometry is deliberately NOT
        # driven by --steps/--step-sleep: the prior A/B needs the clamp
        # edge well clear of 1.5x the converged optimum.
        fresult = run_fleet_ledger_soak(
            seed=args.seed,
            save_stall_extra_s=args.save_stall_extra,
            timeout=args.timeout, workdir=args.workdir,
        )
        artifact = fleetledger_artifact(fresult, args.seed)
        print(_json.dumps(artifact))
        if args.artifact:
            os.makedirs(
                os.path.dirname(os.path.abspath(args.artifact)), exist_ok=True
            )
            with open(args.artifact, "w") as f:
                _json.dump(artifact, f, indent=2)
            print(f"fleet-ledger receipt -> {args.artifact}")
        errors = fresult.check()
        for e in errors:
            print(f"FLEET LEDGER INVARIANT VIOLATED: {e}", file=sys.stderr)
        return 1 if errors else 0

    if args.hang:
        import json as _json

        hresult = run_hang_soak(
            seed=args.seed, workers=args.workers, steps=args.steps,
            checkpoint_every=args.checkpoint_every,
            backoff_limit=args.backoff_limit,
            hang_timeout=args.hang_timeout, timeout=args.timeout,
            workdir=args.workdir, step_sleep_s=args.step_sleep,
            detect_bound_s=args.detect_bound,
            downtime_bound_s=args.downtime_bound,
        )
        artifact = hang_artifact(hresult, args.seed)
        print(_json.dumps(artifact))
        if args.artifact:
            os.makedirs(
                os.path.dirname(os.path.abspath(args.artifact)), exist_ok=True
            )
            with open(args.artifact, "w") as f:
                _json.dump(artifact, f, indent=2)
            print(f"hang soak receipt -> {args.artifact}")
        errors = hresult.check()
        for e in errors:
            print(f"HANG INVARIANT VIOLATED: {e}", file=sys.stderr)
        return 1 if errors else 0

    if args.elastic_general:
        import json as _json

        device, drain, grow = run_elastic_general_soak(
            seed=args.seed, workdir=args.workdir, timeout=args.timeout
        )
        artifact = elastic_general_artifact(device, drain, grow, args.seed)
        print(_json.dumps(artifact))
        if args.artifact:
            os.makedirs(
                os.path.dirname(os.path.abspath(args.artifact)), exist_ok=True
            )
            with open(args.artifact, "w") as f:
                _json.dump(artifact, f, indent=2)
            print(f"elastic-general receipt -> {args.artifact}")
        errors = []
        for tag, errs in (
            ("device-state", device.check()),
            ("drain-during-shrink", drain.check()),
            ("grow-beyond-spec", grow.check()),
        ):
            for e in errs:
                print(
                    f"ELASTIC INVARIANT VIOLATED [{tag}]: {e}",
                    file=sys.stderr,
                )
                errors.append(e)
        return 1 if errors else 0

    if args.elastic:
        import json as _json

        eresult = run_elastic_soak(
            seed=args.seed, kills=args.kills, workers=args.workers,
            total_windows=args.total_windows, timeout=args.timeout,
            workdir=args.workdir, backoff_limit=args.backoff_limit,
            downtime_bound_s=args.downtime_bound,
        )
        artifact = elastic_artifact(eresult, args.seed)
        print(_json.dumps(artifact))
        if args.artifact:
            os.makedirs(
                os.path.dirname(os.path.abspath(args.artifact)), exist_ok=True
            )
            with open(args.artifact, "w") as f:
                _json.dump(artifact, f, indent=2)
            print(f"elastic soak receipt -> {args.artifact}")
        errors = eresult.check()
        for e in errors:
            print(f"ELASTIC INVARIANT VIOLATED: {e}", file=sys.stderr)
        return 1 if errors else 0

    if not args.compare_restore:
        result = one(args.p2p, args.workdir)
        return 1 if report(result) else 0

    # Compare mode: same seed, same schedule, disk-only then p2p. The
    # disk baseline pays the modeled slow-store read on every restore;
    # the acceptance receipt is the p2p p50 cutting it by >2x.
    import json as _json

    root = args.workdir or tempfile.mkdtemp(prefix="tpujob-ckpt-soak-")
    disk = one(False, os.path.join(root, "disk"))
    errors = report(disk, tag="[disk]")
    p2p = one(True, os.path.join(root, "p2p"))
    errors += report(p2p, tag="[p2p]")

    def p50(xs: List[Optional[float]]) -> Optional[float]:
        vals = sorted(x for x in xs if x is not None)
        return vals[len(vals) // 2] if vals else None

    disk_p50, p2p_p50 = p50(disk.effective_downtimes_s), p50(
        p2p.effective_downtimes_s
    )
    if disk_p50 is None or p2p_p50 is None:
        errors.append(
            f"compare: missing effective downtimes (disk={disk_p50} "
            f"p2p={p2p_p50})"
        )
    elif not p2p_p50 * 2 < disk_p50:
        errors.append(
            f"compare: p2p effective-downtime p50 {p2p_p50:.2f}s does not "
            f"cut the disk baseline {disk_p50:.2f}s by >2x"
        )
    artifact = {
        "seed": args.seed,
        "disk_restore_delay_s": args.disk_restore_delay,
        "disk": {
            "effective_downtimes_s": disk.effective_downtimes_s,
            "restore_sources": disk.restore_sources,
            "p50_s": disk_p50,
        },
        "p2p": {
            "effective_downtimes_s": p2p.effective_downtimes_s,
            "restore_sources": p2p.restore_sources,
            "p50_s": p2p_p50,
        },
        "cut_factor": (
            disk_p50 / p2p_p50 if disk_p50 and p2p_p50 else None
        ),
        "pass": not errors,
    }
    path = os.path.join(root, "restore-compare.json")
    with open(path, "w") as f:
        _json.dump(artifact, f, indent=2)
    print(
        f"restore-compare: disk_p50={disk_p50} p2p_p50={p2p_p50} "
        f"cut={artifact['cut_factor']} -> {path}"
    )
    for e in errors:
        print(f"COMPARE FAILED: {e}", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
