"""Telemetry: live per-step data-plane metrics, batched through the store.

Spans (obs/spans.py) cover the *lifecycle* timeline — submit, schedule,
restart, resize. Once a gang is RUNNING the control plane was blind:
step time, throughput and MFU died inside the worker process
(train/metrics.py accumulators). A :class:`Telemetry` object is the
missing stream: each rank folds N steps into one compact batch and
writes it through the same store/API seam spans use, so the reconciler,
the dashboard and the CLI can all read the data plane live.

Design points:

- **Ring-buffered, hard-capped.** Each rank owns ``TELEMETRY_RING_SLOTS``
  slot objects named ``{job}-{trace8}-telem-r{rank}-s{seq % SLOTS}``; a
  new batch OVERWRITES the oldest slot (create, then replace on
  AlreadyExists). A job can therefore never hold more than
  ``SLOTS × ranks`` telemetry objects in the store, no matter how long
  it runs. ``seq`` is the monotonic batch counter; readers sort by it
  and the wrapped slot is simply the one with the smallest live seq.
- **Delta-batched.** Workers accumulate per-step durations locally and
  flush every ``flush_every`` steps — one small write per window per
  rank, not one per step.
- **Best-effort, degradable.** Mirrors the PR 11 cachesvc contract: a
  worker that cannot reach the API keeps training with local-only
  accounting and marks ``degraded`` on the next batch that does get
  through (plus a ``telemetry-degraded`` span attribute at close). A
  telemetry failure is NEVER a job failure.
- **GC'd with the job.** The reconciler deletes telemetry alongside
  spans when the owning job is deleted.

The module also hosts the two pure consumers so they are unit-testable
without a control plane: :func:`detect_stragglers` (median-ratio
outlier rule over one cross-rank window) with :class:`StragglerTracker`
(flap hysteresis), and :func:`goodput_decomposition` (productive vs
lost seconds by cause, folding span-derived restart/resize downtime
with telemetry-derived data-wait/ckpt-stall).
"""

from __future__ import annotations

import logging
import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from tf_operator_tpu.api.types import (
    API_GROUP,
    KIND_TELEMETRY,
    LABEL_GROUP,
    LABEL_JOB_NAME,
    ObjectMeta,
)
from tf_operator_tpu.obs.spans import trace8

# NOTE: same import rule as spans.py — no module-level import from
# tf_operator_tpu.runtime (runtime imports obs); store exception types
# are resolved lazily inside the recorder.

log = logging.getLogger("tpujob.obs")

# Per-rank ring size: the hard per-job store footprint is
# TELEMETRY_RING_SLOTS × ranks objects.
TELEMETRY_RING_SLOTS = 8

# Goodput cause taxonomy (docs/design.md §6.2). restart/resize are
# span-derived (single point of truth: the reconciler's span closes);
# the other three come from the telemetry stream / first-step span.
CAUSE_COMPILE_INIT = "compile-init"
CAUSE_DATA_WAIT = "data-wait"
CAUSE_CKPT_STALL = "ckpt-stall"
CAUSE_RESTART = "restart"
CAUSE_RESIZE = "resize"
# Hang (r15): span-derived like restart/resize — the watchdog opens a
# dedicated "hang" span at declaration and the reconciler closes it when
# the recovered gang is running again, so hang downtime is attributed to
# exactly one cause (the recovery restart deliberately does NOT open a
# "restart" span; docs/design.md §6.3 cause-attribution rule).
CAUSE_HANG = "hang"
# Preemption (r19): span-derived like restart — the reconciler opens the
# same "restart" span for a preemption drain but stamps cause=preemption
# in the span attrs, and both decompose() and the controller's
# lost-seconds counter split on that attr. Keeping preempted downtime
# out of cause=restart matters because the two have different remedies
# (quota/priority policy vs. crash-loop debugging) and different
# accounting (preemptions never charge the backoff budget).
CAUSE_PREEMPTION = "preemption"
GOODPUT_CAUSES = (
    CAUSE_COMPILE_INIT,
    CAUSE_DATA_WAIT,
    CAUSE_CKPT_STALL,
    CAUSE_RESTART,
    CAUSE_RESIZE,
    CAUSE_HANG,
    CAUSE_PREEMPTION,
)


@dataclass
class Telemetry:
    """One rank's step-window batch (store object, ring-buffered)."""

    metadata: ObjectMeta = field(default_factory=ObjectMeta)
    trace_id: str = ""  # job uid
    rank: int = 0
    host: str = ""
    seq: int = 0  # monotonic batch counter per rank (ring wraps, seq doesn't)
    start_step: int = 0  # first step folded into this batch (inclusive)
    end_step: int = 0  # last step folded into this batch (inclusive)
    steps: int = 0  # number of steps in the window
    step_time_s: float = 0.0  # mean wall-clock step time over the window
    tokens_per_s: float = 0.0
    mfu: float = 0.0
    data_wait_s: float = 0.0  # total input-pipeline wait inside the window
    ckpt_stall_s: float = 0.0  # total checkpoint save stall inside the window
    # Run-cumulative stall totals (since start_step of this incarnation):
    # the ring evicts old windows, so per-window deltas under-count a long
    # run — goodput accounting reads these off each rank's LATEST batch,
    # which the ring never evicts.
    data_wait_total_s: float = 0.0
    ckpt_stall_total_s: float = 0.0
    degraded: int = 0  # 1 ⇒ earlier batches were lost to API unreachability
    time: float = 0.0  # wall-clock flush time
    kind: str = KIND_TELEMETRY

    def key(self) -> str:
        return self.metadata.key()


def telemetry_labels(job_name: str) -> Dict[str, str]:
    """Same indexed job-name label as spans: listing a job's telemetry is
    one bucket read."""
    return {LABEL_GROUP: API_GROUP, LABEL_JOB_NAME: job_name}


def telemetry_slot_name(job_name: str, trace_id: str, rank: int, seq: int) -> str:
    """Deterministic ring-slot name; batch ``seq`` lands in slot
    ``seq % TELEMETRY_RING_SLOTS``, overwriting the batch from
    ``TELEMETRY_RING_SLOTS`` windows ago."""
    slot = seq % TELEMETRY_RING_SLOTS
    return f"{job_name}-{trace8(trace_id)}-telem-r{rank}-s{slot}"


class TelemetryRecorder:
    """Best-effort ring-buffer writer (one per worker process).

    ``store`` is anything with the Store CRUD surface (Store, RemoteStore).
    ``degraded`` latches True after the first failed write and is cleared
    only by reading it — the reporter folds it into the next successful
    batch so the gap is visible downstream.
    """

    def __init__(self, store: Any) -> None:
        self._store = store
        self.degraded = False

    def record(self, batch: Telemetry) -> Optional[Telemetry]:
        """Write one batch into its ring slot (create, replace on
        AlreadyExists). Returns the stored object or None on failure —
        never raises."""
        if not batch.trace_id or not batch.metadata.name:
            return None
        try:
            return self._store.create(batch)
        except Exception as exc:  # noqa: BLE001 — telemetry is best-effort
            try:
                from tf_operator_tpu.runtime.store import AlreadyExistsError

                if isinstance(exc, AlreadyExistsError):
                    return self._replace(batch)
            except Exception:  # noqa: BLE001
                pass
            log.debug(
                "telemetry %s/%s not recorded: %s",
                batch.metadata.namespace, batch.metadata.name, exc,
            )
            self.degraded = True
            return None

    def _replace(self, batch: Telemetry) -> Optional[Telemetry]:
        """Overwrite an existing ring slot with the new batch's payload."""

        def mutate(cur):
            for f in (
                "trace_id", "rank", "host", "seq", "start_step", "end_step",
                "steps", "step_time_s", "tokens_per_s", "mfu", "data_wait_s",
                "ckpt_stall_s", "data_wait_total_s", "ckpt_stall_total_s",
                "degraded", "time",
            ):
                setattr(cur, f, getattr(batch, f))

        try:
            return self._store.update_with_retry(
                KIND_TELEMETRY, batch.metadata.namespace,
                batch.metadata.name, mutate,
            )
        except Exception as exc:  # noqa: BLE001
            log.debug(
                "telemetry slot %s/%s not replaced: %s",
                batch.metadata.namespace, batch.metadata.name, exc,
            )
            self.degraded = True
            return None


def job_telemetry(store: Any, namespace: str, job_name: str) -> List[Telemetry]:
    """Every live telemetry batch of a job, ordered (rank, seq). Served
    from the store's job-name label index, like job_trace."""
    batches = store.list(
        KIND_TELEMETRY, namespace=namespace,
        label_selector={LABEL_JOB_NAME: job_name},
    )
    batches.sort(key=lambda b: (b.rank, b.seq))
    return batches


def latest_window(batches: List[Telemetry]) -> Dict[int, Telemetry]:
    """Newest batch per rank (highest seq)."""
    out: Dict[int, Telemetry] = {}
    for b in batches:
        cur = out.get(b.rank)
        if cur is None or b.seq > cur.seq:
            out[b.rank] = b
    return out


def telemetry_summary(batches: List[Telemetry]) -> Dict[str, Any]:
    """Live roll-up for /telemetry, ``tpujob top`` and the dashboard:
    gang tokens/s + mean MFU from the newest window per rank, and the
    per-rank step-time spread (max/median ratio — the straggler signal)."""
    window = latest_window(batches)
    if not window:
        return {
            "ranks": 0, "tokens_per_s": 0.0, "mfu": 0.0,
            "step_time_s": {}, "spread": 0.0, "last_step": 0,
        }
    times = {r: b.step_time_s for r, b in window.items() if b.step_time_s > 0}
    med = statistics.median(times.values()) if times else 0.0
    spread = (max(times.values()) / med) if med > 0 else 0.0
    mfus = [b.mfu for b in window.values() if b.mfu > 0]
    return {
        "ranks": len(window),
        "tokens_per_s": sum(b.tokens_per_s for b in window.values()),
        "mfu": (sum(mfus) / len(mfus)) if mfus else 0.0,
        "step_time_s": {str(r): round(b.step_time_s, 6) for r, b in sorted(window.items())},
        "spread": round(spread, 4),
        "last_step": max(b.end_step for b in window.values()),
        "degraded": int(any(b.degraded for b in window.values())),
    }


# ---------------------------------------------------------------------------
# Straggler detection (pure; the reconciler drives it)
# ---------------------------------------------------------------------------

# A rank is an outlier when its window step time exceeds RATIO × the
# cross-rank median. Median-based so a uniformly slow gang (all ranks
# slow: compile, global input stall) moves the baseline instead of
# flagging everyone.
STRAGGLER_RATIO = 1.5
# Minimum gang size for a meaningful median comparison.
STRAGGLER_MIN_RANKS = 3
# Hysteresis: flag after N consecutive outlier windows, clear after N
# consecutive clean ones — a single noisy window never flips state.
STRAGGLER_FLAG_WINDOWS = 2
STRAGGLER_CLEAR_WINDOWS = 2


def detect_stragglers(
    step_times: Dict[int, float],
    ratio: float = STRAGGLER_RATIO,
    min_ranks: int = STRAGGLER_MIN_RANKS,
) -> List[int]:
    """One window's outlier ranks by the median-ratio rule.

    ``step_times`` maps rank → mean step seconds for the same window.
    Returns [] when the gang is too small, the window is empty, or every
    rank moves together (all-slow ⇒ median moves ⇒ nobody flagged).
    """
    times = {r: t for r, t in step_times.items() if t > 0}
    if len(times) < min_ranks:
        return []
    med = statistics.median(times.values())
    if med <= 0:
        return []
    return sorted(r for r, t in times.items() if t > ratio * med)


@dataclass
class HostRisk:
    """Typed straggler-risk snapshot for one rank (r16 satellite).

    Produced by :meth:`StragglerTracker.host_risk` so the reconciler's
    `_check_stragglers` surface (gauges, events, slow-host annotations)
    and the autopilot (pre-emptive migrate, place_gang deprioritization)
    read ONE shared struct instead of each re-deriving risk from
    gauges. ``host`` is filled in by the reconciler's rank→host mapping
    — the tracker itself only knows ranks."""

    rank: int
    host: str = ""
    flagged: bool = False
    flag_age_windows: int = 0  # windows since the flag fired (0 = unflagged)
    slow_ratio: float = 0.0  # last window's step time / cross-rank median
    flap_count: int = 0  # completed flag→clear cycles (chronic flapper)
    consecutive_bad: int = 0  # current outlier streak (pre-flag ramp)


class StragglerTracker:
    """Per-job flap damping over detect_stragglers verdicts.

    ``observe(window)`` consumes one cross-rank window and returns
    (newly_flagged, newly_cleared) rank lists. A rank must be an outlier
    in ``flag_windows`` CONSECUTIVE windows to flag, and clean in
    ``clear_windows`` consecutive windows to clear — a host flapping
    between fast and slow never commits either way.
    """

    def __init__(
        self,
        ratio: float = STRAGGLER_RATIO,
        min_ranks: int = STRAGGLER_MIN_RANKS,
        flag_windows: int = STRAGGLER_FLAG_WINDOWS,
        clear_windows: int = STRAGGLER_CLEAR_WINDOWS,
    ) -> None:
        self.ratio = ratio
        self.min_ranks = min_ranks
        self.flag_windows = flag_windows
        self.clear_windows = clear_windows
        self._bad: Dict[int, int] = {}  # rank -> consecutive outlier windows
        self._good: Dict[int, int] = {}  # rank -> consecutive clean windows
        self.flagged: Dict[int, int] = {}  # rank -> windows-to-flag when it fired
        self.windows_seen = 0
        self._flaps: Dict[int, int] = {}  # rank -> completed flag→clear cycles
        self._last_ratio: Dict[int, float] = {}  # rank -> last window t/median

    def observe(self, step_times: Dict[int, float]) -> Tuple[List[int], List[int]]:
        self.windows_seen += 1
        outliers = set(
            detect_stragglers(step_times, ratio=self.ratio, min_ranks=self.min_ranks)
        )
        times = {r: t for r, t in step_times.items() if t > 0}
        med = statistics.median(times.values()) if len(times) >= self.min_ranks else 0.0
        for rank, t in times.items():
            self._last_ratio[rank] = (t / med) if med > 0 else 0.0
        newly_flagged: List[int] = []
        newly_cleared: List[int] = []
        for rank in step_times:
            if rank in outliers:
                self._bad[rank] = self._bad.get(rank, 0) + 1
                self._good[rank] = 0
                if self._bad[rank] >= self.flag_windows and rank not in self.flagged:
                    self.flagged[rank] = self.windows_seen
                    newly_flagged.append(rank)
            else:
                self._good[rank] = self._good.get(rank, 0) + 1
                self._bad[rank] = 0
                if rank in self.flagged and self._good[rank] >= self.clear_windows:
                    del self.flagged[rank]
                    self._flaps[rank] = self._flaps.get(rank, 0) + 1
                    newly_cleared.append(rank)
        return newly_flagged, newly_cleared

    def host_risk(self) -> Dict[int, HostRisk]:
        """Typed risk snapshot for every rank the tracker has seen; the
        one struct `_check_stragglers` and the autopilot share."""
        out: Dict[int, HostRisk] = {}
        ranks = (
            set(self._last_ratio) | set(self.flagged) | set(self._bad)
        )
        for rank in sorted(ranks):
            flagged = rank in self.flagged
            out[rank] = HostRisk(
                rank=rank,
                flagged=flagged,
                flag_age_windows=(
                    self.windows_seen - self.flagged[rank] if flagged else 0
                ),
                slow_ratio=self._last_ratio.get(rank, 0.0),
                flap_count=self._flaps.get(rank, 0),
                consecutive_bad=self._bad.get(rank, 0),
            )
        return out


# ---------------------------------------------------------------------------
# Goodput accounting (pure; reconciler + /telemetry endpoint share it)
# ---------------------------------------------------------------------------


def goodput_decomposition(
    spans: List[Any],
    batches: List[Telemetry],
    submit: float,
    end: float,
) -> Dict[str, Any]:
    """Productive vs lost seconds for one job, by cause.

    - ``compile-init``: submit → first step (the ``first-step`` span's
      start, i.e. everything before the data plane produced work).
    - ``data-wait`` / ``ckpt-stall``: summed from telemetry batches,
      averaged across ranks (they stall the same wall-clock gang step,
      so summing over ranks would over-count the gang's lost wall time).
    - ``restart`` / ``resize``: widths of closed restart/resize spans —
      the same single source the downtime histograms observe, so the
      two surfaces can never disagree or double-count.

    Returns {"wall_s", "lost_s": {cause: s}, "goodput_ratio"} with the
    ratio clamped to [0, 1].
    """
    wall = max(0.0, end - submit)
    lost = {c: 0.0 for c in GOODPUT_CAUSES}
    for s in spans:
        if s.op == "first-step" and s.start_time > 0:
            lost[CAUSE_COMPILE_INIT] = min(wall, max(0.0, s.start_time - submit))
        elif s.op == "restart" and s.end_time:
            attrs = getattr(s, "attrs", None) or {}
            cause = (
                CAUSE_PREEMPTION
                if attrs.get("cause") == CAUSE_PREEMPTION
                else CAUSE_RESTART
            )
            lost[cause] += max(0.0, s.end_time - s.start_time)
        elif s.op == "resize" and s.end_time:
            lost[CAUSE_RESIZE] += max(0.0, s.end_time - s.start_time)
        elif s.op == "hang" and s.end_time:
            lost[CAUSE_HANG] += max(0.0, s.end_time - s.start_time)
    # Per-rank stall totals: prefer the run-cumulative counters on each
    # rank's LATEST batch (eviction-proof — the ring drops old windows but
    # never the newest), falling back to summing window deltas for
    # producers that predate the cumulative fields.
    latest: Dict[int, Telemetry] = {}
    deltas: Dict[int, Dict[str, float]] = {}
    for b in batches:
        if b.rank not in latest or b.seq > latest[b.rank].seq:
            latest[b.rank] = b
        acc = deltas.setdefault(b.rank, {"dw": 0.0, "cs": 0.0})
        acc["dw"] += max(0.0, b.data_wait_s)
        acc["cs"] += max(0.0, b.ckpt_stall_s)
    if latest:
        n = len(latest)
        dw = cs = 0.0
        for rank, b in latest.items():
            if b.data_wait_total_s > 0 or b.ckpt_stall_total_s > 0:
                dw += max(0.0, b.data_wait_total_s)
                cs += max(0.0, b.ckpt_stall_total_s)
            else:
                dw += deltas[rank]["dw"]
                cs += deltas[rank]["cs"]
        lost[CAUSE_DATA_WAIT] = dw / n
        lost[CAUSE_CKPT_STALL] = cs / n
    total_lost = min(wall, sum(lost.values()))
    ratio = 1.0 if wall <= 0 else max(0.0, min(1.0, 1.0 - total_lost / wall))
    return {
        "wall_s": round(wall, 6),
        "lost_s": {c: round(v, 6) for c, v in lost.items()},
        "goodput_ratio": round(ratio, 6),
    }


# ---------------------------------------------------------------------------
# Worker-side reporter (JobContext constructs it; workloads drive it)
# ---------------------------------------------------------------------------


class StepTelemetry:
    """Per-rank step accumulator + delta batcher + profile-directive arm.

    The workload step loop calls ``step(duration_s, ...)`` once per
    completed step; every ``flush_every`` steps the window folds into one
    Telemetry batch and ships through ``recorder``. With a ``poll``
    callback (JobContext wires poll_profile_directive), each flush also
    checks for a new on-demand profile directive; the chief then wraps
    the next N steps in train.profile.profile_ctx and reports the capture
    via ``on_capture`` (epoch, steps, path) when the window closes.

    Everything here is best-effort: a dead API degrades to local-only
    accounting (``degraded`` latches; the next delivered batch carries
    it), never an exception into the step loop.
    """

    def __init__(
        self,
        recorder: Optional[TelemetryRecorder],
        namespace: str,
        job_name: str,
        trace_id: str,
        rank: int,
        host: str = "",
        flush_every: int = 10,
        tokens_per_step: float = 0.0,
        flops_per_step: float = 0.0,
        n_chips: int = 1,
        start_step: int = 0,
        poll_directive: Optional[Callable[[], Optional[Dict[str, Any]]]] = None,
        on_capture: Optional[Callable[[int, int, str], None]] = None,
        profile_root: str = "",
    ) -> None:
        self._recorder = recorder
        self.namespace = namespace
        self.job_name = job_name
        self.trace_id = trace_id
        self.rank = rank
        self.host = host
        self.flush_every = max(1, int(flush_every))
        self.tokens_per_step = float(tokens_per_step)
        self.flops_per_step = float(flops_per_step)
        self.n_chips = max(1, int(n_chips))
        self._step = int(start_step)
        self._window_start = int(start_step) + 1
        self._durations: List[float] = []
        self._data_wait = 0.0
        self._ckpt_stall = 0.0
        self._data_wait_total = 0.0
        self._ckpt_stall_total = 0.0
        self.seq = 0
        self.batches_sent = 0
        self._poll = poll_directive
        self._on_capture = on_capture
        self._profile_root = profile_root
        self._profile_epoch_done = 0
        self._profile: Optional[Dict[str, Any]] = None  # armed capture state

    @property
    def degraded(self) -> bool:
        return bool(self._recorder and self._recorder.degraded)

    def step(
        self,
        duration_s: float,
        data_wait_s: float = 0.0,
        ckpt_stall_s: float = 0.0,
        now: Optional[float] = None,
    ) -> None:
        """Account one completed step; flushes on window boundaries."""
        self._step += 1
        self._durations.append(max(0.0, float(duration_s)))
        self._data_wait += max(0.0, float(data_wait_s))
        self._ckpt_stall += max(0.0, float(ckpt_stall_s))
        self._data_wait_total += max(0.0, float(data_wait_s))
        self._ckpt_stall_total += max(0.0, float(ckpt_stall_s))
        self._tick_profile()
        if len(self._durations) >= self.flush_every:
            self.flush(now=now)

    def flush(self, now: Optional[float] = None) -> Optional[Telemetry]:
        """Fold the open window into one batch and ship it (best-effort).
        Also the profile-directive poll point (between-steps boundary)."""
        batch: Optional[Telemetry] = None
        if self._durations:
            now = time.time() if now is None else now
            mean = sum(self._durations) / len(self._durations)
            batch = Telemetry(
                metadata=ObjectMeta(
                    name=telemetry_slot_name(
                        self.job_name, self.trace_id, self.rank, self.seq
                    ),
                    namespace=self.namespace,
                    labels=telemetry_labels(self.job_name),
                ),
                trace_id=self.trace_id,
                rank=self.rank,
                host=self.host,
                seq=self.seq,
                start_step=self._window_start,
                end_step=self._step,
                steps=len(self._durations),
                step_time_s=mean,
                tokens_per_s=(self.tokens_per_step / mean) if mean > 0 else 0.0,
                mfu=self._mfu(mean),
                data_wait_s=self._data_wait,
                ckpt_stall_s=self._ckpt_stall,
                data_wait_total_s=self._data_wait_total,
                ckpt_stall_total_s=self._ckpt_stall_total,
                degraded=1 if self.degraded else 0,
                time=now,
            )
            if self._recorder is not None:
                was_degraded = self._recorder.degraded
                if self._recorder.record(batch) is not None:
                    self.batches_sent += 1
                    # Delivered: clear the latch AFTER stamping this batch,
                    # so the gap stays visible exactly once.
                    if was_degraded:
                        self._recorder.degraded = False
            self.seq += 1
            self._durations = []
            self._data_wait = 0.0
            self._ckpt_stall = 0.0
            self._window_start = self._step + 1
        self._maybe_arm_profile()
        return batch

    def close(self) -> None:
        """Final flush + abort any capture still open (best-effort)."""
        self.flush()
        self._finish_profile(aborted=True)

    # -- MFU ----------------------------------------------------------------

    def _mfu(self, mean_step_s: float) -> float:
        if not self.flops_per_step or mean_step_s <= 0:
            return 0.0
        from tf_operator_tpu.train.metrics import mfu

        # off-TPU there is no MFU: the gauge stays 0, not a number
        # against an invented peak
        return float(mfu(self.flops_per_step, mean_step_s, self.n_chips) or 0.0)

    # -- on-demand profiling ------------------------------------------------

    def _maybe_arm_profile(self) -> None:
        if self._poll is None or self._profile is not None:
            return
        try:
            directive = self._poll()
        except Exception:  # noqa: BLE001
            return
        if not directive:
            return
        epoch = int(directive.get("epoch", 0) or 0)
        steps = int(directive.get("steps", 0) or 0)
        if epoch <= self._profile_epoch_done or steps <= 0:
            return
        root = directive.get("dir") or self._profile_root
        if not root:
            return
        try:
            from tf_operator_tpu.train.profile import profile_ctx

            cm = profile_ctx(str(root))
            cm.__enter__()
        except Exception as exc:  # noqa: BLE001 — profiler missing ⇒ skip
            log.debug("profile capture (epoch %d) not armed: %s", epoch, exc)
            self._profile_epoch_done = epoch
            return
        self._profile = {
            "epoch": epoch, "steps": steps, "remaining": steps,
            "dir": str(root), "cm": cm, "start": time.time(),
        }

    def _tick_profile(self) -> None:
        if self._profile is None:
            return
        self._profile["remaining"] -= 1
        if self._profile["remaining"] <= 0:
            self._finish_profile(aborted=False)

    def _finish_profile(self, aborted: bool) -> None:
        prof = self._profile
        if prof is None:
            return
        self._profile = None
        try:
            prof["cm"].__exit__(None, None, None)
        except Exception as exc:  # noqa: BLE001
            log.debug("profile capture (epoch %d) stop failed: %s",
                      prof["epoch"], exc)
        self._profile_epoch_done = prof["epoch"]
        if aborted or self._on_capture is None:
            return
        try:
            self._on_capture(prof["epoch"], prof["steps"], prof["dir"])
        except Exception as exc:  # noqa: BLE001
            log.debug("profile capture (epoch %d) not reported: %s",
                      prof["epoch"], exc)
