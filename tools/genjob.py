"""TPUJob load generator + control-plane bench.

Reference parity: hack/genjob/genjob.go — templated job generation for
controller load/gang-scheduling experiments (``--nr-tfjobs``,
``--scheduler-name``); here ``--nr-jobs`` with optional direct submission
so one command can put O(100) concurrent jobs on the operator (the
reference's design scale target, tf_job_design_doc.md:24-26).

``--bench`` (r6) is the control-plane scale oracle: for each level in
``--bench-levels`` it deploys a FRESH operator daemon, submits that many
concurrent no-op jobs over HTTP, waits for every job to reach a terminal
state, scrapes /metrics for the reconcile-latency histogram, and emits a
one-line JSON artifact (jobs/min + p50/p99 sync latency per level) —
the checked-in ``artifacts/controlplane_r*.json`` format. Exit is
nonzero if ANY job at ANY level fails or never finishes, which is what
lets CI run a small level as a correctness gate.

Usage:
    python -m tools.genjob --nr-jobs 20 --out-dir /tmp/jobs        # write specs
    python -m tools.genjob --nr-jobs 20 --submit --server http://… # submit
    python -m tools.genjob --bench --bench-levels 50,200,500 \
        --bench-out artifacts/controlplane_r6.json                 # bench
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from tf_operator_tpu.api.types import (
    ObjectMeta,
    ProcessTemplate,
    ReplicaSpec,
    ReplicaType,
    SchedulingSpec,
    TPUJob,
    TPUJobSpec,
    TopologySpec,
)
from tf_operator_tpu.api.types import _to_jsonable

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The r5 baseline this round's tentpole is measured against
# (BASELINE.md "500 concurrent" row): 189.4 jobs/min, submit 60.8 s.
R5_BASELINE_500 = 189.4

# The r6 single-tenant throughput the fleet-scheduler round must not
# regress by more than 10% (artifacts/controlplane_r6.json, 500 level).
R6_BASELINE_500 = 429.1


def build_job(
    name: str,
    workers: int,
    steps: int,
    entrypoint: str,
    topology: str,
    cpu_env: bool,
    namespace: str = "default",
    queue: str = "",
    priority: str = "",
    chips: int = 0,
    sleep_s: float = 0.0,
    workload_extra: dict = None,
    env_extra: dict = None,
) -> TPUJob:
    env = {}
    if cpu_env:
        env = {
            "JAX_PLATFORMS": "cpu",
            "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
            "XLA_FLAGS": "",
        }
    env.update(env_extra or {})
    template = ProcessTemplate(entrypoint=entrypoint, env=env,
                               chips_per_process=chips)
    workload = {"dim": 16, "steps": steps}
    if sleep_s:
        workload["sleep_s"] = sleep_s
    workload.update(workload_extra or {})
    spec = TPUJobSpec(
        replica_specs={ReplicaType.WORKER: ReplicaSpec(replicas=workers, template=template)},
        workload=workload,
    )
    if topology:
        spec.topology = TopologySpec(slice_type=topology)
    if queue or priority:
        spec.scheduling = SchedulingSpec(queue=queue, priority_class=priority)
    return TPUJob(metadata=ObjectMeta(name=name, namespace=namespace), spec=spec)


def wait_for_terminal(client, jobs, timeout: float, t0: float) -> dict:
    """Poll the job list until every submitted job is terminal (or the
    deadline passes); returns the load report the --wait path prints.
    One LIST per round (not a GET per job): polling must not load the
    very server whose throughput is being measured, and one transient
    HTTP error must not abort the test."""
    terminal = {"Done", "Failed"}
    pending = {j.metadata.name for j in jobs}
    done: dict = {}
    deadline = time.time() + timeout
    while pending and time.time() < deadline:
        try:
            listed = client.list("default")
        except Exception:
            time.sleep(0.5)
            continue
        for j in listed:
            name = j.metadata.name
            if name in pending:
                phase = j.status.phase().value
                if phase in terminal:
                    done[name] = phase
                    pending.discard(name)
        if pending:
            time.sleep(0.5)
    wall_s = time.perf_counter() - t0
    succeeded = sum(1 for v in done.values() if v == "Done")
    return {
        "metric": "controller_jobs_per_min",
        "value": round(len(done) / wall_s * 60.0, 1) if wall_s else 0.0,
        "unit": "jobs/min",
        "jobs": len(jobs),
        "succeeded": succeeded,
        "failed": len(done) - succeeded,
        "unfinished": len(pending),
        "wall_s": round(wall_s, 2),
    }


# ---- --bench: the control-plane scale oracle ----------------------------


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _histogram_quantile(buckets, total: int, q: float) -> float:
    """Estimate a quantile (seconds) from cumulative Prometheus buckets
    [(le_seconds, cumulative_count)] by linear interpolation within the
    containing bucket — the standard histogram_quantile() estimate."""
    if total <= 0:
        return 0.0
    rank = q * total
    prev_le, prev_cum = 0.0, 0
    for le, cum in buckets:
        if cum >= rank:
            span = cum - prev_cum
            frac = (rank - prev_cum) / span if span else 1.0
            return prev_le + (le - prev_le) * frac
        prev_le, prev_cum = le, cum
    return prev_le  # rank beyond the last finite bucket: clamp


def _parse_histogram(text: str, family: str) -> tuple:
    """Extract ([(le_seconds, cumulative)], count) for one unlabeled
    Prometheus histogram family from exposition text."""
    import re

    buckets = []
    total = 0
    for line in text.splitlines():
        m = re.match(rf'{family}_bucket\{{le="([^"]+)"\}} (\d+)', line)
        if m:
            le = m.group(1)
            if le != "+Inf":
                buckets.append((float(le), int(m.group(2))))
            continue
        m = re.match(rf"{family}_count (\d+)", line)
        if m:
            total = int(m.group(1))
    return buckets, total


def _scrape_sync_latency(server: str) -> dict:
    """Read the reconcile-latency + TTFS histograms from /metrics →
    p50/p99 ms. TTFS (submit→first-step, trace-span-derived) is the
    cross-component number the whole framework is graded on."""
    import urllib.request

    with urllib.request.urlopen(server + "/metrics", timeout=10) as resp:
        text = resp.read().decode()
    buckets, total = _parse_histogram(text, "tpujob_sync_duration_seconds")
    out = {
        "syncs": total,
        "sync_p50_ms": round(_histogram_quantile(buckets, total, 0.5) * 1e3, 2),
        "sync_p99_ms": round(_histogram_quantile(buckets, total, 0.99) * 1e3, 2),
    }
    tb, tn = _parse_histogram(text, "tpujob_time_to_first_step_seconds")
    out["ttfs_jobs"] = tn
    out["ttfs_p50_ms"] = round(_histogram_quantile(tb, tn, 0.5) * 1e3, 1)
    out["ttfs_p99_ms"] = round(_histogram_quantile(tb, tn, 0.99) * 1e3, 1)
    # r11 cold/warm split: the reconciler folds TTFS into a second family
    # keyed on the first-step span's warm attribute (warm worker slot
    # and/or compile-cache hit). Both populations reported whenever they
    # have samples — the classic no-op bench lands everything in cold.
    for pop in ("cold", "warm"):
        pb, pn = _parse_histogram(
            text, f"tpujob_time_to_first_step_{pop}_seconds"
        )
        if pn:
            out[f"ttfs_{pop}_jobs"] = pn
            out[f"ttfs_{pop}_p50_ms"] = round(
                _histogram_quantile(pb, pn, 0.5) * 1e3, 1
            )
            out[f"ttfs_{pop}_p99_ms"] = round(
                _histogram_quantile(pb, pn, 0.99) * 1e3, 1
            )
    # Async-checkpoint overlap receipt (r8): per-accepted-save step-loop
    # stall, folded from workload save-stall spans at job terminal. Zero
    # samples (bench workloads without checkpointing) is normal — omit.
    sb, sn = _parse_histogram(text, "tpujob_checkpoint_save_stall_seconds")
    if sn:
        out["save_stalls"] = sn
        out["save_stall_p50_ms"] = round(
            _histogram_quantile(sb, sn, 0.5) * 1e3, 2
        )
        out["save_stall_p99_ms"] = round(
            _histogram_quantile(sb, sn, 0.99) * 1e3, 2
        )
    # Goodput accounting (r13): the per-job goodput ratio gauge (mean over
    # jobs that reported one) and the per-cause lost-seconds counters.
    ratios = _parse_labeled_gauges(text, "tpujob_goodput_ratio")
    if ratios:
        out["goodput_jobs"] = len(ratios)
        out["goodput_ratio"] = round(sum(ratios) / len(ratios), 4)
    lost = _parse_cause_counters(text, "tpujob_lost_seconds_total")
    if lost:
        out["lost_seconds"] = {k: round(v, 3) for k, v in sorted(lost.items())}
    # Hang plane (r15): declared-hang count plus the hang-downtime
    # histogram (declaration-backdated span widths, closed at recovered
    # gang-RUNNING). Zero hangs is the healthy bench case — report
    # hangs_total: 0 and omit the downtime quantile (no samples).
    out["hangs_total"] = _parse_counter(text, "tpujob_hangs_total")
    hb, hn = _parse_histogram(text, "tpujob_hang_downtime_seconds")
    if hn:
        out["hang_downtime_p50_ms"] = round(
            _histogram_quantile(hb, hn, 0.5) * 1e3, 1
        )
    return out


def _parse_labeled_gauges(text: str, family: str) -> list:
    """All sample values of one labeled gauge family from exposition text."""
    import re

    return [
        float(m.group(1))
        for line in text.splitlines()
        for m in [re.match(rf"{family}\{{[^}}]*\}} (\S+)", line)]
        if m
    ]


def _parse_counter(text: str, family: str) -> int:
    """Value of one unlabeled counter family (0 when absent)."""
    import re

    for line in text.splitlines():
        m = re.match(rf"{family} (\S+)", line)
        if m:
            return int(float(m.group(1)))
    return 0


def _parse_cause_counters(text: str, family: str) -> dict:
    """{cause: value} for a counter family labeled with cause="..."."""
    import re

    out: dict = {}
    for line in text.splitlines():
        m = re.match(rf'{family}\{{[^}}]*cause="([^"]+)"[^}}]*\}} (\S+)', line)
        if m:
            out[m.group(1)] = out.get(m.group(1), 0.0) + float(m.group(2))
    return out


def _start_operator(args, tag: str, extra=()):
    """Deploy a fresh operator daemon for one bench level; returns
    (popen, server_url, workdir, log_path) once /healthz answers."""
    import subprocess
    import tempfile
    import urllib.request

    port = _free_port()
    server = f"http://127.0.0.1:{port}"
    workdir = tempfile.mkdtemp(prefix=f"tpujob-bench-{tag}-")
    log_path = os.path.join(workdir, "operator.log")
    cmd = [
        sys.executable, "-m", "tf_operator_tpu.cli.operator",
        "--port", str(port),
        "--log-dir", os.path.join(workdir, "process-logs"),
        "--backend", args.bench_backend,
        *extra,
    ]
    with open(log_path, "ab") as log:
        operator = subprocess.Popen(
            cmd, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True, cwd=REPO_ROOT,
        )
    deadline = time.time() + 30
    while True:
        try:
            with urllib.request.urlopen(server + "/healthz", timeout=2):
                break
        except OSError:
            if operator.poll() is not None or time.time() > deadline:
                _stop_operator(operator, workdir, keep=True)
                raise RuntimeError(
                    f"operator never became healthy; see {log_path}"
                )
            time.sleep(0.2)
    return operator, server, workdir, log_path


def _stop_operator(operator, workdir: str, keep: bool = False) -> None:
    import shutil
    import signal
    import subprocess

    if operator.poll() is None:
        operator.send_signal(signal.SIGTERM)
        try:
            operator.wait(timeout=15)
        except subprocess.TimeoutExpired:
            operator.kill()
            operator.wait()
    if not keep:
        shutil.rmtree(workdir, ignore_errors=True)


def _bench_level(n_jobs: int, args) -> dict:
    """One bench level: fresh operator daemon → submit n_jobs no-op jobs
    → wait terminal → scrape latency → tear down."""
    from tf_operator_tpu.dashboard.client import TPUJobClient

    operator, server, workdir, log_path = _start_operator(args, str(n_jobs))
    try:
        jobs = [
            build_job(
                f"bench{n_jobs}-{i}", args.workers, args.steps,
                "tf_operator_tpu.workloads.noop:main", args.topology, True,
            )
            for i in range(n_jobs)
        ]
        client = TPUJobClient(server)
        t0 = time.perf_counter()
        for job in jobs:
            client.create(job)
        submit_s = time.perf_counter() - t0
        report = wait_for_terminal(client, jobs, args.timeout, t0)
        latency = _scrape_sync_latency(server)
        row = {
            "jobs": n_jobs,
            "jobs_per_min": report["value"],
            "succeeded": report["succeeded"],
            "failed": report["failed"],
            "unfinished": report["unfinished"],
            "submit_s": round(submit_s, 2),
            "wall_s": report["wall_s"],
            **latency,
        }
        print(json.dumps(row), flush=True)
        return row
    finally:
        _stop_operator(operator, workdir)


def run_bench(args) -> int:
    levels = [int(s) for s in str(args.bench_levels).split(",") if s.strip()]
    rows = [_bench_level(n, args) for n in levels]
    artifact = {
        "metric": "controlplane_bench",
        "unit": "jobs/min",
        "backend": args.bench_backend,
        "workers_per_job": args.workers,
        "payload": "tf_operator_tpu.workloads.noop:main",
        "levels": rows,
        "baseline_r5_jobs_per_min_500": R5_BASELINE_500,
    }
    line = json.dumps(artifact)
    print(line)
    if args.bench_out:
        os.makedirs(os.path.dirname(args.bench_out) or ".", exist_ok=True)
        with open(args.bench_out, "w") as f:
            f.write(line + "\n")
    # Correctness gate (the CI stage's contract): every job at every
    # level must have Succeeded.
    bad = [
        r for r in rows
        if r["failed"] or r["unfinished"] or r["succeeded"] != r["jobs"]
    ]
    return 1 if bad else 0


# ---- --bench-ttfs: the sub-second time-to-first-step oracle (r11) -------


def _wait_gauge(server: str, name: str, want: float, timeout: float) -> bool:
    """Poll /metrics until gauge ``name`` >= want (pool-warm sync point)."""
    import re
    import urllib.request

    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(server + "/metrics", timeout=5) as r:
                m = re.search(rf"^{name} ([0-9.e+-]+)$",
                              r.read().decode(), re.MULTILINE)
            if m and float(m.group(1)) >= want:
                return True
        except OSError:
            pass
        time.sleep(0.25)
    return False


def _ttfs_submit_wave(client, jobs, timeout: float, inflight: int) -> dict:
    """Submit jobs with a bounded in-flight window (repeat-submit shape:
    a stream of submissions, not one thundering batch — 100 concurrent
    gangs would measure control-plane queueing, not TTFS) and wait until
    every job is terminal."""
    t0 = time.perf_counter()
    pending = list(jobs)
    live: list = []
    done: dict = {}
    deadline = time.time() + timeout
    while (pending or live) and time.time() < deadline:
        while pending and len(live) < inflight:
            job = pending.pop(0)
            client.create(job)
            live.append(job.metadata.name)
        try:
            listed = {j.metadata.name: j for j in client.list("default")}
        except Exception:
            time.sleep(0.2)
            continue
        for name in list(live):
            j = listed.get(name)
            if j is not None and j.status.phase().value in ("Done", "Failed"):
                done[name] = j.status.phase().value
                live.remove(name)
        if pending or live:
            time.sleep(0.1)
    succeeded = sum(1 for v in done.values() if v == "Done")
    return {
        "jobs": len(jobs),
        "succeeded": succeeded,
        "failed": len(done) - succeeded,
        "unfinished": len(jobs) - len(done),
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def _ttfs_wave(tag: str, args, machinery: bool, keyer, seed: bool = False) -> dict:
    """One TTFS wave on a fresh operator: submit ``--bench-ttfs-jobs``
    single-process modeled-compile jobs (workloads/compiled.py) with a
    bounded in-flight window, wait terminal, scrape the TTFS split.
    ``machinery`` toggles the whole r11 stack (cachesvc + AOT-at-
    admission + warm pool); ``keyer(i)`` names each job's compile key —
    unique per job = every submission cold-compiles a fresh program,
    constant = repeat submissions of the same workload."""
    from tf_operator_tpu.dashboard.client import TPUJobClient

    extra = ()
    if machinery:
        extra = (
            "--compile-cache",
            "--aot-workers", "4",
            "--warm-pool", str(args.bench_ttfs_inflight),
        )
    operator, server, workdir, log_path = _start_operator(
        args, f"ttfs-{tag}", extra=extra
    )
    try:
        if machinery:
            # Measure steady state, not pool bring-up: a production host
            # agent warms its pool at agent start, long before any job
            # arrives. Wait for the warm-idle gauge to report full.
            _wait_gauge(server, "tpujob_warmpool_warm_idle",
                        args.bench_ttfs_inflight, timeout=60.0)
        # Hermetic local tier: point cached_compile's directory inside the
        # wave's workdir so no state leaks across waves or bench runs
        # (JAX_PLATFORMS=cpu keeps enable() from touching jax itself).
        cache_dir = os.path.join(workdir, "compile-cache")
        jobs = [
            build_job(
                f"ttfs-{tag}-{i}", 1, 0,
                "tf_operator_tpu.workloads.compiled:main", "", True,
                workload_extra={"aot": {
                    "key": keyer(i),
                    "compile_ms": args.bench_compile_ms,
                }},
                env_extra={"JAX_COMPILATION_CACHE_DIR": cache_dir},
            )
            for i in range(args.bench_ttfs_jobs)
        ]
        client = TPUJobClient(server)
        if seed:
            # Repeat-submit semantics: the measured jobs re-submit a
            # workload the fleet has already compiled once. Run one seed
            # job with the same key to terminal, outside the timed wave.
            seed_job = build_job(
                f"ttfs-{tag}-seed", 1, 0,
                "tf_operator_tpu.workloads.compiled:main", "", True,
                workload_extra={"aot": {
                    "key": keyer(0),
                    "compile_ms": args.bench_compile_ms,
                }},
                env_extra={"JAX_COMPILATION_CACHE_DIR": cache_dir},
            )
            client.create(seed_job)
            wait_for_terminal(client, [seed_job], args.timeout,
                              time.perf_counter())
        report = _ttfs_submit_wave(
            client, jobs, args.timeout, args.bench_ttfs_inflight
        )
        latency = _scrape_sync_latency(server)
        import urllib.request

        with urllib.request.urlopen(server + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        row = {
            "wave": tag,
            "machinery": machinery,
            **report,
            **latency,
            "aot_kicked": _scrape_counter(
                text, "tpujob_aot_compiles_kicked_total"),
            "aot_published": _scrape_counter(
                text, "tpujob_aot_compiles_published_total"),
        }
        print(json.dumps(row), flush=True)
        return row
    finally:
        _stop_operator(operator, workdir)


def run_ttfs_bench(args) -> int:
    """Three waves, each on a fresh operator (same-host A/B, the r7
    precedent for honest regression calls):

    - ``baseline``: machinery OFF, unique compile keys — the pre-change
      cold population (every job pays spawn + modeled compile serially).
    - ``cold``: the full r11 stack ON, unique compile keys — first
      submission of a never-seen program; the speedup mechanisms are
      AOT-at-admission (compile overlaps scheduling + spawn; the gang
      member waits out the compile *intent* instead of recompiling) and
      the warm worker pool (no cold fork/imports).
    - ``warm``: stack ON, every job shares ONE key — repeat submissions;
      after the first publish, every job is a pure cache hit.

    Gates (the r11 acceptance): warm p50 under the bound; cold p50 at or
    under ``--bench-ttfs-cold-factor`` x the same-host baseline p50; and
    zero cache-integrity failures surfaced as job failures — every job
    in every wave must end Done (a corrupt/dead-cachesvc path degrades
    to local compile by design, so any Failed job is a real defect)."""
    nonce = f"{os.getpid()}-{int(time.time())}"
    waves = [
        _ttfs_wave("baseline", args, False, lambda i: f"b-{nonce}-{i}"),
        _ttfs_wave("cold", args, True, lambda i: f"c-{nonce}-{i}"),
        _ttfs_wave("warm", args, True, lambda i: f"w-{nonce}", seed=True),
    ]
    base, cold, warm = waves
    warm_p50 = warm.get("ttfs_warm_p50_ms", warm.get("ttfs_p50_ms", 0.0))
    artifact = {
        "metric": "ttfs_bench",
        "unit": "ms",
        "backend": args.bench_backend,
        "jobs_per_wave": args.bench_ttfs_jobs,
        "inflight": args.bench_ttfs_inflight,
        "modeled_compile_ms": args.bench_compile_ms,
        "payload": "tf_operator_tpu.workloads.compiled:main",
        "waves": waves,
        "pre_cold_p50_ms": base.get("ttfs_p50_ms", 0.0),
        "cold_p50_ms": cold.get("ttfs_p50_ms", 0.0),
        "warm_p50_ms": warm_p50,
        "warm_bound_ms": args.bench_ttfs_warm_bound_ms,
        "cold_factor_bound": args.bench_ttfs_cold_factor,
    }
    line = json.dumps(artifact)
    print(line)
    if args.bench_out:
        os.makedirs(os.path.dirname(args.bench_out) or ".", exist_ok=True)
        with open(args.bench_out, "w") as f:
            f.write(line + "\n")
    ok = True
    for w in waves:
        if w["failed"] or w["unfinished"] or w["succeeded"] != w["jobs"]:
            print(f"FAIL: wave {w['wave']}: not every job Succeeded "
                  "(cache-integrity or degradation surfaced as a job "
                  "failure)", file=sys.stderr)
            ok = False
    if warm_p50 >= args.bench_ttfs_warm_bound_ms:
        print(f"FAIL: warm TTFS p50 {warm_p50}ms >= bound "
              f"{args.bench_ttfs_warm_bound_ms}ms", file=sys.stderr)
        ok = False
    bound = args.bench_ttfs_cold_factor * artifact["pre_cold_p50_ms"]
    if artifact["cold_p50_ms"] > bound:
        print(f"FAIL: cold TTFS p50 {artifact['cold_p50_ms']}ms > "
              f"{args.bench_ttfs_cold_factor} x baseline "
              f"{artifact['pre_cold_p50_ms']}ms = {bound:.1f}ms",
              file=sys.stderr)
        ok = False
    return 0 if ok else 1


# ---- --bench-elastic: the elastic-gang resize oracle (r12) --------------


def run_elastic_bench(args) -> int:
    """The r12 elasticity receipt: drive the seeded kill/return schedule
    through the elastic chaos soak (``chaos/soak.py``) and report one
    JSON line — resize downtime p50/p99, tokens/s before/during/after
    the shrink, and the hard gates the CI ``elastic-soak`` stage rides
    on: zero full gang restarts, bit-identical eval after the re-grow
    vs an uninterrupted run at the same token count, and at least one
    resize restored from a peer depot rather than disk."""
    from tf_operator_tpu.chaos.soak import (
        elastic_artifact,
        run_elastic_soak,
        run_grow_beyond_spec_probe,
    )

    result = run_elastic_soak(
        seed=args.seed,
        kills=args.bench_elastic_kills,
        workers=args.workers,
        total_windows=args.bench_elastic_windows,
        timeout=args.timeout,
        device_state=args.bench_elastic_device_state,
        preempt_during_resize=args.bench_elastic_preempt_during_resize,
        queue_quota=(
            args.workers if args.bench_elastic_preempt_during_resize else 0
        ),
    )
    artifact = elastic_artifact(result, args.seed)
    violations = result.check()
    if args.bench_elastic_grow_beyond_spec:
        # r19 probe: the same receipt line grows a grow_beyond_spec
        # section — world past spec on loaned in-quota chips, cleanly
        # first-reclaimed under injected queue pressure.
        grow = run_grow_beyond_spec_probe(
            seed=args.seed, timeout=args.timeout
        )
        artifact["grow_beyond_spec"] = {
            "spec_world": grow.spec_world,
            "elastic_max_world": grow.max_world,
            "grew_to": grow.grew_to,
            "overspec_seen": grow.overspec_seen,
            "resize_history": grow.resize_history,
            "quota_violations": grow.quota_violations,
            "pass": not grow.check(),
        }
        violations += grow.check()
        artifact["pass"] = not violations
    line = json.dumps(artifact)
    print(line)
    if args.bench_out:
        os.makedirs(os.path.dirname(args.bench_out) or ".", exist_ok=True)
        with open(args.bench_out, "w") as f:
            f.write(line + "\n")
    for v in violations:
        print(f"FAIL: {v}", file=sys.stderr)
    return 1 if violations else 0


# ---- --bench-tenants: the multi-tenant fleet-scheduler oracle (r7) ------


def _parse_labeled_histogram(text: str, family: str, match=None) -> tuple:
    """([(le_seconds, cumulative)], count) for a LABELED histogram family,
    summing across every series whose labels include ``match``."""
    import re

    line_re = re.compile(rf"{family}_(bucket|count)\{{([^}}]*)\}} ([0-9.eE+-]+)")
    buckets: dict = {}
    total = 0
    for line in text.splitlines():
        m = line_re.match(line)
        if not m:
            continue
        kind, labelstr, val = m.groups()
        labels = dict(re.findall(r'(\w+)="([^"]*)"', labelstr))
        if match and any(labels.get(k) != v for k, v in match.items()):
            continue
        if kind == "bucket":
            le = labels.get("le", "")
            if le and le != "+Inf":
                buckets[float(le)] = buckets.get(float(le), 0) + int(float(val))
        else:
            total += int(float(val))
    return sorted(buckets.items()), total


def _scrape_counter(text: str, family: str) -> int:
    import re

    total = 0
    for line in text.splitlines():
        m = re.match(rf"{family}(?:\{{[^}}]*\}})? ([0-9.eE+-]+)", line)
        if m:
            total += int(float(m.group(1)))
    return total


def _create_sched_objects(client, tenants: int, quota_chips: int) -> None:
    """High/low PriorityClasses plus one Queue per tenant namespace —
    created BEFORE any job so admission sees the quota from job one."""
    from tf_operator_tpu.sched.objects import PriorityClass, Queue, QueueSpec

    for name, value in (("high", 100), ("low", 0)):
        client.create_object(PriorityClass(
            metadata=ObjectMeta(name=name, namespace="default"), value=value,
        ))
    for i in range(tenants):
        client.create_object(Queue(
            metadata=ObjectMeta(name="main", namespace=f"tenant{i}"),
            spec=QueueSpec(quota_chips=quota_chips),
        ))


def _preemption_probe(client, args) -> dict:
    """The warm-resume receipt, run against the live benched operator:
    a one-job-quota namespace holds a low-priority sleeper; a high-
    priority submission must preempt it (victim restart cause
    ``preemption``, preemption_count not restart_count) and the victim
    must still finish after the high job releases the quota."""
    from tf_operator_tpu.sched.objects import Queue, QueueSpec

    chips, workers = args.bench_chips, args.workers
    demand = chips * workers
    client.create_object(Queue(
        metadata=ObjectMeta(name="main", namespace="probe"),
        spec=QueueSpec(quota_chips=demand),  # exactly one job fits
    ))
    mk = lambda name, prio, sleep: build_job(
        name, workers, 0, "tf_operator_tpu.workloads.noop:main", "", True,
        namespace="probe", queue="main", priority=prio,
        chips=chips, sleep_s=sleep,
    )
    out = {"ok": False, "error": ""}
    try:
        client.create(mk("victim", "low", 12.0))
        deadline = time.time() + 30
        while time.time() < deadline:
            if client.get_job("probe", "victim").status.phase().value == "Running":
                break
            time.sleep(0.25)
        else:
            out["error"] = "victim never started running"
            return out

        t_high = time.time()
        client.create(mk("preemptor", "high", 1.0))
        high = client.wait_for_job("probe", "preemptor", timeout=60)
        out["high_wait_s"] = round(time.time() - t_high, 2)
        if high.status.phase().value != "Done":
            out["error"] = f"preemptor finished {high.status.phase().value}"
            return out

        victim = client.wait_for_job("probe", "victim", timeout=90)
        out.update(
            victim_phase=victim.status.phase().value,
            preemption_count=victim.status.preemption_count,
            restart_count=victim.status.restart_count,
            last_restart_cause=victim.status.last_restart_cause,
        )
        if victim.status.phase().value != "Done":
            out["error"] = "victim did not finish after preemption"
        elif victim.status.preemption_count < 1:
            out["error"] = "victim was never preempted"
        elif victim.status.restart_count != 0:
            out["error"] = "preemption was charged to restart_count/backoff"
        elif victim.status.last_restart_cause != "preemption":
            out["error"] = (
                f"restart cause {victim.status.last_restart_cause!r}, "
                "expected 'preemption'"
            )
        elif out["high_wait_s"] > args.bench_preempt_wait_bound:
            out["error"] = (
                f"high-priority admission took {out['high_wait_s']}s "
                f"(bound {args.bench_preempt_wait_bound}s)"
            )
        else:
            out["ok"] = True
    except Exception as exc:  # probe failures fail the bench, not crash it
        out["error"] = f"{type(exc).__name__}: {exc}"
    return out


def _sched_bench_level(n_jobs: int, args) -> dict:
    """One multi-tenant level: fresh operator (sharded reconciler) →
    Queues/PriorityClasses → n_jobs spread over the tenants with the
    high/low priority mix → wait terminal while polling per-tenant
    running demand against quota → queue-wait + preemption metrics."""
    import urllib.request

    from tf_operator_tpu.dashboard.client import TPUJobClient

    tenants = args.bench_tenants
    shards = str(max(2, min(tenants, 4)))
    operator, server, workdir, log_path = _start_operator(
        args, f"sched{n_jobs}",
        extra=("--threadiness", shards, "--reconcile-shards", shards),
    )
    try:
        client = TPUJobClient(server)
        _create_sched_objects(client, tenants, args.bench_quota_chips)

        n_high = max(1, int(n_jobs * args.bench_priority_mix))
        jobs = [
            build_job(
                f"sb{n_jobs}-{i}", args.workers, 0,
                "tf_operator_tpu.workloads.noop:main", "", True,
                namespace=f"tenant{i % tenants}", queue="main",
                priority="high" if i < n_high else "low",
                chips=args.bench_chips,
            )
            for i in range(n_jobs)
        ]
        t0 = time.perf_counter()
        for job in jobs:
            client.create(job)
        submit_s = time.perf_counter() - t0

        # Wait loop doubling as the quota oracle: each poll, sum the chips
        # of LIVE Process objects per tenant namespace — the store-side
        # ground truth of chip occupancy (job phases lag the handoff; a
        # preemption victim can still read Running one status-write after
        # its gang is gone). The peak must never exceed the tenant
        # queue's quota_chips: the two-phase preemption handoff releases
        # the victim's quota only once its gang is observably gone, so
        # victim and preemptor processes never coexist in a snapshot.
        pending = {(j.metadata.namespace, j.metadata.name) for j in jobs}
        done: dict = {}
        peak = {f"tenant{i}": 0 for i in range(tenants)}
        deadline = time.time() + args.timeout
        while pending and time.time() < deadline:
            try:
                listed = client.list(None)
                for i in range(tenants):
                    ns = f"tenant{i}"
                    live = sum(
                        max(p.spec.chips, 0)
                        for p in client.list_objects("Process", ns)
                        if not p.is_finished()
                    )
                    peak[ns] = max(peak[ns], live)
            except Exception:
                time.sleep(0.5)
                continue
            for j in listed:
                k = (j.metadata.namespace, j.metadata.name)
                if k in pending and j.status.phase().value in ("Done", "Failed"):
                    done[k] = j.status.phase().value
                    pending.discard(k)
            if pending:
                time.sleep(0.5)
        wall_s = time.perf_counter() - t0

        probe = _preemption_probe(client, args)

        with urllib.request.urlopen(server + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        qb, qn = _parse_labeled_histogram(text, "tpujob_queue_wait_seconds")
        hb, hn = _parse_labeled_histogram(
            text, "tpujob_queue_wait_seconds", match={"priority": "high"}
        )
        quota_violations = [
            {"tenant": ns, "peak_chips": used,
             "quota_chips": args.bench_quota_chips}
            for ns, used in sorted(peak.items())
            if used > args.bench_quota_chips
        ]
        per_tenant = {}
        for i in range(tenants):
            ns = f"tenant{i}"
            t_done = [v for k, v in done.items() if k[0] == ns]
            per_tenant[ns] = {
                "jobs": sum(1 for j in jobs if j.metadata.namespace == ns),
                "succeeded": sum(1 for v in t_done if v == "Done"),
                "jobs_per_min": round(len(t_done) / wall_s * 60.0, 1) if wall_s else 0.0,
                "peak_chips": peak.get(ns, 0),
            }
        succeeded = sum(1 for v in done.values() if v == "Done")
        row = {
            "jobs": n_jobs,
            "tenants": tenants,
            "priority_mix": args.bench_priority_mix,
            "quota_chips": args.bench_quota_chips,
            "jobs_per_min": round(len(done) / wall_s * 60.0, 1) if wall_s else 0.0,
            "succeeded": succeeded,
            "failed": len(done) - succeeded,
            "unfinished": len(pending),
            "submit_s": round(submit_s, 2),
            "wall_s": round(wall_s, 2),
            "queue_waits": qn,
            "queue_wait_p50_ms": round(_histogram_quantile(qb, qn, 0.5) * 1e3, 1),
            "queue_wait_p99_ms": round(_histogram_quantile(qb, qn, 0.99) * 1e3, 1),
            "queue_wait_high_p99_ms": round(_histogram_quantile(hb, hn, 0.99) * 1e3, 1),
            "preemptions_requested": _scrape_counter(
                text, "tpujob_preemptions_requested_total"
            ),
            "quota_violations": quota_violations,
            "per_tenant": per_tenant,
            "probe": probe,
        }
        print(json.dumps(row), flush=True)
        return row
    finally:
        _stop_operator(operator, workdir)


def run_sched_bench(args) -> int:
    levels = [int(s) for s in str(args.bench_levels).split(",") if s.strip()]
    rows = [_sched_bench_level(n, args) for n in levels]
    single = None
    if args.bench_single_level:
        single = _bench_level(args.bench_single_level, args)
    artifact = {
        "metric": "sched_bench",
        "unit": "jobs/min",
        "backend": args.bench_backend,
        "tenants": args.bench_tenants,
        "priority_mix": args.bench_priority_mix,
        "quota_chips": args.bench_quota_chips,
        "workers_per_job": args.workers,
        "payload": "tf_operator_tpu.workloads.noop:main",
        "levels": rows,
        "single_tenant": single,
        "single_tenant_floor": args.bench_single_floor,
        "baseline_r6_jobs_per_min_500": R6_BASELINE_500,
    }
    line = json.dumps(artifact)
    print(line)
    if args.bench_out:
        os.makedirs(os.path.dirname(args.bench_out) or ".", exist_ok=True)
        with open(args.bench_out, "w") as f:
            f.write(line + "\n")
    # The CI contract: every job Succeeded, no tenant ever observed over
    # its chip quota, the preemption probe's receipts all held, and the
    # single-tenant control stays above the regression floor (absolute
    # jobs/min via --bench-single-floor; the checked-in r6 number was
    # captured on a faster host, so an absolute gate against it would
    # fail at the seed commit too — regression calls need a same-host
    # A/B, which is how the r7 artifact's floor was chosen).
    ok = True
    for r in rows:
        if r["failed"] or r["unfinished"] or r["succeeded"] != r["jobs"]:
            print(f"FAIL: level {r['jobs']}: not every job Succeeded", file=sys.stderr)
            ok = False
        if r["quota_violations"]:
            print(f"FAIL: level {r['jobs']}: quota exceeded: "
                  f"{r['quota_violations']}", file=sys.stderr)
            ok = False
        if not r["probe"].get("ok"):
            print(f"FAIL: level {r['jobs']}: preemption probe: "
                  f"{r['probe'].get('error')}", file=sys.stderr)
            ok = False
    if single is not None:
        floor = args.bench_single_floor
        if single["failed"] or single["unfinished"]:
            print("FAIL: single-tenant control: not every job Succeeded",
                  file=sys.stderr)
            ok = False
        elif floor and single["jobs_per_min"] < floor:
            print(f"FAIL: single-tenant control {single['jobs_per_min']} "
                  f"jobs/min under the floor {floor:.1f}", file=sys.stderr)
            ok = False
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tpujob-genjob")
    p.add_argument("--nr-jobs", type=int, default=1)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--steps", type=int, default=2)
    p.add_argument("--prefix", default="genjob")
    p.add_argument("--entrypoint", default="tf_operator_tpu.workloads.smoke:main")
    p.add_argument("--topology", default="", help="slice type, e.g. v5p-32")
    p.add_argument("--no-cpu-env", action="store_true",
                   help="don't inject the CPU-platform env (run on real TPU)")
    p.add_argument("--out-dir", default=None, help="write one JSON spec per job")
    p.add_argument("--submit", action="store_true", help="submit to the operator")
    p.add_argument("--server", default="http://127.0.0.1:8080")
    p.add_argument("--wait", action="store_true",
                   help="after --submit, wait for every job to reach a "
                        "terminal state and print a JSON load report "
                        "(jobs/min, success count) — the controller-scale "
                        "oracle for the reference's O(100)-job design target")
    p.add_argument("--timeout", type=float, default=900.0)
    p.add_argument("--cleanup", action="store_true",
                   help="delete the generated jobs after the report")
    p.add_argument("--bench", action="store_true",
                   help="self-contained control-plane bench: per level in "
                        "--bench-levels, deploy a fresh operator, submit "
                        "that many concurrent no-op jobs, report jobs/min "
                        "+ p50/p99 sync latency as one JSON line; exit "
                        "nonzero unless every job Succeeded")
    p.add_argument("--bench-levels", default="50,200,500",
                   help="comma-separated concurrent-job counts")
    p.add_argument("--bench-out", default=None,
                   help="also write the bench JSON line to this path "
                        "(the artifacts/controlplane_r*.json format)")
    p.add_argument("--bench-backend", choices=("native", "local"),
                   default="native",
                   help="process backend for the benched operator "
                        "(native = C++ supervisor, the deploy default)")
    p.add_argument("--bench-tenants", type=int, default=0,
                   help="with --bench: >0 switches to the multi-tenant "
                        "fleet-scheduler bench — jobs spread over N tenant "
                        "namespaces, each with a quota'd Queue, mixed "
                        "high/low PriorityClasses, quota/preemption oracles")
    p.add_argument("--bench-priority-mix", type=float, default=0.2,
                   help="fraction of bench jobs submitted at high priority")
    p.add_argument("--bench-quota-chips", type=int, default=32,
                   help="per-tenant Queue chip quota (bench jobs hold "
                        "workers x --bench-chips chips while admitted)")
    p.add_argument("--bench-chips", type=int, default=4,
                   help="chips_per_process each bench job requests")
    p.add_argument("--bench-preempt-wait-bound", type=float, default=60.0,
                   help="max seconds the probe's high-priority job may wait "
                        "for admission via preemption before the bench "
                        "fails (covers the victim's full graceful drain "
                        "plus sync latency on a loaded control plane)")
    p.add_argument("--bench-single-level", type=int, default=0,
                   help="also run one classic single-tenant level as the "
                        "no-fleet-overhead throughput control")
    p.add_argument("--bench-single-floor", type=float, default=0.0,
                   help="fail unless the single-tenant control clears this "
                        "many jobs/min (0 = correctness-only; pick the "
                        "floor from a same-host baseline run, not from an "
                        "artifact captured on different hardware)")
    p.add_argument("--bench-ttfs", action="store_true",
                   help="run the r11 time-to-first-step bench: three waves "
                        "(baseline / cold-with-machinery / warm repeat-"
                        "submit), each on a fresh operator; gates warm p50 "
                        "and the cold-vs-baseline ratio")
    p.add_argument("--bench-ttfs-jobs", type=int, default=100,
                   help="jobs per TTFS wave")
    p.add_argument("--bench-compile-ms", type=int, default=600,
                   help="modeled XLA compile cost each cache miss pays "
                        "(workloads/compiled.py)")
    p.add_argument("--bench-ttfs-warm-bound-ms", type=float, default=1000.0,
                   help="fail if the warm wave's warm-population TTFS p50 "
                        "is at or above this (the sub-second headline)")
    p.add_argument("--bench-ttfs-cold-factor", type=float, default=0.5,
                   help="fail if the cold wave's TTFS p50 exceeds this "
                        "fraction of the same-host baseline p50")
    p.add_argument("--bench-ttfs-inflight", type=int, default=4,
                   help="bounded submission window (and warm-pool size): "
                        "repeat-submit is a stream, not one batch")
    p.add_argument("--bench-elastic", action="store_true",
                   help="run the r12 elastic-gang resize bench: seeded "
                        "kill/return schedule through the elastic chaos "
                        "soak; one JSON line with resize downtime p50/p99 "
                        "and tokens/s before/during/after the shrink; "
                        "exits nonzero unless zero full restarts, "
                        "bit-identical eval, and >=1 peer-depot restore")
    p.add_argument("--bench-elastic-kills", type=int, default=2,
                   help="kill/return events in the elastic schedule")
    p.add_argument("--bench-elastic-windows", type=int, default=400,
                   help="total data windows the elastic workload consumes")
    p.add_argument("--bench-elastic-device-state", action="store_true",
                   help="carry a real device param/opt pytree through "
                        "every resize (train/reshard.py); hardens the "
                        "gate to bit-identical final params vs an "
                        "uninterrupted run")
    p.add_argument("--bench-elastic-preempt-during-resize",
                   action="store_true",
                   help="stamp a fleet preemption mid-shrink (r19 "
                        "composition probe): the drain must defer to the "
                        "post-resize epoch, under a store-audited Queue")
    p.add_argument("--bench-elastic-grow-beyond-spec", action="store_true",
                   help="also run the r19 grow-beyond-spec probe: world "
                        "past spec on loaned in-quota chips, cleanly "
                        "first-reclaimed under injected queue pressure")
    p.add_argument("--seed", type=int, default=12,
                   help="schedule seed for --bench-elastic")
    args = p.parse_args(argv)

    if args.bench_elastic:
        if args.workers < 3:
            args.workers = 3  # need a chief + >=2 killable members
        if args.timeout > 300.0:
            args.timeout = 150.0  # soak bound, not the submit default
        return run_elastic_bench(args)
    if args.bench_ttfs:
        return run_ttfs_bench(args)
    if args.bench:
        if args.bench_tenants > 0:
            return run_sched_bench(args)
        return run_bench(args)

    jobs = [
        build_job(
            f"{args.prefix}-{i}", args.workers, args.steps, args.entrypoint,
            args.topology, not args.no_cpu_env,
        )
        for i in range(args.nr_jobs)
    ]

    if args.out_dir:
        os.makedirs(args.out_dir, exist_ok=True)
        for job in jobs:
            path = os.path.join(args.out_dir, f"{job.metadata.name}.json")
            with open(path, "w") as f:
                json.dump(_to_jsonable(job.to_dict()), f, indent=2)
        print(f"wrote {len(jobs)} specs to {args.out_dir}")

    if args.submit:
        from tf_operator_tpu.dashboard.client import TPUJobClient

        client = TPUJobClient(args.server)
        t0 = time.perf_counter()
        for job in jobs:
            client.create(job)
        submit_s = time.perf_counter() - t0
        print(f"submitted {len(jobs)} jobs to {args.server} in {submit_s:.2f}s")

        if args.wait:
            report = wait_for_terminal(client, jobs, args.timeout, t0)
            report["submit_s"] = round(submit_s, 2)
            print(json.dumps(report))
            if args.cleanup:
                for job in jobs:
                    try:
                        client.delete("default", job.metadata.name)
                    except Exception:
                        pass
            if report["unfinished"] or report["succeeded"] != len(jobs):
                return 1
    elif not args.out_dir:
        for job in jobs:
            print(json.dumps(_to_jsonable(job.to_dict())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
