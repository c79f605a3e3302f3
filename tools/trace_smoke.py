"""Trace smoke test: the CI gate for end-to-end lifecycle tracing.

Submits a small batch of no-op jobs against a RUNNING operator (the
deployed cluster the e2e stage already stood up), waits for them to
finish, fetches one job's trace through the dashboard's trace endpoint
(the same document ``tpujob trace`` prints), and asserts the contract
the observability subsystem exists to keep:

- the document is valid Chrome trace-event JSON (``traceEvents`` of
  M/X/i events with pid/tid/ts);
- the timeline contains the ``scheduled`` and ``first-step`` spans
  (so submit→scheduled and TTFS are derivable);
- spans from >= 3 distinct components are present (controller +
  agent/backend + trainer at minimum — the cross-component stitching
  is the whole point);
- a smoke serve job's trace carries the per-request span schema
  (``request-admitted`` → ``first-token`` → ``finished``, one finished
  span per request, each with a ``tokens`` attr — the rows the
  reconciler folds into ``tpujob_request_ttft_seconds`` /
  ``tpujob_request_tokens_total`` at terminal);
- the job's ``/telemetry`` payload carries >= 1 ring batch with
  per-rank monotonic step ranges and finite MFU (the r13 telemetry
  plane works end to end even for a no-op payload).

Usage:
    python -m tools.trace_smoke --server http://127.0.0.1:8080
"""

from __future__ import annotations

import argparse
import sys
import time

from tf_operator_tpu.dashboard.client import TPUJobApiError, TPUJobClient
from tf_operator_tpu.serve.spec import build_serve_job
from tools.genjob import build_job

REQUIRED_EVENT_KEYS = ("name", "ph", "pid", "tid")


def validate_chrome_trace(doc: dict) -> list:
    """Schema violations in a Chrome trace-event document; [] = valid."""
    errs = []
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        return [f"traceEvents missing/empty: {type(events).__name__}"]
    for i, ev in enumerate(events):
        for k in REQUIRED_EVENT_KEYS:
            if k not in ev:
                errs.append(f"event {i} missing {k!r}: {ev}")
        ph = ev.get("ph")
        if ph not in ("M", "X", "i", "B", "E"):
            errs.append(f"event {i} has unknown phase {ph!r}")
        if ph in ("X", "i") and "ts" not in ev:
            errs.append(f"event {i} ({ph}) missing ts")
        if ph == "X" and "dur" not in ev:
            errs.append(f"event {i} (X) missing dur")
    return errs


def telemetry_errors(payload: dict) -> list:
    """Schema violations in a /telemetry payload; [] = valid. The golden
    contract: >= 1 batch, per-rank monotonic step ranges, finite MFU."""
    import math

    errs = []
    batches = payload.get("batches")
    if not isinstance(batches, list) or not batches:
        return [f"telemetry batches missing/empty: {batches!r}"]
    by_rank: dict = {}
    for i, b in enumerate(batches):
        for k in ("rank", "seq", "start_step", "end_step", "step_time_s", "mfu"):
            if k not in b:
                errs.append(f"batch {i} missing {k!r}: {sorted(b)}")
        if not math.isfinite(float(b.get("mfu", 0.0))):
            errs.append(f"batch {i} has non-finite mfu: {b.get('mfu')!r}")
        if int(b.get("end_step", 0)) < int(b.get("start_step", 0)):
            errs.append(f"batch {i} step range inverted: {b}")
        by_rank.setdefault(int(b.get("rank", -1)), []).append(b)
    for rank, bs in by_rank.items():
        bs.sort(key=lambda b: int(b.get("seq", 0)))
        for prev, cur in zip(bs, bs[1:]):
            if int(cur["end_step"]) <= int(prev["end_step"]):
                errs.append(
                    f"rank {rank} steps not monotonic across seqs: "
                    f"{prev['end_step']} -> {cur['end_step']}"
                )
    summary = payload.get("summary") or {}
    if not summary.get("ranks"):
        errs.append(f"summary missing/empty: {summary!r}")
    return errs


SERVE_SMOKE_REQUESTS = 4


def serve_trace_errors(doc: dict, requests: int) -> list:
    """Request-span schema violations in a serve job's trace; [] = valid."""
    errs = validate_chrome_trace(doc)
    slices = [ev for ev in doc.get("traceEvents", ()) if ev.get("ph") == "X"]
    by_op: dict = {}
    for ev in slices:
        by_op.setdefault(ev.get("name"), []).append(ev)
    for op in ("request-admitted", "first-token", "finished"):
        if op not in by_op:
            errs.append(
                f"serve trace missing {op!r} spans (ops: {sorted(by_op)})"
            )
    finished = by_op.get("finished", [])
    if len(finished) != requests:
        errs.append(
            f"expected {requests} 'finished' spans (one per request), "
            f"got {len(finished)}"
        )
    for ev in finished:
        args = ev.get("args", {})
        if "request" not in args:
            errs.append(f"finished span missing 'request' attr: {args}")
        tokens = args.get("tokens")
        if not (isinstance(tokens, str) and tokens.isdigit() and int(tokens) > 0):
            errs.append(f"finished span 'tokens' attr not a count: {tokens!r}")
    return errs


def run_serve_smoke(client: TPUJobClient, timeout: float) -> list:
    """Submit one smoke serve job, return request-span schema errors."""
    name = f"tracesmoke-serve-{int(time.time()) % 100000}"
    # a span-schema smoke, not a device run: it asks for the CPU itself
    job = build_serve_job(name, env={"JAX_PLATFORMS": "cpu"}, workload={
        "requests": SERVE_SMOKE_REQUESTS, "prompt_len": 6,
        "max_new_tokens": 6, "arrival_rate": 0.0,
    })
    client.create(job)
    try:
        done = client.wait_for_job("default", name, timeout=timeout)
        phase = done.status.phase().value
        if phase != "Done":
            return [f"serve smoke job finished {phase}"]
        doc = client.trace("default", name)
        errs = serve_trace_errors(doc, SERVE_SMOKE_REQUESTS)
        if not errs:
            print(
                f"serve trace ok: {name} events={len(doc['traceEvents'])} "
                f"requests={SERVE_SMOKE_REQUESTS}"
            )
        return errs
    finally:
        try:
            client.delete("default", name)
        except TPUJobApiError:
            pass


def run(server: str, jobs: int, workers: int, timeout: float) -> int:
    client = TPUJobClient(server)
    names = []
    for i in range(jobs):
        job = build_job(
            f"tracesmoke-{int(time.time()) % 100000}-{i}", workers, 1,
            "tf_operator_tpu.workloads.noop:main", "", True,
        )
        client.create(job)
        names.append(job.metadata.name)
    print(f"submitted {jobs} no-op jobs")
    for name in names:
        done = client.wait_for_job("default", name, timeout=timeout)
        phase = done.status.phase().value
        if phase != "Done":
            print(f"FAIL: {name} finished {phase}", file=sys.stderr)
            return 1

    # One job's trace is the assertion target; the rest exercised volume.
    target = names[0]
    doc = client.trace("default", target)
    errs = validate_chrome_trace(doc)

    ops = {
        ev.get("name")
        for ev in doc.get("traceEvents", ())
        if ev.get("ph") in ("X", "i")
    }
    for required in ("scheduled", "first-step"):
        if required not in ops:
            errs.append(f"trace missing required span {required!r} (ops: {sorted(ops)})")
    components = doc.get("otherData", {}).get("components", [])
    if len(components) < 3:
        errs.append(f"expected spans from >= 3 components, got {components}")
    timings = doc.get("otherData", {})
    if timings.get("time_to_first_step_s") is None:
        errs.append("otherData.time_to_first_step_s not derived")

    # The telemetry plane rides the same smoke job: even a no-op payload
    # must land >= 1 ring batch with a sane schema (r13).
    telemetry = client.telemetry("default", target)
    terrs = telemetry_errors(telemetry)
    if not terrs:
        print(
            f"telemetry ok: {target} batches={len(telemetry['batches'])} "
            f"ranks={telemetry['summary']['ranks']}"
        )
    errs.extend(terrs)

    errs.extend(run_serve_smoke(client, timeout))

    # best-effort cleanup so reruns aren't poisoned
    for name in names:
        try:
            client.delete("default", name)
        except TPUJobApiError:
            pass

    if errs:
        for e in errs:
            print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(
        f"trace ok: {target} events={len(doc['traceEvents'])} "
        f"components={components} "
        f"ttfs={timings.get('time_to_first_step_s'):.3f}s "
        f"scheduled={timings.get('time_to_scheduled_s'):.3f}s"
    )
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tpujob-trace-smoke")
    p.add_argument("--server", default="http://127.0.0.1:8080")
    p.add_argument("--jobs", type=int, default=3)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--timeout", type=float, default=300.0)
    args = p.parse_args(argv)
    try:
        return run(args.server, args.jobs, args.workers, args.timeout)
    except (TPUJobApiError, TimeoutError, OSError) as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
