"""E2E smoke driver with TAP output.

Reference parity: test/e2e/main.go — builds the canonical small job
programmatically (1 coordinator + workers, main.go:83-97), polls it to
Succeeded (:106-129), asserts per-replica resources exist (:135-148),
deletes and asserts GC (:150-191), TAP output (:244-252), and ``--num-jobs``
parallel submissions (:208-238). The TF_CONFIG-era MASTER/PS/WORKER
topology collapses to Coordinator/Worker on a TPU slice.

Usage:
    python -m tools.e2e --server http://127.0.0.1:8080 [--num-jobs 2]
"""

from __future__ import annotations

import argparse
import sys
import threading

from tf_operator_tpu.api.types import (
    ObjectMeta,
    ProcessTemplate,
    ReplicaSpec,
    ReplicaType,
    TPUJob,
    TPUJobSpec,
)
from tf_operator_tpu.dashboard.client import TPUJobApiError, TPUJobClient

# CPU env for the smoke gang: the e2e driver checks the control plane
# and must run anywhere, including on a host whose chip is busy.
_CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
    "XLA_FLAGS": "",
}


def build_smoke_job(name: str, workers: int) -> TPUJob:
    """The tf_smoke analogue: every process joins the gang and the mesh-wide
    matmul checks every device (examples/tf_sample/tf_sample/tf_smoke.py)."""
    template = ProcessTemplate(
        entrypoint="tf_operator_tpu.workloads.smoke:main", env=dict(_CPU_ENV)
    )
    return TPUJob(
        metadata=ObjectMeta(name=name),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.COORDINATOR: ReplicaSpec(replicas=1, template=template),
                ReplicaType.WORKER: ReplicaSpec(replicas=workers, template=template),
            },
            workload={"dim": 32},
        ),
    )


def run_one(client: TPUJobClient, name: str, workers: int, timeout: float) -> str:
    """Run the full lifecycle for one job; returns '' or a failure message."""
    ns = "default"
    try:
        job = build_smoke_job(name, workers)
        client.create(job)
        # per-replica resources exist while running (main.go:135-148)
        detail = None
        import time

        deadline = time.time() + timeout
        want = 1 + workers
        while time.time() < deadline:
            detail = client.get(ns, name)
            if len(detail.get("processes", [])) >= want:
                break
            if detail["job"].get("phase") in ("Failed", "Done"):
                break
            time.sleep(0.5)
        n_procs = len((detail or {}).get("processes", []))
        if n_procs != want:
            return f"expected {want} processes, saw {n_procs}"
        done = client.wait_for_job(ns, name, timeout=timeout)
        phase = done.status.phase().value
        if phase != "Done":
            return f"terminal phase {phase}: {done.status.message}"
        client.delete(ns, name)
        client.wait_for_delete(ns, name, timeout=60)
        return ""
    except (TPUJobApiError, TimeoutError, OSError) as exc:
        try:  # best-effort cleanup so reruns aren't poisoned
            client.delete(ns, name)
        except Exception:
            pass
        return str(exc)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tpujob-e2e")
    p.add_argument("--server", default="http://127.0.0.1:8080")
    p.add_argument("--num-jobs", type=int, default=1,
                   help="parallel submissions (main.go:208-238)")
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--timeout", type=float, default=300.0)
    args = p.parse_args(argv)

    client = TPUJobClient(args.server)
    results: dict = {}

    def worker(i: int) -> None:
        results[i] = run_one(client, f"e2e-smoke-{i}", args.workers, args.timeout)

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(args.num_jobs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # TAP (main.go:244-252)
    print(f"1..{args.num_jobs}")
    failures = 0
    for i in range(args.num_jobs):
        msg = results.get(i, "no result")
        if msg:
            failures += 1
            print(f"not ok {i + 1} - e2e-smoke-{i}: {msg}")
        else:
            print(f"ok {i + 1} - e2e-smoke-{i}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
