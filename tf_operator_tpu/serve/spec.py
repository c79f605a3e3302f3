"""Serve TPUJob construction — the seam shared by ``tpujob submit
--workload serve``, tools/preempt_probe.py, and
tools/trace_smoke.py's smoke serve job. One builder so the workload-key
vocabulary (kv_page_size, kv_pool_pages, requests, ...) has exactly one
authoritative spelling."""

from __future__ import annotations

from typing import Any, Dict, Optional

from tf_operator_tpu.api.types import (
    JOB_CLASS_SERVING,
    ObjectMeta,
    ProcessTemplate,
    ReplicaSpec,
    ReplicaType,
    SchedulingSpec,
    TPUJob,
    TPUJobSpec,
)

SERVE_ENTRYPOINT = "tf_operator_tpu.workloads.serve:main"

# The workload-config vocabulary workloads/serve.py reads (defaults are
# the tiny smoke size; a real deployment overrides).
SERVE_WORKLOAD_DEFAULTS: Dict[str, Any] = {
    "preset": "tiny",
    "requests": 8,          # number of synthetic requests to serve
    "prompt_len": 8,        # mean synthetic prompt length (tokens)
    "max_new_tokens": 16,   # generation budget per request
    "arrival_rate": 20.0,   # Poisson arrivals per second (0 ⇒ all at t=0)
    "seed": 0,              # arrival schedule + prompt RNG
    "kv_page_size": 16,
    "kv_pool_pages": 64,
    "max_slots": 4,
    "prefill_chunk": 16,
    "report_every": 4,      # engine steps between live status reports
}


def build_serve_job(
    name: str,
    namespace: str = "default",
    env: Optional[Dict[str, str]] = None,
    queue: str = "",
    priority: str = "",
    chips: int = 1,
    workload: Optional[Dict[str, Any]] = None,
) -> TPUJob:
    """One-worker serve job: the engine is a single-process decode loop
    (multi-host serving is roadmap, not r10). job_class="serving" rides
    along so the fleet scheduler treats it as latency-sensitive.

    The worker runs on whatever backend its machine gives jax — on a TPU
    host, the TPU — and asks the scheduler for the one chip it uses.
    Nothing here pins a platform: a caller that wants the CPU (tests,
    soaks, tools/preempt_probe.py) passes that ``env`` itself."""
    wl = dict(SERVE_WORKLOAD_DEFAULTS)
    wl.update(workload or {})
    spec = TPUJobSpec(
        replica_specs={
            ReplicaType.WORKER: ReplicaSpec(
                replicas=1,
                template=ProcessTemplate(
                    entrypoint=SERVE_ENTRYPOINT, env=dict(env or {}),
                    chips_per_process=chips,
                ),
            )
        },
        workload=wl,
        scheduling=SchedulingSpec(
            queue=queue, priority_class=priority, job_class=JOB_CLASS_SERVING
        ),
    )
    return TPUJob(metadata=ObjectMeta(name=name, namespace=namespace), spec=spec)
