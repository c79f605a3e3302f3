"""Operator preemption probe: a serve job preempts a training victim.

Deploys a FRESH operator daemon and replays the mixed-priority story end
to end: a training job (lm, checkpointing) holds a one-job-quota Queue; a
serve job submitted with job_class="serving" (fleet base priority 100 vs
training's 0) must preempt it; the victim must drain and warm-resume
(preemption_count 1, restart_count 0, cause "preemption") and still
finish, while every serve request completes (eval_metrics receipt) and
the reconciler folds the request spans into tpujob_request_ttft_seconds /
tpujob_request_tokens_total at terminal. A control-plane check on the
CPU: it measures nothing.

Usage:
    python -m tools.preempt_probe --seed 7 --out artifacts/preempt_probe.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env() -> dict:
    return {
        "JAX_PLATFORMS": "cpu",
        "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
        "XLA_FLAGS": "",
        # native tracebacks in the kept process logs when a probe child
        # dies on a signal — costs nothing, saves a bisect.
        "PYTHONFAULTHANDLER": "1",
    }


def _victim_job(checkpoint_dir: str, chips: int):
    """Low-priority (job_class defaults to training → fleet base 0) lm
    trainer with periodic checkpoints, long enough to still be running
    when the serve job lands, short enough to finish after warm-resume."""
    from tf_operator_tpu.api.types import (
        ObjectMeta,
        ProcessTemplate,
        ReplicaSpec,
        ReplicaType,
        SchedulingSpec,
        TPUJob,
        TPUJobSpec,
    )

    return TPUJob(
        metadata=ObjectMeta(name="victim", namespace="probe"),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=1,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.lm:main",
                        env=_cpu_env(),
                        chips_per_process=chips,
                    ),
                )
            },
            workload={
                "preset": "tiny", "steps": 3000, "batch_size": 2,
                "seq_len": 16, "checkpoint_dir": checkpoint_dir,
                "checkpoint_every": 50, "data": "fixed",
            },
            scheduling=SchedulingSpec(queue="main"),
        ),
    )


def run_probe(args) -> dict:
    sys.path.insert(0, REPO_ROOT)
    import urllib.request

    from tf_operator_tpu.api.types import ObjectMeta
    from tf_operator_tpu.dashboard.client import TPUJobClient
    from tf_operator_tpu.sched.objects import Queue, QueueSpec
    from tf_operator_tpu.serve.spec import build_serve_job
    from tools.genjob import (
        _parse_histogram,
        _scrape_counter,
        _start_operator,
        _stop_operator,
    )

    chips = 4
    out = {"ok": False, "error": ""}
    op_args = argparse.Namespace(bench_backend=args.backend)
    operator, server, workdir, log_path = _start_operator(op_args, "serve")
    try:
        client = TPUJobClient(server)
        # exactly one job's chips fit: the serve job can only run by
        # preempting the training victim
        client.create_object(Queue(
            metadata=ObjectMeta(name="main", namespace="probe"),
            spec=QueueSpec(quota_chips=chips),
        ))
        ckpt_dir = os.path.join(workdir, "victim-ckpt")
        client.create(_victim_job(ckpt_dir, chips))
        deadline = time.time() + 60
        while time.time() < deadline:
            if client.get_job("probe", "victim").status.phase().value == "Running":
                break
            time.sleep(0.25)
        else:
            out["error"] = "victim never started running"
            return out
        # wait for one committed checkpoint so the resume is warm, not a
        # from-scratch rerun (bounded: preemption is correct either way)
        deadline = time.time() + 45
        while time.time() < deadline:
            if os.path.isdir(ckpt_dir) and any(os.scandir(ckpt_dir)):
                break
            time.sleep(0.5)

        serve = build_serve_job(
            "server", namespace="probe", queue="main", chips=chips,
            env=_cpu_env(),
            workload={
                "requests": 6, "prompt_len": 8, "max_new_tokens": 8,
                "arrival_rate": 0.0, "seed": args.seed, "report_every": 1,
            },
        )
        t0 = time.time()
        client.create(serve)
        sjob = client.wait_for_job("probe", "server", timeout=180)
        out["serve_wait_s"] = round(time.time() - t0, 2)
        out["serve_phase"] = sjob.status.phase().value
        # eval_metrics round-trips through the REST store as a plain dict
        # ({"step":..., "metrics": {...}}) on client-fetched jobs.
        em = sjob.status.eval_metrics
        if isinstance(em, dict):
            metrics = em.get("metrics") or {}
        else:
            metrics = getattr(em, "metrics", {}) or {}
        out["requests_total"] = int(metrics.get("requests_total", 0))
        out["requests_completed"] = int(metrics.get("requests_completed", 0))

        victim = client.wait_for_job("probe", "victim", timeout=300)
        out.update(
            victim_phase=victim.status.phase().value,
            preemption_count=victim.status.preemption_count,
            restart_count=victim.status.restart_count,
            last_restart_cause=victim.status.last_restart_cause,
        )

        # terminal-fold receipt: the reconciler turned the serve job's
        # request spans into fleet metrics
        with urllib.request.urlopen(server + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        _, ttft_n = _parse_histogram(text, "tpujob_request_ttft_seconds")
        out["ttft_samples"] = ttft_n
        out["tokens_total_metric"] = _scrape_counter(
            text, "tpujob_request_tokens_total"
        )

        if sjob.status.phase().value != "Done":
            out["error"] = f"serve job finished {sjob.status.phase().value}"
        elif out["requests_completed"] != out["requests_total"] or not out["requests_total"]:
            out["error"] = (
                f"serve completed {out['requests_completed']}/"
                f"{out['requests_total']} requests"
            )
        elif victim.status.phase().value != "Done":
            out["error"] = "victim did not finish after preemption"
        elif victim.status.preemption_count != 1:
            out["error"] = (
                f"victim preemption_count {victim.status.preemption_count}, "
                "expected exactly 1"
            )
        elif victim.status.restart_count != 0:
            out["error"] = "preemption was charged to restart_count/backoff"
        elif victim.status.last_restart_cause != "preemption":
            out["error"] = (
                f"restart cause {victim.status.last_restart_cause!r}, "
                "expected 'preemption'"
            )
        elif not ttft_n:
            out["error"] = "no tpujob_request_ttft_seconds samples at terminal"
        else:
            out["ok"] = True
    except Exception as exc:  # a failure is the probe's result, not a crash
        out["error"] = f"{type(exc).__name__}: {exc}"
        out["log"] = log_path
    finally:
        _stop_operator(operator, workdir, keep=not out["ok"])
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--backend", choices=("native", "local"), default="native",
                   help="process backend for the probe's operator")
    p.add_argument("--out", default=None,
                   help="write the one-line JSON artifact here")
    args = p.parse_args(argv)

    probe = run_probe(args)
    line = json.dumps(probe)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if not probe["ok"]:
        print(f"FAIL: probe: {probe['error']}", file=sys.stderr)
    return 0 if probe["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
