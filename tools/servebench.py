"""Serving bench: continuous-batching A/B + the operator preemption probe.

The r10 acceptance oracle, in two halves:

- **A/B bench** (default): one in-process ServeEngine serves the SAME
  seeded request trace twice — ``mode="continuous"`` (iteration-level
  admission, immediate eviction) vs ``mode="static"`` (admit only into an
  empty batch, hold every slot until the whole batch drains: the
  classic request-level batcher). Same params, same compiled step
  functions (a warmup run pays the jit once, outside both timed runs),
  same arrival schedule — the only variable is the batching policy.
  Emits a one-line JSON artifact (tokens/s both modes, ratio, p50/p99
  TTFT, per-token latency) and gates: every request completed in both
  modes, zero KV page leaks, continuous >= --min-ratio x static
  tokens/s at equal-or-better p99 TTFT.

- **--probe**: deploys a FRESH operator daemon and replays the
  mixed-priority story end to end: a training job (lm, checkpointing)
  holds a one-job-quota Queue; a serve job submitted with
  job_class="serving" (fleet base priority 100 vs training's 0) must
  preempt it; the victim must drain and warm-resume (preemption_count
  1, restart_count 0, cause "preemption") and still finish, while every
  serve request completes (eval_metrics receipt) and the reconciler
  folds the request spans into tpujob_request_ttft_seconds /
  tpujob_request_tokens_total at terminal.

Usage:
    python -m tools.servebench --seed 7 --out artifacts/servebench.json
    python -m tools.servebench --seed 7 --probe --out ...   # + operator run
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quantile(xs, q):
    if not xs:
        return 0.0
    ys = sorted(xs)
    idx = min(len(ys) - 1, int(round(q * (len(ys) - 1))))
    return ys[idx]


# ---- in-process continuous-vs-static A/B --------------------------------


def _mode_row(res, n_requests: int) -> dict:
    ttfts = res.ttfts()
    lats = res.token_latencies()
    return {
        "completed": res.completed,
        "requests": n_requests,
        "generated_tokens": res.generated_tokens,
        "steps": res.steps,
        "wall_s": round(res.wall_s, 3),
        "tokens_per_s": round(res.tokens_per_s, 1),
        "ttft_p50_ms": round(_quantile(ttfts, 0.50) * 1e3, 1),
        "ttft_p99_ms": round(_quantile(ttfts, 0.99) * 1e3, 1),
        "token_latency_p50_ms": round(_quantile(lats, 0.50) * 1e3, 2),
        "token_latency_p99_ms": round(_quantile(lats, 0.99) * 1e3, 2),
        "kv_page_leaks": res.free_pages_start - res.free_pages_end,
    }


def run_ab(args) -> dict:
    # A scheduler-policy A/B at preset tiny, on the CPU BY DESIGN and
    # said so: it pins the platform itself (not a silent setdefault) and
    # names the device in its artifact. Its tokens/s compare two batching
    # policies on one host; they are not device numbers.
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO_ROOT)
    import jax

    from tf_operator_tpu.models.transformer import init_transformer, preset
    from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine
    from tf_operator_tpu.workloads.serve import synthesize_requests

    cfg = preset(args.preset)
    scfg = ServeConfig(
        page_size=args.kv_page_size,
        pool_pages=args.kv_pool_pages,
        max_slots=args.max_slots,
        prefill_chunk=args.prefill_chunk,
    )
    params = init_transformer(jax.random.PRNGKey(args.seed), cfg)
    engine = ServeEngine(cfg, params, scfg)
    wl = {
        "seed": args.seed,
        "requests": args.requests,
        "prompt_len": args.prompt_len,
        "max_new_tokens": args.max_new_tokens,
        "arrival_rate": args.arrival_rate,
    }
    # warmup: pay prefill+decode jit outside both timed runs so the A/B
    # compares policies, not compile order
    engine.run(synthesize_requests({**wl, "requests": 2}, cfg.vocab))

    rows = {}
    for mode in ("continuous", "static"):
        reqs = synthesize_requests(wl, cfg.vocab)
        res = engine.run(reqs, mode=mode)
        rows[mode] = _mode_row(res, len(reqs))
        print(f"{mode}: {json.dumps(rows[mode])}", flush=True)
    cont, stat = rows["continuous"], rows["static"]
    ratio = (
        cont["tokens_per_s"] / stat["tokens_per_s"]
        if stat["tokens_per_s"] else 0.0
    )
    return {
        "metric": "serve_bench",
        "unit": "tokens/s",
        "device": jax.devices()[0].platform,
        "preset": args.preset,
        "seed": args.seed,
        "requests": args.requests,
        "max_slots": args.max_slots,
        "kv_page_size": args.kv_page_size,
        "kv_pool_pages": args.kv_pool_pages,
        "arrival_rate": args.arrival_rate,
        "continuous": cont,
        "static": stat,
        "continuous_vs_static": round(ratio, 2),
    }


def gate_ab(artifact: dict, min_ratio: float) -> list:
    """The CI contract as a list of human-readable failures (empty = pass)."""
    bad = []
    for mode in ("continuous", "static"):
        row = artifact[mode]
        if row["completed"] != row["requests"]:
            bad.append(
                f"{mode}: only {row['completed']}/{row['requests']} "
                f"requests completed"
            )
        if row["kv_page_leaks"]:
            bad.append(f"{mode}: {row['kv_page_leaks']} KV pages leaked")
    ratio = artifact["continuous_vs_static"]
    if ratio < min_ratio:
        bad.append(
            f"continuous/static tokens/s ratio {ratio} under the "
            f"{min_ratio}x floor"
        )
    if artifact["continuous"]["ttft_p99_ms"] > artifact["static"]["ttft_p99_ms"]:
        bad.append(
            f"continuous p99 TTFT {artifact['continuous']['ttft_p99_ms']}ms "
            f"worse than static {artifact['static']['ttft_p99_ms']}ms"
        )
    return bad


# ---- --probe: serve-preempts-training on a live operator ----------------


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
    except Exception as exc:  # probe failures fail the bench, not crash it
        out["error"] = f"{type(exc).__name__}: {exc}"
        out["log"] = log_path
    finally:
        _stop_operator(operator, workdir, keep=not out["ok"])
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--preset", default="tiny")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="Poisson req/s; 0 = all at t=0 (pure policy A/B)")
    p.add_argument("--max-slots", type=int, default=6)
    p.add_argument("--kv-page-size", type=int, default=8)
    p.add_argument("--kv-pool-pages", type=int, default=96)
    p.add_argument("--prefill-chunk", type=int, default=16)
    p.add_argument("--min-ratio", type=float, default=1.5,
                   help="continuous must beat static tokens/s by this factor")
    p.add_argument("--probe", action="store_true",
                   help="also run the serve-preempts-training operator probe")
    p.add_argument("--backend", choices=("native", "local"), default="native",
                   help="process backend for the probe's operator")
    p.add_argument("--out", default=None,
                   help="write the one-line JSON artifact here")
    args = p.parse_args(argv)

    artifact = run_ab(args)
    bad = gate_ab(artifact, args.min_ratio)

    if args.probe:
        probe = run_probe(args)
        artifact["probe"] = probe
        if not probe.get("ok"):
            bad.append(f"probe: {probe.get('error')}")

    line = json.dumps(artifact)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    for msg in bad:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
