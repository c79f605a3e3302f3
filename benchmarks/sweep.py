"""Find the knee once: the highest rate a serving cell's engine sustains
without a growing backlog. Sets the engine up once and offers each rate for
``--seconds``; prints per rate the completed tokens/s, how many requests
were in the system half-way and at the close, and the tails.

    python3 benchmarks/sweep.py --workload <cell> --rates 4,5,6,7,8 --seconds 15
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run, traffic  # noqa: E402


def in_system(requests, t: float) -> int:
    return sum(1 for r in requests
               if r.arrival <= t and not (0 <= r.finished <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell, runner, ctx = run.prepare(args.workload, args.seed, args.seconds)
    job = runner.setup(ctx)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        reqs = traffic.requests(args.seed, job.cfg.vocab, mix, args.seconds)
        served = job.serve(reqs, args.seconds, stop_at_close=True)
        s = job.summarise(served, args.seconds)
        e = job.end_to_end(s)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "serve_tokens_per_s": e["serve_tokens_per_s"],
            "in_system_half": in_system(served["requests"], args.seconds / 2),
            "in_system_close": in_system(served["requests"], args.seconds),
            "ttft_p50_ms": e["ttft_p50_ms"], "ttft_p95_ms": e["ttft_p95_ms"],
            "itl_p50_ms": e["itl_p50_ms"], "itl_p95_ms": e["itl_p95_ms"],
            "queue_wait_p95_ms": 1e3 * runner.percentile(s["queue_wait_s"], 0.95),
            "engine_step_p50_ms": 1e3 * runner.percentile(s["engine_step_s"], 0.5),
            "min_free_pages": s["min_free_pages"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
