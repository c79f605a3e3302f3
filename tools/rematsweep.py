"""Selective-remat sweep at north-star shapes (r5, VERDICT r4 #1).

Full remat replays qkv+attn+wo+gate+up in the backward (+23 of the 31
per-layer fwd matmul units at gqa-2048 shapes); "dots" saves every matmul
output and OOMs at every batch that fits full remat. This tool measures
the ladder BETWEEN them (transformer._REMAT_SAVE_SETS — named-activation
policies over the flash residuals, the post-attention residual stream,
and the MLP pre-activations) on the real chip, batch by batch.

Each (policy, batch) cell runs ``bench.py`` in a SUBPROCESS
(BENCH_MODEL=gqa-2048) so every measurement starts from an empty chip —
a fragmented heap would otherwise fake OOMs for the larger policies. OOM
is detected from RESOURCE_EXHAUSTED in the child's stderr and reported
as a row, not an error: "this policy does not fit at this batch" is the
receipt the sweep exists to produce.

``--flops`` instead compiles the train step under each policy (no
execution — works on CPU too) and prints the compiled-executable FLOP
counts: the driver-verifiable receipt that each tier actually retires
recompute rather than renaming it.

Usage:
    python -m tools.rematsweep [--policies full,save_qkv_mid,...] \
        [--batches 6,4,2,1] [--steps 20] [--out REMAT_SWEEP.json]
    python -m tools.rematsweep --flops [--batch 1]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

# The environment as this sweep was STARTED with: the advisory memplan
# below pins this parent to the CPU backend (it must not take the chip —
# each bench child needs it), and that pin must not leak into a child.
_START_ENV = dict(os.environ)

DEFAULT_POLICIES = (
    "full",
    "save_mid",
    "save_qkv",
    "save_qkv_mid",
    "save_qkv_mid_up",
    "save_qkv_mid_mlp",
    "save_mlp_mid",
)


def _memplan_gb(policy: str, batch: int, seq: int) -> float:
    from tools.memplan import plan

    remat = True if policy == "full" else policy
    out = plan("gqa-2048", {"dp": 1}, batch, seq, remat=remat)
    return out["total_gb"]


def run_cell(policy: str, batch: int, seq: int, steps: int, timeout: int):
    env = dict(
        _START_ENV,
        BENCH_MODEL="gqa-2048",
        BENCH_BATCH=str(batch),
        BENCH_SEQ=str(seq),
        BENCH_STEPS=str(steps),
        BENCH_NORTHSTAR="0",
        BENCH_ATTN="flash",
        BENCH_REMAT="1" if policy == "full" else policy,
        BENCH_DATA="fixed",
        BENCH_ACCUM="1",
    )
    env.pop("BENCH_PROFILE", None)
    env.pop("BENCH_DEVICE_LOOP", None)
    row = {"policy": policy, "batch": batch, "seq": seq}
    try:
        row["memplan_gb"] = round(_memplan_gb(policy, batch, seq), 2)
    except Exception as exc:  # noqa: BLE001 — the plan is advisory
        row["memplan_gb"] = f"error: {exc}"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "bench.py")],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    if proc.returncode != 0:
        err = proc.stderr[-2000:]
        if "RESOURCE_EXHAUSTED" in err or "Out of memory" in err:
            row["status"] = "OOM"
            for line in reversed(proc.stderr.splitlines()):
                if "RESOURCE_EXHAUSTED" in line:
                    row["oom_detail"] = line.strip()[:200]
                    break
        else:
            row["status"] = f"error rc={proc.returncode}"
            row["stderr_tail"] = err[-400:]
        return row
    bench = json.loads(proc.stdout.strip().splitlines()[-1])
    row.update(
        status="ok",
        mfu=bench["mfu"],
        mfu_6nd=bench["mfu_6nd"],
        tokens_per_sec_per_chip=bench["value"],
        step_time_s=bench["step_time_s"],
        loss=bench["loss"],
    )
    return row


def flops_receipt(batch: int, seq: int, policies) -> list:
    """Compiled-executable FLOPs per policy (no execution). The recompute
    each tier retires must show up HERE, in XLA's own cost model."""
    import jax

    from tf_operator_tpu.models.transformer import (
        init_transformer,
        lm_loss,
        preset,
    )

    rows = []
    for policy in policies:
        remat = True if policy == "full" else policy
        cfg = preset("gqa-2048", max_seq=seq, attn_impl="flash", remat=remat)
        params = jax.eval_shape(
            lambda k: init_transformer(k, cfg), jax.random.PRNGKey(0)
        )
        tok = jax.ShapeDtypeStruct((batch, seq), "int32")

        def step(p, t, _cfg=cfg):
            return jax.grad(lambda q: lm_loss(q, t, _cfg))(p)

        compiled = jax.jit(step).lower(params, tok).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        rows.append(
            {
                "policy": policy,
                "batch": batch,
                "seq": seq,
                "compiled_gflops": round(float(cost.get("flops", 0.0)) / 1e9, 1),
                "bytes_accessed_gb": round(
                    float(cost.get("bytes accessed", 0.0)) / 2**30, 2
                ),
            }
        )
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--policies", default=",".join(DEFAULT_POLICIES))
    p.add_argument("--batches", default="6,4,2,1")
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--timeout", type=int, default=600)
    p.add_argument("--out", default=None, help="write rows as JSON to this path")
    p.add_argument("--flops", action="store_true",
                   help="compiled-FLOPs receipt instead of timed runs")
    p.add_argument("--batch", type=int, default=1, help="--flops batch size")
    args = p.parse_args(argv)
    policies = [s.strip() for s in args.policies.split(",") if s.strip()]

    if args.flops:
        rows = flops_receipt(args.batch, args.seq, policies)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
        return 0

    rows = []
    for policy in policies:
        for batch in (int(b) for b in args.batches.split(",")):
            row = run_cell(policy, batch, args.seq, args.steps, args.timeout)
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    best = max(
        (r for r in rows if r.get("status") == "ok"),
        key=lambda r: r["mfu"],
        default=None,
    )
    if best:
        print("# best:", json.dumps(best))
    return 0


if __name__ == "__main__":
    sys.exit(main())
