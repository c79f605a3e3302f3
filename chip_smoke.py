#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Default run (one chip): bring an operator up, then drive the main path
twice through the entry points a user calls — ``tools.deploy up`` →
``tpujob submit`` → ``tpujob wait`` → worker log:

1. *train*: ``examples/gqa_2048_northstar.json`` (``workloads.lm``, preset
   gqa-2048 at full width and depth, t=2048, flash attention,
   ``save:resid_mid`` remat, adamw) for a few steps on one fixed batch, no
   checkpoint.
2. *serve*: ``tpujob submit --workload serve`` at the same preset's widths
   and depth: a handful of requests, all arriving at t=0, through the
   paged continuous-batching engine, and one of them held against plain
   un-paged greedy decoding through the model's own forward pass.

This launcher never imports jax: a process that has touched jax holds the
chip, and the workers need it. Everything it knows about the device it
reads from the ``run report: {...}`` line each worker logs. Each phase
prints one JSON line; the LAST line is the verdict,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``,
and the exit code is 0 only then. A phase that fails, a worker that was
not on a TPU, a compiled program without its Pallas kernels
(``tpu_custom_call``), or an engine program that copies or slices a whole
KV pool side or layer (``*_pool_copies`` of ``ServeEngine.compile()``)
ends ``"ok": false`` and exit 1 — so on a machine
with no TPU this script fails by construction, whatever ``--preset``.

    python chip_smoke.py                  # the chip run (what the driver runs)
    python chip_smoke.py --preset tiny    # CPU rehearsal of the control flow
    python chip_smoke.py --chips 4        # ONLY the sharded path + its control

``--chips 4`` runs one child process that drives all four chips: gqa-2048
on a ``{"fsdp": 4}`` mesh (``JobContext.build_mesh``) against the same
model, seed and global batch on a one-device mesh, three steps each.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(ROOT, ".cache", "chip_smoke")  # git-ignored
REPORT_MARK = "run report: "

# b=6 is the batch this preset was tuned at, and it still fits one 16 GB
# v5e under the installed jax 0.9.0 (PR 21 chip run: peak_bytes_in_use
# 9.59e9), although memory_analysis() of the same program adds up to more.
# It is the largest that fits, so the job states the smallest ``*_mid``
# set: under ``save_mid`` (flash_o + flash_lse since PR 33) the compiler
# rematerialises MLP matmuls of its own to fit and the step reads 0.660 s
# for 0.584 (PR 33 chip run; PERF.md §6).
TRAIN_BATCH = 6

SIZES = {
    # preset -> (train workload overrides, serve workload overrides)
    "gqa-2048": (
        {"preset": "gqa-2048", "steps": 7, "batch_size": TRAIN_BATCH,
         "seq_len": 2048, "attn": "flash", "remat": "save:resid_mid"},
        # full depth (12 layers), f32 weights and pools as the engine keeps
        # them; max_seq bounds the page table (prompt <= 512, +32 new)
        {"preset": "gqa-2048", "max_seq": 640, "requests": 8,
         "prompt_len": 256, "max_new_tokens": 32, "arrival_rate": 0,
         "kv_page_size": 64, "kv_pool_pages": 80, "max_slots": 8,
         "prefill_chunk": 128, "check_greedy": 1},
    ),
    "tiny": (
        {"preset": "tiny", "steps": 7, "batch_size": 4, "seq_len": 64,
         "attn": "flash", "remat": False},
        {"preset": "tiny", "requests": 8, "prompt_len": 16,
         "max_new_tokens": 8, "arrival_rate": 0, "kv_page_size": 8,
         "kv_pool_pages": 64, "max_slots": 4, "prefill_chunk": 16,
         "check_greedy": 1},
    ),
}
# the four-chip comparison: a global batch that divides by 4 and that one
# chip holds next to the f32 master weights and adam state
FSDP_SHAPES = {"gqa-2048": (4, 2048), "tiny": (4, 64)}
FSDP_LOSS_TOL = 0.05  # |fsdp=4 loss - one-device loss|, bf16 compute


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def run(cmd, timeout: float, env=None) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, *cmd], cwd=ROOT, capture_output=True, text=True,
        timeout=timeout, check=False, env=env,
    )


def tpujob(server: str, *args, timeout: float = 60.0):
    return run(
        ["-m", "tf_operator_tpu.cli.tpujob", "--server", server, *args],
        timeout,
    )


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def worker_report(server: str, job: str) -> dict:
    """The worker's own account of its run: the last ``run report`` line
    of rank 0's log. Raises when there is none — a worker that never got
    that far did not pass."""
    out = tpujob(server, "logs", "default", f"{job}-worker-0")
    lines = [ln for ln in out.stdout.splitlines() if REPORT_MARK in ln]
    if not lines:
        raise RuntimeError(
            f"no run report in {job}-worker-0's log; its tail:\n"
            + "\n".join(out.stdout.splitlines()[-25:])
        )
    return json.loads(lines[-1].split(REPORT_MARK, 1)[1])


def run_job(server: str, name: str, submit_args, timeout: float) -> dict:
    """submit → wait → phase + the worker's report. Raises on anything
    but a Succeeded job."""
    t0 = time.time()
    sub = tpujob(server, "submit", *submit_args)
    if sub.returncode != 0:
        raise RuntimeError(f"submit failed: {sub.stdout}{sub.stderr}")
    tpujob(server, "wait", "default", name, "--timeout", str(timeout),
           timeout=timeout + 30)
    got = json.loads(tpujob(server, "get", "default", name).stdout)
    conditions = [
        c["type"] for c in got["job"]["status"].get("conditions") or []
        if c.get("status")
    ]
    state = "Succeeded" if "Succeeded" in conditions else (
        conditions[-1] if conditions else "Unknown"
    )
    if state != "Succeeded":
        log = tpujob(server, "logs", "default", f"{name}-worker-0").stdout
        raise RuntimeError(
            f"job {name} ended {state} (conditions {conditions}); worker "
            f"log tail:\n" + "\n".join(log.splitlines()[-25:])
        )
    report = worker_report(server, name)
    report["job"] = state
    report["phase_wall_s"] = round(time.time() - t0, 1)
    return report


def train_phase(server: str, preset: str) -> dict:
    with open(os.path.join(ROOT, "examples", "gqa_2048_northstar.json")) as f:
        spec = json.load(f)
    spec["metadata"]["name"] = "smoke-train"
    # a few steps on one fixed batch, no checkpoint
    spec["spec"]["workload"] = dict(SIZES[preset][0])
    path = os.path.join(WORKDIR, "train.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    r = run_job(server, "smoke-train", [path], timeout=900)
    losses = r.get("losses") or []
    r["checks"] = {
        "losses_finite": bool(losses) and all(map(math.isfinite, losses)),
        "loss_falls": len(losses) >= 2 and losses[-1] < losses[0],
        "kernel_in_step": r.get("step_tpu_custom_calls", 0) > 0,
    }
    return r


def serve_phase(server: str, preset: str) -> dict:
    sets = []
    for k, v in SIZES[preset][1].items():
        sets += ["--set", f"{k}={v}"]
    r = run_job(
        server, "smoke-serve",
        ["--workload", "serve", "--name", "smoke-serve", *sets], timeout=900,
    )
    parity = r.get("greedy_parity") or []
    r["checks"] = {
        "all_finished": r.get("completed") == r.get("requests"),
        "no_page_leak": r.get("page_leaks") == 0,
        "greedy_parity": bool(parity) and all(
            p["max_logit_gap"] <= r["greedy_tol"] for p in parity
        ),
        "kernel_in_decode": r.get("decode_tpu_custom_calls", 0) > 0,
        "kernel_in_prefill": r.get("prefill_tpu_custom_calls", 0) > 0,
        # the KV pool is written and read where it lies: neither program
        # copies, slices or relays a whole pool side or layer of one
        "pool_in_place": r.get("decode_pool_copies") == 0
        and r.get("prefill_pool_copies") == 0,
    }
    return r


def verdict(reports: list, errors: list, want_count: int) -> int:
    """Print the last line and return the exit code."""
    devices = [
        {"platform": r.get("platform"), "kind": r.get("kind"),
         "count": r.get("count")} for r in reports
    ]
    failed = [
        f"{r.get('workload')}:{name}" for r in reports
        for name, ok in (r.get("checks") or {}).items() if not ok
    ]
    on_tpu = bool(devices) and all(
        d["platform"] == "tpu" and d["count"] == want_count for d in devices
    )
    if errors or failed or not on_tpu:
        say({
            "ok": False,
            "device": devices[0] if devices else None,
            "errors": [e.splitlines()[0][:300] for e in errors],
            "failed_checks": failed,
            "why": None if on_tpu else
            f"every worker must report platform tpu x{want_count}; got {devices}",
        })
        return 1
    say({"ok": True, "device": devices[0]})
    return 0


def one_chip(args) -> int:
    shutil.rmtree(WORKDIR, ignore_errors=True)
    os.makedirs(WORKDIR)
    port = free_port()
    server = f"http://127.0.0.1:{port}"
    deploy = ["-m", "tools.deploy", "--deploy-dir",
              os.path.join(WORKDIR, "deploy"), "--port", str(port)]
    reports, errors = [], []
    env = dict(os.environ)
    if args.preset != "tiny":
        # The real size is for the chip only: the operator's workers
        # inherit this, so where jax cannot have the TPU they fail at
        # start-up, in seconds, instead of grinding through a 0.8B-param
        # step on a CPU. The rehearsal size runs wherever jax lands.
        env["JAX_PLATFORMS"] = "tpu"
    up = run([*deploy, "up"], timeout=180, env=env)
    try:
        if up.returncode != 0:
            raise RuntimeError(f"tools.deploy up failed: {up.stdout}{up.stderr}")
        for name, phase in (("train", train_phase), ("serve", serve_phase)):
            try:
                report = phase(server, args.preset)
            except Exception as exc:  # noqa: BLE001 — reported, and fatal
                errors.append(f"{name}: {exc}")
                say({"phase": name, "error": str(exc)})
                continue
            reports.append(report)
            say({"phase": name, **report})
    except Exception as exc:  # noqa: BLE001 — reported, and fatal
        errors.append(str(exc))
    finally:
        # stop everything this run started: deleting a job kills its
        # workers' process groups (one that hung past its wait included),
        # then the operator goes
        for job in ("smoke-train", "smoke-serve"):
            tpujob(server, "delete", "default", job)
        deadline = time.time() + 15
        while time.time() < deadline and "smoke-" in tpujob(server, "list").stdout:
            time.sleep(0.5)
        run([*deploy, "down"], timeout=60)
    return verdict(reports, errors, want_count=1)


# ---- --chips 4: the sharded path and its one-device control --------------


def fsdp_child(preset_name: str) -> None:
    """Runs in its own process — the only one that touches jax."""
    import gc

    import jax
    import jax.numpy as jnp

    from tf_operator_tpu.models.transformer import (
        init_transformer,
        lm_loss,
        preset,
        transformer_logical_axes,
    )
    from tf_operator_tpu.parallel.mesh import build_mesh
    from tf_operator_tpu.rendezvous.context import JobContext
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

    batch, seq = FSDP_SHAPES[preset_name]
    kw = {"attn_impl": "flash"}
    if preset_name == "gqa-2048":
        kw["remat"] = "save_mid"
    cfg = preset(preset_name, **kw)
    devices = jax.devices()
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab
    )

    def three_steps(mesh):
        trainer = Trainer(
            mesh,
            loss_fn=lambda p, b, e: lm_loss(p, b, cfg, mesh=mesh),
            init_fn=lambda k: init_transformer(k, cfg),
            logical_axes=transformer_logical_axes(cfg),
            # threefry init: the rbg stream changes with the mesh
            config=TrainerConfig(optimizer="adamw", fast_init_rng=False),
        )
        text = trainer.compile_step(
            jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        ).as_text()
        state = trainer.init(jax.random.PRNGKey(0))
        jax.block_until_ready(state.params)
        in_use = [
            (d.memory_stats() or {}).get("bytes_in_use") for d in devices
        ]
        local = jax.device_put(tokens, trainer.batch_sharding)
        losses = []
        for _ in range(3):
            state, m = trainer.step(state, local)
            losses.append(float(m["loss"]))
        del state, trainer, local
        gc.collect()
        return losses, in_use, text

    sharded, in_use4, text = three_steps(
        JobContext(mesh_axes={"fsdp": 4}).build_mesh()
    )
    single, in_use1, _ = three_steps(build_mesh({"fsdp": 1}, devices=devices[:1]))
    diffs = [abs(a - b) for a, b in zip(sharded, single)]
    spread = None
    if all(b is not None for b in in_use4):
        # state spread over four devices, not sitting on the first: after
        # init every device holds about a quarter of what one device held
        spread = max(in_use4) <= 0.5 * in_use1[0] and min(in_use4) > 0
    report = {
        "workload": "fsdp4-vs-1", "preset": preset_name,
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "batch_size": batch, "seq_len": seq,
        "losses_fsdp4": sharded, "losses_one_device": single,
        "max_abs_loss_diff": max(diffs), "loss_tol": FSDP_LOSS_TOL,
        "bytes_in_use_after_init_fsdp4": in_use4,
        "bytes_in_use_after_init_one_device": in_use1,
        "all_gathers_in_step": text.count("all-gather"),
        "step_tpu_custom_calls": text.count("tpu_custom_call"),
        "checks": {
            "losses_finite": all(map(math.isfinite, sharded + single)),
            "losses_agree": max(diffs) <= FSDP_LOSS_TOL,
            "state_is_sharded": bool(spread),
            "step_gathers_params": text.count("all-gather") > 0,
            "kernel_in_step": text.count("tpu_custom_call") > 0,
        },
    }
    print(REPORT_MARK + json.dumps(report), flush=True)


def four_chips(args) -> int:
    proc = run([os.path.abspath(__file__), "--fsdp-child", "--preset",
                args.preset], timeout=1500)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith(REPORT_MARK)]
    if proc.returncode != 0 or not lines:
        err = f"fsdp child exited {proc.returncode}: {proc.stderr[-2000:]}"
        say({"phase": "fsdp4-vs-1", "error": err})
        return verdict([], [err], want_count=4)
    report = json.loads(lines[-1][len(REPORT_MARK):])
    say({"phase": "fsdp4-vs-1", **report})
    return verdict([report], [], want_count=4)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--preset", choices=sorted(SIZES), default="gqa-2048",
                   help="'tiny' rehearses the control flow on a CPU "
                        "(and still ends ok: false there)")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--fsdp-child", action="store_true",
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.fsdp_child:
        sys.path.insert(0, ROOT)
        fsdp_child(args.preset)
        return 0
    return four_chips(args) if args.chips == 4 else one_chip(args)


if __name__ == "__main__":
    sys.exit(main())
