"""CPU rehearsal of chip_smoke.py at preset ``tiny`` — the no-fallback rule
as a test: the script drives every phase through the operator (deploy up →
submit → wait → worker log), both jobs succeed, and the run still ENDS
``"ok": false`` with a non-zero exit, because no worker was on a TPU and no
compiled program holds a Mosaic kernel."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_rehearsal_runs_every_phase_and_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--preset", "tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    # the control flow ran end to end: both jobs succeeded on the CPU
    assert set(phases) == {"train", "serve"}, proc.stdout + proc.stderr
    for name, ph in phases.items():
        assert "error" not in ph, ph
        assert ph["job"] == "Succeeded" and ph["platform"] == "cpu"
    train, serve = phases["train"], phases["serve"]
    assert train["checks"]["losses_finite"] and train["checks"]["loss_falls"]
    assert serve["checks"]["all_finished"] and serve["checks"]["no_page_leak"]
    # the engine against the model, un-paged: exact on the CPU
    assert serve["checks"]["greedy_parity"]
    assert serve["greedy_parity"][0]["max_logit_gap"] == 0.0
    # ...and none of that makes it a pass: no TPU, no kernel, no ok
    assert not train["checks"]["kernel_in_step"]
    assert not serve["checks"]["kernel_in_decode"]
    last = lines[-1]
    assert last["ok"] is False and proc.returncode != 0
    assert "tpu" in last["why"]
