"""The harness end to end at ``tiny`` on the CPU, through a test-only
device check; the command itself still refuses to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import run
from conftest import REPO, cpu_devices

TRAIN, SERVE = "mistral7b-train-1chip", "mistral7b-serve-chat"


def cell_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["workloads"]]


def test_command_fails_without_a_tpu():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    out = subprocess.run(
        [sys.executable if command[0].startswith("python") else command[0],
         *command[1:], "--workload", TRAIN, "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


@pytest.mark.parametrize("cell", cell_names())
def test_every_cell_runs_end_to_end_at_tiny(tiny_root, cell):
    result = run.run_cell(cell, 2**31 + 3, 2.0, False, root=tiny_root,
                          device_check=cpu_devices)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"  # every result names its device
    loaded = run.load_cell(tiny_root, cell)
    assert set(result["metrics"]) == {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in result["metrics"]
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    json.dumps(result)


def test_new_cell_config_mix_and_metric_are_found_as_new_files(tiny_root):
    """A later PR adds files and BENCHMARK.json entries, and edits nothing."""
    home = os.path.join(tiny_root, "benchmarks")
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _, fs in os.walk(home) for p in fs}
    cfg = json.load(open(os.path.join(home, "configs", "mistral-7b-v0.1-train1.json")))
    cfg["name"] = "new-config"
    json.dump(cfg, open(os.path.join(home, "configs", "new-config.json"), "w"))
    mix = json.load(open(os.path.join(home, "traffic", "pretrain-4k.json")))
    mix["batch_size"] = 2
    json.dump(mix, open(os.path.join(home, "traffic", "new-mix.json"), "w"))
    with open(os.path.join(home, "metrics", "steps_counted.py"), "w") as f:
        f.write("def read(run):\n    return float(run.samples['steps'])\n")
    with open(os.path.join(home, "metrics", "never_there.py"), "w") as f:
        f.write("def read(run):\n    return None\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "new-config", "source": "x",
                             "file": "benchmarks/configs/new-config.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-cell", "config": "new-config",
                               "traffic": "new-mix", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("new-cell")
    for name in ("steps_counted.new", "never_there"):
        bench["per_layer"].append({
            "name": name, "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "trainer",
            "moves": "train_tokens_per_s", "workloads": ["new-cell"]})
    json.dump(bench, open(path, "w"))

    loaded = run.load_cell(tiny_root, "new-cell")
    assert loaded.mix["batch_size"] == 2 and loaded.config["name"] == "new-config"
    names = {m["name"] for m in loaded.per_layer}
    assert {"steps_counted.new", "never_there", "mfu", "data_wait_ms"} <= names
    # a quantity split by what it moves shares the one reader of the quantity
    assert run.reader_path(home, "steps_counted.new").endswith("steps_counted.py")
    assert run.reader_path(home, "device_idle_share.train").endswith("device_idle_share.py")
    assert "engine_step_ms" not in names  # moves a metric this cell does not report
    result = run.run_cell("new-cell", 5, 1.0, False, root=tiny_root,
                          device_check=cpu_devices)
    assert result["correct"] and set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    after = {p: open(os.path.join(dp, p)).read()
             for dp, _, fs in os.walk(home) for p in fs if p in before}
    assert after == before


def test_unknown_workload_and_too_few_chips_give_no_result(tiny_root):
    with pytest.raises(SystemExit):
        run.run_cell("no-such-cell", 1, 1.0, False, root=tiny_root,
                     device_check=cpu_devices)
    with pytest.raises(SystemExit):
        run.require_tpu(1)  # the CPU is not a TPU
