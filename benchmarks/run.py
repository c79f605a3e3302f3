"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip: it builds the cell's program through the
same two classes the operator's workers drive (``train.trainer.Trainer`` /
``serve.engine.ServeEngine``), warms up, measures for ``--seconds``,
decides ``correct`` against ``benchmarks/reference.py`` and prints the
result as the last line of standard output. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.

Everything that belongs to one thing is a file of its own, found by the
name ``BENCHMARK.json`` gives it — a cell names a config (``configs/``)
and a mix (``traffic/``), the mix names its runner (``runners/``), a
per-layer metric is a reader under ``metrics/`` — so a later change adds
files and entries and edits nothing that is here.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:  # run as a file: make `benchmarks` and the program importable
    sys.path.insert(0, ROOT)

HF_TO_SIZES = {
    "vocab": "vocab_size", "d_model": "hidden_size",
    "n_layers": "num_hidden_layers", "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
    "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
}


class Check(SimpleNamespace):
    """One number compared with its limit (``value <= limit`` passes)."""

    def __init__(self, name: str, value: float, limit: float):
        ok = bool(math.isfinite(value) and value <= limit)
        super().__init__(name=name, value=value, limit=limit, ok=ok)


def say(msg: str) -> None:
    print(msg, flush=True)


def _load_py(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(home: str, metric: str) -> str:
    """A per-layer metric's reader: ``metrics/<name>.py``, or, for a quantity
    split by the end-to-end metric it moves (``device_idle_share.train``,
    ``.serve``: one entry each in BENCHMARK.json), the one reader of the
    quantity, ``metrics/<name before the last dot>.py``."""
    own = os.path.join(home, "metrics", f"{metric}.py")
    if os.path.exists(own) or "." not in metric:
        return own
    return os.path.join(home, "metrics", f"{metric.rsplit('.', 1)[0]}.py")


def load_cell(root: str, workload: str) -> SimpleNamespace:
    """Everything the files say about one cell."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    home = os.path.join(root, bench["paths"][0])
    from benchmarks import traffic

    mix = traffic.load_mix(cell["traffic"], home)

    def in_cell(metric) -> bool:
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if in_cell(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if in_cell(m) and m["moves"] in reported]
    return SimpleNamespace(
        name=workload, chips=int(cell["chips"]), config=config, mix=mix,
        home=home, end_to_end=e2e, per_layer=per_layer,
        sizes={k: config[v] for k, v in HF_TO_SIZES.items()},
    )


def require_tpu(chips: int) -> List[Any]:
    """The chips to run on, or no run at all."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark needs a TPU; JAX found {devices[0].platform!r} "
            f"({devices[0].device_kind}) — no result")
    if len(devices) < chips:
        raise SystemExit(
            f"cell asks for {chips} chips, JAX found {len(devices)} — no result")
    return devices[:chips]


class _CompileCounter:
    """Counts XLA backend compiles from now on: the window should see none."""

    def __init__(self):
        import jax.monitoring

        self.n, self.on = 0, True
        jax.monitoring.register_event_duration_secs_listener(self._heard)

    def _heard(self, event: str, duration: float, **kw) -> None:
        if self.on and event.endswith("backend_compile_duration"):
            self.n += 1

    def stop(self) -> int:
        self.on = False
        return self.n


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def prepare(workload: str, seed: int, seconds: float, *, root: Optional[str] = None,
            device_check: Optional[Callable[[int], List[Any]]] = None):
    """The cell's files, its devices, its runner and the context a runner
    gets: what a run, the control reading and the rate sweep all start from."""
    cell = load_cell(root or ROOT, workload)
    devices = (device_check or require_tpu)(cell.chips)

    from benchmarks import flops
    from tf_operator_tpu.train import compile_cache

    cache = compile_cache.enable()
    say(f"cell {workload}: seed={seed} seconds={seconds} "
        f"devices={len(devices)}x{devices[0].device_kind} compile_cache={cache}")
    runner = _load_py(
        os.path.join(cell.home, "runners", f"{cell.mix['runner']}.py"),
        f"benchmarks_runner_{cell.mix['runner']}")
    ctx = SimpleNamespace(
        cell=cell, config=cell.config, mix=cell.mix, sizes=cell.sizes,
        seed=int(seed), seconds=float(seconds), devices=devices,
        chips=cell.chips, say=say, Check=Check,
        peaks=flops.peaks_for(devices[0].device_kind)
        if devices[0].platform == "tpu" else None,
    )
    return cell, runner, ctx


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, *,
    root: str = ROOT,
    device_check: Callable[[int], List[Any]] = require_tpu,
    t_process: Optional[float] = None,
) -> Dict[str, Any]:
    """One run of one cell; returns the result line's object."""
    t_process = time.time() if t_process is None else t_process
    cell, runner, ctx = prepare(workload, seed, seconds, root=root,
                                device_check=device_check)
    devices = ctx.devices

    import jax

    from benchmarks import trace_reduce

    job = runner.setup(ctx)
    setup_s = time.time() - t_process
    compiles = _CompileCounter()
    samples = job.window(float(seconds))
    say(f"note compiles_in_window: {compiles.stop()!r}")

    reduced = None
    if trace:
        trace_dir = os.path.join(root, ".cache", "bench_trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
                samples["traced"] = job.traced_window()
        finally:
            jax.profiler.stop_trace()
        reduced = trace_reduce.reduce_dir(trace_dir, len(devices))
    peak = _peak_bytes(devices)

    values = dict(job.end_to_end(samples), setup_s=setup_s)
    for name, v in sorted(samples.get("notes", {}).items()):
        say(f"note {name}: {v!r}")
    for name, v in sorted(values.items()):  # the cell names some of these
        say(f"value {name}: {v!r}")

    job.release()
    checks: List[Check] = job.check(samples)
    for c in checks:
        say(f"check {c.name}: value={c.value!r} limit={c.limit!r} "
            f"{'ok' if c.ok else 'NOT OK'}")
    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        record = SimpleNamespace(
            samples=samples, trace=reduced, sizes=cell.sizes, mix=cell.mix,
            config=cell.config, peaks=ctx.peaks, chips=cell.chips, say=say)
        for m in cell.per_layer:
            v = _load_py(reader_path(cell.home, m["name"]),
                         "benchmarks_metric_" + m["name"].replace(".", "_").replace("-", "_")
                         ).read(record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(checks) and all(c.ok for c in checks),
        "attempted": int(samples["attempted"]), "failed": int(samples["failed"]),
        "metrics": metrics, "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": reduced.top_ops(10), "idle_gaps": reduced.top_gaps(10)}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
