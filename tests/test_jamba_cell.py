"""The Jamba reasoning serve cell end to end at a tiny size on the CPU,
through ``benchmarks/run.py``'s own ``run_cell`` with a temporary benchmark
root cut from the cell's OWN config and mix: the cell's files are found by
name, ``correct`` is decided as on the chip (served tokens and the state path
against ``benchmarks/reference_jamba.py``), every new per-layer reader gives a
number or ``None``, and a program without the Mamba layer ends with "no
result"."""

import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CELL = "jamba2-3b-serve-reason-sat"
CONFIG = "ai21-jamba2-3b-serve1"
MIX = "reason-1.5knee"
NEW_METRICS = ("mfu_mamba_serve", "ssm_prefill_roofline", "ssm_decode_roofline",
               "ssm_device_share")
# the accepted readers of a saturated serve cell, read on this cell too
SHARED_READERS = ("state_store_share", "engine_host_step_ms", "device_prefill_share",
                  "idle_host_bound_share", "decode_slot_occupancy",
                  "kv_reserved_unused_share", "engine_runs_ahead_share",
                  "chunk_carries_decode_share")
# the published shape at toy widths: two periods of [mamba, mamba, attn, mamba]
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=1, num_hidden_layers=8, vocab_size=256,
            attn_layer_period=4, attn_layer_offset=2, mamba_d_state=4, mamba_dt_rank=8)
TINY_WORKLOAD = dict(
    preset="ai21-jamba2-3b", vocab=256, d_model=64, n_layers=8, n_heads=4,
    n_kv_heads=1, d_ff=128, mamba_d_state=4, mamba_dt_rank=8,
    layer_pattern=["mamba", "mamba", [0, False], "mamba"], max_seq=192,
    kv_page_size=8, kv_pool_pages=96, max_slots=4, prefill_chunk=32)
# served to completion: a loaded CPU has its first tokens inside the 4-second window
TINY_MIX = dict(rate_per_s=12.0, trace_after_s=0.5, trace_seconds=2, stop_at_close=False,
                prompt_len={"median": 64, "sigma": 0.5, "min": 16, "max": 128},
                output_len={"median": 8, "sigma": 0.6, "min": 4, "max": 16})
# true float32 on the CPU: the engine's tokens are the reference's
TINY_LIMITS = {"served_logit_gap_max": {"limit": 2e-3},
               "served_logit_gap_mean": {"limit": 1e-4},
               "state_path_rel_gap": {"limit": 1e-4},
               "engine_state_rel_gap": {"limit": 1e-4}}


def make_root(dst) -> str:
    dst = str(dst)
    home = os.path.join(dst, "benchmarks")
    for sub in ("configs", "traffic", "runners", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub), os.path.join(home, sub))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    path = os.path.join(home, "traffic", MIX + ".json")
    mix = json.load(open(path))
    mix.update(TINY_MIX)
    json.dump(mix, open(path, "w"))
    path = os.path.join(home, "configs", CONFIG + ".json")
    cfg = json.load(open(path))
    cfg.update(TINY)
    cfg["workload"] = TINY_WORKLOAD
    cfg["limits"] = TINY_LIMITS
    json.dump(cfg, open(path, "w"))
    return dst


def cpu_devices(chips):
    import jax

    return jax.devices()[:chips]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench") / "root")


SEED = 2**31 + 11


@pytest.fixture(scope="module")
def ran(root):
    """ONE run of the cell through ``run.run_cell``; a spy on the runner's
    ``setup`` keeps the job — its engine too, which a run releases before the
    reference is computed — and the window's samples."""
    from benchmarks import run

    kept = {}
    load = run._load_py

    def keep(setup, ctx):
        job = kept["job"] = setup(ctx)
        window = job.window
        job.window = lambda seconds: kept.setdefault("samples", window(seconds))
        job.release = lambda: setattr(job, "alone", job.served_alone())
        return job

    def spy(path, name):
        mod = load(path, name)
        if hasattr(mod, "setup"):
            mod.setup = lambda ctx, setup=mod.setup: keep(setup, ctx)
        return mod

    run._load_py = spy
    try:
        result = run.run_cell(CELL, SEED, 4.0, False, root=root,
                              device_check=cpu_devices)
    finally:
        run._load_py = load
    return result, kept["job"], kept["samples"]


def test_the_cells_files_are_found_by_name_and_it_runs_correct_at_tiny(ran, root):
    from benchmarks import run

    result, job, _ = ran
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    json.dumps(result)
    cell = run.load_cell(root, CELL)
    assert cell.mix["runner"] == "serve_jamba" and cell.config["name"] == CONFIG
    names = {m["name"] for m in cell.per_layer}
    reason = {f"{m}.reason" for m in SHARED_READERS}
    assert names == set(NEW_METRICS) | reason | {
        "engine_step_ms.sat", "kv_pool_peak_share.sat", "device_idle_share.sat"}
    for m in sorted(names):  # every reader is a file the harness finds by name
        assert os.path.exists(run.reader_path(cell.home, m)), m
    for m in reason:  # the quantity's one reader
        assert os.path.basename(run.reader_path(cell.home, m)) == m[:-7] + ".py"
    for other in ("mistral7b-serve-sat", "olmohybrid7b-serve-longdoc-sat"):
        assert not (set(NEW_METRICS) | reason) & {
            m["name"] for m in run.load_cell(root, other).per_layer}
    assert job.sizes["pattern"] == ("mamba", "mamba", "attn", "mamba")
    assert job.cache["state_store_bytes"] == job.engine.store.bytes \
        == 5 * 6 * 4 * (4 * 128 + 3 * 128)
    assert job.cache["page_bytes"] == 2 * 4 * 2 * 8 * 1 * 16


def test_the_real_files_state_the_published_model_uncut():
    """``BENCHMARK.json`` names the cell once, on one chip, under the catalog's
    URL with nothing reduced; the config file carries the published keys and a
    limit for every number the runner's ``check`` compares."""
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cells = [c for c in bench["workloads"] if c["name"] == CELL]
    assert cells == [dict(cells[0], config=CONFIG, traffic=MIX, chips=1)]
    assert len(bench["workloads"]) == 8
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == [] and entry["source"].endswith(
        "ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    cfg = json.load(open(os.path.join(REPO, entry["file"])))
    published = dict(
        attn_layer_offset=7, attn_layer_period=14, expert_layer_offset=1,
        expert_layer_period=2, hidden_size=2560, intermediate_size=8192,
        mamba_d_conv=4, mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
        max_position_embeddings=262144, num_attention_heads=20, num_experts=1,
        num_experts_per_tok=1, num_hidden_layers=28, num_key_value_heads=1,
        num_logits_to_keep=1, rms_norm_eps=1e-6, vocab_size=65536)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == [] and cfg["rope_theta"] is None
    assert cfg["workload"] == dict(
        preset="ai21-jamba2-3b", n_layers=28, max_seq=2560, kv_page_size=64,
        kv_pool_pages=2560, max_slots=64, prefill_chunk=256)
    assert set(cfg["limits"]) == {"served_logit_gap_max", "served_logit_gap_mean",
                                  "state_path_rel_gap", "engine_state_rel_gap"}
    assert cfg["precision"]["control"] == "float8,state_bf16"
    mix = json.load(open(os.path.join(REPO, "benchmarks", "traffic", MIX + ".json")))
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= cfg["workload"]["max_seq"]
    assert mix["rate_per_s"] == pytest.approx(1.5 * mix["knee_per_s"])


def test_every_new_reader_gives_a_number_or_none(ran, root):
    """The readers on the samples of a real (untraced, then traced-shaped)
    run: the trace readers None without a trace, numbers with a made one."""
    from benchmarks import run
    from tests.test_smallthinker_cell import _fake_trace

    _, job, samples = ran
    cell = run.load_cell(root, CELL)
    assert any(p and n for p, n in samples["window_work"])
    record = SimpleNamespace(
        samples=samples, trace=None, sizes=cell.sizes, mix=cell.mix,
        config=cell.config, peaks=None, chips=1, say=lambda s: None)

    def read(metric):
        return run._load_py(run.reader_path(cell.home, metric),
                            "m_" + metric.replace(".", "_")).read(record)

    assert all(read(m) is None for m in NEW_METRICS)
    # the measured window ran to its last request, the traced one is CUT at
    # its end and counts from its ``trace_after_s`` on: the live counters and
    # the pool's peak are there all the same
    assert samples["engine_counters"]["admitted"] == samples["attempted"]
    assert 0 < samples["pool_peak_in_use"] <= 96
    traced = job.traced_window()
    counters = traced["engine_counters"]
    assert counters["lin_slot_steps"] == 6 * counters["decode_slot_tokens"] > 0
    assert counters["state_resets"] == counters["admitted"] <= traced["attempted"]
    assert traced["page_leaks"] in (None, 0)  # None where the close cut the run
    assert job.page_leaks == 0  # of the last run that drained
    # a run cut at its first step returns no RunResult and still has its counters
    from benchmarks import traffic
    cut = job.summarise(job.serve(traffic.requests(SEED, 256, cell.mix, 1.0), 0.0, True), 0.0)
    assert cut["page_leaks"] is None and cut["unfinished"] > 0
    assert cut["engine_counters"]["prefill_chunks"] >= 1
    assert 0 < traced["pool_peak_in_use"] <= 96
    record.samples = dict(samples, traced=traced)
    record.peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    record.trace = _fake_trace(
        {"ssm_chunk_fwd.3": 0.2, "ssm_step.1": 0.1, "paged_attention.2": 0.1,
         "fusion.9": 0.4}, busy_s=1.0)
    values = {m: read(m) for m in NEW_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["ssm_device_share"] == pytest.approx(30.0)
    assert 0 < read("state_store_share.reason") < 100
    assert read("kv_pool_peak_share.sat") > 0 and read("engine_step_ms.sat") > 0
    # on another architecture's samples (the hybrid cell's sizes) they are silent
    record.samples = dict(samples, model_sizes={"lin_heads": 4},
                          traced=dict(traced, model_sizes={"lin_heads": 4}))
    assert all(read(m) is None for m in NEW_METRICS if m != "ssm_device_share")


def test_the_traced_window_traces_its_last_seconds_alone(ran, tmp_path):
    """Called as the harness calls it, under an open profiler session and
    window annotation: the newest trace holds ONE window annotation, the
    runner's own over the run's last ``trace_seconds``, with the engine's
    closing counters (the whole run's) inside it; the samples count the
    traced part; and the harness finds a session to stop."""
    import jax
    from jax.profiler import ProfileData

    from benchmarks import span_reduce, trace_reduce

    _, job, _ = ran
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
            traced = job.traced_window()
    finally:
        jax.profiler.stop_trace()
    assert job.trace_dir == str(tmp_path)
    planes = ProfileData.from_file(trace_reduce.find_xplane(str(tmp_path))).planes
    events = [e for p in planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events]
    (window,) = [e for e in events if e.name == trace_reduce.WINDOW_ANNOTATION]
    # it opens at the run's first step boundary past ``trace_after_s``
    assert 0 < window.duration_ns * 1e-9 < traced["wall_s"] - 0.45
    (closing,) = [e for e in events if e.name == "serve.counters"]
    assert window.start_ns < closing.start_ns < window.start_ns + window.duration_ns
    whole, part = span_reduce._attrs(closing), traced["engine_counters"]
    assert 0 < part["decode_steps"] <= whole["decode_steps"]
    assert 0 < part["prefill_chunks"] < whole["prefill_chunks"]
    steps = [e for e in events if e.name == "serve.step"]
    assert 0 < len(steps) and all(e.start_ns >= window.start_ns for e in steps)


def test_the_state_bf16_control_fails_the_state_path_by_ten_limits(ran):
    """What a bfloat16 recurrent state reads on ``state_path_rel_gap``, the
    one number that sees the state alone: far over its limit, where the
    program's own state path stands under it."""
    _, job, samples = ran
    checks = {c.name: c for c in job.check(samples, control="state_bf16")}
    limit = TINY_LIMITS["state_path_rel_gap"]["limit"]
    assert checks["state_path_rel_gap"].ok
    assert checks["state_path_rel_gap"].value < limit
    assert checks["control.state_bf16:state_path_rel_gap"].value > 10 * limit
    assert not checks["control.state_bf16:state_path_rel_gap"].ok
    # the same of the state the ENGINE left after the request it served alone
    assert checks["engine_state_rel_gap"].ok
    assert checks["control.state_bf16:engine_state_rel_gap"].value > 10 * limit
    assert checks["kv_page_leaks"].ok and checks["kv_page_leaks"].value == 0.0


def test_flop_and_byte_counts_at_the_published_sizes():
    from benchmarks import flops_jamba as fl

    sizes = dict(vocab=65536, d_model=2560, n_layers=28, n_heads=20, n_kv_heads=1,
                 d_ff=8192, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank=160,
                 pattern=("mamba",) * 7 + ("attn",) + ("mamba",) * 6)
    assert fl.layer_counts(sizes) == (26, 2)
    # ISSUE 47's arithmetic, matmul parameters only
    assert fl.mamba_mixer_matmul_params(sizes) == \
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert fl.attn_mixer_matmul_params(sizes) == 2 * 2560 * 2560 + 2 * 2560 * 128
    # ~2 x 2.86e9 matmul parameters a token + the head an output token + the rest
    per_token = fl.serve_flops(sizes, [(192, 512)]) / (192 + 511)
    assert 5.7e9 < per_token < 6.3e9
    assert fl.serve_flops(sizes, [(0, 0)]) == 0.0
    # one 256-row call: the state once, the rows once; bound by bytes on v5e
    f, b = fl.ssm_chunk_cost(sizes, 256, 1)
    assert b == 26 * 4 * (256 * (3 * 5120 + 32) + 2 * 16 * 5120)
    assert f == 26 * 256 * 7 * 5120 * 16 and f / 197e12 < b / 819e9
    assert fl.ssm_chunk_cost(sizes, 600, 3)[1] == 26 * 4 * (
        600 * (3 * 5120 + 32) + 3 * 2 * 16 * 5120)
    f, b = fl.ssm_step_cost(sizes, 64 * 26)
    assert b > 64 * 26 * 2 * 4 * 16 * 5120 and f == 64 * 26 * 7 * 5120 * 16


def test_a_program_without_the_mamba_layer_ends_with_no_result(root, monkeypatch):
    """The parent commit given the new files: the runner looks for the preset
    and the fields before it builds anything."""
    from benchmarks import run
    from tf_operator_tpu.models import transformer as tr

    monkeypatch.setattr(
        tr, "CONFIG_OVERRIDE_FIELDS",
        frozenset(tr.CONFIG_OVERRIDE_FIELDS - {"mamba_d_state"}))
    with pytest.raises(SystemExit, match="no result"):
        run.run_cell(CELL, SEED, 1.0, False, root=root, device_check=cpu_devices)
