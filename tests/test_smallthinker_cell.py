"""The SmallThinker share cell end to end at a tiny size on the CPU, through
``benchmarks/run.py``'s own ``run_cell`` with a temporary benchmark root:
``correct`` is decided as on the chip (the followed steps against
``benchmarks/reference_smallthinker.py``), the float8 control fails, and
every new per-layer reader gives a number or ``None``."""

import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CELL = "smallthinker21b-train-ep4share"
CONFIG = "smallthinker-21ba3b-ep4share-train1"
NEW_METRICS = ("mfu_routed", "flash_window_roofline", "gmm_roofline",
               "gmm_device_share", "moe_block_padding_share",
               "moe_held_load_max_over_mean", "moe_rows_walked_share")
# the published shape at toy widths: 4 layers = one period, head width its
# own number (4 x 32 != 64), top-6 of 8 router outputs, 2 experts held
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=32, moe_ffn_hidden_size=32, intermediate_size=32,
            moe_router_outputs=8, moe_num_primary_experts=2, vocab_size=256,
            sliding_window_size=16)
TINY_WORKLOAD = dict(
    vocab=256, d_model=64, n_heads=4, n_kv_heads=2, d_head=32, d_ff=32,
    n_experts=8, experts_held=2, max_seq=64,
    layer_pattern=[[0, False], [16, True], [16, True], [16, True]])
# set the way PERF.md sets the real ones: over six seeds on the CPU the
# program's largest readings were loss 2.6e-4 / 4.2e-4, gradient difference
# 4.7e-2 (dense leaves) / 9.0e-2 (routed leaves), change 2.1e-3; the float8
# control's smallest were loss 1.4e-4 / 4.0e-4 (hardly moved), gradient
# difference 0.20 / 0.24, change 3.4e-3
TINY_LIMITS = {"loss_step1_abs_gap": {"limit": 0.0015},
               "loss_step2_abs_gap": {"limit": 0.0015},
               "grad1_diff_dense_leaf_gap": {"limit": 0.10},
               "grad1_diff_routed_leaf_gap": {"limit": 0.15},
               "param_change_norm_worst_leaf_gap": {"limit": 0.0028},
               # 576 choices a step here, one flip is 0.17 %: at this size the
               # number guards and does not separate (program <= 3.4e-3 over
               # six seeds, a bfloat16 router the same); PERF.md has the chip's
               "routed_step1_rel_gap": {"limit": 0.02}}


def make_root(dst) -> str:
    dst = str(dst)
    home = os.path.join(dst, "benchmarks")
    for sub in ("configs", "traffic", "runners", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub), os.path.join(home, sub))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    path = os.path.join(home, "traffic", "pretrain-8k-ep4share.json")
    mix = json.load(open(path))
    mix.update(seq_len=48, batch_size=2, rows=12)
    json.dump(mix, open(path, "w"))
    path = os.path.join(home, "configs", CONFIG + ".json")
    cfg = json.load(open(path))
    cfg.update(TINY)
    cfg["workload"].update(TINY_WORKLOAD)
    cfg["limits"] = TINY_LIMITS
    json.dump(cfg, open(path, "w"))
    return dst


def cpu_devices(chips):
    import jax

    return jax.devices()[:chips]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench") / "root")


SEED = 2**31 + 5


@pytest.fixture(scope="module")
def ran(root):
    """ONE run of the cell through ``run.run_cell``; a spy on the runner's
    ``setup`` keeps the job, so that the control and the readers can be put
    to the very steps the result was decided on."""
    from benchmarks import run

    kept = {}
    load = run._load_py

    def spy(path, name):
        mod = load(path, name)
        if hasattr(mod, "setup"):
            setup = mod.setup
            mod.setup = lambda ctx: kept.setdefault("job", setup(ctx))
        return mod

    run._load_py = spy
    try:
        result = run.run_cell(CELL, SEED, 1.0, False, root=root,
                              device_check=cpu_devices)
    finally:
        run._load_py = load
    return result, kept["job"]


def test_cell_runs_correct_at_tiny_through_run_cell(ran, root):
    from benchmarks import run

    result, job = ran
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)
    cell = run.load_cell(root, CELL)
    assert set(NEW_METRICS) | {"loader_wait_ms", "data_wait_ms",
                               "device_idle_share.train"} <= {
        m["name"] for m in cell.per_layer}
    # the dense count and the sum over every Pallas kernel are not read here
    assert not {"mfu", "flash_train_roofline"} & {m["name"] for m in cell.per_layer}
    for dense in ("mistral7b-train-1chip", "mistral7b-train-fsdp4"):
        names = {m["name"] for m in run.load_cell(root, dense).per_layer}
        assert {"mfu", "flash_train_roofline"} <= names and not set(NEW_METRICS) & names


def test_float8_control_fails_a_limit_on_the_steps_the_result_was_decided_on(ran):
    _, job = ran
    checks = job.check({"failed": 0}, control="float8")
    sound = [c for c in checks if not c.name.startswith("control.")]
    control = [c for c in checks if c.name.startswith("control.")]
    assert len(sound) == 7 and all(c.ok for c in sound), [vars(c) for c in sound]
    assert any(not c.ok for c in control), [vars(c) for c in control]


def test_counters_and_readers_of_a_real_run(ran, root):
    from benchmarks import run

    _, job = ran
    counters = job.counters
    assert len(counters) == len(job.step_s) > 0
    # fetched once, after the window; the followed steps' counts were kept
    assert all(type(v) is float for c in counters for v in c.values())
    assert len(job.program["routed_here"]) == job.followed == 2
    layers = job.sizes["n_layers"]
    for c in counters:
        # one set a step; rows computed in whole blocks; no choice lost
        assert 0 < c["moe_routed_here"] <= c["moe_rows_computed"]
        assert c["moe_rows_computed"] % 256 == 0
        assert c["moe_held_load_max"] >= c["moe_held_load_mean"] > 0
        assert c["moe_held_load_mean"] * 2 == pytest.approx(c["moe_routed_here"])
        assert c["moe_routed_here"] <= layers * 2 * 48 * 6
        # segments of 1 + 2 blocks under a bound of 3 + 2 a layer: one or two trips
        assert c["moe_rows_bound"] == layers * 5 * 256
        assert c["moe_rows_computed"] <= c["moe_rows_walked"] <= layers * 2 * 3 * 256
        assert c["moe_rows_walked"] % (3 * 256) == 0
    # readers: program counters give numbers on any device; device-trace
    # readers give None without a trace, mfu_routed None without peaks
    cell = run.load_cell(root, CELL)
    samples = {"counters": counters, "model_sizes": job.sizes, "elapsed_s": 1.0,
               "steps": len(counters), "data_wait_s": job.data_wait_s}
    record = SimpleNamespace(
        samples=samples, trace=None, sizes=cell.sizes, mix=cell.mix,
        config=cell.config, peaks=None, chips=1, say=lambda s: None)

    def read(metric):
        return run._load_py(run.reader_path(cell.home, metric), "m_" + metric).read(record)

    values = {m: read(m) for m in NEW_METRICS}
    assert 0 < values["moe_block_padding_share"] < 100
    assert values["moe_held_load_max_over_mean"] >= 1.0
    assert 60.0 <= values["moe_rows_walked_share"] <= 120.0
    for m in ("mfu_routed", "flash_window_roofline", "gmm_roofline",
              "gmm_device_share"):
        assert values[m] is None
    record.peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    assert read("mfu_routed") > 0
    assert read("data_wait_ms") >= 0  # the generic readers are fed too
    assert read("device_idle_share.train") is None
    assert read("loader_wait_ms") is None  # a span of the trace


def _fake_trace(names_seconds, busy_s):
    """A reduced trace with the given ops, as trace_reduce builds one."""
    from benchmarks import trace_reduce

    ops, texts = {}, {}
    for name, sec in names_seconds.items():
        text = (f"%{name} = bf16[8,8]{{1,0}} custom-call(%fusion.1, %gmm_fwd.9), "
                'custom_call_target="tpu_custom_call"')
        label = trace_reduce.parse_op(text)[2]
        ops[label], texts[label] = sec, text
    dev = trace_reduce.DeviceReduced(
        name="/device:TPU:0", busy_s=busy_s, ops=ops, texts=texts,
        collective_s=0.0, collective_exposed_s=0.0, gaps=[])
    return trace_reduce.Reduced(window_s=busy_s * 1.01, devices=[dev])


def test_trace_readers_match_kernels_by_instruction_name(root):
    """Device-trace readers against a reduced trace: kernels are found by
    the instruction's own name, not by operands that mention a kernel."""
    from benchmarks import flops_smallthinker, kernel_seconds, run

    trace = _fake_trace({
        "flash_fwd.2": 0.2, "flash_bwd_dqkv.1": 0.2,
        "gmm_fwd.3": 0.05, "gmm_fwd_scaled.1": 0.02, "transpose_jvp_gmm_dx__.4": 0.04,
        "gmm_dw.2": 0.06, "gmm_dw_scaled.1": 0.03, "fusion.77": 0.4}, busy_s=1.0)
    assert kernel_seconds.seconds(trace, "gmm_") == pytest.approx(0.2)
    assert kernel_seconds.seconds(trace, "flash_fwd", "flash_bwd") == pytest.approx(0.4)
    # the ONE backward kernel since PR 43 answers to the readers' own key
    # (``flash_window_roofline.KERNELS``), once, and nothing to the old second
    assert kernel_seconds.seconds(trace, "flash_bwd_dq", "flash_bwd_dkv") == pytest.approx(0.2)
    assert [n.split()[0] for n in kernel_seconds.names(trace, "flash_bwd_dq")] == [
        "flash_bwd_dqkv.1"]
    assert kernel_seconds.names(trace, "flash_bwd_dkv") == []
    assert "fusion.77" not in " ".join(kernel_seconds.names(trace, "gmm_"))

    pattern = ((0, False), (4096, True), (4096, True), (4096, True))
    sizes = dict(vocab=37984, d_model=2560, n_layers=4, n_heads=28, n_kv_heads=4,
                 head_dim=128, d_ff=768, n_experts=64, top_k=6, held=16,
                 pattern=pattern)
    counters = [{"moe_routed_here": 98304.0, "moe_rows_computed": 114688.0,
                 "moe_held_load_max": 6500.0, "moe_held_load_mean": 6144.0,
                 "moe_rows_walked": 6 * 53248.0, "moe_rows_bound": 4 * 200704.0}] * 4
    record = SimpleNamespace(
        samples={"model_sizes": sizes, "counters": counters, "elapsed_s": 2.0,
                 "traced": {"steps": 4, "counters": counters}},
        trace=trace, mix={"batch_size": 2, "seq_len": 8192}, chips=1,
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9}, say=lambda s: None)
    home = os.path.join(root, "benchmarks")
    values = {m: run._load_py(run.reader_path(home, m), "t_" + m).read(record)
              for m in NEW_METRICS}
    assert all(v is not None for v in values.values()), values
    assert values["gmm_device_share"] == pytest.approx(20.0)
    assert values["moe_block_padding_share"] == pytest.approx(100 * (1 - 98304 / 114688))
    assert values["moe_rows_walked_share"] == pytest.approx(100 * 6 * 53248 / (4 * 200704))
    # a program from before the walk has no such counter: the line leaves it out
    record.samples["counters"] = [
        {k: v for k, v in c.items() if not k.startswith("moe_rows_w")} for c in counters]
    assert run._load_py(run.reader_path(home, "moe_rows_walked_share"),
                        "t_walked").read(record) is None
    assert run.reader_path(home, "moe_rows_walked_share.ep16share") == run.reader_path(
        home, "moe_rows_walked_share")
    f, _ = flops_smallthinker.flash_window_cost(sizes, 2, 8192)
    assert values["flash_window_roofline"] == pytest.approx(100 * 4 * f / 197e12 / 0.4)
    for m in ("mfu_routed", "flash_window_roofline", "gmm_roofline"):
        assert 0 < values[m] < 100
    # window-aware pairs: a window layer at twice the window scores 75 % of a global layer's
    assert flops_smallthinker.visible_pairs(8192, 4096) == 25_167_872
    assert flops_smallthinker.visible_pairs(8192, 0) == 33_558_528
