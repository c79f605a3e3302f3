"""The JoyAI-LLM-Flash share cell end to end at a tiny size on the CPU,
through ``benchmarks/run.py``'s own ``run_cell`` with a temporary benchmark
root: ``correct`` is decided as on the chip (the followed steps against
``benchmarks/reference_joyai.py``), every new
per-layer reader gives a number or ``None``, and a program without the new
fields ends with "no result"."""

import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CELL = "joyai-flash-train-ep16share"
CONFIG = "joyai-llm-flash-ep16share-train1"
NEW_METRICS = ("mfu_latent_routed", "flash_latent_roofline",
               "gmm_device_share.ep16share", "moe_block_padding_share.ep16share",
               "moe_held_load_max_over_mean.ep16share",
               "moe_rows_walked_share.ep16share")
# the published shape at toy widths: a dense lead + 1 expert layer, 4 heads
# of 16 | 8 | 16 through ranks 32 / 16, top-2 of 8 router outputs, 2 held
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
            intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=2,
            moe_router_outputs=8, n_routed_experts=2, num_experts_per_tok=2,
            vocab_size=256)
TINY_WORKLOAD = dict(
    vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=32,
    d_ff_dense=96, q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=16, n_experts=8, moe_top_k=2, experts_held=2,
    max_seq=64)
# 64 tokens a step in bfloat16: the numbers guard here and do not separate
# (PERF.md has the chip's). Over six seeds on the CPU the program's largest
# readings were loss 1.8e-3 / 4.2e-3, module loss 1.7e-3, gradient difference
# 0.33 (in front of a router) / 0.44 (behind one), change 1.6e-2, routed
# count 2.3e-2, bias 0.19; the float8 control's smallest gradient
# differences 0.49 / 0.66, change 2.4e-2
TINY_LIMITS = {"loss_step1_abs_gap": {"limit": 0.0055},
               "loss_step2_abs_gap": {"limit": 0.0125},
               "loss_mtp_step1_abs_gap": {"limit": 0.005},
               "grad1_diff_dense_leaf_gap": {"limit": 0.40},
               "grad1_diff_routed_leaf_gap": {"limit": 0.54},
               "param_change_norm_worst_leaf_gap": {"limit": 0.02},
               "routed_step1_rel_gap": {"limit": 0.07},
               "bias_differs_share": {"limit": 0.5}}


def make_root(dst) -> str:
    dst = str(dst)
    home = os.path.join(dst, "benchmarks")
    for sub in ("configs", "traffic", "runners", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub), os.path.join(home, sub))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    path = os.path.join(home, "traffic", "pretrain-8k-ep16share.json")
    mix = json.load(open(path))
    mix.update(seq_len=32, batch_size=2, rows=8)
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


SEED = 2**31 + 7


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
    json.dumps(result)
    cell = run.load_cell(root, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) | {"loader_wait_ms", "data_wait_ms",
                               "device_idle_share.train"} <= names
    # the dense count, the SmallThinker counts and their kernels' shares
    # are not read here, nor this cell's in theirs
    assert not {"mfu", "flash_train_roofline", "mfu_routed", "gmm_roofline",
                "flash_window_roofline", "gmm_device_share"} & names
    for other in ("mistral7b-train-1chip", "smallthinker21b-train-ep4share"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in run.load_cell(root, other).per_layer}


def test_a_missing_or_shifted_module_fails_its_own_loss(ran):
    """The total hides the module behind the main loss; its own number does
    not: the reference's module loss against another target shift."""
    from benchmarks.runners import train_joyai

    _, job = ran
    limits = job.ctx.config["limits"]
    ref = dict(job.program)
    shifted = dict(job.program, losses_mtp=[x + 0.05 for x in job.program["losses_mtp"]])
    names = {c.name: c.ok for c in train_joyai.compare(
        job.ctx.Check, shifted, ref, limits)}
    assert names.pop("loss_mtp_step1_abs_gap") is False and all(names.values())


def test_counters_bias_and_readers_of_a_real_run(ran, root):
    import numpy as np

    from benchmarks import run

    _, job = ran
    counters = job.counters
    assert len(counters) == len(job.step_s) > 0
    assert all(type(v) is float for c in counters for v in c.values())
    assert len(job.program["routed_here"]) == job.followed == 2
    assert len(job.program["losses_mtp"]) == 2 and job.program["losses_mtp"][0] > 0
    # the bias after two steps: (the expert layer + the module's) x 8 outputs,
    # every entry moved by the rate twice, once (a load on the mean stands
    # still) or there and back
    bias = job.program["bias"]
    assert bias.shape == (2, 8)
    assert set(np.round(np.abs(bias) / 0.001).astype(int).ravel()) <= {0, 1, 2}
    layers = 2  # expert layers: 1 + the module's
    for c in counters:
        assert 0 < c["moe_routed_here"] <= c["moe_rows_computed"]
        assert c["moe_rows_computed"] % 256 == 0
        assert c["moe_held_load_mean"] * 2 == pytest.approx(c["moe_routed_here"])
        assert c["moe_routed_here"] <= layers * 2 * 32 * 2
        assert c["moe_all_load_max_over_mean"] >= 1.0
        # at toy size the segment (1 + 2 blocks) is the whole bound: one trip
        assert c["moe_rows_walked"] == c["moe_rows_bound"] == layers * 3 * 256
        # an entry moves by the rate a step, set-up's three warm-up steps included
        assert 0 < c["moe_bias_abs_max"] <= 0.001 * (len(counters) + 3) + 1e-6
        assert c["loss_main"] > 0 and c["loss_mtp"] > 0
    cell = run.load_cell(root, CELL)
    samples = {"counters": counters, "model_sizes": job.sizes, "elapsed_s": 1.0,
               "steps": len(counters), "data_wait_s": job.data_wait_s}
    record = SimpleNamespace(
        samples=samples, trace=None, sizes=cell.sizes, mix=cell.mix,
        config=cell.config, peaks=None, chips=1, say=lambda s: None)

    def read(metric):
        return run._load_py(run.reader_path(cell.home, metric),
                            "m_" + metric.replace(".", "_")).read(record)

    values = {m: read(m) for m in NEW_METRICS}
    assert 0 < values["moe_block_padding_share.ep16share"] < 100
    assert values["moe_held_load_max_over_mean.ep16share"] >= 1.0
    assert values["moe_rows_walked_share.ep16share"] == 100.0
    for m in ("mfu_latent_routed", "flash_latent_roofline",
              "gmm_device_share.ep16share"):
        assert values[m] is None
    record.peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    assert read("mfu_latent_routed") > 0
    assert read("data_wait_ms") >= 0
    assert read("device_idle_share.train") is None
    assert read("loader_wait_ms") is None


def test_trace_readers_and_flop_counts_at_the_published_sizes(root):
    from benchmarks import flops_joyai, run
    from tests.test_smallthinker_cell import _fake_trace

    trace = _fake_trace({
        "flash_fwd.2": 0.2, "flash_bwd_dqkv.1": 0.2,  # found by the reader's ``flash_bwd_dq``
        "gmm_fwd.3": 0.05, "gmm_dw.2": 0.05, "fusion.77": 0.4}, busy_s=1.0)
    sizes = dict(vocab=16160, d_model=2048, n_layers=5, n_dense=1, n_heads=32,
                 q_rank=1536, kv_rank=512, nope=128, rope=64, v_dim=128, d_ff=768,
                 d_ff_dense=7168, n_experts=256, held=16, n_shared=1)
    # ISSUE 30's arithmetic, matmul parameters only (norms left out)
    assert flops_joyai.attention_matmul_params(sizes) == 26_347_520 - 2_048
    assert flops_joyai.expert_layer_params(sizes) == 31_594_496 - 2_048 - 4_096
    assert flops_joyai.expert_params(sizes) == 4_718_592
    routed = 8 * 2 * 8192 * 5 / 16
    per_token = flops_joyai.train_flops_per_step(sizes, 2, 8192, routed) / (2 * 8192)
    assert 3.3e9 < per_token < 3.5e9
    counters = [{"moe_routed_here": routed, "moe_rows_computed": routed * 1.3,
                 "moe_held_load_max": 600.0, "moe_held_load_mean": 512.0,
                 "moe_rows_walked": 5 * 12288.0, "moe_rows_bound": 5 * 135168.0}] * 4
    record = SimpleNamespace(
        samples={"model_sizes": sizes, "counters": counters, "elapsed_s": 8.0,
                 "traced": {"steps": 4, "counters": counters}},
        trace=trace, mix={"batch_size": 2, "seq_len": 8192}, chips=1,
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9}, say=lambda s: None)
    home = os.path.join(root, "benchmarks")
    values = {m: run._load_py(run.reader_path(home, m),
                              "t_" + m.replace(".", "_")).read(record)
              for m in NEW_METRICS}
    assert all(v is not None for v in values.values()), values
    assert values["gmm_device_share.ep16share"] == pytest.approx(10.0)
    assert values["moe_rows_walked_share.ep16share"] == pytest.approx(100 * 12288 / 135168)
    f, b = flops_joyai.flash_latent_cost(sizes, 2, 8192)
    assert f / 197e12 > b / 819e9  # bound by flops at these shapes
    assert values["flash_latent_roofline"] == pytest.approx(100 * 4 * f / 197e12 / 0.4)
    assert 0 < values["mfu_latent_routed"] < 100


def test_a_program_without_the_new_fields_ends_with_no_result(root, monkeypatch):
    """The parent commit given the new data files: the runner reads
    CONFIG_OVERRIDE_FIELDS before it builds anything."""
    from benchmarks import run
    from tf_operator_tpu.models import transformer as tr

    monkeypatch.setattr(
        tr, "CONFIG_OVERRIDE_FIELDS",
        frozenset(tr.CONFIG_OVERRIDE_FIELDS - {"attn_kind", "mtp_depth", "tied_head"}))
    with pytest.raises(SystemExit, match="no result"):
        run.run_cell(CELL, SEED, 1.0, False, root=root, device_check=cpu_devices)
