"""The Olmo-Hybrid long-document serve cell end to end at a tiny size on the
CPU, through ``benchmarks/run.py``'s own ``run_cell`` with a temporary
benchmark root cut from the cell's OWN config and mix: ``correct`` is
decided as on the chip (served tokens against
``benchmarks/reference_olmo_hybrid.py``), every new per-layer reader gives a
number or ``None``, and a program without the linear layer ends with "no
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

CELL = "olmohybrid7b-serve-longdoc-sat"
CONFIG = "olmo-hybrid-7b-serve1"
MIX = "longdoc-1.5knee"
NEW_METRICS = ("mfu_hybrid_serve", "gdn_prefill_roofline", "gdn_decode_roofline",
               "gdn_device_share", "state_store_share")
# the accepted span readers of a saturated serve cell, read on this cell too
SHARED_READERS = ("engine_host_step_ms", "device_prefill_share", "idle_host_bound_share",
                  "decode_slot_occupancy", "kv_reserved_unused_share",
                  "engine_runs_ahead_share",  # since PR 38
                  "chunk_carries_decode_share")  # since PR 45
# the published shape at toy widths: two periods of 3 linear + 1 full layer
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=4, num_hidden_layers=8, vocab_size=256,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=16)
TINY_WORKLOAD = dict(
    preset="olmo-hybrid-7b", vocab=256, d_model=64, n_layers=8, n_heads=4,
    n_kv_heads=4, d_ff=128, lin_heads=4, lin_dk=8, lin_dv=16, max_seq=192,
    kv_page_size=8, kv_pool_pages=96, max_slots=4, prefill_chunk=32)
# served to completion: a loaded CPU has its first tokens inside the 4-second window
TINY_MIX = dict(rate_per_s=12.0, trace_seconds=1, stop_at_close=False,
                prompt_len={"median": 64, "sigma": 0.5, "min": 16, "max": 128},
                output_len={"median": 8, "sigma": 0.6, "min": 4, "max": 16})
# true float32 on the CPU: the engine reads 0.0 on every seed tried (its
# tokens are the reference's); the float8 control reads max >= 0.02
TINY_LIMITS = {"served_logit_gap_max": {"limit": 2e-3},
               "served_logit_gap_mean": {"limit": 1e-4},
               "state_path_rel_gap": {"limit": 1e-4}}


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
    ``setup`` keeps the job — its engine too, which a run releases before
    the reference is computed — and the window's samples, for the tests of
    the readers."""
    from benchmarks import run

    kept = {}
    load = run._load_py

    def keep(setup, ctx):
        job = kept["job"] = setup(ctx)
        window = job.window
        job.window = lambda seconds: kept.setdefault("samples", window(seconds))
        job.release = lambda: None
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


def test_cell_runs_correct_at_tiny_through_run_cell(ran, root):
    from benchmarks import run

    result, job, _ = ran
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    json.dumps(result)
    cell = run.load_cell(root, CELL)
    names = {m["name"] for m in cell.per_layer}
    # the new readers and the three list-less readers of a saturated serve cell
    longdoc = {f"{m}.longdoc" for m in SHARED_READERS}
    assert names == set(NEW_METRICS) | longdoc | {
        "engine_step_ms.sat", "kv_pool_peak_share.sat", "device_idle_share.sat"}
    for m in longdoc:  # the quantity's one reader, as reader_path finds it
        assert os.path.basename(run.reader_path(cell.home, m)) == m[:-8] + ".py"
    for other in ("mistral7b-serve-sat", "mistral7b-serve-chat"):
        assert not (set(NEW_METRICS) | longdoc) & {
            m["name"] for m in run.load_cell(root, other).per_layer}
    assert job.sizes["pattern"] == ("linear", "linear", "linear", "full")
    assert job.cache["state_store_bytes"] == job.engine.store.bytes \
        == 5 * 6 * 4 * (4 * 8 * 16 + 3 * 128)


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
    # the traced window runs to its last request: counters, the pool's peak
    traced = job.traced_window()
    counters = traced["engine_counters"]
    assert counters["lin_slot_steps"] == 6 * counters["decode_slot_tokens"] > 0
    assert counters["state_resets"] == counters["admitted"] == traced["attempted"]
    assert traced["page_leaks"] == 0
    record.samples = dict(samples, traced=traced)
    record.peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    record.trace = _fake_trace(
        {"gdn_chunk_fwd.3": 0.2, "gdn_step.1": 0.1, "paged_attention.2": 0.1,
         "fusion.9": 0.4}, busy_s=1.0)
    values = {m: read(m) for m in NEW_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["gdn_device_share"] == pytest.approx(30.0)
    assert 0 < values["state_store_share"] < 100
    assert read("kv_pool_peak_share.sat") > 0 and read("engine_step_ms.sat") > 0


def test_flop_and_byte_counts_at_the_published_sizes():
    from benchmarks import flops_olmo_hybrid as fl

    sizes = dict(vocab=100352, d_model=3840, n_layers=8, n_heads=30, n_kv_heads=30,
                 d_ff=11008, lin_heads=30, lin_dk=96, lin_dv=192, lin_conv=4,
                 pattern=("linear", "linear", "linear", "full"))
    assert fl.layer_counts(sizes) == (6, 2)
    # ISSUE 37's arithmetic, matmul parameters only
    assert fl.linear_mixer_matmul_params(sizes) == 3840 * 17280 + 3840 * 60 + 5760 * 3840
    assert fl.full_mixer_matmul_params(sizes) == 4 * 3840 ** 2
    # a 2,048-token prompt and 96 outputs: ~2 x 1.66e9 FLOPs a token + the rest
    per_token = fl.serve_flops(sizes, [(2048, 96)]) / (2048 + 95)
    assert 3.3e9 < per_token < 3.8e9
    assert fl.serve_flops(sizes, [(0, 0)]) == 0.0
    # one 256-row call: the state once, the rows once; bound by bytes on v5e
    f, b = fl.gdn_chunk_cost(sizes, [256])
    assert b == 6 * 4 * (256 * 30 * (2 * 96 + 2 * 192 + 2) + 2 * 30 * 96 * 192)
    assert f / 197e12 < b / 819e9
    f, b = fl.gdn_step_cost(sizes, 96)
    assert b > 96 * 2 * 4 * 30 * 96 * 192 and f == 96 * 6 * 30 * 96 * 192
    assert list(fl.prefill_rows([(0, [1] * 600, [2])], 256)) == [256, 256, 88]


def test_a_program_without_the_linear_layer_ends_with_no_result(root, monkeypatch):
    """The parent commit given the new files: the runner looks for the preset
    and the fields before it builds anything."""
    from benchmarks import run
    from tf_operator_tpu.models import transformer as tr

    monkeypatch.setattr(
        tr, "CONFIG_OVERRIDE_FIELDS",
        frozenset(tr.CONFIG_OVERRIDE_FIELDS - {"lin_heads"}))
    with pytest.raises(SystemExit, match="no result"):
        run.run_cell(CELL, SEED, 1.0, False, root=root, device_check=cpu_devices)
