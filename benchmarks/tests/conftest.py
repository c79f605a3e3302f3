"""Shared by the benchmark's own tests: everything runs on the CPU at the
``tiny`` preset, through a temporary benchmark root that holds copies of
the data files cut to tiny sizes."""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # four virtual devices: the fsdp=4 cell's mesh
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import pytest  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
TINY_WORKLOADS = {
    "train": {"preset": "tiny", "attn": "flash", "remat": "save_mid",
              "fused_xent": True},
    "serve": {"preset": "tiny", "max_seq": 128, "kv_page_size": 16,
              "kv_pool_pages": 64, "max_slots": 4, "prefill_chunk": 16},
}
# limits for the tiny size, set the way PERF.md sets the real ones: over six
# seeds on the CPU the program's largest readings were loss 5.3e-4, gradient
# 2.3e-3, change 1.8e-3 (bf16 products) and 0.0 for serving (true f32); the
# float8 controls' smallest were gradient 1.1e-2 and, for serving, max gap
# 5.5e-2 and mean gap 4e-3
TINY_LIMITS = {
    "train": {"loss_step1_abs_gap": {"limit": 0.002},
              "loss_step2_abs_gap": {"limit": 0.002},
              "grad1_norm_worst_leaf_gap": {"limit": 0.006},
              "param_change_norm_worst_leaf_gap": {"limit": 0.006}},
    "serve": {"served_logit_gap_max": {"limit": 1e-4},
              "served_logit_gap_mean": {"limit": 2e-6}},
}
TINY_MIXES = {
    "train": dict(seq_len=64, rows=12),
    "serve": dict(rate_per_s=20.0, trace_seconds=1,
                  prompt_len={"median": 24, "sigma": 0.8, "min": 8, "max": 64},
                  output_len={"median": 8, "sigma": 0.6, "min": 4, "max": 16}),
}


def cpu_devices(chips):
    import jax

    return jax.devices()[:chips]


def make_tiny_root(dst) -> str:
    """A benchmark root whose configs and mixes are the repo's own files cut
    to the tiny preset; runners and metric readers are the repo's, copied."""
    dst = str(dst)
    home = os.path.join(dst, "benchmarks")
    for sub in ("configs", "traffic", "runners", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub), os.path.join(home, sub))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    for name in os.listdir(os.path.join(home, "traffic")):
        path = os.path.join(home, "traffic", name)
        mix = json.load(open(path))
        mix.update(TINY_MIXES[mix["runner"]])
        json.dump(mix, open(path, "w"))
    for name in os.listdir(os.path.join(home, "configs")):
        path = os.path.join(home, "configs", name)
        cfg = json.load(open(path))
        kind = "serve" if "kv_page_size" in cfg["workload"] else "train"
        cfg.update(TINY)
        cfg["workload"] = dict(TINY_WORKLOADS[kind])
        cfg["limits"] = TINY_LIMITS[kind]
        json.dump(cfg, open(path, "w"))
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path / "root")
