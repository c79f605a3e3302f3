"""flops.py against counts worked by hand for Mistral-7B-v0.1's layer."""

import pytest

from benchmarks import flops

MISTRAL = dict(vocab=32000, d_model=4096, n_layers=32, n_heads=32,
               n_kv_heads=8, d_ff=14336)


def test_layer_and_embedding_parameters():
    # wq 4096x4096 + wk, wv 4096x1024 each + wo 4096x4096 = 41,943,040
    # gate, up, down: 3 x 4096 x 14336 = 176,160,768
    assert flops.layer_matmul_params(MISTRAL) == 41_943_040 + 176_160_768
    assert flops.layer_matmul_params(MISTRAL) == 218_103_808
    # tied head: embedding once; 2 norm gains a layer and the final one
    assert flops.param_count(MISTRAL) == (
        131_072_000 + 32 * (218_103_808 + 8192) + 4096)
    assert flops.head_dim(MISTRAL) == 128


def test_train_flops_of_one_step():
    sizes = dict(MISTRAL, n_layers=3)
    b, t = 2, 4096
    body = 6 * 3 * 218_103_808 * b * t
    head = 6 * 32000 * 4096 * b * (t - 1)
    # causal: t(t+1)/2 pairs, QK^T and PV at 2 x 32 x 128 each, x3 for training
    attn = 3 * 3 * b * 4 * 32 * 128 * (t * (t + 1) // 2)
    assert flops.train_flops_per_step(sizes, b, t) == pytest.approx(body + head + attn)
    assert flops.train_flops_per_step(sizes, b, t) == pytest.approx(41.07e12, rel=2e-3)


def test_flash_and_paged_attention_costs():
    sizes = dict(MISTRAL, n_layers=1)
    f, by = flops.flash_train_cost(sizes, batch=1, seq_len=4096)
    assert f == pytest.approx(3 * 4 * 4096 * (4096 * 4097 // 2))
    q, kv, lse = 4096 * 4096 * 2, 4096 * 1024 * 2, 4096 * 32 * 4
    assert by == (2 * q + 2 * kv + lse) + (4 * q + 4 * kv + lse)
    # one decode token over 100 cached positions: 4 x 4096 x 100 FLOPs, K and V
    # of 100 positions x 8 x 128 x 4 B, q and o of 4096 x 4 B
    f, by = flops.paged_attention_cost(sizes, [(1, 100)])
    assert f == 4 * 4096 * 100
    assert by == 2 * 100 * 1024 * 4 + 2 * 4096 * 4
    # a 128-row chunk ending at 256: rows see 129..256 keys
    f, _ = flops.paged_attention_cost(sizes, [(128, 256)])
    assert f == 4 * 4096 * sum(range(129, 257))


def test_roofline_share_and_peaks():
    peaks = flops.peaks_for("TPU v5 lite")
    assert peaks == {"flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9}
    share, bound = flops.roofline_share_pct(197e12, 1.0, 2.0, peaks)
    assert (share, bound) == (50.0, "flops")
    share, bound = flops.roofline_share_pct(1.0, 819e9, 4.0, peaks)
    assert (share, bound) == (25.0, "bytes")
    with pytest.raises(SystemExit):
        flops.peaks_for("TPU v9000")
