"""MFU accounting (train.metrics).

The reference has no training telemetry; MFU is this framework's
north-star surface (BASELINE.md). These pin the FLOP-accounting math so
bench numbers stay comparable across rounds — especially the r3
attention-aware formula that fixed the long-context under-report.
"""

from tf_operator_tpu.train.metrics import (
    attention_train_flops,
    transformer_train_flops,
    transformer_train_flops_exact,
)


def test_6nd_rule():
    assert transformer_train_flops(100, 10) == 6000.0


def test_attention_term_palm_formula():
    # 12 * L * t * d per token, times tokens_per_step
    assert attention_train_flops(2, 8, 16, 4) == 12.0 * 2 * 16 * 8 * 4


def test_exact_is_sum_of_terms():
    n, d, L, t = 1_000_000, 64, 4, 128
    toks = 256
    assert transformer_train_flops_exact(n, toks, L, d, t) == (
        transformer_train_flops(n, toks) + attention_train_flops(L, d, t, toks)
    )


def test_long_context_correction_magnitude():
    """The bug the r3 fix closes: at t=8192 on gpt-small the attention term
    ~equals the 6ND term, so 6ND-only MFU halves the true number."""
    from tf_operator_tpu.models.transformer import PRESETS

    cfg = PRESETS["gpt-small"]
    t = 8192
    toks = 2 * t
    six_nd = transformer_train_flops(cfg.n_active_params(), toks)
    exact = transformer_train_flops_exact(
        cfg.n_active_params(), toks, cfg.n_layers, cfg.d_model, t
    )
    assert 1.9 < exact / six_nd < 2.1
    # and at short context the correction is small (<10%)
    t = 512
    toks = 32 * t
    six_nd = transformer_train_flops(cfg.n_active_params(), toks)
    exact = transformer_train_flops_exact(
        cfg.n_active_params(), toks, cfg.n_layers, cfg.d_model, t
    )
    assert exact / six_nd < 1.10


# -- no invented peak (PR 21) -----------------------------------------------


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_peak_is_keyed_by_device_kind():
    from tf_operator_tpu.train.metrics import peak_flops_per_chip

    assert peak_flops_per_chip(_Dev("tpu", "TPU v5 lite")) == 197e12
    assert peak_flops_per_chip(_Dev("tpu", "TPU v4")) == 275e12


def test_unknown_tpu_kind_is_an_error_not_a_default():
    import pytest

    from tf_operator_tpu.train.metrics import mfu, peak_flops_per_chip

    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        peak_flops_per_chip(_Dev("tpu", "TPU v9 hyper"))
    with pytest.raises(ValueError):
        mfu(1e12, 1.0, 1, device=_Dev("tpu", "TPU v9 hyper"))
    with pytest.raises(ValueError):  # a CPU has no peak to divide by
        peak_flops_per_chip(_Dev("cpu", "cpu"))


def test_off_tpu_there_is_no_mfu():
    """The workloads log "n/a", not a number against an invented 1e12."""
    from tf_operator_tpu.train.metrics import fmt_mfu, mfu

    assert mfu(1e12, 1.0, 1, device=_Dev("cpu", "cpu")) is None
    assert fmt_mfu(None) == "n/a" and fmt_mfu(0.5731) == "0.573"
    assert mfu(197e12, 2.0, 1, device=_Dev("tpu", "TPU v5 lite")) == 0.5
