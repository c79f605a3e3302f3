"""Step-time and MFU telemetry.

The reference's only timing is per-sync controller latency logging
(SURVEY.md §5); training telemetry is the TPU framework's north-star
metric surface (BASELINE.md: ≥50% MFU ResNet-50, images/sec/chip,
submit→first-step latency).
"""

from __future__ import annotations

from typing import Dict, Optional

# Peak dense bf16 FLOP/s per chip, matched on jax's ``device_kind``
# (lower-cased substring, most specific first). Source: Google Cloud TPU
# documentation, the per-generation system-architecture pages.
_PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6 lite": 918e12,  # Trillium
    "v6e": 918e12,
}


def peak_flops_per_chip(device=None) -> float:
    """Peak bf16 FLOP/s of the attached TPU chip. A device that is not in
    the table is an error, not a default: a CPU has no peak worth
    dividing by, and an unknown TPU generation measured against another
    generation's peak is a wrong number with a right-looking name."""
    import jax

    dev = device or jax.devices()[0]
    kind = getattr(dev, "device_kind", "")
    if dev.platform == "tpu":
        for marker, flops in _PEAK_FLOPS.items():
            if marker in kind.lower():
                return flops
    raise ValueError(
        f"no peak FLOP/s known for device kind {kind!r} (platform "
        f"{dev.platform!r}); add it to train/metrics._PEAK_FLOPS with its "
        "source"
    )


def mfu(model_flops_per_step: float, step_seconds: float, n_chips: int,
        device=None) -> Optional[float]:
    """Model FLOPs utilization: achieved / peak. ``None`` off-TPU — there
    is no MFU of a CPU run, and callers log "n/a" (``fmt_mfu``) instead
    of a number against an invented peak. An unknown TPU kind raises."""
    import jax

    dev = device or jax.devices()[0]
    if dev.platform != "tpu":
        return None
    peak = peak_flops_per_chip(dev) * n_chips
    return model_flops_per_step / (step_seconds * peak)


def fmt_mfu(value: Optional[float]) -> str:
    return "n/a" if value is None else f"{value:.3f}"


def run_report(**extra) -> Dict[str, object]:
    """What a worker says, in one JSON-able dict, about the run it just
    made: the device as jax reports it, the device's peak memory where
    the backend reports it, this process's compile-cache counters, and
    whatever the workload adds (compile seconds, custom-call counts,
    losses). Workloads log it as one ``run report: {...}`` line — the
    line a launcher that never touches jax (chip_smoke.py) reads the
    device facts from."""
    import jax

    from tf_operator_tpu.train import compile_cache

    dev = jax.local_devices()[0]  # in a gang, devices()[0] may be a peer's
    mem = dev.memory_stats() or {}
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "compile_cache": compile_cache.stats(),
        **extra,
    }


def transformer_train_flops(n_params: int, tokens_per_step: int) -> float:
    """6ND rule: fwd 2ND + bwd 4ND.

    This deliberately EXCLUDES the attention score/value matmuls (they scale
    with sequence length, not parameter count) — at t=8192 on gpt-small the
    attention term is the same order as 6ND, so a 6ND-only MFU under-reports
    long-context utilization by ~2x. Use transformer_train_flops_exact for
    honest long-context accounting; report both."""
    return 6.0 * n_params * tokens_per_step


def attention_train_flops(
    n_layers: int, d_model: int, seq_len: int, tokens_per_step: int
) -> float:
    """Attention matmul FLOPs (PaLM appendix-B accounting): per token the
    QK^T and AV einsums each cost 2·t·d fwd per layer, so fwd = 4·L·t·d and
    train (fwd+bwd = 3x fwd) = 12·L·t·d per token. Counted over the full
    t^2 score matrix, per the PaLM convention, even for causal models —
    a causal kernel that skips masked blocks shows up as MFU > its dense
    counterpart, which is the honest reading (it did less wall-clock work
    for the same model math)."""
    return 12.0 * n_layers * seq_len * d_model * tokens_per_step


def transformer_train_flops_exact(
    n_params: int,
    tokens_per_step: int,
    n_layers: int,
    d_model: int,
    seq_len: int,
) -> float:
    """6ND plus the attention term — the exact model-FLOPs accounting for
    long-context MFU (6ND alone halves the reported utilization at
    t≈8k on gpt-small-class models)."""
    return transformer_train_flops(n_params, tokens_per_step) + attention_train_flops(
        n_layers, d_model, seq_len, tokens_per_step
    )


def resnet_train_flops(fwd_flops_per_image: float, images_per_step: int) -> float:
    """Training ≈ 3× forward."""
    return 3.0 * fwd_flops_per_image * images_per_step
