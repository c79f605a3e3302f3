"""Operation and byte counts for the Jamba serve cell, from the layer's
EQUATIONS: what serving the tokens needs, never what a kernel happens to
execute (a padded row, an inactive slot, a tile's masked keys count for
nothing), so the same work reads the same whatever implements it and a share
of a peak cannot pass 100 %.

``sizes`` is the runner's ``model_sizes``: the harness's group plus
``mamba_d_state``, ``mamba_d_conv``, ``mamba_expand``, ``mamba_dt_rank`` and
``pattern`` (one period's layer kinds, ``"mamba"`` / ``"attn"``).
"""

from __future__ import annotations

from typing import Iterable, Tuple


def layer_counts(sizes) -> Tuple[int, int]:
    """(Mamba layers, attention layers) of the model as run."""
    period = list(sizes["pattern"])
    periods = sizes["n_layers"] // len(period)
    return periods * period.count("mamba"), periods * period.count("attn")


def inner(sizes) -> int:
    return sizes["mamba_expand"] * sizes["d_model"]


def mamba_mixer_matmul_params(sizes) -> int:
    """W_in, W_x, W_dt and W_out of one Mamba layer (2560·10240 + 5120·192 +
    160·5120 + 5120·2560 at the published widths)."""
    d, I, N, R = (sizes["d_model"], inner(sizes), sizes["mamba_d_state"],
                  sizes["mamba_dt_rank"])
    return d * 2 * I + I * (R + 2 * N) + R * I + I * d


def attn_mixer_matmul_params(sizes) -> int:
    hd = sizes["d_model"] // sizes["n_heads"]
    return 2 * sizes["d_model"] * hd * (sizes["n_heads"] + sizes["n_kv_heads"])


def scan_flops_per_token(sizes) -> float:
    """One token through one Mamba layer's recurrence: per (channel, state)
    entry Δ·A, the exponential, the decay's product, Δ·u·B (two), the sum and
    S·C with its sum — 7 operations an entry."""
    return 7.0 * inner(sizes) * sizes["mamba_d_state"]


def serve_flops(sizes, work: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of serving ``work`` = (prompt tokens prefilled, output
    tokens produced) a request: 2 per matmul parameter per token through
    every layer, causal attention over each token's own context in the
    attention layers, the recurrence and the convolution's taps in the Mamba
    ones, and the head once per OUTPUT token (a prompt's other positions need
    no logits)."""
    n_mamba, n_attn = layer_counts(sizes)
    d, f = sizes["d_model"], sizes["d_ff"]
    per_token = 2.0 * (n_mamba * mamba_mixer_matmul_params(sizes)
                       + n_attn * attn_mixer_matmul_params(sizes)
                       + (n_mamba + n_attn) * 3 * d * f) \
        + n_mamba * (scan_flops_per_token(sizes)
                     + 2.0 * sizes["mamba_d_conv"] * inner(sizes))
    head = 2.0 * sizes["vocab"] * d
    total = 0.0
    for n_prompt, n_out in work:
        if not n_prompt:
            continue  # its prefill did not end inside the window: nothing completed
        # an output token is produced by the pass over the token before it:
        # the first by the prompt's last chunk, the others by decode steps;
        # position i attends its i + 1 keys in every attention layer (q·k and
        # p·v, 2 FLOPs each a query head's width)
        passes = n_prompt + max(n_out - 1, 0)
        total += passes * per_token + n_out * head
        total += n_attn * 4.0 * d * passes * (passes + 1) / 2.0
    return total


def ssm_chunk_cost(sizes, rows: int, calls: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the scan over many tokens needs for ``calls`` prefill
    calls of ``rows`` valid rows in all (the engine's ``prefill_tokens`` and
    ``prefill_chunks``), all Mamba layers: the recurrence's own arithmetic
    for every valid row; u, Δ, B, C read and y written once a row, the state
    read and written once a CALL."""
    n_mamba, _ = layer_counts(sizes)
    I, N = inner(sizes), sizes["mamba_d_state"]
    return (n_mamba * rows * scan_flops_per_token(sizes),
            n_mamba * 4.0 * (rows * (3 * I + 2 * N) + calls * 2 * N * I))


def ssm_step_cost(sizes, slot_steps: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the recurrent step needs for ``slot_steps`` (active
    slot, Mamba layer) updates: the state read and written once each, beside
    u, Δ, B, C and the output."""
    I, N = inner(sizes), sizes["mamba_d_state"]
    return (slot_steps * scan_flops_per_token(sizes),
            slot_steps * 4.0 * (2 * N * I + 3 * I + 2 * N))
