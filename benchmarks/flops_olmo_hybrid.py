"""Operation and byte counts for the Olmo-Hybrid serve cell, from the WORK:
what serving the tokens needs, never what a kernel happens to execute (the
chunked scan's extra products, a padded row, an inactive slot count for
nothing), so a share of a peak cannot pass 100 %.

``sizes`` is the runner's ``model_sizes``: the harness's group plus
``lin_heads``, ``lin_dk``, ``lin_dv``, ``lin_conv`` and ``pattern`` (one
period's layer kinds).
"""

from __future__ import annotations

from typing import Iterable, Tuple


def layer_counts(sizes) -> Tuple[int, int]:
    """(linear layers, full layers) of the model as run."""
    period = list(sizes["pattern"])
    periods = sizes["n_layers"] // len(period)
    return periods * period.count("linear"), periods * period.count("full")


def linear_mixer_matmul_params(sizes) -> int:
    """W_qkv, W_z, W_ba and W_o of one linear layer (ISSUE 37: 3840·17,280 +
    3840·60 + 5,760·3840 at the published widths)."""
    d, H, dk, dv = (sizes[k] for k in ("d_model", "lin_heads", "lin_dk", "lin_dv"))
    return d * H * (2 * dk + 2 * dv) + d * 2 * H + H * dv * d


def full_mixer_matmul_params(sizes) -> int:
    hd = sizes["d_model"] // sizes["n_heads"]
    return 2 * sizes["d_model"] * hd * (sizes["n_heads"] + sizes["n_kv_heads"])


def recurrence_flops_per_token(sizes) -> float:
    """One token through one linear layer's recurrence in its own
    (recurrent) form: k·S, the rank-one write, q·S — three passes over the
    [d_k, d_v] state of 2 FLOPs an entry a head — and the convolution's
    K taps over the [q | k | v] channels."""
    H, dk, dv, K = (sizes[k] for k in ("lin_heads", "lin_dk", "lin_dv", "lin_conv"))
    return 6.0 * H * dk * dv + 2.0 * K * H * (2 * dk + dv)


def serve_flops(sizes, work: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of serving ``work`` = (prompt tokens prefilled, output
    tokens produced) a request: 2 per matmul parameter per token through
    every layer, causal attention over each token's own context in the full
    layers, the recurrence in the linear ones, and the head once per OUTPUT
    token (a prompt's other positions need no logits)."""
    n_lin, n_full = layer_counts(sizes)
    d, f = sizes["d_model"], sizes["d_ff"]
    per_token = 2.0 * (n_lin * linear_mixer_matmul_params(sizes)
                       + n_full * full_mixer_matmul_params(sizes)
                       + (n_lin + n_full) * 3 * d * f) \
        + n_lin * recurrence_flops_per_token(sizes)
    head = 2.0 * sizes["vocab"] * d
    total = 0.0
    for n_prompt, n_out in work:
        if not n_prompt:
            continue  # its prefill did not end inside the window: nothing completed
        # an output token is produced by the pass over the token before it:
        # the first by the prompt's last chunk, the others by decode steps;
        # position i attends its i + 1 keys in every full layer
        passes = n_prompt + max(n_out - 1, 0)
        total += passes * per_token + n_out * head
        total += n_full * 4.0 * d * passes * (passes + 1) / 2.0
    return total


def gdn_chunk_cost(sizes, chunk_rows: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) the chunked-scan kernel needs for prefill calls of
    ``chunk_rows`` valid rows each, all linear layers: the recurrence's own
    arithmetic for every valid row; q, k, v and the two gates read and the
    output written once a row, the state read and written once a CALL."""
    n_lin, _ = layer_counts(sizes)
    H, dk, dv = (sizes[k] for k in ("lin_heads", "lin_dk", "lin_dv"))
    flops = bytes_ = 0.0
    for rows in chunk_rows:
        flops += rows * 6.0 * H * dk * dv
        bytes_ += 4.0 * (rows * H * (2 * dk + 2 * dv + 2) + 2 * H * dk * dv)
    return n_lin * flops, n_lin * bytes_


def gdn_step_cost(sizes, slot_steps: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the recurrent-step kernel needs for ``slot_steps``
    (active slot, linear layer) updates: the state read and written once
    each, beside q, k, v, the gates and the output."""
    H, dk, dv = (sizes[k] for k in ("lin_heads", "lin_dk", "lin_dv"))
    return (slot_steps * 6.0 * H * dk * dv,
            slot_steps * 4.0 * H * (2 * dk * dv + 2 * dk + 2 * dv + 2))


def prefill_rows(finished, chunk: int):
    """Valid rows of every prefill call the finished requests needed."""
    for _, prompt, _ in finished:
        n = len(prompt)
        for start in range(0, n, chunk):
            yield min(chunk, n - start)
