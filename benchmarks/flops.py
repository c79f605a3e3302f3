"""Operation and byte counts from shapes, and the table of peaks.

The yardstick's arithmetic: what the ALGORITHM needs, never what a
kernel happens to execute (recomputation counts for nothing), so that a
share of a peak cannot pass 100%. The parameter and 6·N arithmetic is a
copy of ``tf_operator_tpu/train/metrics.py:145-181`` and
``TransformerConfig.n_params``; attention is counted CAUSAL (half the
square), unlike the original's PaLM convention, because a causal kernel
that skips masked blocks must not read above 100%.

``sizes`` is a config file's ``model`` group: vocab, d_model, n_layers,
n_heads, n_kv_heads, d_ff.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; a kind that is not in the table is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise SystemExit(
            f"benchmarks/peaks.json has no entry for device kind "
            f"{device_kind!r}: add its published peaks with their source"
        )
    return table[device_kind]


def head_dim(sizes) -> int:
    return sizes["d_model"] // sizes["n_heads"]


def layer_matmul_params(sizes) -> int:
    d, f = sizes["d_model"], sizes["d_ff"]
    kv = sizes["n_kv_heads"] * head_dim(sizes)
    return d * d + 2 * d * kv + d * d + 3 * d * f  # wq, wk+wv, wo, gate+up+down


def param_count(sizes) -> int:
    """All parameters, tied head counted once (embedding == output head)."""
    d, L = sizes["d_model"], sizes["n_layers"]
    return sizes["vocab"] * d + L * (layer_matmul_params(sizes) + 2 * d) + d


def attention_flops_causal(sizes, seq_len: int) -> float:
    """Forward QK^T + PV for ONE sequence in ONE layer, causal: position
    i needs i+1 keys, two matmuls of 2·n_heads·head_dim each."""
    pairs = seq_len * (seq_len + 1) // 2
    return 4.0 * sizes["n_heads"] * head_dim(sizes) * pairs


def train_flops_per_step(sizes, batch: int, seq_len: int) -> float:
    """Model FLOPs of one training step (forward + backward = 3 x forward,
    no recompute): 6 per matmul parameter per token — the output head only
    on the seq_len-1 positions that have a target — plus causal attention."""
    tokens = batch * seq_len
    body = 6.0 * sizes["n_layers"] * layer_matmul_params(sizes) * tokens
    head = 6.0 * sizes["vocab"] * sizes["d_model"] * batch * (seq_len - 1)
    attn = 3.0 * sizes["n_layers"] * batch * attention_flops_causal(sizes, seq_len)
    return body + head + attn


def flash_train_cost(sizes, batch: int, seq_len: int, act_bytes: int = 2
                     ) -> Tuple[float, float]:
    """(FLOPs, bytes) the flash forward + backward kernels need for one
    step, all layers: forward 2 matmuls, backward the 4 it cannot avoid
    (dV, dP, dQ, dK; the score recompute is the kernel's own choice).
    Bytes: forward reads q,k,v and writes o; backward reads q,k,v,o,do and
    writes dq,dk,dv; the f32 log-sum-exp rows ride along both ways."""
    L = sizes["n_layers"]
    fwd = batch * attention_flops_causal(sizes, seq_len)
    q = batch * seq_len * sizes["n_heads"] * head_dim(sizes) * act_bytes
    kv = batch * seq_len * sizes["n_kv_heads"] * head_dim(sizes) * act_bytes
    lse = batch * seq_len * sizes["n_heads"] * 4
    fwd_bytes = 2 * q + 2 * kv + lse
    bwd_bytes = 4 * q + 4 * kv + lse
    return L * 3.0 * fwd, float(L * (fwd_bytes + bwd_bytes))


def paged_attention_cost(sizes, calls: Iterable[Tuple[int, int]],
                         pool_bytes: int = 4) -> Tuple[float, float]:
    """(FLOPs, bytes) the paged decode-attention kernel needs, all layers,
    for ``calls`` = (rows, keys) pairs: ``rows`` query positions that each
    attend a prefix of at most ``keys`` cached positions of ONE sequence
    (a decode token: rows=1; a prefill chunk: rows=chunk, causal inside).
    K and V of a sequence need reading once per call, whatever the rows."""
    nh, nkv, hd = sizes["n_heads"], sizes["n_kv_heads"], head_dim(sizes)
    flops = bytes_ = 0.0
    for rows, keys in calls:
        first = keys - rows + 1  # keys seen by the call's first row
        pairs = rows * (first + keys) / 2.0
        flops += 4.0 * nh * hd * pairs
        bytes_ += 2.0 * keys * nkv * hd * pool_bytes  # K and V pages
        bytes_ += 2.0 * rows * nh * hd * pool_bytes  # q in, o out
    return sizes["n_layers"] * flops, sizes["n_layers"] * bytes_


def roofline_share_pct(flops: float, bytes_: float, seconds: float, peaks
                       ) -> Tuple[float, str]:
    """Least time the chip could take over the measured time, in percent,
    and which peak bounds it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = bytes_ / peaks["bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
