"""Operation and byte counts for the SmallThinker share cell: what the
ALGORITHM needs for one training step, from shapes and from the step's own
routing counters — never what a kernel happens to execute (the remat
replay of a layer's forward counts for nothing), so that a share of a
peak cannot pass 100%.

``sizes`` is what ``runners/train_smallthinker.model_sizes`` builds from
the config file: vocab, d_model, n_layers, n_heads, n_kv_heads, head_dim,
d_ff (the expert width), n_experts, top_k, held, pattern ((window,
rotary), ...). ``routed`` is the step's count of token-choices routed to
held experts, summed over layers (the program's ``moe_routed_here``).
"""

from __future__ import annotations

from typing import Tuple


def visible_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs one head of one sequence scores: causal, and
    with ``window`` > 0 only the last ``window`` keys of every query."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layer_pairs(sizes, seq_len: int) -> int:
    """Visible pairs of one sequence, summed over the layers by kind."""
    pattern = sizes["pattern"]
    return sum(visible_pairs(seq_len, pattern[l % len(pattern)][0])
               for l in range(sizes["n_layers"]))


def dense_matmul_params(sizes) -> int:
    """Matmul parameters every token passes in one layer outside the
    experts: wq, wk, wv, wo and the router."""
    d, hd = sizes["d_model"], sizes["head_dim"]
    q, kv = sizes["n_heads"] * hd, sizes["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + d * sizes["n_experts"]


def expert_params(sizes) -> int:
    return 3 * sizes["d_model"] * sizes["d_ff"]


def train_flops_per_step(sizes, batch: int, seq_len: int, routed: float) -> float:
    """Model FLOPs of one step (forward + backward = 3 x forward, no
    recompute): 6 per matmul parameter per token outside the experts, 6
    per expert parameter per COUNTED routed choice, the head on the
    seq_len-1 positions with a target, attention by the visible pairs of
    each layer kind (two matmuls of 2·n_heads·head_dim a pair)."""
    tokens = batch * seq_len
    body = 6.0 * sizes["n_layers"] * dense_matmul_params(sizes) * tokens
    experts = 6.0 * expert_params(sizes) * routed
    head = 6.0 * sizes["vocab"] * sizes["d_model"] * batch * (seq_len - 1)
    attn = 3.0 * 4.0 * sizes["n_heads"] * sizes["head_dim"] \
        * batch * layer_pairs(sizes, seq_len)
    return body + experts + head + attn


def flash_window_cost(sizes, batch: int, seq_len: int, act_bytes: int = 2
                      ) -> Tuple[float, float]:
    """(FLOPs, bytes) the flash forward + backward kernels need for one
    step, all layers, with window-aware pairs: forward 2 matmuls,
    backward the 4 it cannot avoid. Bytes as ``flops.flash_train_cost``:
    forward reads q,k,v and writes o; backward reads q,k,v,o,do and writes
    dq,dk,dv; the f32 log-sum-exp rows ride along both ways."""
    L, hd = sizes["n_layers"], sizes["head_dim"]
    flops = 3.0 * 4.0 * sizes["n_heads"] * hd * batch * layer_pairs(sizes, seq_len)
    q = batch * seq_len * sizes["n_heads"] * hd * act_bytes
    kv = batch * seq_len * sizes["n_kv_heads"] * hd * act_bytes
    lse = batch * seq_len * sizes["n_heads"] * 4
    return flops, float(L * ((2 * q + 2 * kv + lse) + (4 * q + 4 * kv + lse)))


def gmm_cost(sizes, routed: float, act_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) the grouped matmuls need for one step, all layers,
    for ``routed`` counted rows: a product is 2·rows·K·N; the forward has
    3 (gate, up, down), the backward 3 for dx and 3 for dw. Bytes, a
    product: the held experts' weights once (bf16 read forward and dx, f32
    written by dw) plus the rows in and out."""
    d, f = sizes["d_model"], sizes["d_ff"]
    flops = 9.0 * 2.0 * routed * d * f
    weights = sizes["n_layers"] * sizes["held"] * d * f
    rows = routed * (d + f) * act_bytes  # one product's rows in + rows out
    fwd_dx = 6.0 * (weights * act_bytes + rows)
    dw = 3.0 * (weights * 4 + rows)
    return flops, fwd_dx + dw
