"""Operation and byte counts for the JoyAI-LLM-Flash share cell: what the
ALGORITHM needs for one training step, from shapes and from the step's own
routing counters — never what a kernel happens to execute (the remat
replay of a layer's forward counts for nothing), so that a share of a
peak cannot pass 100%.

``sizes`` is what ``runners/train_joyai.model_sizes`` builds from the
config file: vocab, d_model, n_layers (dense lead included), n_dense,
n_heads, q_rank, kv_rank, nope, rope, v_dim, d_ff (an expert's width),
d_ff_dense, n_experts, held, n_shared. ``routed`` is the step's count of
token-choices routed to held experts, summed over the expert layers and
the module's (the program's ``moe_routed_here``).
"""

from __future__ import annotations

from typing import Tuple


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs one head of one sequence scores."""
    return seq_len * (seq_len + 1) // 2


def attention_matmul_params(sizes) -> int:
    """Latent attention's five matrices: W_qa, W_qb, W_kva, W_kvb, W_o."""
    d, nh = sizes["d_model"], sizes["n_heads"]
    qr, kvr = sizes["q_rank"], sizes["kv_rank"]
    nope, rope, dv = sizes["nope"], sizes["rope"], sizes["v_dim"]
    return (d * qr + qr * nh * (nope + rope) + d * (kvr + rope)
            + kvr * nh * (nope + dv) + nh * dv * d)


def expert_layer_params(sizes) -> int:
    """Matmul parameters every token passes in one expert layer outside
    its routed experts: attention, the router, the shared expert."""
    d = sizes["d_model"]
    return (attention_matmul_params(sizes) + d * sizes["n_experts"]
            + sizes["n_shared"] * 3 * d * sizes["d_ff"])


def dense_layer_params(sizes) -> int:
    return attention_matmul_params(sizes) + 3 * sizes["d_model"] * sizes["d_ff_dense"]


def expert_params(sizes) -> int:
    return 3 * sizes["d_model"] * sizes["d_ff"]


def pair_flops(sizes) -> float:
    """Forward FLOPs of one (query, key) pair of all heads: the score over
    the q/k width, the value product over the v width."""
    return 2.0 * sizes["n_heads"] * (sizes["nope"] + sizes["rope"] + sizes["v_dim"])


def train_flops_per_step(sizes, batch: int, seq_len: int, routed: float) -> float:
    """Model FLOPs of one step (forward + backward = 3 x forward, no
    recompute): 6 per matmul parameter per token outside the routed
    experts — the main stack on every position, the prediction module
    (W_eh and one expert layer) on the seq_len - 2 that have its target —
    6 per expert parameter per COUNTED routed choice, the head twice, on
    the seq_len - 1 positions of the main loss and the seq_len - 2 of the
    module's, and causal attention at q/k width + v width a pair."""
    d = sizes["d_model"]
    n_moe = sizes["n_layers"] - sizes["n_dense"]
    tokens, mtp_tokens = batch * seq_len, batch * (seq_len - 2)
    body = 6.0 * tokens * (sizes["n_dense"] * dense_layer_params(sizes)
                           + n_moe * expert_layer_params(sizes))
    module = 6.0 * mtp_tokens * (2 * d * d + expert_layer_params(sizes))
    experts = 6.0 * expert_params(sizes) * routed
    head = 6.0 * sizes["vocab"] * d * batch * ((seq_len - 1) + (seq_len - 2))
    attn = 3.0 * pair_flops(sizes) * batch * (
        sizes["n_layers"] * causal_pairs(seq_len) + causal_pairs(seq_len - 2))
    return body + module + experts + head + attn


def flash_latent_cost(sizes, batch: int, seq_len: int, act_bytes: int = 2
                      ) -> Tuple[float, float]:
    """(FLOPs, bytes) the flash forward + backward kernels need for one
    step over the n_layers + 1 blocks they run in (the module's too, on
    whole rows as the program feeds it): forward 2 matmuls, backward the 4
    it cannot avoid, each at its own width. Bytes: forward reads q, k
    (q/k width), v and writes o (v width); backward reads q, k, v, o, do
    and writes dq, dk, dv; the f32 log-sum-exp rows ride along both ways."""
    blocks = sizes["n_layers"] + 1
    flops = 3.0 * pair_flops(sizes) * batch * causal_pairs(seq_len)
    rows = batch * seq_len * sizes["n_heads"]
    qk = rows * (sizes["nope"] + sizes["rope"]) * act_bytes
    v = rows * sizes["v_dim"] * act_bytes
    lse = rows * 4
    return blocks * flops, float(blocks * (6 * qk + 6 * v + 2 * lse))
