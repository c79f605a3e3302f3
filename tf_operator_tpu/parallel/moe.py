"""Expert parallelism: top-k MoE with all-to-all dispatch.

k_top=1 is Switch-style routing; k_top=2 is Mixtral-style (each token's
two highest-gated experts, gate weights renormalized over the chosen).

Experts are sharded over the ``ep`` mesh axis; tokens are routed by a gating
network, dispatched to their expert's device with ``all_to_all`` (ragged
traffic rides ICI), processed, and combined back weighted by the gate
probability. Capacity-factor dropping keeps shapes static for XLA; what a
dropped token yields is the caller's choice (``dropped=`` — passthrough
for standalone use, zero when feeding a residual stream).

New TPU-native surface (reference has no MoE support, SURVEY.md §2.3).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tf_operator_tpu.parallel.collectives import axis_size


def expert_capacity(capacity_factor: float, k_top: int, local_tokens: int,
                    n_experts: int) -> int:
    """THE per-expert queue length rule — one definition for every
    routing path (single-device, ep-sharded, and ep-inside-pipeline):
    capacity = cf·k·T_local/E, floored, at least 1. A second copy of
    this formula diverging (different rounding, forgetting k_top) would
    give pp x ep different drop patterns than non-pipelined ep with
    nothing pinning the difference."""
    return max(1, int(capacity_factor * k_top * local_tokens / n_experts))


def _renormalize_survivors(w, kept, surviving):
    """``w * kept / surviving`` where any choice survived, else ``w``. The
    divisor is made 1 where nothing survived, not clamped to a tiny value:
    the unselected branch's gradient divides by the divisor SQUARED, 1e-40
    is a float32 denormal, and the compiled CPU step flushes it to zero —
    0 / 0, a NaN router gradient for every token with all its choices
    dropped (PR 32: seen the first time the einsum path ran under ``jit``)."""
    alive = surviving > 0
    return jnp.where(alive, w * kept / jnp.where(alive, surviving, 1.0), w)


def _route(x, gate_logits, capacity: int, k_top: int = 1, dropped: str = "passthrough"):
    """Top-k routing bookkeeping shared by the sharded and single-device
    paths. Each token goes to its ``k_top`` highest-gated experts; with
    k_top > 1 the chosen gate probs are renormalized to sum to 1 (the
    Mixtral rule). Queue slots are claimed in token order per expert.

    Partial capacity drops (k_top > 1, some but not all choices
    overflow): in "zero" mode the dropped choice simply contributes 0
    (the Switch training convention — drops are an efficiency artifact,
    not a reweighting); in "passthrough" mode weights renormalize over
    the SURVIVING choices so the output stays a full-strength convex mix
    rather than a silently attenuated one.

    Returns (dispatch_w [T,E,C] — combine weights, keep_any [T] — token
    has >= 1 surviving choice, inbox [E,C,d], stats — router
    observability: expert_load [E] (fraction of token-choices assigned to
    each expert), mean_gate [E] (mean router probability), drop_frac
    (fraction of token-choices that overflowed capacity))."""
    n_experts = gate_logits.shape[-1]
    with jax.named_scope("sec_router"):
        gate_probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
        top_p, top_i = jax.lax.top_k(gate_probs, k_top)  # [T, k]
        if k_top > 1:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # assign[t, e] = 1 if e is one of t's choices; w[t, e] = its gate weight
    choice_onehot = jax.nn.one_hot(top_i, n_experts, dtype=jnp.float32)  # [T,k,E]
    assign = jnp.sum(choice_onehot, axis=1)  # [T, E] (0/1: top_k is distinct)
    w = jnp.einsum("tke,tk->te", choice_onehot, top_p)  # [T, E]

    # Position of each (token, choice) within its expert's queue; beyond
    # capacity that choice drops.
    pos = (jnp.cumsum(assign, axis=0) - 1.0) * assign  # [T, E]
    kept = assign * (pos < capacity)  # [T, E]
    if k_top > 1 and dropped == "passthrough":
        surviving = jnp.sum(w * kept, axis=-1, keepdims=True)
        w = _renormalize_survivors(w, kept, surviving)
    pos_onehot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch = kept[:, :, None] * pos_onehot  # [T, E, C] 0/1
    dispatch_w = dispatch * w[:, :, None]  # combine side carries gate weights
    keep_any = jnp.sum(kept, axis=-1) > 0
    # Expert inboxes from local tokens: [E, C, d]
    inbox = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    n_choices = jnp.float32(x.shape[0] * k_top)
    with jax.named_scope("sec_router"):
        stats = {
            "expert_load": jnp.sum(assign, axis=0) / n_choices,  # [E]
            "mean_gate": jnp.mean(gate_probs, axis=0),  # [E]
            "drop_frac": 1.0 - jnp.sum(kept) / n_choices,
        }
    return dispatch_w, keep_any, inbox, stats


def _route_sparse(x, gate_logits, capacity: int, k_top: int = 1,
                  dropped: str = "passthrough"):
    """Sort-based routing — the same queue semantics as ``_route`` (slots
    claimed in token order per expert, identical drop patterns) at
    O(T·d + T log T) instead of the one-hot einsum's O(T²·d): with
    capacity_factor 2 the dispatch einsum is a [T, 2T] × [T, d] matmul —
    ~4·T²·d FLOPs per layer, measured ~4x the ACTIVE expert FLOPs at
    bench shapes, and the combine einsum pays it again. Here dispatch is
    a scatter-add and combine a gather.

    Returns (slot [T,k] int32 — flat inbox slot e·C + rank (E·C = the
    dump row for capacity-dropped choices), w [T,k] f32 combine weights,
    keep_any [T], inbox [E,C,d] f32, stats) — inbox layout identical to
    _route's, so the ep all_to_all path is impl-agnostic."""
    tokens, d = x.shape
    n_experts = gate_logits.shape[-1]
    with jax.named_scope("sec_router"):
        gate_probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
        top_p, top_i = jax.lax.top_k(gate_probs, k_top)  # [T, k]
        if k_top > 1:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_i.reshape(-1).astype(jnp.int32)  # [T*k], t-major: the
    # stable sort below then orders each expert's queue by token index —
    # exactly _route's cumsum-over-tokens position assignment (one token
    # contributes at most one choice per expert, so k-order within a
    # token never ties in a queue)
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.bincount(flat_e, length=n_experts)  # [E]
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix
    rank_sorted = jnp.arange(flat_e.shape[0]) - offsets[flat_e[order]]
    ranks = jnp.zeros_like(flat_e).at[order].set(rank_sorted.astype(jnp.int32))
    kept = (ranks < capacity).reshape(tokens, k_top)  # [T, k]
    slot = jnp.where(
        kept, (flat_e * capacity + ranks).reshape(tokens, k_top),
        n_experts * capacity,
    ).astype(jnp.int32)

    w = top_p
    if k_top > 1 and dropped == "passthrough":
        surviving = jnp.sum(w * kept, axis=-1, keepdims=True)
        w = _renormalize_survivors(w, kept, surviving)
    keep_any = jnp.any(kept, axis=-1)

    # inbox by scatter-add: each kept (token, choice) owns a unique slot;
    # dropped choices pile harmlessly into the dump row, sliced off.
    x_rep = jnp.broadcast_to(
        x.astype(jnp.float32)[:, None, :], (tokens, k_top, d)
    ).reshape(tokens * k_top, d)
    inbox = jnp.zeros((n_experts * capacity + 1, d), jnp.float32)
    inbox = inbox.at[slot.reshape(-1)].add(x_rep)
    inbox = inbox[:-1].reshape(n_experts, capacity, d)

    n_choices = jnp.float32(tokens * k_top)
    with jax.named_scope("sec_router"):
        stats = {
            "expert_load": counts.astype(jnp.float32) / n_choices,
            "mean_gate": jnp.mean(gate_probs, axis=0),
            "drop_frac": 1.0 - jnp.sum(kept) / n_choices,
        }
    return slot, w, keep_any, inbox, stats


def _combine_sparse(outbox, slot, w):
    """Gather each choice's expert output back to its token and weight by
    the gate: out[t] = Σ_k w[t,k] · outbox_flat[slot[t,k]]. The dump row
    is appended as zeros, so dropped choices contribute nothing even in
    "zero" mode where their w is untouched."""
    n_experts, capacity, d = outbox.shape
    flat = jnp.concatenate(
        [outbox.reshape(n_experts * capacity, d), jnp.zeros((1, d), outbox.dtype)]
    )
    gathered = flat[slot]  # [T, k, d]
    return jnp.einsum("tk,tkd->td", w, gathered)


def expert_activation(name: str):
    """The gate activation of a gated expert MLP: ``silu`` (SwiGLU) or
    ``relu`` (ReGLU)."""
    if name == "silu":
        return jax.nn.silu
    if name == "relu":
        return jax.nn.relu
    raise ValueError(f"unknown expert activation {name!r} (silu | relu)")


def _segment_blocks(tk: int, held: int, n_experts: int, block_rows: int) -> int:
    """Row-blocks a segment of the expert walk: the rows this share of the
    experts gets under EVEN routing plus a round-up block an expert — from
    the shapes a call observes, no setting. held == n_experts gives the
    lossless bound itself (one segment)."""
    return -(-(tk * held) // (n_experts * block_rows)) + held


class _Walk(NamedTuple):
    """What is static about one expert walk (hashable: the op's
    non-differentiated argument)."""
    k_top: int
    block_rows: int
    seg_blocks: int
    act: Callable
    interpret: bool

    @property
    def kernel(self) -> dict:
        return dict(block_rows=self.block_rows, interpret=self.interpret)


class _Routing(NamedTuple):
    """The tk-sized bookkeeping of one call, held choices sorted first:
    ``order`` [T·k] (sorted position -> flattened choice), per held expert
    its ``counts``, its ``offsets`` among the sorted choices, the running
    sum of its blocks ``bounds`` and its first row ``pad_start`` in the
    padded buffer."""
    order: jax.Array
    counts: jax.Array
    offsets: jax.Array
    bounds: jax.Array
    pad_start: jax.Array


def _segment_inputs(j, x, top_p, idx: _Routing, walk: _Walk):
    """Segment ``j`` of the padded buffer (``walk.seg_blocks`` row-blocks
    from block ``j·seg_blocks``) as (block_expert, valid, src_choice, tok,
    x_seg, s_pad): its block→expert map and, per slot, whether a routed
    choice sits there, that choice's index into the flattened [T·k] choices,
    its token, the token's row and its gate weight. Blocks behind the last
    occupied one (and behind the lossless bound) are SENTINELS (-1);
    sentinel and round-up slots read token 0 with gate weight 0. The
    experts' tables are read once a BLOCK; a slot adds its row within the
    block. Dispatch is a row GATHER of the segment's slots, and each slot's
    gate weight rides the down-projection kernel as a row scale (the fused
    combine epilogue, r6): garbage slots scale by 0."""
    held, tk = idx.counts.shape[0], idx.order.shape[0]
    B, S = walk.block_rows, walk.seg_blocks
    block = j * S + jnp.arange(S, dtype=jnp.int32)
    owner = jnp.sum(block[:, None] >= idx.bounds, axis=1).astype(jnp.int32)
    block_expert = jnp.where(owner < held, owner, -1)
    e_b = jnp.maximum(block_expert, 0)
    # [S, B]: a slot's rank among its expert's sorted choices
    rank = (block * B - idx.pad_start[e_b])[:, None] + jnp.arange(B, dtype=jnp.int32)
    valid = (rank < jnp.where(block_expert >= 0, idx.counts[e_b], 0)[:, None]).reshape(-1)
    src_choice = idx.order[
        jnp.clip(idx.offsets[e_b][:, None] + rank, 0, tk - 1).reshape(-1)]
    tok = jnp.where(valid, src_choice // walk.k_top, 0)
    x_seg = x[tok]  # [seg_blocks·B, d]
    s_pad = jnp.where(valid, top_p.reshape(-1)[src_choice], 0.0)
    return block_expert, valid, src_choice, tok, x_seg, s_pad


def _segment_forward(j, out, x, top_p, weights, idx, walk):
    """``out`` [T, d] float32 plus segment j's experts' outputs: the combine
    is the ``moe_combine`` kernel (grouped_matmul.combine_rows) adding the
    slots' rows onto their tokens in place (``h`` is pre-weighted), so the
    sum over a token's choices runs in float32, in the order of the slots."""
    from tf_operator_tpu.ops.grouped_matmul import combine_rows, gmm

    run = partial(gmm, **walk.kernel)
    w_gate, w_up, w_down = weights
    with jax.named_scope("sec_moe_dispatch"):
        block_expert, valid, _, tok, x_seg, s_pad = _segment_inputs(
            j, x, top_p, idx, walk)
    with jax.named_scope("sec_moe_experts"):
        zg = run(x_seg, w_gate, block_expert)
        zu = run(x_seg, w_up, block_expert)
        h = run(walk.act(zg) * zu, w_down, block_expert, row_scale=s_pad)
    with jax.named_scope("sec_moe_dispatch"):
        return combine_rows(out, h, tok, valid, groups=idx.counts.shape[0],
                            **walk.kernel)


def _walk_trips(idx, walk):
    """Segments that hold an occupied block: the sort puts held choices
    first, so everything behind ``bounds[-1]`` blocks is sentinel."""
    return -(-idx.bounds[-1] // walk.seg_blocks)


def _cast_weights(weights, dtype):
    with jax.named_scope("sec_moe_experts"):
        return tuple(w.astype(dtype) for w in weights)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _expert_walk(walk, x, top_p, weights, idx):
    """Σ over the held experts' routed choices of gate weight × expert(x),
    [T, d] in ``x.dtype``, as a WALK over fixed-size segments of the padded
    buffer whose trip count is read on the device (_walk_trips): the cost
    follows the rows routed here, and the last possible trip ends at the
    lossless bound. Reverse mode cannot see through a dynamic trip count,
    so the op brings its own backward — the same walk."""
    tokens, d = x.shape
    weights = _cast_weights(weights, x.dtype)
    with jax.named_scope("sec_moe_dispatch"):  # the loop's own plumbing too
        out = jax.lax.fori_loop(
            0, _walk_trips(idx, walk),
            lambda j, out: _segment_forward(j, out, x, top_p, weights, idx, walk),
            jnp.zeros((tokens, d), jnp.float32))
        return out.astype(x.dtype)


def _expert_walk_fwd(walk, x, top_p, weights, idx):
    # residuals are the op's INPUTS: nothing segment- or bound-sized is kept,
    # and under a remat policy the replayed forward walk is dead code
    return _expert_walk(walk, x, top_p, weights, idx), (x, top_p, weights, idx)


def _expert_walk_bwd(walk, res, g):
    """Per segment: gather its rows and its rows of ``g`` again, gate and up
    again (what ``save_mid`` replays anyway), the kernels' cotangents
    (grouped_matmul.gmm_grads), then ``dx`` combined as the forward's
    ``out`` is (its two input cotangents go to ``moe_combine`` as they are:
    their float32 sum is never written out), ``d top_p`` scatter-added, and
    the three weight gradients summed on float32 carries — rounded once,
    after the last segment."""
    from tf_operator_tpu.ops.grouped_matmul import combine_rows, gmm, gmm_grads

    x, top_p, weights, idx = res
    kernel = walk.kernel
    w_gate, w_up, w_down = _cast_weights(weights, x.dtype)
    with jax.named_scope("sec_moe_experts"):  # transposed once, not a segment
        t_gate, t_up, t_down = (
            jnp.swapaxes(w, 1, 2) for w in (w_gate, w_up, w_down))

    def segment(j, carry):
        dx, d_top_p, dw_gate, dw_up, dw_down = carry
        with jax.named_scope("sec_moe_dispatch"):
            block_expert, valid, src_choice, tok, x_seg, s_pad = _segment_inputs(
                j, x, top_p, idx, walk)
            # a sentinel or round-up slot reads row 0 of ``g``: its gate weight
            # is 0, so every cotangent it feeds but ``ds`` is an exact zero
            g_h = g[tok]
        with jax.named_scope("sec_moe_experts"):
            zg = gmm(x_seg, w_gate, block_expert, **kernel)
            zu = gmm(x_seg, w_up, block_expert, **kernel)
            a, act_vjp = jax.vjp(lambda zg, zu: walk.act(zg) * zu, zg, zu)
            da, dw_down_j, ds = gmm_grads(
                a, t_down, block_expert, g_h, row_scale=s_pad, **kernel)
            dzg, dzu = act_vjp(da)
            dx_g, dw_gate_j = gmm_grads(x_seg, t_gate, block_expert, dzg, **kernel)
            dx_u, dw_up_j = gmm_grads(x_seg, t_up, block_expert, dzu, **kernel)
            dw_gate, dw_up, dw_down = (
                dw_gate + dw_gate_j, dw_up + dw_up_j, dw_down + dw_down_j)
            # the combine waits for the weight-gradient kernels: by then the
            # two gathered segments (x_seg, g_h) are dead and the body holds
            # two segment-sized buffers fewer while the kernel runs
            dx_g, dx_u, dw_gate, dw_up, dw_down = jax.lax.optimization_barrier(
                (dx_g, dx_u, dw_gate, dw_up, dw_down))
        with jax.named_scope("sec_moe_dispatch"):
            dx = combine_rows(dx, (dx_g, dx_u), tok, valid,
                              groups=idx.counts.shape[0], **kernel)
            d_top_p = d_top_p.at[src_choice].add(jnp.where(valid, ds, 0))
        return dx, d_top_p, dw_gate, dw_up, dw_down

    with jax.named_scope("sec_moe_dispatch"):
        carry = (jnp.zeros(x.shape, jnp.float32),
                 jnp.zeros((top_p.size,), jnp.float32),
                 *(jnp.zeros(w.shape, jnp.float32) for w in weights))
        dx, d_top_p, *dw = jax.lax.fori_loop(
            0, _walk_trips(idx, walk), segment, carry)
        dx = dx.astype(x.dtype)
        d_top_p = d_top_p.reshape(top_p.shape).astype(top_p.dtype)
    with jax.named_scope("sec_moe_experts"):
        dw = tuple(g_w.astype(w.dtype) for g_w, w in zip(dw, weights))
    return dx, d_top_p, dw, None


_expert_walk.defvjp(_expert_walk_fwd, _expert_walk_bwd)


def _moe_single_gmm(x, gate_logits, expert_params, k_top: int = 1,
                    block_rows: int = 256, act=jax.nn.silu, first: int = 0,
                    score: str = "softmax", bias=None, scale: float = 1.0):
    """Padding-free single-device MoE over the Pallas grouped-matmul
    kernel (ops/grouped_matmul.gmm — the Megablocks-style path, r5), for
    ALL the experts or for ONE CHIP'S SHARE of them.

    The router always scores every expert (``gate_logits`` [T, E]) and
    the gate weights are the softmax over the k chosen, whichever chip
    holds them. ``expert_params`` carries the ``held`` experts
    ``first .. first + held - 1`` (held == E, first == 0: the whole
    layer). Choices whose expert is not held are left out BEFORE the
    sort; the output is the partial sum over the held experts' choices,
    which is what the residual stream gets — nothing stands in for the
    absent chips (the destination-0 segment of _moe_local_gmm without
    its exchanges).

    Held choices are sorted by expert (first; the rest behind them) and
    each expert's rows padded only to the ROW-BLOCK quantum; a
    scalar-prefetched block→expert map steers every block's weight-tile
    load. The padded buffer has the lossless bound nb = ceil(T·k/B) + held
    blocks — every choice could land here, so no choice of a held expert
    ever drops, at any load — but it is never built: the experts run as a
    WALK over segments of ``seg_blocks`` = ceil(T·k·held / (E·B)) + held
    blocks (the rows this share gets under even routing plus a round-up
    block an expert), ceil(occupied blocks / seg_blocks) of them, counted
    on the device (_expert_walk). A segment is a row GATHER of its slots,
    the three grouped matmuls, and the ``moe_combine`` kernel adding its
    weighted rows onto the float32 [T, d] result in place, in the order the
    sort made (grouped_matmul.combine_rows: no scatter, no second sort);
    only a segment's tail can be sentinel blocks (-1: zeros written, no MXU
    work). held == E makes the segment the whole buffer: one segment under
    plain autodiff, no loop. ragged_dot was measured at ~19 TFLOP/s on the
    same shapes (full-height masked-matmul lowering) — the kernel exists
    because the XLA-level formulations all lose; see grouped_matmul.py.

    ``score="sigmoid"`` is the bias-balanced router (DeepSeek-V3's
    ``noaux_tc`` at one group): s = sigmoid(logits) in float32, the top-k
    is taken over s + ``bias`` ([E] float32, model STATE: it steers the
    choice and nothing else), the weights are s of the chosen — never
    s + bias — over their sum, times ``scale``.

    Stats carry the routing counters of this call beside the router
    observability: ``expert_count`` (choices per router output, all E:
    what a bias update reads), ``routed_here`` (choices routed to held experts),
    ``rows_computed`` (occupied blocks × B: what the kernels multiply),
    ``rows_walked`` (segments walked × seg_blocks × B: what the gathers and
    the combines move) beside ``rows_bound`` (nb × B: what they moved before
    the walk), ``held_load_max`` / ``held_load_mean`` (choices per held
    expert)."""
    tokens, d = x.shape
    n_experts = gate_logits.shape[-1]
    held = expert_params["w_gate"].shape[0]
    with jax.named_scope("sec_router"):
        if score == "sigmoid":
            gate_probs = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
            _, top_i = jax.lax.top_k(
                gate_probs if bias is None else gate_probs + bias, k_top)
            top_p = jnp.take_along_axis(gate_probs, top_i, axis=-1)
            top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20) * scale
        elif score != "softmax":
            raise ValueError(f"unknown router score {score!r} (softmax | sigmoid)")
        else:
            gate_probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
            top_p, top_i = jax.lax.top_k(gate_probs, k_top)  # [T, k]
            if k_top > 1:
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    tk = tokens * k_top
    B = block_rows
    nb = -(-tk // B) + held  # static lossless bound incl. per-expert pad
    walk = _Walk(k_top, B, _segment_blocks(tk, held, n_experts, B), act,
                 jax.default_backend() != "tpu")
    with jax.named_scope("sec_moe_dispatch"):
        chosen = top_i.reshape(-1).astype(jnp.int32)  # [T*k], t-major
        local = chosen - first
        here = (local >= 0) & (local < held)
        flat_e = jnp.where(here, local, held)  # not held: a spare bucket, sorted last
        order = jnp.argsort(flat_e, stable=True)
        counts = jnp.bincount(flat_e, length=held + 1).astype(jnp.int32)[:held]
        offsets = jnp.cumsum(counts) - counts  # unpadded sorted offsets

        # an expert with no routed row owns no block: the dw kernel zeroes
        # every (expert, col-tile) at its walk's first step, so its gradient
        # is an exact zero all the same (test_gmm_zero_token_expert_gets_zero_grad)
        blocks_per_e = -(-counts // B)
        bounds = jnp.cumsum(blocks_per_e)  # [held]
        pad_start = (bounds - blocks_per_e) * B
        idx = _Routing(order, counts, offsets, bounds, pad_start)

    weights = tuple(expert_params[k] for k in ("w_gate", "w_up", "w_down"))
    if walk.seg_blocks == nb:  # held == E: the segment is the whole buffer
        with jax.named_scope("sec_moe_dispatch"):
            out = jnp.zeros((tokens, d), jnp.float32)
        out = _segment_forward(
            0, out, x, top_p, _cast_weights(weights, x.dtype), idx, walk
        ).astype(x.dtype)
        trips = jnp.int32(1)
    else:
        out = _expert_walk(walk, x, top_p, weights, idx)
        trips = _walk_trips(idx, walk)
    with jax.named_scope("sec_router"):
        held_counts = counts.astype(jnp.float32)
        all_counts = jnp.bincount(chosen, length=n_experts)
        stats = {
            "expert_load": all_counts.astype(jnp.float32) / tk,
            "expert_count": all_counts.astype(jnp.int32),
            "mean_gate": jnp.mean(gate_probs, axis=0),
            "drop_frac": jnp.float32(0.0),
            "routed_here": jnp.sum(held_counts),
            "rows_computed": (bounds[-1] * B).astype(jnp.float32),
            "rows_walked": (trips * (walk.seg_blocks * B)).astype(jnp.float32),
            "rows_bound": jnp.float32(nb * B),
            "held_load_max": jnp.max(held_counts),
            "held_load_mean": jnp.mean(held_counts),
        }
    return out, stats


def _moe_local_gmm(x, gate_logits, expert_params, axis_name: str,
                   k_top: int = 1, block_rows: int = 256, act=jax.nn.silu):
    """Padding-free EP-SHARDED MoE over the Pallas grouped-matmul kernel
    (r6 — the tentpole that brings the gmm path to the flagship ep
    layouts; before this, dispatch_impl="gmm" silently degraded to
    capacity queues under an ep axis).

    The obstruction the capacity path existed to solve: ``all_to_all``
    needs static shapes, but per-(source-shard, expert) token counts are
    data-dependent. Resolution:

    1. COUNT EXCHANGE — each shard routes its T·k token-choices, counts
       per global expert, and all_to_alls the [S, E/S] count matrix, so
       every shard knows exactly how many rows it will receive from each
       source for each of its local experts before touching the payload.
    2. BLOCK-QUANTUM BUFFERS — the payload a2a moves one statically
       sized segment per (source, dest) pair: seg_blocks = ceil(T·k/B) +
       E_local row-blocks (the lossless bound — all of a source's
       choices could route to one destination, plus worst-case
       per-expert round-up to the kernel's B-row quantum). Within a
       segment, each expert's rows sit at block-aligned offsets computed
       from the counts, so the RECEIVER can rebuild an exact
       block→expert steering map with pure index arithmetic — no
       capacity queues, no drops, ever.
    3. SENTINEL-SKIPPED COMPUTE — buffer occupancy is data-dependent but
       the kernel grid is static; unoccupied blocks get block_expert=-1
       and the kernel writes zeros without spending MXU work, so expert
       FLOPs scale with ROUTED tokens (+ ≤B-row round-up per
       (source, expert)), not with the worst-case buffer.
    4. FUSED COMBINE — gate weights ride the payload a2a as a [S_cap]
       f32 sidecar and are applied inside the down-projection kernel's
       epilogue (gmm row_scale), so the return-path combine is a pure
       gather+sum at the source.

    The trade receipted in docs/design.md: wire bytes are S× the active
    rows (worst-case-sized segments traverse the a2a even when lightly
    occupied) vs cf× for capacity queues — identical at the flagship
    ep=2/cf=2 point, and the ~2× PADDING FLOPS (the r4 decomposition's
    top structural term) are retired outright. Gradients: garbage rows
    carry zero cotangents by construction (their outputs are never
    gathered and their gate-weight sidecar is hard 0), and the dw kernel
    zero-initializes every expert tile, so zero-token experts get exact
    zero gradients (pinned by the ep-gmm tests)."""
    n_shards = axis_size(axis_name)
    tokens, d = x.shape
    n_experts = gate_logits.shape[-1]
    e_local = n_experts // n_shards
    B = block_rows
    tk = tokens * k_top

    with jax.named_scope("sec_router"):
        gate_probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
        top_p, top_i = jax.lax.top_k(gate_probs, k_top)  # [T, k]
        if k_top > 1:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_i.reshape(-1).astype(jnp.int32)  # [T*k], t-major
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.bincount(flat_e, length=n_experts).astype(jnp.int32)
    offsets = jnp.cumsum(counts) - counts  # unpadded sorted offsets [E]
    rank_sorted = jnp.arange(tk, dtype=jnp.int32) - offsets[flat_e[order]]
    ranks = jnp.zeros((tk,), jnp.int32).at[order].set(rank_sorted)

    # --- send layout: [dest segment | expert region | rank] -------------
    seg_blocks = -(-tk // B) + e_local  # static lossless bound (blocks)
    s_cap = seg_blocks * B              # rows per (src, dest) segment
    pad_rows = (-(-counts // B)) * B    # [E] B-aligned region per expert
    pad_r = pad_rows.reshape(n_shards, e_local)
    bounds_rows = jnp.cumsum(pad_r, axis=1)          # [S, E_l]
    off_in_seg = (bounds_rows - pad_r).reshape(-1)   # [E] flat == expert id

    send_slot = (
        (flat_e // e_local) * s_cap + off_in_seg[flat_e] + ranks
    )  # [T*k] — each choice's row in the send buffer (and, after the
    # return all_to_all, in the received-output buffer: the exchange is
    # symmetric, so the send layout IS the combine layout)

    # fill the send buffer by row GATHER (the cheap direction on TPU —
    # same rationale as _moe_single_gmm's x_seg)
    r = jnp.arange(n_shards * s_cap, dtype=jnp.int32)
    seg, u = r // s_cap, r % s_cap
    le_r = jnp.sum(u[:, None] >= bounds_rows[seg], axis=1).astype(jnp.int32)
    in_region = le_r < e_local
    e_r = seg * e_local + jnp.minimum(le_r, e_local - 1)
    rank_r = u - off_in_seg[e_r]
    valid = in_region & (rank_r < counts[e_r])
    src_choice = order[jnp.clip(offsets[e_r] + rank_r, 0, tk - 1)]
    x_send = x[jnp.where(valid, src_choice // k_top, 0)]  # [S*S_cap, d]
    s_send = jnp.where(
        valid, top_p.reshape(-1)[jnp.clip(src_choice, 0, tk - 1)], 0.0
    )  # gate-weight sidecar; hard 0 on garbage rows kills their outputs
    # AND their backward (ds flows only through the where)

    # --- exchanges ------------------------------------------------------
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name, split_axis=0,
                  concat_axis=0, tiled=False)
    counts_rcv = a2a(counts.reshape(n_shards, e_local))      # [S(src), E_l]
    x_rcv = a2a(x_send.reshape(n_shards, s_cap, d))          # [S(src), S_cap, d]
    s_rcv = a2a(s_send.reshape(n_shards, s_cap))             # [S(src), S_cap]

    # --- dest-side block→expert map from the exchanged counts -----------
    pad_blocks_rcv = -(-counts_rcv // B)                     # [S, E_l]
    bounds_blocks = jnp.cumsum(pad_blocks_rcv, axis=1)       # [S, E_l]
    b = jnp.arange(n_shards * seg_blocks, dtype=jnp.int32)
    seg_b, ub = b // seg_blocks, b % seg_blocks
    le_b = jnp.sum(ub[:, None] >= bounds_blocks[seg_b], axis=1).astype(jnp.int32)
    block_expert = jnp.where(le_b < e_local, le_b, -1).astype(jnp.int32)

    from tf_operator_tpu.ops.grouped_matmul import gmm

    interpret = jax.default_backend() != "tpu"
    run = partial(gmm, block_rows=B, interpret=interpret)
    x_flat = x_rcv.reshape(n_shards * s_cap, d)
    with jax.named_scope("sec_moe_experts"):
        zg = run(x_flat, expert_params["w_gate"].astype(x.dtype), block_expert)
        zu = run(x_flat, expert_params["w_up"].astype(x.dtype), block_expert)
        h = run(act(zg) * zu,
                expert_params["w_down"].astype(x.dtype), block_expert,
                row_scale=s_rcv.reshape(-1))

    # --- return results to source shards, combine -----------------------
    h_ret = a2a(h.reshape(n_shards, s_cap, -1)).reshape(n_shards * s_cap, -1)
    gathered = h_ret[send_slot.reshape(tokens, k_top)]  # [T, k, d] pre-weighted
    out = jnp.sum(gathered.astype(jnp.float32), axis=1)

    with jax.named_scope("sec_router"):
        stats = {
            "expert_load": counts.astype(jnp.float32) / tk,
            "mean_gate": jnp.mean(gate_probs, axis=0),
            "drop_frac": jnp.float32(0.0),
        }
    return out.astype(x.dtype), stats


def _dropped_value(x, dropped: str):
    """What capacity-dropped tokens contribute: their input unchanged
    ("passthrough" — moe_apply as a standalone transform) or nothing
    ("zero" — moe_apply as the MLP branch of a residual stream, the
    Switch-Transformer rule: an overflowed token's MLP contributes 0)."""
    if dropped == "passthrough":
        return x.astype(jnp.float32)
    if dropped == "zero":
        return jnp.zeros_like(x, jnp.float32)
    raise ValueError(f"unknown dropped mode {dropped!r}")


def _moe_single(x, gate_logits, expert_params, expert_fn, capacity: int, dropped: str,
                k_top: int = 1, dispatch_impl: str = "sort",
                expert_act: str = "silu", expert_first: int = 0,
                score: str = "softmax", bias=None, scale: float = 1.0):
    """All experts on one device: same routing math, no collectives — the
    fallback when the mesh has no ep axis (or no mesh at all).

    NOTE on drop patterns: this path runs ONE global per-expert capacity
    queue while the sharded path runs per-(data-shard x ep-shard) queues,
    so WHICH tokens overflow differs between CPU and pod runs of the same
    config — the routing math and aggregate load stats agree, but numeric
    outputs are not bitwise-comparable across mesh layouts whenever any
    tokens drop (drop_frac > 0)."""
    tokens, d = x.shape
    n_experts = gate_logits.shape[-1]
    if dispatch_impl == "gmm":
        from tf_operator_tpu.ops.grouped_matmul import gmm_block_rows

        # the gmm path runs the experts as grouped ragged matmuls over
        # the SwiGLU parameter triple directly — a custom expert_fn
        # cannot be honored here, so reject anything but that layout
        # loudly instead of silently computing different math
        if set(expert_params) != {"w_gate", "w_up", "w_down"}:
            raise ValueError(
                "dispatch_impl='gmm' computes a SwiGLU expert from "
                "{w_gate, w_up, w_down} stacked params and ignores "
                f"expert_fn; got param keys {sorted(expert_params)} — use "
                "dispatch_impl='sort' for custom expert bodies"
            )
        return _moe_single_gmm(
            x, gate_logits, expert_params, k_top,
            block_rows=gmm_block_rows(),
            act=expert_activation(expert_act), first=expert_first,
            score=score, bias=bias, scale=scale,
        )
    if score != "softmax":
        raise ValueError(
            "a sigmoid (bias-balanced) router runs on dispatch_impl='gmm' only"
        )
    if jax.tree_util.tree_leaves(expert_params)[0].shape[0] != n_experts:
        raise ValueError(
            "a share of the experts (fewer expert weights than router "
            "outputs) runs on dispatch_impl='gmm' only"
        )
    with jax.named_scope("sec_moe_dispatch"):
        if dispatch_impl == "sort":
            slot, w, keep_any, inbox, stats = _route_sparse(
                x, gate_logits, capacity, k_top, dropped)
        else:
            dispatch_w, keep_any, inbox, stats = _route(
                x, gate_logits, capacity, k_top, dropped)

    # vmap over the stacked expert dim — ONE batched-matmul program for
    # all experts. r4: the previous fori_loop ran E sequential [C,d]
    # matmul chains with a dynamic-slice parameter gather and an
    # acc.at[e].set copy per step; at moe-small shapes the identical
    # FLOPs measured 15.1 ms looped vs 8.1 ms batched (an earlier
    # installation's reading), and the batched form runs at 87% of the
    # chip's chained matmul rate.
    with jax.named_scope("sec_moe_experts"):
        outbox = jax.vmap(
            lambda w_e, t: expert_fn(w_e, t.astype(x.dtype))
        )(expert_params, inbox).astype(jnp.float32)
    with jax.named_scope("sec_moe_dispatch"):
        if dispatch_impl == "sort":
            combined = _combine_sparse(outbox, slot, w)
        else:
            combined = jnp.einsum("tec,ecd->td", dispatch_w, outbox)
        out = jnp.where(keep_any[:, None], combined, _dropped_value(x, dropped))
    return out.astype(x.dtype), stats


def _moe_local(x, gate_logits, expert_params, expert_fn, axis_name: str, capacity: int,
               dropped: str, k_top: int = 1, stat_axes: tuple = (),
               dispatch_impl: str = "sort", block_rows: int = 256,
               expert_act: str = "silu"):
    """Per-device body. x: [tokens_local, d]; gate_logits: [tokens_local, E];
    expert_params: this device's experts (leading dim E_local).
    ``stat_axes``: every mesh axis the token dim shards over (data axes +
    ep) — router stats pmean over all of them to give the global view.
    The sort/einsum impls build the same [E, C, d] inbox layout, so the
    capacity all_to_all exchange is impl-agnostic; "gmm" (r6) replaces
    the capacity queues with block-quantum buffers (_moe_local_gmm)."""
    n_shards = axis_size(axis_name)
    tokens, d = x.shape
    n_experts = gate_logits.shape[-1]
    experts_per_shard = n_experts // n_shards

    if dispatch_impl == "gmm":
        if not isinstance(expert_params, dict) or set(expert_params) != {
            "w_gate", "w_up", "w_down"
        }:
            raise ValueError(
                "dispatch_impl='gmm' computes a SwiGLU expert from "
                "{w_gate, w_up, w_down} stacked params and ignores "
                f"expert_fn; got param keys {sorted(expert_params)} — use "
                "dispatch_impl='sort' for custom expert bodies"
            )
        # the layout and the exchanges; the router's part and the kernels
        # name their own sections inside
        with jax.named_scope("sec_moe_dispatch"):
            out, stats = _moe_local_gmm(
                x, gate_logits, expert_params, axis_name, k_top, block_rows,
                act=expert_activation(expert_act),
            )
        for ax in stat_axes or (axis_name,):
            stats = jax.tree_util.tree_map(
                lambda s: jax.lax.pmean(s, ax), stats)
        return out, stats
    with jax.named_scope("sec_moe_dispatch"):
        if dispatch_impl == "sort":
            slot, w, keep_any, inbox, stats = _route_sparse(
                x, gate_logits, capacity, k_top, dropped)
        else:
            dispatch_w, keep_any, inbox, stats = _route(
                x, gate_logits, capacity, k_top, dropped)

        # all_to_all: regroup so each shard holds inboxes for ITS experts from
        # every shard: [E, C, d] -> [E_local * n_shards, C, d] where the leading
        # dim interleaves (source_shard, local_expert).
        inbox = inbox.reshape(n_shards, experts_per_shard, capacity, d)
        inbox = jax.lax.all_to_all(inbox, axis_name, split_axis=0, concat_axis=0, tiled=False)
        # Now: [n_shards(source), E_local, C, d] on each device.
        inbox = inbox.reshape(n_shards, experts_per_shard, capacity, d)

    # Run each local expert over its gathered tokens — vmapped over the
    # expert dim into one batched-matmul program (r4, same rationale as
    # _moe_single: the fori_loop form measured 1.87x slower on identical
    # FLOPs).
    def one_expert(params_e, toks):  # toks: [n_shards, C, d]
        out = expert_fn(params_e, toks.reshape(n_shards * capacity, d).astype(x.dtype))
        return out.astype(jnp.float32).reshape(n_shards, capacity, d)

    with jax.named_scope("sec_moe_experts"):
        outbox = jax.vmap(one_expert, in_axes=(0, 1), out_axes=1)(
            expert_params, inbox
        )

    with jax.named_scope("sec_moe_dispatch"):
        # Return results to source shards.
        outbox = jax.lax.all_to_all(outbox, axis_name, split_axis=0, concat_axis=0, tiled=False)
        outbox = outbox.reshape(n_experts, capacity, d)

        # Combine: weight by gate prob; dropped tokens per the dropped mode.
        if dispatch_impl == "sort":
            combined = _combine_sparse(outbox, slot, w)
        else:
            combined = jnp.einsum("tec,ecd->td", dispatch_w, outbox)
        out = jnp.where(keep_any[:, None], combined, _dropped_value(x, dropped))
    # Aggregate router stats across token shards (every shard routed its
    # own slice; the job-level view is the mean over all of them).
    for ax in stat_axes or (axis_name,):
        stats = jax.tree_util.tree_map(lambda s: jax.lax.pmean(s, ax), stats)
    return out.astype(x.dtype), stats


def moe_apply(
    x,
    gate_logits,
    expert_params,
    expert_fn: Callable,
    mesh,
    axis_name: str = "ep",
    capacity_factor: float = 2.0,
    dropped: str = "passthrough",
    batch_axes: tuple = ("dp", "fsdp"),
    k_top: int = 1,
    return_stats: bool = False,
    dispatch_impl: str = "sort",
    expert_act: str = "silu",
    expert_first: int = 0,
    score: str = "softmax",
    bias=None,
    scale: float = 1.0,
):
    """Top-k MoE layer with experts sharded over ``axis_name``
    (``k_top=1`` — Switch; ``k_top=2`` — Mixtral-style with renormalized
    gate weights; capacity scales with k_top: total slot demand is
    k_top x tokens).

    x: [tokens, d]; the token dim shards over (batch_axes… , ep) — data
    replicas keep their own token slices (each dp group runs its own
    ep-wide all_to_all; without this, every dp replica would all-gather
    and re-route the full global batch) and within a replica each ep
    shard routes its slice, the all_to_all exchanging (token-shard ×
    expert-shard) traffic so every expert processes distinct tokens from
    every source shard. expert_params: pytree with leading dim n_experts
    (sharded over ep, replicated over the batch axes).
    ``dropped`` picks what capacity-overflowed tokens yield: their input
    ("passthrough", standalone-transform default) or 0 ("zero" — required
    when the caller adds the result to a residual stream, else a dropped
    token gains its own input twice).
    ``return_stats`` also returns router observability (the seam training
    loops and the load-balance tests read): {"expert_load": [E] fraction
    of token-choices per expert, "mean_gate": [E] mean router probability,
    "drop_frac": scalar} — globally averaged over token shards.

    NOTE: drop PATTERNS (which specific tokens overflow) differ between
    the single-device path (one global queue per expert) and the sharded
    path (per-shard queues) — see _moe_single; aggregate stats agree.

    ``dispatch_impl``: "sort" (default, r3 — argsort/scatter/gather
    dispatch, O(T·d)) or "einsum" (the one-hot-matmul formulation,
    O(T²·d) — kept as the parity oracle), or "gmm" (r5/r6 — the Pallas
    grouped-matmul kernel, ops/grouped_matmul.py: no capacity queues,
    no drops, padding only to the kernel's row-block quantum; r6 runs it
    under ep sharding too via count-exchange + block-quantum all_to_all
    buffers, _moe_local_gmm — the flagship layouts no longer degrade to
    capacity queues). Same queue semantics for sort/einsum, same drop
    patterns, same stats (pinned by the impl-parity tests); the
    end-to-end win is recorded in BASELINE.md.

    ``expert_act`` ("silu" | "relu") is the gate activation the gmm
    dispatch applies (the other dispatches call ``expert_fn``, which
    carries its own). ONE CHIP'S SHARE:
    ``expert_params`` may hold fewer experts than ``gate_logits`` has
    outputs — experts ``expert_first ..`` of a layer whose other experts
    live on absent chips. The router still scores all of them and the
    result is the held experts' partial sum (_moe_single_gmm); gmm
    dispatch, no ep axis. ``score`` / ``bias`` / ``scale``: the sigmoid
    bias-balanced router (_moe_single_gmm), under the same two conditions."""
    from tf_operator_tpu.parallel.collectives import shard_map

    if dispatch_impl not in ("sort", "einsum", "gmm"):
        raise ValueError(f"unknown dispatch_impl {dispatch_impl!r}")
    n_experts = gate_logits.shape[-1]
    tokens = x.shape[0]
    if mesh is None or axis_name not in getattr(mesh, "axis_names", ()) or (
        mesh.shape[axis_name] == 1
    ):
        capacity = expert_capacity(capacity_factor, k_top, tokens, n_experts)
        out, stats = _moe_single(
            x, gate_logits, expert_params, expert_fn, capacity, dropped, k_top,
            dispatch_impl, expert_act, expert_first, score, bias, scale,
        )
        return (out, stats) if return_stats else out
    if score != "softmax":
        raise ValueError(
            "a sigmoid (bias-balanced) router runs on one chip with no "
            f"exchange; the mesh has {axis_name}={mesh.shape[axis_name]}"
        )
    if jax.tree_util.tree_leaves(expert_params)[0].shape[0] != n_experts:
        raise ValueError(
            "a share of the experts runs on one chip with no exchange; "
            f"the mesh has {axis_name}={mesh.shape[axis_name]}"
        )
    if dispatch_impl == "gmm" and (
        not isinstance(expert_params, dict)
        or set(expert_params) != {"w_gate", "w_up", "w_down"}
    ):
        raise ValueError(
            "dispatch_impl='gmm' computes a SwiGLU expert from "
            "{w_gate, w_up, w_down} stacked params and ignores expert_fn; "
            f"got param keys {sorted(expert_params)} — use "
            "dispatch_impl='sort' for custom expert bodies"
        )
    ep = mesh.shape[axis_name]
    data_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    n_data = math.prod(mesh.shape[a] for a in data_axes) if data_axes else 1
    if n_experts % ep:
        raise ValueError(f"{n_experts} experts not divisible by ep={ep}")
    if tokens % (ep * n_data):
        raise ValueError(
            f"{tokens} tokens not divisible by ep={ep} x data={n_data}"
        )
    local_tokens = tokens // (ep * n_data)
    capacity = expert_capacity(capacity_factor, k_top, local_tokens, n_experts)

    token_spec = P((*data_axes, axis_name))
    param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), expert_params)
    stat_specs = {"expert_load": P(), "mean_gate": P(), "drop_frac": P()}
    from tf_operator_tpu.ops.grouped_matmul import gmm_block_rows

    fn = shard_map(
        partial(_moe_local, expert_fn=expert_fn, axis_name=axis_name, capacity=capacity,
                dropped=dropped, k_top=k_top, stat_axes=(*data_axes, axis_name),
                dispatch_impl=dispatch_impl,
                block_rows=gmm_block_rows(),
                expert_act=expert_act),
        mesh=mesh,
        in_specs=(token_spec, token_spec, param_specs),
        out_specs=(token_spec, stat_specs),
    )
    out, stats = fn(x, gate_logits, expert_params)
    return (out, stats) if return_stats else out
