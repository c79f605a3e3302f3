"""The plain reference for SmallThinker-21BA3B-Instruct on one chip's share
of a four-chip expert group: what the cell's ``correct`` is decided against.

The layer equations of ISSUE 26 / PERF.md §4 in straightforward
``jax.numpy``: float32 with every product at ``Precision.HIGHEST``, no
kernel, no sort, no cache, nothing of the program (it imports the dense
reference's helpers and nothing else). Layer ``l`` is of kind
``pattern[l % len(pattern)]`` = (window, rotary): a global layer without
positional encoding or a window layer with rotary embedding. The router
reads the attention-side norm's output (it sits BEFORE attention), takes
its top ``top_k`` of ``n_experts`` scores by repeated argmax and weighs
them by the softmax over the chosen; the experts are ReGLU; of each layer
only experts ``first .. first + held - 1`` exist here, every one of them
computed densely over all tokens and multiplied by its gate (zero where it
was not chosen), so the result is the held experts' partial sum.

Departures from the published model, each also in the config file's
``departures``: the output head is the embedding transposed (tied); the
vocabulary is a slice of the published one; the absent experts add nothing
(that IS the share); the router's load-balance and z losses are the
program's defaults, SmallThinker publishes no training recipe.

``sizes``: vocab, d_model, n_layers, n_heads, n_kv_heads, head_dim, d_ff,
rope_theta, norm_eps, n_experts, top_k, held, first, pattern ((window,
rotary), ...), aux_weight, zloss_weight. ``precision`` selects the
controls as in ``benchmarks/reference.py``; there the router's product is
computed in bfloat16 (one step under the float32 the config states for
it) and every other product in the named precision.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import (
    _dot,
    adamw_update,
    clip_by_global_norm,
    leaf_names,
    leaf_norms,
    rms_norm,
    rope,
)


def freeze(sizes) -> tuple:
    """``sizes`` as a hashable static argument."""
    return tuple(sorted(
        (k, tuple(tuple(p) for p in v) if k == "pattern" else v)
        for k, v in sizes.items()))


def _leaf_shapes(sizes) -> Dict[str, tuple]:
    """The stacked matrices with their fan-in, in the order the program's
    ``init_transformer`` draws their keys (0..7)."""
    d, f, L = sizes["d_model"], sizes["d_ff"], sizes["n_layers"]
    hd, E = sizes["head_dim"], sizes["held"]
    q, kv = sizes["n_heads"] * hd, sizes["n_kv_heads"] * hd
    return {
        "wq": ((L, d, q), d), "wk": ((L, d, kv), d), "wv": ((L, d, kv), d),
        "wo": ((L, q, d), q),
        "w_gate": ((L, E, d, f), d), "w_up": ((L, E, d, f), d),
        "w_down": ((L, E, f, d), f),
        "w_router": ((L, d, sizes["n_experts"]), d),
    }


def init_weights(seed: int, sizes, sharding=None) -> Dict[str, Any]:
    """Float32 weights from ``seed``: the seeded normal variates the cell
    is initialised with (one key split into embedding and layers, the
    layers' key into eight, one draw a stacked matrix scaled by
    fan_in**-0.5, embedding normal * 0.02, norm gains 1)."""
    place = sharding or (lambda shape: None)
    k_embed, k_layers = jax.random.split(jax.random.PRNGKey(seed))
    ks = jax.random.split(k_layers, 8)
    d, L = sizes["d_model"], sizes["n_layers"]

    def normal(key, shape, scale):
        return jax.jit(
            lambda k: jax.random.normal(k, shape, jnp.float32) * scale,
            out_shardings=place(shape),
        )(key)

    layers = {
        name: normal(ks[i], shape, fan_in ** -0.5)
        for i, (name, (shape, fan_in)) in enumerate(_leaf_shapes(sizes).items())
    }
    ones = lambda shape: jax.device_put(  # noqa: E731
        jnp.ones(shape, jnp.float32), place(shape))
    layers["attn_norm"] = ones((L, d))
    layers["mlp_norm"] = ones((L, d))
    return {
        "embed": normal(k_embed, (sizes["vocab"], d), 0.02),
        "final_norm": ones((d,)),
        "layers": layers,
    }


# ---- the block --------------------------------------------------------------


def windowed_attention(q, k, v, window: int, precision: str, q_block: int):
    """q, k, v [t, h, hd] (kv heads already repeated) -> [t, h, hd]: key j is
    visible to query i iff j <= i and, with ``window`` > 0, i - j < window.
    Query rows in blocks of ``q_block`` against every key, masked, one
    block after the other (``lax.map``: the [h, q_block, t] scores of one
    block are all that lives at a time, forward and backward)."""
    t, h, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    cols = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(args):
        qb, start = args
        s = _dot("qhd,khd->hqk", qb, k, precision).astype(jnp.float32) * scale
        rows = (start + jnp.arange(qb.shape[0]))[:, None]
        mask = cols <= rows
        if window:
            mask = mask & (rows - cols < window)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return _dot("hqk,khd->qhd", p, v, precision)

    nb = t // q_block if t % q_block == 0 and t > q_block else 1
    size = t // nb
    out = jax.lax.map(block, (q.reshape(nb, size, h, hd), jnp.arange(nb) * size))
    return out.reshape(t, h, hd)


def route(r, top_k: int):
    """Router scores r [t, E] float32 -> (gates [t, E]: the softmax over each
    row's ``top_k`` largest scores, zero elsewhere; chosen [t, E] 0/1). The
    top-k is ``top_k`` rounds of argmax, the lowest index on a tie."""
    n = r.shape[-1]
    left, chosen = r, jnp.zeros(r.shape, bool)
    for _ in range(top_k):
        pick = jax.nn.one_hot(jnp.argmax(left, axis=-1), n, dtype=bool)
        chosen = chosen | pick
        left = jnp.where(pick, -jnp.inf, left)
    gates = jax.nn.softmax(jnp.where(chosen, r, -jnp.inf), axis=-1)
    return gates, chosen


def expert_mix(h, gates, lw, precision: str = "float32"):
    """Σ_e gates[:, e] · Wdown_e·(relu(Wgate_e·h) ⊙ Wup_e·h) over the experts
    ``lw`` holds (w_gate / w_up [E, d, f], w_down [E, f, d]); ``gates``
    [t, E] are their columns of the router's gates, zero where an expert was
    not chosen. Every expert is computed densely over all rows, one after
    the other; the running sum is not an input of the rematerialised term,
    so the backward pass keeps no copy of it per expert."""
    @jax.checkpoint
    def term(h, ew):
        w_gate, w_up, w_down, gate = ew
        z = jax.nn.relu(_dot("td,df->tf", h, w_gate, precision)) \
            * _dot("td,df->tf", h, w_up, precision)
        out = _dot("tf,fd->td", z, w_down, precision)
        return gate[:, None].astype(out.dtype) * out

    out, _ = jax.lax.scan(
        lambda acc, ew: (acc + term(h, ew), None), jnp.zeros_like(h),
        (lw["w_gate"], lw["w_up"], lw["w_down"], jnp.swapaxes(gates, 0, 1)))
    return out


def _layer(x, lw, kind, positions, sizes, precision: str, q_block: int):
    """One layer on [t, d]; returns (x2, chosen [t, E], softmax over all E
    scores [t, E], logsumexp of the scores [t])."""
    window, rotary = kind
    t = x.shape[0]
    nh, nkv, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    eps = sizes["norm_eps"]
    u = rms_norm(x, lw["attn_norm"], eps)
    q = _dot("td,dn->tn", u, lw["wq"], precision).reshape(t, nh, hd)
    k = _dot("td,dn->tn", u, lw["wk"], precision).reshape(t, nkv, hd)
    v = _dot("td,dn->tn", u, lw["wv"], precision).reshape(t, nkv, hd)
    if rotary:
        q = rope(q, positions, sizes["rope_theta"])
        k = rope(k, positions, sizes["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)  # query head i reads kv head i // g
    v = jnp.repeat(v, nh // nkv, axis=1)
    a = windowed_attention(q, k, v, window, precision, q_block).reshape(t, nh * hd)
    x1 = x + _dot("tn,nd->td", a, lw["wo"], precision)

    # the router, placed before attention: it reads u. Stated float32; a
    # control computes it one step lower, in bfloat16.
    r = _dot("td,de->te", u, lw["w_router"],
             "float32" if precision == "float32" else "bfloat16").astype(jnp.float32)
    gates, chosen = route(r, sizes["top_k"])

    h = rms_norm(x1, lw["mlp_norm"], eps)
    first = sizes["first"]
    moe = expert_mix(h, gates[:, first:first + sizes["held"]], lw, precision)
    return (x1 + moe, chosen, jax.nn.softmax(r, axis=-1),
            jax.scipy.special.logsumexp(r, axis=-1))


def split_layers(w, sizes):
    """``w`` with its stacked ``layers`` cut into one dict a layer (as it
    is if that was done before). The gradient is taken with respect to the
    cut form: differentiating through ``stacked[l]`` would build every
    layer's gradient as a whole zero-padded stack and add the stacks up."""
    if isinstance(w["layers"], tuple):
        return w
    return dict(w, layers=tuple(
        jax.tree_util.tree_map(lambda a: a[l], w["layers"])
        for l in range(sizes["n_layers"])))


def hidden_states(w, tokens, sizes, precision="float32", q_block=256):
    """tokens [t] -> (final-norm hidden states [t, d], per-layer routing
    sums: choices per expert [L, E], summed router probability [L, E],
    summed squared logsumexp [L])."""
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    x = w["embed"][tokens].astype(dt)
    positions = jnp.arange(tokens.shape[0])
    pattern = sizes["pattern"]
    counts, probs, lse2 = [], [], []
    for l, lw in enumerate(split_layers(w, sizes)["layers"]):
        kind = pattern[l % len(pattern)]
        body = jax.checkpoint(
            lambda x, lw, kind=kind: _layer(
                x, lw, kind, positions, sizes, precision, q_block))
        x, chosen, p, lse = body(x, lw)
        counts.append(jnp.sum(chosen, axis=0).astype(jnp.float32))
        probs.append(jnp.sum(p, axis=0))
        lse2.append(jnp.sum(jnp.square(lse)))
    h = rms_norm(x, w["final_norm"], sizes["norm_eps"])
    return h, (jnp.stack(counts), jnp.stack(probs), jnp.stack(lse2))


def _ce_sum(h, embed, targets, precision: str, block: int):
    """Sum over rows of -log softmax(h·embedᵀ)[target], rows in blocks of
    ``block``, one block after the other (the last padded with rows of
    weight 0)."""
    n = h.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, block, h.shape[1])
    tp = jnp.pad(targets, (0, pad)).reshape(nb, block)
    live = (jnp.arange(nb * block) < n).reshape(nb, block)

    @jax.checkpoint
    def piece(hb, tb, wb):
        lg = _dot("td,vd->tv", hb, embed, precision).astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(wb, picked, 0.0))

    total, _ = jax.lax.scan(
        lambda acc, x: (acc + piece(*x), None), jnp.zeros((), jnp.float32),
        (hp, tp, live))
    return total


def row_loss(w, tokens, load, n_tokens, n_targets, sizes,
             precision="float32", q_block=256, ce_block=1024):
    """One row's part of the batch's loss: its cross-entropy sum over the
    batch's target count, plus its part of the router losses — per layer
    E · Σ_e load[l, e] · (Σ_rows' tokens p_e) / n_tokens (``load``: the
    batch's share of choices per expert, a constant) and Σ lse² / n_tokens,
    both averaged over layers — so that the rows' parts add up to the
    program's loss. Returns (loss part, routing counts [L, E])."""
    h, (counts, probs, lse2) = hidden_states(w, tokens, sizes, precision, q_block)
    ce = _ce_sum(h[:-1], w["embed"], tokens[1:], precision, ce_block)
    lb = sizes["n_experts"] * jnp.mean(jnp.sum(load * probs, axis=-1)) / n_tokens
    z = jnp.mean(lse2) / n_tokens
    return (ce / n_targets + sizes["aux_weight"] * lb
            + sizes["zloss_weight"] * z), counts


# ---- training: loss, gradient, clipped AdamW --------------------------------


@partial(jax.jit, static_argnames=("sizes_t", "precision"))
def _row_counts(w, row, sizes_t, precision):
    sizes = dict(sizes_t)
    return hidden_states(w, row, sizes, precision)[1][0]


@partial(jax.jit, static_argnames=("sizes_t", "precision", "n_tokens", "n_targets"),
         donate_argnums=(1,))
def _accumulate(w, acc, row, load, sizes_t, precision, n_tokens, n_targets):
    sizes = dict(sizes_t)
    (loss, _), g = jax.value_and_grad(row_loss, has_aux=True)(
        split_layers(w, sizes), row, load, n_tokens, n_targets, sizes, precision)
    layers = acc["layers"]  # each layer's gradient added where it lies
    for l, g_l in enumerate(g.pop("layers")):
        layers = jax.tree_util.tree_map(lambda a, b: a.at[l].add(b), layers, g_l)
    rest = {k: acc[k] + v for k, v in g.items()}
    return loss, dict(rest, layers=layers)


def loss_and_grad(w, batch: np.ndarray, sizes, precision="float32"):
    """The program's loss over ``batch`` [b, t] — mean token cross-entropy
    plus the weighted router losses over all b·t tokens — and its gradient.
    Two passes, one row at a time: the first counts each layer's choices
    per expert over the whole batch (the load-balance loss multiplies that
    constant share by the differentiable mean probability), the second
    adds each row's part into a float32 accumulator. Also returns the
    held experts' choice counts [L, held]."""
    st = freeze(sizes)
    rows = [jnp.asarray(r, jnp.int32) for r in batch]
    n_tokens = batch.shape[0] * batch.shape[1]
    n_targets = batch.shape[0] * (batch.shape[1] - 1)
    counts = sum(_row_counts(w, r, st, precision) for r in rows)
    load = counts / float(n_tokens * sizes["top_k"])
    acc = jax.tree_util.tree_map(jnp.zeros_like, w)
    total = 0.0
    for r in rows:
        loss, acc = _accumulate(w, acc, r, load, st, precision, n_tokens, n_targets)
        total += float(loss)
    first = sizes["first"]
    return total, acc, np.asarray(counts)[:, first:first + sizes["held"]]


def train_reference(seed: int, sizes, opt, batches: Sequence[np.ndarray],
                    precision="float32", sharding=None) -> Dict[str, Any]:
    """Follow ``len(batches)`` optimizer steps from the seeded weights, as
    ``benchmarks.reference.train_reference`` does for the dense model:
    each step's loss, the per-leaf norms of the first gradient as the
    optimizer gets it (after clipping) and of the parameters' change over
    all the steps; ``grad1``, that first gradient itself, on the host by
    leaf; and ``routed_here``, each step's count of choices routed to held
    experts, summed over layers."""
    w = init_weights(seed, sizes, sharding)
    losses, routed, history, change, grad1, first = [], [], [], None, None, None
    for n, batch in enumerate(batches, 1):
        loss, grad, held_counts = loss_and_grad(w, np.asarray(batch), sizes, precision)
        losses.append(loss)
        routed.append(float(held_counts.sum()))
        grad = clip_by_global_norm(grad, opt["grad_clip"])
        if grad1 is None:
            grad1 = leaf_norms(grad)
            first = dict(zip(leaf_names(grad), jax.device_get(
                jax.tree_util.tree_leaves(grad))))
        delta = adamw_update(history + [grad], w, opt)
        if change is not None:
            delta_sum = jax.tree_util.tree_map(
                lambda c, d: jax.device_put(c, d.sharding) + d, change, delta)
        else:
            delta_sum = delta
        if n < len(batches):
            w = jax.tree_util.tree_map(jnp.add, w, delta)
            history.append(jax.device_get(grad))
            change = jax.device_get(delta_sum)
        del grad, delta
    return {"losses": losses, "grad1_norms": grad1, "grad1": first,
            "change_norms": leaf_norms(delta_sum), "routed_here": routed}
