"""The plain reference for JoyAI-LLM-Flash on one chip's share of a
sixteen-chip expert group: what the cell's ``correct`` is decided against.

The layer equations of ISSUE 30 / PERF.md §4 in straightforward
``jax.numpy``: float32 with every product at ``Precision.HIGHEST``, no
kernel, no sort, no cache, nothing of the program (it imports the dense
reference's helpers and nothing else).

Every layer attends through two low-rank projections (latent attention):
``c_q = RMSNorm(u·W_qa)``, ``q = c_q·W_qb`` -> [heads, nope | rope];
``[c_kv | k_r] = u·W_kva``, ``c_kv = RMSNorm(c_kv)``, ``[k_n | v] =
c_kv·W_kvb`` -> [heads, nope | v]; rotary embedding over ADJACENT pairs on
the ``rope``-wide parts only, ``k_r`` one vector a token for all heads;
scores ``(q_n·k_n + q_r·k_r) / sqrt(nope + rope)``; the [heads · v] result
through ``W_o``. The first ``n_dense`` layers have a dense SwiGLU MLP; the
others a router ``s = sigmoid(h·W_r)`` in float32 whose top ``top_k`` of
``n_experts`` is taken over ``s + b`` (``b``: the balancing bias, state)
by repeated argmax, weighs the chosen by ``s`` (never ``s + b``) over
their sum times ``scale``, over SwiGLU experts of which only ``first ..
first + held - 1`` exist here (each computed densely over all tokens and
multiplied by its gate, zero where it was not chosen), beside a shared
expert that every token passes. The head is a matrix of its own.

The multi-token-prediction module (DeepSeek-V3's form, the config says only
its depth): ``x_i = [RMSNorm_e(Emb(t_{i+1})) | RMSNorm_h(h_i)] · W_eh`` over
the main model's final-normed states ``h``, one whole expert layer with a
bias of its own, a final norm of its own, the shared head, cross-entropy
against ``t_{i+2}``; ``loss = CE_main + mtp_weight · CE_mtp``. After a step
``b <- b + rate · sign(mean load - load_e)`` in every expert layer, loads
counted over the step's tokens and all ``n_experts`` outputs.

Departures from the published model, each also in the config file's
``departures``: the vocabulary is a slice of the published one; the absent
experts add nothing (that IS the share); the module runs over all T
positions of a row, the last two fed the row's first tokens (a roll) —
they are left out of the loss and reach no other position under causal
attention, but their 16 choices a row are counted in the loads.

``sizes``: vocab, d_model, n_layers, n_dense, n_heads, q_rank, kv_rank,
nope, rope, v_dim, d_ff (an expert's width), d_ff_dense, rope_theta,
norm_eps, n_experts, top_k, held, first, n_shared, scale, bias_rate,
mtp_weight. ``precision`` selects the controls as in
``benchmarks/reference.py``; there the router's product is computed in
bfloat16 (one step under the float32 the config states for it) and every
other product in the named precision.
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
)


def freeze(sizes) -> tuple:
    """``sizes`` as a hashable static argument."""
    return tuple(sorted(sizes.items()))


# ---- weights ---------------------------------------------------------------


def _init_layers(key, sizes, L: int, dense: bool, normal, ones) -> Dict[str, Any]:
    """``L`` stacked layers: the draw the cell is initialised with — keys
    3..7 of ``key``'s split of eight for W_o, the MLP / experts and the
    router, keys 0..6 of ``fold_in(key, 8)``'s for the four low-rank
    matrices and the shared expert, each normal * fan_in**-0.5, gains 1."""
    d, nh = sizes["d_model"], sizes["n_heads"]
    qr, kvr = sizes["q_rank"], sizes["kv_rank"]
    nope, rope, dv = sizes["nope"], sizes["rope"], sizes["v_dim"]
    ks = jax.random.split(key, 8)
    kx = jax.random.split(jax.random.fold_in(key, 8), 8)

    def mat(k, fan_in, *shape):
        return normal(k, (L,) + shape, fan_in ** -0.5)

    out = {
        "attn_norm": ones((L, d)), "mlp_norm": ones((L, d)),
        "q_norm": ones((L, qr)), "kv_norm": ones((L, kvr)),
        "wq_a": mat(kx[0], d, d, qr),
        "wq_b": mat(kx[1], qr, qr, nh * (nope + rope)),
        "wkv_a": mat(kx[2], d, d, kvr + rope),
        "wkv_b": mat(kx[3], kvr, kvr, nh * (nope + dv)),
        "wo": mat(ks[3], nh * dv, nh * dv, d),
    }
    if dense:
        f = sizes["d_ff_dense"]
        out.update(w_gate=mat(ks[4], d, d, f), w_up=mat(ks[5], d, d, f),
                   w_down=mat(ks[6], f, f, d))
        return out
    f, E, fs = sizes["d_ff"], sizes["held"], sizes["n_shared"] * sizes["d_ff"]
    out.update(
        w_router=mat(ks[7], d, d, sizes["n_experts"]),
        w_gate=mat(ks[4], d, E, d, f), w_up=mat(ks[5], d, E, d, f),
        w_down=mat(ks[6], f, E, f, d),
        ws_gate=mat(kx[4], d, d, fs), ws_up=mat(kx[5], d, d, fs),
        ws_down=mat(kx[6], fs, fs, d))
    return out


@partial(jax.jit, static_argnames=("shape", "scale", "where"))
def _normal(key, shape, scale, where):
    """One seeded draw, born where it lives (one compile a distinct shape)."""
    out = jax.random.normal(key, shape, jnp.float32) * scale
    return out if where is None else jax.lax.with_sharding_constraint(out, where)


def init_weights(seed: int, sizes, sharding=None) -> Dict[str, Any]:
    """Float32 weights from ``seed``: the key split into (embedding,
    expert layers); the dense lead, the module and the head from
    ``fold_in(key, 1 / 2 / 3)``; embedding and head normal * 0.02."""
    place = sharding or (lambda shape: None)
    key = jax.random.PRNGKey(seed)
    k_embed, k_layers = jax.random.split(key)
    d = sizes["d_model"]

    def normal(k, shape, scale):
        return _normal(k, shape, scale, place(shape))

    def ones(shape):
        return jax.device_put(jnp.ones(shape, jnp.float32), place(shape))

    k_mtp = jax.random.fold_in(key, 2)
    n_dense = sizes["n_dense"]
    return {
        "embed": normal(k_embed, (sizes["vocab"], d), 0.02),
        "final_norm": ones((d,)),
        "head": normal(jax.random.fold_in(key, 3), (sizes["vocab"], d), 0.02),
        "layers": _init_layers(k_layers, sizes, sizes["n_layers"] - n_dense,
                               False, normal, ones),
        "lead": _init_layers(jax.random.fold_in(key, 1), sizes, n_dense, True,
                             normal, ones),
        "mtp": {
            "norm_e": ones((d,)), "norm_h": ones((d,)),
            "w_eh": normal(jax.random.fold_in(k_mtp, 0), (2 * d, d),
                           (2 * d) ** -0.5),
            "layer": _init_layers(jax.random.fold_in(k_mtp, 1), sizes, 1, False,
                                  normal, ones),
            "final_norm": ones((d,)),
        },
    }


def zero_bias(sizes) -> Dict[str, Any]:
    E = sizes["n_experts"]
    return {"layers": jnp.zeros((sizes["n_layers"] - sizes["n_dense"], E), jnp.float32),
            "mtp": jnp.zeros((1, E), jnp.float32)}


# ---- the block --------------------------------------------------------------


def rope_pairs(x, positions, theta):
    """x [t, heads, width]: the ADJACENT pairs (2i, 2i + 1) rotate by
    position * theta**(-i / (width / 2)). The rotated pairs leave as (all
    first members | all second members): the same order on q and k, which
    their product does not see."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, precision: str, q_block: int):
    """q, k [t, h, dqk], v [t, h, dv] -> [t, h, dv]; scores over
    sqrt(dqk). Query rows in blocks of ``q_block`` against every key,
    masked, one block after the other (``lax.map``: the [h, q_block, t]
    scores of one block are all that lives at a time)."""
    t, h, dqk = q.shape
    scale = 1.0 / math.sqrt(dqk)
    cols = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(args):
        qb, start = args
        s = _dot("qhd,khd->hqk", qb, k, precision).astype(jnp.float32) * scale
        rows = (start + jnp.arange(qb.shape[0]))[:, None]
        s = jnp.where((cols <= rows)[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return _dot("hqk,khd->qhd", p, v, precision)

    nb = t // q_block if t % q_block == 0 and t > q_block else 1
    size = t // nb
    out = jax.lax.map(block, (q.reshape(nb, size, h, dqk), jnp.arange(nb) * size))
    return out.reshape(t, h, v.shape[-1])


def latent_attention(u, lw, positions, sizes, precision: str, q_block: int):
    """u [t, d] (the normed layer input) -> [t, heads · v_dim]."""
    t = u.shape[0]
    nh, kvr = sizes["n_heads"], sizes["kv_rank"]
    nope, rope, dv = sizes["nope"], sizes["rope"], sizes["v_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    c_q = rms_norm(_dot("td,dr->tr", u, lw["wq_a"], precision), lw["q_norm"], eps)
    q = _dot("tr,rn->tn", c_q, lw["wq_b"], precision).reshape(t, nh, nope + rope)
    ckv = _dot("td,dr->tr", u, lw["wkv_a"], precision)
    c_kv = rms_norm(ckv[:, :kvr], lw["kv_norm"], eps)
    k_r = rope_pairs(ckv[:, None, kvr:], positions, theta)  # [t, 1, rope]
    kv = _dot("tr,rn->tn", c_kv, lw["wkv_b"], precision).reshape(t, nh, nope + dv)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], positions, theta)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (t, nh, rope))], -1)
    a = causal_attention(q, k, kv[..., nope:], precision, q_block)
    return a.reshape(t, nh * dv)


def swiglu(h, w_gate, w_up, w_down, precision: str):
    z = jax.nn.silu(_dot("td,df->tf", h, w_gate, precision)) \
        * _dot("td,df->tf", h, w_up, precision)
    return _dot("tf,fd->td", z, w_down, precision)


def route(s, bias, top_k: int, scale: float):
    """Sigmoid scores s [t, E] float32 -> (gates [t, E]: each row's chosen
    scores over their sum times ``scale``, zero elsewhere; chosen [t, E]).
    The choice is ``top_k`` rounds of argmax over s + bias, the lowest
    index on a tie; the weights never see the bias."""
    n = s.shape[-1]
    left = s + bias
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(top_k):
        pick = jax.nn.one_hot(jnp.argmax(left, axis=-1), n, dtype=bool)
        chosen = chosen | pick
        left = jnp.where(pick, -jnp.inf, left)
    picked = jnp.where(chosen, s, 0.0)
    gates = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * scale
    return gates, chosen


def expert_mix(h, gates, lw, precision: str):
    """Σ_e gates[:, e] · SwiGLU_e(h) over the experts ``lw`` holds, one
    after the other; the running sum is not an input of the rematerialised
    term, so the backward pass keeps no copy of it per expert."""
    @jax.checkpoint
    def term(h, ew):
        w_gate, w_up, w_down, gate = ew
        out = swiglu(h, w_gate, w_up, w_down, precision)
        return gate[:, None].astype(out.dtype) * out

    out, _ = jax.lax.scan(
        lambda acc, ew: (acc + term(h, ew), None), jnp.zeros_like(h),
        (lw["w_gate"], lw["w_up"], lw["w_down"], jnp.swapaxes(gates, 0, 1)))
    return out


def _layer(x, lw, bias, positions, sizes, precision: str, q_block: int):
    """One layer on [t, d]; ``bias`` None: a dense layer. Returns (x2,
    chosen [t, E] or None)."""
    eps = sizes["norm_eps"]
    u = rms_norm(x, lw["attn_norm"], eps)
    a = latent_attention(u, lw, positions, sizes, precision, q_block)
    x1 = x + _dot("tn,nd->td", a, lw["wo"], precision)
    h = rms_norm(x1, lw["mlp_norm"], eps)
    if bias is None:
        return x1 + swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], precision), None
    # the router: stated float32; a control computes it one step lower
    r = _dot("td,de->te", h, lw["w_router"],
             "float32" if precision == "float32" else "bfloat16").astype(jnp.float32)
    gates, chosen = route(jax.nn.sigmoid(r), bias, sizes["top_k"], sizes["scale"])
    first = sizes["first"]
    moe = expert_mix(h, gates[:, first:first + sizes["held"]], lw, precision)
    shared = swiglu(h, lw["ws_gate"], lw["ws_up"], lw["ws_down"], precision)
    return x1 + moe + shared, chosen


def split_layers(w, sizes):
    """``w`` with its stacked expert ``layers`` cut into one dict a layer
    (as it is if that was done before): differentiating through
    ``stacked[l]`` would build every layer's gradient as a whole
    zero-padded stack and add the stacks up. ``lead`` and the module's
    layer are stacks of one and stay."""
    if isinstance(w["layers"], tuple):
        return w
    n = sizes["n_layers"] - sizes["n_dense"]
    return dict(w, layers=tuple(
        jax.tree_util.tree_map(lambda a: a[l], w["layers"]) for l in range(n)))


def _ce_sum(h, head, targets, live, precision: str, block: int):
    """Sum over the rows where ``live`` of -log softmax(h·headᵀ)[target],
    rows in blocks of ``block``, one block after the other."""
    n = h.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, block, h.shape[1])
    tp = jnp.pad(targets, (0, pad)).reshape(nb, block)
    lp = jnp.pad(live, (0, pad)).reshape(nb, block)

    @jax.checkpoint
    def piece(hb, tb, wb):
        lg = _dot("td,vd->tv", hb, head, precision).astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(wb, picked, 0.0))

    total, _ = jax.lax.scan(
        lambda acc, x: (acc + piece(*x), None), jnp.zeros((), jnp.float32),
        (hp, tp, lp))
    return total


def row_losses(w, tokens, bias, sizes, precision="float32", q_block=64,
               ce_block=1024):
    """One row [t]: (Σ main cross-entropy over its t - 1 targets, Σ module
    cross-entropy over its t - 2, choices per router output [expert
    layers + 1, E], the module's last)."""
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    t = tokens.shape[0]
    positions = jnp.arange(t)
    w = split_layers(w, sizes)
    eps = sizes["norm_eps"]

    def run(x, lw, b):
        body = jax.checkpoint(
            lambda x, lw: _layer(x, lw, b, positions, sizes, precision, q_block))
        return body(x, lw)

    x = w["embed"][tokens].astype(dt)
    for l in range(sizes["n_dense"]):
        x, _ = run(x, jax.tree_util.tree_map(lambda a: a[l], w["lead"]), None)
    counts = []
    for l, lw in enumerate(w["layers"]):
        x, chosen = run(x, lw, bias["layers"][l])
        counts.append(jnp.sum(chosen, axis=0))
    h = rms_norm(x, w["final_norm"], eps)
    live = jnp.ones((t,), bool)
    ce_main = _ce_sum(h[:-1], w["head"], tokens[1:], live[1:], precision, ce_block)

    m = w["mtp"]
    e_next = w["embed"][jnp.roll(tokens, -1)].astype(dt)
    x = _dot("tc,cd->td", jnp.concatenate(
        [rms_norm(e_next, m["norm_e"], eps), rms_norm(h, m["norm_h"], eps)], -1),
        m["w_eh"], precision)
    x, chosen = run(x, jax.tree_util.tree_map(lambda a: a[0], m["layer"]),
                    bias["mtp"][0])
    counts.append(jnp.sum(chosen, axis=0))
    ce_mtp = _ce_sum(rms_norm(x, m["final_norm"], eps), w["head"],
                     jnp.roll(tokens, -2), positions < t - 2, precision, ce_block)
    return ce_main, ce_mtp, jnp.stack(counts).astype(jnp.int32)


# ---- training: loss, gradient, clipped AdamW, the bias ---------------------


@partial(jax.jit, static_argnames=("sizes_t", "precision", "n_main", "n_mtp"))
def _row_grad(w, row, bias, sizes_t, precision, n_main, n_mtp):
    """One row's part of the batch's loss and of its gradient (the expert
    layers' as one dict a layer: ``split_layers``)."""
    sizes = dict(sizes_t)

    def part(w):
        ce_main, ce_mtp, counts = row_losses(w, row, bias, sizes, precision)
        loss = ce_main / n_main + sizes["mtp_weight"] * ce_mtp / n_mtp
        return loss, (ce_main / n_main, ce_mtp / n_mtp, counts)

    (loss, aux), g = jax.value_and_grad(part, has_aux=True)(split_layers(w, sizes))
    return (loss,) + aux, g


def loss_and_grad(w, bias, batch: np.ndarray, sizes, precision="float32"):
    """The program's loss over ``batch`` [b, t] — mean main cross-entropy
    plus ``mtp_weight`` times the module's mean — and its gradient, one row
    at a time. The rows' gradients are added up on the HOST (float32) and
    the sum goes back to where its weight lives: beside the weights the
    device then holds one row's gradient and its temporaries, no
    accumulator (a row of 8,192 tokens leaves no room for one). Returns
    (loss, main part, module part, gradient, choices per router output
    [expert layers + 1, E] over the whole batch)."""
    st = freeze(sizes)
    b, t = batch.shape
    acc, total, main, mtp, counts = None, 0.0, 0.0, 0.0, 0
    for row in batch:
        (loss, ce_main, ce_mtp, c), g = _row_grad(
            w, jnp.asarray(row, jnp.int32), bias, st, precision,
            b * (t - 1), b * (t - 2))
        total, main, mtp = total + float(loss), main + float(ce_main), mtp + float(ce_mtp)
        counts = counts + np.asarray(c, np.int64)
        g = jax.device_get(g)
        g["layers"] = jax.tree_util.tree_map(lambda *a: np.stack(a), *g["layers"])
        acc = g if acc is None else jax.tree_util.tree_map(np.add, acc, g)
        del g
    grad = jax.tree_util.tree_map(lambda a, p: jax.device_put(a, p.sharding), acc, w)
    return total, main, mtp, grad, counts


def bias_update(bias, counts: np.ndarray, sizes) -> Dict[str, Any]:
    """b + rate · sign(mean load − load_e), in whole numbers: a layer's
    choices over E against E times an expert's own."""
    step = sizes["bias_rate"] * np.sign(
        counts.sum(axis=-1, keepdims=True) - counts * sizes["n_experts"]
    ).astype(np.float32)
    n = bias["layers"].shape[0]
    return {"layers": bias["layers"] + step[:n], "mtp": bias["mtp"] + step[n:]}


def train_reference(seed: int, sizes, opt, batches: Sequence[np.ndarray],
                    precision="float32", sharding=None) -> Dict[str, Any]:
    """Follow ``len(batches)`` optimizer steps from the seeded weights and a
    zero bias, as ``benchmarks.reference.train_reference`` does for the
    dense model: each step's loss and its two parts; the first gradient as
    the optimizer gets it (after clipping), whole on the host by leaf
    (``grad1``), and its norms; the norms of the parameters' change over
    all the steps; ``routed_here``, each step's count of choices routed to
    held experts over the five expert layers; ``bias``, the balancing bias
    after the last step [expert layers + 1, E]."""
    w = init_weights(seed, sizes, sharding)
    bias = zero_bias(sizes)
    losses, mains, mtps, routed = [], [], [], []
    history, change, grad1, first = [], None, None, None
    lo, hi = sizes["first"], sizes["first"] + sizes["held"]
    for n, batch in enumerate(batches, 1):
        loss, main, mtp, grad, counts = loss_and_grad(
            w, bias, np.asarray(batch), sizes, precision)
        losses.append(loss), mains.append(main), mtps.append(mtp)
        routed.append(float(counts[:, lo:hi].sum()))
        bias = bias_update(bias, counts, sizes)
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
    return {"losses": losses, "losses_main": mains, "losses_mtp": mtps,
            "grad1_norms": grad1, "grad1": first,
            "change_norms": leaf_norms(delta_sum), "routed_here": routed,
            "bias": np.concatenate([np.asarray(bias["layers"]),
                                    np.asarray(bias["mtp"])])}
