"""The plain reference: what `correct` is decided against.

A pre-norm decoder block as Mistral-7B-v0.1's published code describes it
(RMSNorm, rotary embedding in the rotate-half layout over the whole head,
grouped-query attention without biases, SwiGLU), the token cross-entropy,
global-norm clipping and AdamW, in straightforward ``jax.numpy``:
float32 under ``precision="float32"`` with every product at
``Precision.HIGHEST``, no kernels, no cache, no paging. It imports nothing
of the program and takes nothing the program has made: weights come from
``init_weights(seed)``, which draws the same seeded normal variates the
benchmark's cells are initialised with (fan-in scaled, embedding 0.02).

Departures from the published model, both stated in every config file: the
output head is the embedding transposed (tied), and no sliding window is
applied (exact while no sequence exceeds 4096).

Memory, not mathematics: layers run under ``lax.scan`` with a
rematerialised body and attention is taken in blocks of query rows, so
the reference fits beside nothing else on one chip. ``precision`` also
selects the CONTROLS, the same mathematics in the precision a later change
would be tempted by: ``"bfloat16"`` (weights, activations and products in
bfloat16, float32 accumulation) and ``"float8"`` (products of
per-tensor-scaled float8_e4m3 values on top of that).

``sizes`` is a config file's ``model`` group.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST

def _leaf_shapes(sizes) -> Dict[str, tuple]:
    """The stacked matrices, in the order their keys are drawn."""
    d, f, L = sizes["d_model"], sizes["d_ff"], sizes["n_layers"]
    hd = d // sizes["n_heads"]
    kv = sizes["n_kv_heads"] * hd
    return {
        "wq": (L, d, d), "wk": (L, d, kv), "wv": (L, d, kv), "wo": (L, d, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }


def init_weights(seed: int, sizes, sharding=None) -> Dict[str, Any]:
    """Float32 weights from ``seed``: one key split into (embedding,
    layers), the layers' key into eight, one normal draw per stacked
    [n_layers, fan_in, fan_out] matrix scaled by fan_in**-0.5, embedding
    normal * 0.02, norm gains 1. ``sharding(shape)`` may place each leaf."""
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
        name: normal(ks[i], shape, shape[1] ** -0.5)
        for i, (name, shape) in enumerate(_leaf_shapes(sizes).items())
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


# ---- products in the stated precision -------------------------------------


def _fp8(x):
    """Per-tensor absmax scaling into float8_e4m3 and back: the values a
    scaled fp8 product would multiply. Straight-through for gradients: only
    forward values are rounded, cotangents pass unrounded."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32)) / 448.0 + 1e-30
    q = (x32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return (x32 + jax.lax.stop_gradient(q - x32)).astype(jnp.bfloat16)


def _dot(eq: str, a, b, precision: str):
    if precision == "float32":
        return jnp.einsum(eq, a, b, precision=_HIGHEST)
    if precision == "float8":
        a, b = _fp8(a), _fp8(b)
    out = jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.bfloat16)


# ---- the block --------------------------------------------------------------


def rms_norm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain).astype(x.dtype)


def rope(x, positions, theta):
    """x [t, heads, head_dim]; pairs (i, i + head_dim/2) rotate by
    position * theta**(-i / (head_dim/2))."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, precision: str, q_block: int):
    """q [t, h, hd], k/v [t, h, hd] (kv heads already repeated) -> [t, h, hd].
    Query rows in blocks of ``q_block`` against every key, masked."""
    t, h, hd = q.shape
    scale = 1.0 / math.sqrt(hd)

    @jax.checkpoint
    def block(qb, start):
        s = _dot("qhd,khd->hqk", qb, k, precision).astype(jnp.float32) * scale
        rows = start + jnp.arange(qb.shape[0])
        mask = rows[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return _dot("hqk,khd->qhd", p, v, precision)

    nb = max(1, t // q_block) if t % q_block == 0 else 1
    size = t // nb
    outs = [block(q[i * size:(i + 1) * size], i * size) for i in range(nb)]
    return jnp.concatenate(outs, axis=0) if nb > 1 else outs[0]


def _layer(x, lw, positions, sizes, precision: str, q_block: int):
    t = x.shape[0]
    nh, nkv = sizes["n_heads"], sizes["n_kv_heads"]
    hd = sizes["d_model"] // nh
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    h = rms_norm(x, lw["attn_norm"], eps)
    q = _dot("td,dn->tn", h, lw["wq"], precision).reshape(t, nh, hd)
    k = _dot("td,dn->tn", h, lw["wk"], precision).reshape(t, nkv, hd)
    v = _dot("td,dn->tn", h, lw["wv"], precision).reshape(t, nkv, hd)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    k = jnp.repeat(k, nh // nkv, axis=1)  # query head i reads kv head i // g
    v = jnp.repeat(v, nh // nkv, axis=1)
    a = causal_attention(q, k, v, precision, q_block).reshape(t, nh * hd)
    x = x + _dot("tn,nd->td", a, lw["wo"], precision)
    h = rms_norm(x, lw["mlp_norm"], eps)
    gate = _dot("td,df->tf", h, lw["w_gate"], precision)
    up = _dot("td,df->tf", h, lw["w_up"], precision)
    return x + _dot("tf,fd->td", jax.nn.silu(gate) * up, lw["w_down"], precision)


def hidden_states(w, tokens, sizes, precision="float32", q_block=1024):
    """tokens [t] -> final-norm hidden states [t, d] of ONE sequence."""
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    x = w["embed"][tokens].astype(dt)
    positions = jnp.arange(tokens.shape[0])
    body = jax.checkpoint(
        lambda x, lw: (_layer(x, lw, positions, sizes, precision, q_block), None)
    )
    x, _ = jax.lax.scan(body, x, w["layers"])
    return rms_norm(x, w["final_norm"], sizes["norm_eps"])


def logits(w, tokens, sizes, precision="float32", q_block=1024):
    """tokens [t] -> logits [t, vocab] float32 (tied head)."""
    h = hidden_states(w, tokens, sizes, precision, q_block)
    return _dot("td,vd->tv", h, w["embed"], precision).astype(jnp.float32)


def sequence_ce_sum(w, tokens, sizes, precision="float32", q_block=1024):
    """Sum over positions 0..t-2 of -log p(tokens[i+1] | tokens[:i+1])."""
    lg = logits(w, tokens, sizes, precision, q_block)[:-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


# ---- serving: teacher-forced gaps -------------------------------------------


@partial(jax.jit, static_argnames=("sizes_t", "precision", "rows"))
def _rows_logits(w, seq, start, sizes_t, precision, rows):
    sizes = dict(sizes_t)
    h = hidden_states(w, seq, sizes, precision)
    h = jax.lax.dynamic_slice_in_dim(h, start, rows, axis=0)
    return _dot("td,vd->tv", h, w["embed"], precision).astype(jnp.float32)


def served_logits(w, sizes, prompt: Sequence[int], tokens: Sequence[int],
                  pad_to: int, rows: int, precision: str = "float32") -> np.ndarray:
    """One pass over prompt + served tokens (teacher-forced: causal
    attention makes row i what greedy decoding saw when it chose token i).
    Returns the logits [len(tokens), vocab] at the served positions."""
    n_p, n_t = len(prompt), len(tokens)
    seq = np.zeros(pad_to, np.int32)  # padding sits after every judged row
    seq[: n_p + n_t - 1] = list(prompt) + list(tokens[:-1])
    start = min(n_p - 1, pad_to - rows)
    off = n_p - 1 - start
    out = _rows_logits(w, jnp.asarray(seq), start, tuple(sorted(sizes.items())),
                       precision, rows)
    return np.asarray(out)[off: off + n_t]


def gaps(ref: np.ndarray, judged) -> np.ndarray:
    """Per position, how far the judged token's logit lies below ``ref``'s best."""
    judged = np.asarray(judged, np.int64)
    return ref.max(axis=-1) - np.take_along_axis(ref, judged[:, None], axis=1)[:, 0]


# ---- training: loss, gradient, clipped AdamW --------------------------------


@partial(jax.jit, static_argnames=("sizes_t", "precision"), donate_argnums=(1,))
def _accumulate(w, acc, row, sizes_t, precision):
    loss, g = jax.value_and_grad(sequence_ce_sum)(w, row, dict(sizes_t), precision)
    return loss, jax.tree_util.tree_map(jnp.add, acc, g)


def loss_and_grad(w, batch: np.ndarray, sizes, precision="float32"):
    """Mean token cross-entropy over every row of ``batch`` [b, t] and its
    gradient, one row at a time into a float32 accumulator."""
    st = tuple(sorted(sizes.items()))
    acc = jax.tree_util.tree_map(jnp.zeros_like, w)
    total = 0.0
    for row in batch:
        loss, acc = _accumulate(w, acc, jnp.asarray(row, jnp.int32), st, precision)
        total += float(loss)
    count = batch.shape[0] * (batch.shape[1] - 1)
    grad = jax.tree_util.tree_map(jax.jit(lambda a: a / count), acc)
    return total / count, grad


def leaf_names(tree) -> List[str]:
    """Every leaf's path, ``layers/wq`` style, in flattening order."""
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> Dict[str, float]:
    """Euclidean norm of every leaf, by its path."""
    return {
        name: float(jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32)))))
        for name, leaf in zip(leaf_names(tree), jax.tree_util.tree_leaves(tree))
    }


def clip_by_global_norm(grad, max_norm):
    if not max_norm:
        return grad
    total = math.sqrt(sum(v * v for v in leaf_norms(grad).values()))
    if total <= max_norm:
        return grad
    return jax.tree_util.tree_map(
        jax.jit(lambda g: g * (max_norm / total)), grad)


def adamw_update(history: List[Any], params, opt) -> Any:
    """AdamW's update after ``len(history)`` steps from the clipped
    gradients of all of them (moments start at zero, so they are sums over
    the history: nothing else needs keeping)."""
    n = len(history)
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]

    @jax.jit
    def leaf(p, *gs):
        m = sum((1 - b1) * b1 ** (n - 1 - i) * g for i, g in enumerate(gs))
        v = sum((1 - b2) * b2 ** (n - 1 - i) * g * g for i, g in enumerate(gs))
        m_hat, v_hat = m / (1 - b1 ** n), v / (1 - b2 ** n)
        return -lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)

    return jax.tree_util.tree_map(  # host copies go back where their leaf lives
        lambda p, *gs: leaf(p, *(jax.device_put(g, p.sharding) for g in gs)),
        params, *history)


def train_reference(seed: int, sizes, opt, batches: Sequence[np.ndarray],
                    precision="float32", sharding=None) -> Dict[str, Any]:
    """Follow ``len(batches)`` optimizer steps from the seeded weights.
    Returns each step's loss, the per-leaf norms of the first gradient as
    the optimizer gets it (after clipping) and of the parameters' change
    over all the steps. Earlier steps' gradients and the change so far wait on
    the host, so that weights, one accumulator and one gradient are all the
    device holds."""
    w = init_weights(seed, sizes, sharding)
    losses, history, change, grad1 = [], [], None, None
    for n, batch in enumerate(batches, 1):
        loss, grad = loss_and_grad(w, np.asarray(batch), sizes, precision)
        losses.append(loss)
        grad = clip_by_global_norm(grad, opt["grad_clip"])
        if grad1 is None:
            grad1 = leaf_norms(grad)
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
    return {"losses": losses, "grad1_norms": grad1,
            "change_norms": leaf_norms(delta_sum)}
