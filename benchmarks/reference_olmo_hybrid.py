"""The plain reference for the Olmo-Hybrid serve cell: what its ``correct``
is decided against.

Olmo-Hybrid-7B's forward pass in straightforward ``jax.numpy`` — float32
under ``precision="float32"`` with every product at ``Precision.HIGHEST``,
no kernels, no chunks, no cache, no paging. It imports nothing of the
program and takes nothing the program has made: weights come from
``init_weights(seed)``, which draws the same seeded variates the program's
``init_transformer`` draws for preset ``olmo-hybrid-7b``.

A LINEAR layer (Gated DeltaNet; H heads, keys d_k, values d_v wide), per
token x [d]:

- ``[q~ | k~ | v~] = x·W_qkv``, ``z = x·W_z``, ``[b | a] = x·W_ba``;
- a causal depthwise convolution of K = 4 taps over time on every channel
  of ``[q~ | k~ | v~]`` — written as four shifted products, zeros before the
  sequence's start, no bias — then SiLU;
- per head ``q = l2norm(q)·d_k^-1/2``, ``k = l2norm(k)`` (``x·rsqrt(Σx² +
  1e-6)``), ``beta = 2·sigmoid(b)``, ``alpha = exp(−exp(A_log)·softplus(a +
  dt_bias))``;
- the state ``S [d_k, d_v]``, float32, zero at the start, TOKEN BY TOKEN
  under ``lax.scan``: ``S_t = alpha_t (I − beta_t k_t k_tᵀ) S_{t−1} + beta_t
  k_t v_tᵀ``, ``o_t = S_tᵀ q_t``;
- ``y = (RMSNorm_{d_v}(o) ⊙ SiLU(z))·W_o``, one gain vector for all heads.

A FULL layer: ``q = RMSNorm(x·W_q)``, ``k = RMSNorm(x·W_k)`` over the whole
projection, heads of ``d / n_heads``, one key a query head, NO rotary
embedding, dense causal softmax attention, ``·W_o``.

Either mixer F, then the SwiGLU MLP M, in the OLMo-2 order: ``x = x +
RMSNorm(F(x))``, ``x = x + RMSNorm(M(x))``; a final RMSNorm and an untied
head. ``sizes`` is the config's ``HF_TO_SIZES`` group plus ``lin_heads``,
``lin_dk``, ``lin_dv``, ``lin_conv`` and ``pattern`` (a period's layer kinds,
``"linear"`` / ``"full"``), as the runner builds it from the config file.

``precision`` also selects the CONTROLS, the same mathematics in a
precision a later change would be tempted by: ``"float8"`` / ``"bfloat16"``
(products, as ``benchmarks/reference.py``) and ``"state_bf16"`` — float32
throughout, but the recurrent state rounded to bfloat16 after every token
(``lax.reduce_precision``: a bare float32 -> bfloat16 -> float32 cast is a
round trip the TPU compiler removes under its default
``xla_allow_excess_precision``, and the control then computes float32).

``linear_state`` is the state a linear layer holds after a sequence: what
the runner's ``state_path_rel_gap`` holds the program's state path to.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference
from benchmarks.reference import gaps, rms_norm  # noqa: F401 — gaps: the runner's surface


def layer_kinds(sizes) -> list:
    """Every layer's kind, the period repeated."""
    period = list(sizes["pattern"])
    return [period[l % len(period)] for l in range(sizes["n_layers"])]


def init_weights(seed: int, sizes) -> Dict[str, Any]:
    """Float32 weights from ``seed``, drawn as the program draws them: the
    seed's key split into (embedding, layers); the layers' key split into
    eight for the attention and MLP matrices, each ONE normal draw of the
    whole stack [layers of its kind, fan_in, fan_out] scaled fan_in**-0.5;
    the linear mixers from ``fold_in(layers' key, 9)`` split into eight
    (taps scaled K**-0.5, A uniform in [1, 16) kept as its log, the step dt
    log-uniform in [1e-3, 1e-1) kept through the inverse softplus); the head
    from ``fold_in(seed's key, 3)``; embedding and head normal * 0.02; every
    norm gain 1."""
    key = jax.random.PRNGKey(seed)
    k_embed, k_layers = jax.random.split(key)
    ks = jax.random.split(k_layers, 8)
    kl = jax.random.split(jax.random.fold_in(k_layers, 9), 8)
    d, f, L, V = sizes["d_model"], sizes["d_ff"], sizes["n_layers"], sizes["vocab"]
    H, dk, dv, K = (sizes[n] for n in ("lin_heads", "lin_dk", "lin_dv", "lin_conv"))
    kinds = layer_kinds(sizes)
    n_lin, n_full = kinds.count("linear"), kinds.count("full")
    ch, hv = H * (2 * dk + dv), H * dv

    def normal(k, fan_in, *shape):
        return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32)
                       * fan_in ** -0.5)(k)

    def table(k):
        return jax.jit(lambda k: jax.random.normal(k, (V, d), jnp.float32) * 0.02)(k)

    dt = jnp.exp(jax.random.uniform(
        kl[6], (n_lin, H), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    return {
        "embed": table(k_embed), "head": table(jax.random.fold_in(key, 3)),
        "final_norm": ones(d),
        "mixer_norm": ones(L, d), "mlp_norm": ones(L, d),
        "w_gate": normal(ks[4], d, L, d, f), "w_up": normal(ks[5], d, L, d, f),
        "w_down": normal(ks[6], f, L, f, d),
        "full": {
            "wq": normal(ks[0], d, n_full, d, d), "wk": normal(ks[1], d, n_full, d, d),
            "wv": normal(ks[2], d, n_full, d, d), "wo": normal(ks[3], d, n_full, d, d),
            "q_norm": ones(n_full, d), "k_norm": ones(n_full, d),
        },
        "linear": {
            "w_qkv": normal(kl[0], d, n_lin, d, ch), "w_z": normal(kl[1], d, n_lin, d, hv),
            "w_ba": normal(kl[2], d, n_lin, d, 2 * H),
            "taps": normal(kl[3], K, n_lin, K, ch),
            "w_o": normal(kl[4], hv, n_lin, hv, d),
            "A_log": jnp.log(jax.random.uniform(kl[5], (n_lin, H), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "o_norm": ones(n_lin, dv),
        },
    }


# ---- the layers -------------------------------------------------------------


def _dot(eq: str, a, b, precision: str):
    """``reference._dot``; the bfloat16-state control multiplies as float32."""
    return reference._dot(eq, a, b, "float32" if precision == "state_bf16" else precision)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule_scan(q, k, v, alpha, beta, state0=None, round_state=False):
    """The recurrence, one token at a time. q, k [t, H, d_k], v [t, H, d_v],
    alpha, beta [t, H] (alpha the decay itself) -> (o [t, H, d_v], the last
    state [H, d_k, d_v]). ``round_state`` rounds the state to bfloat16's 8
    exponent and 7 mantissa bits after every token (the bfloat16-state
    control), by an op no compiler pass may fold away."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    if state0 is None:
        state0 = jnp.zeros((H, dk, dv), jnp.float32)

    def token(S, x):
        # products and sums written out elementwise: float32 multiply-adds
        # (no matrix unit, so no operand is ever rounded), one pass over S
        q_t, k_t, v_t, a_t, b_t = x
        S = a_t[:, None, None] * S
        kS = jnp.sum(k_t[:, :, None] * S, axis=1)
        S = S + k_t[:, :, None] * (b_t[:, None] * (v_t - kS))[:, None, :]
        if round_state:
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.sum(q_t[:, :, None] * S, axis=1)

    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    S, o = jax.lax.scan(token, f32(state0), tuple(map(f32, (q, k, v, alpha, beta))))
    return o, S


def recurrence_inputs(x, lw, sizes, precision: str):
    """x [t, d] -> what the recurrence reads: q, k [t, H, d_k], v [t, H,
    d_v], alpha, beta [t, H], all float32."""
    t = x.shape[0]
    H, dk, dv, K = (sizes[n] for n in ("lin_heads", "lin_dk", "lin_dv", "lin_conv"))
    pre = _dot("td,dn->tn", x, lw["w_qkv"], precision)
    ba = _dot("td,dn->tn", x, lw["w_ba"], precision).astype(jnp.float32)
    # tap j reads the input K - 1 - j positions back; zeros before the start
    padded = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1]), pre.dtype), pre])
    u = jax.nn.silu(sum(padded[j:j + t] * lw["taps"][j].astype(pre.dtype)
                        for j in range(K))).astype(jnp.float32)
    q = l2_norm(u[:, : H * dk].reshape(t, H, dk)) * dk ** -0.5
    k = l2_norm(u[:, H * dk: 2 * H * dk].reshape(t, H, dk))
    v = u[:, 2 * H * dk:].reshape(t, H, dv)
    beta = 2.0 * jax.nn.sigmoid(ba[:, :H])
    alpha = jnp.exp(-jnp.exp(lw["A_log"]) * jax.nn.softplus(ba[:, H:] + lw["dt_bias"]))
    return q, k, v, alpha, beta


def linear_mixer(x, lw, sizes, precision: str):
    t = x.shape[0]
    H, dv = sizes["lin_heads"], sizes["lin_dv"]
    z = _dot("td,dn->tn", x, lw["w_z"], precision)
    o, _ = delta_rule_scan(*recurrence_inputs(x, lw, sizes, precision),
                           round_state=precision == "state_bf16")
    gated = rms_norm(o, lw["o_norm"], sizes["norm_eps"]).astype(x.dtype) \
        * jax.nn.silu(z.reshape(t, H, dv))
    return _dot("tn,nd->td", gated.reshape(t, H * dv), lw["w_o"], precision)


@partial(jax.jit, static_argnames=("sizes_t", "precision"))
def _first_linear(w, seq, sizes_t, precision):
    sizes = dict(sizes_t)
    assert layer_kinds(sizes)[0] == "linear"
    lw = jax.tree_util.tree_map(lambda a: a[0], w["linear"])
    dt = jnp.float32 if precision in ("float32", "state_bf16") else jnp.bfloat16
    inputs = recurrence_inputs(w["embed"][seq].astype(dt), lw, sizes, precision)
    return inputs, delta_rule_scan(*inputs, round_state=precision == "state_bf16")[1]


def linear_state(w, sizes, tokens: Sequence[int], precision: str = "float32"):
    """The FIRST layer's recurrence over ``tokens`` (its input is the
    embedding: no other layer's products stand before it) -> ((q, k, v,
    alpha, beta) as ``recurrence_inputs`` gives them, the state [H, d_k,
    d_v] after the last token)."""
    return _first_linear(w, jnp.asarray(tokens, jnp.int32), _static(sizes), precision)


def full_mixer(x, lw, sizes, precision: str, q_block: int):
    """Dense causal attention, no positional encoding, query rows in blocks."""
    t, nh = x.shape[0], sizes["n_heads"]
    hd = sizes["d_model"] // nh
    eps = sizes["norm_eps"]
    q = rms_norm(_dot("td,dn->tn", x, lw["wq"], precision), lw["q_norm"], eps)
    k = rms_norm(_dot("td,dn->tn", x, lw["wk"], precision), lw["k_norm"], eps)
    v = _dot("td,dn->tn", x, lw["wv"], precision)
    q, k, v = (a.reshape(t, nh, hd) for a in (q, k, v))

    def block(qb, start):
        s = _dot("qhd,khd->hqk", qb, k, precision).astype(jnp.float32) / math.sqrt(hd)
        seen = (start + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1).astype(x.dtype)
        return _dot("hqk,khd->qhd", p, v, precision)

    nb = t // q_block if t % q_block == 0 and t > q_block else 1
    size = t // nb
    a = jnp.concatenate([block(q[i * size:(i + 1) * size], i * size)
                         for i in range(nb)])
    return _dot("tn,nd->td", a.reshape(t, nh * hd), lw["wo"], precision)


def hidden_states(w, tokens, sizes, precision="float32", q_block=1024):
    """tokens [t] -> final-norm hidden states [t, d] of ONE sequence."""
    dt = jnp.float32 if precision in ("float32", "state_bf16") else jnp.bfloat16
    eps = sizes["norm_eps"]
    x = w["embed"][tokens].astype(dt)
    seen = {"linear": 0, "full": 0}
    for l, kind in enumerate(layer_kinds(sizes)):
        lw = jax.tree_util.tree_map(lambda a: a[seen[kind]], w[kind])
        seen[kind] += 1
        y = linear_mixer(x, lw, sizes, precision) if kind == "linear" \
            else full_mixer(x, lw, sizes, precision, q_block)
        x = x + rms_norm(y, w["mixer_norm"][l], eps)
        gate = _dot("td,df->tf", x, w["w_gate"][l], precision)
        up = _dot("td,df->tf", x, w["w_up"][l], precision)
        y = _dot("tf,fd->td", jax.nn.silu(gate) * up, w["w_down"][l], precision)
        x = x + rms_norm(y, w["mlp_norm"][l], eps)
    return rms_norm(x, w["final_norm"], eps)


def logits(w, tokens, sizes, precision="float32", q_block=1024):
    """tokens [t] -> logits [t, vocab] float32 (the untied head)."""
    h = hidden_states(w, tokens, sizes, precision, q_block)
    return _dot("td,vd->tv", h, w["head"], precision).astype(jnp.float32)


# ---- serving: teacher-forced gaps (the surface of benchmarks/reference.py) ----


def _static(sizes):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in sizes.items()))


@partial(jax.jit, static_argnames=("sizes_t", "precision", "rows"))
def _rows_logits(w, seq, start, sizes_t, precision, rows):
    h = hidden_states(w, seq, dict(sizes_t), precision)
    h = jax.lax.dynamic_slice_in_dim(h, start, rows, axis=0)
    return _dot("td,vd->tv", h, w["head"], precision).astype(jnp.float32)


def served_logits(w, sizes, prompt: Sequence[int], tokens: Sequence[int],
                  pad_to: int, rows: int, precision: str = "float32") -> np.ndarray:
    """One pass over prompt + served tokens (teacher-forced: every layer is
    causal, so row i is what greedy decoding saw when it chose token i).
    Returns the logits [len(tokens), vocab] at the served positions."""
    n_p, n_t = len(prompt), len(tokens)
    seq = np.zeros(pad_to, np.int32)  # padding sits after every judged row
    seq[: n_p + n_t - 1] = list(prompt) + list(tokens[:-1])
    start = min(n_p - 1, pad_to - rows)
    off = n_p - 1 - start
    out = _rows_logits(w, jnp.asarray(seq), start, _static(sizes), precision, rows)
    return np.asarray(out)[off: off + n_t]
