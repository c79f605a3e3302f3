"""The plain reference for the Jamba serve cell: what its ``correct`` is
decided against.

AI21-Jamba2-3B's forward pass in straightforward ``jax.numpy`` — float32
under ``precision="float32"`` with every product at ``Precision.HIGHEST``,
no kernels, no chunks, no cache, no paging, no batching. It imports nothing
of the program and takes nothing the program has made: weights come from
``init_weights(seed)``, which draws the same seeded variates the program's
``init_transformer`` draws for preset ``ai21-jamba2-3b``.

A MAMBA layer (Mamba-1; inner width I = expand·d, state N, step rank R, K
taps), per sequence h [t, d] (after the layer's input norm):

- ``[x | z] = h·W_in`` (no bias);
- a causal depthwise convolution of K taps over time on every channel of x —
  written as K shifted products, zeros before the sequence's start — plus
  its bias, then SiLU: ``x'``;
- ``[δ | B | C] = x'·W_x`` (no bias), each through its own RMSNorm (Jamba's
  three inner norms, eps 1e-6); ``Δ = softplus(δ·W_dt + b_dt)``; ``A =
  −exp(A_log)``;
- the state ``S [I, N]``, float32, zero at the start, TOKEN BY TOKEN under
  ``lax.scan``: ``S_t = exp(Δ_t ⊗ A) ⊙ S_{t−1} + (Δ_t ⊙ x'_t) ⊗ B_t``,
  ``y_t = S_t·C_t + D ⊙ x'_t``;
- ``out = (y ⊙ SiLU(z))·W_out``.

An ATTENTION layer: ``q = h·W_q`` (n_heads of 128), ``k, v = h·W_k, h·W_v``
(ONE key/value head of 128), NO positional encoding, dense causal softmax
at scale 128^-1/2, ``·W_o``.

Either mixer F, then the SiLU-gated MLP M, pre-norm: ``x = x + F(norm(x))``,
``x = x + M(norm(x))``; a final RMSNorm and the head TIED to the embedding.
``sizes`` is the config's ``HF_TO_SIZES`` group plus ``mamba_d_state``,
``mamba_d_conv``, ``mamba_expand``, ``mamba_dt_rank`` and ``pattern`` (a
period's layer kinds, ``"mamba"`` / ``"attn"``), as the runner builds it from
the config file. Consecutive layers of one kind run under ``lax.scan`` over
their index (memory and compile time, not mathematics: a layer's weights are
read where they lie).

``precision`` also selects the CONTROLS, the same mathematics in a precision
a later change would be tempted by: ``"float8"`` / ``"bfloat16"`` (products,
as ``benchmarks/reference.py``) and ``"state_bf16"`` — float32 throughout,
but the recurrent state rounded to bfloat16 after every token
(``lax.reduce_precision``: a bare float32 -> bfloat16 -> float32 cast is a
round trip the TPU compiler removes).

``mamba_state`` is the state the first Mamba layer holds after a sequence:
what the runner's ``state_path_rel_gap`` holds the program's state path to.
Under ``precision="stated"`` — the products as the config's
``precision.stated`` says the program multiplies, everything else float32 —
it is what ``engine_state_rel_gap`` holds the ENGINE's own state to: the
engine's products put its state some 6e-3 from the float32 one, as far as a
bfloat16 state lies, so only a reference that multiplies as stated can tell
a state that is float32 from one that is not.
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
    the Mamba mixers from ``fold_in(layers' key, 10)`` split into eight (taps
    scaled K**-0.5, the step's bias the inverse softplus of a log-uniform
    step in [1e-3, 1e-1)); ``A_log = log(1 .. N)`` a channel, ``D = 1``, the
    convolution's bias 0, every norm gain 1 as the published layer
    constructs them; the embedding (= the head) normal * 0.02."""
    key = jax.random.PRNGKey(seed)
    k_embed, k_layers = jax.random.split(key)
    ks = jax.random.split(k_layers, 8)
    km = jax.random.split(jax.random.fold_in(k_layers, 10), 8)
    d, f, L, V = sizes["d_model"], sizes["d_ff"], sizes["n_layers"], sizes["vocab"]
    nh, nkv = sizes["n_heads"], sizes["n_kv_heads"]
    hd = d // nh
    N, K, R = sizes["mamba_d_state"], sizes["mamba_d_conv"], sizes["mamba_dt_rank"]
    I = sizes["mamba_expand"] * d
    kinds = layer_kinds(sizes)
    n_m, n_a = kinds.count("mamba"), kinds.count("attn")

    def normal(k, fan_in, *shape):
        return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32)
                       * fan_in ** -0.5)(k)

    dt = jnp.exp(jax.random.uniform(
        km[4], (n_m, I), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    return {
        "embed": jax.jit(lambda k: jax.random.normal(k, (V, d), jnp.float32) * 0.02)(k_embed),
        "final_norm": ones(d),
        "mixer_norm": ones(L, d), "mlp_norm": ones(L, d),
        "w_gate": normal(ks[4], d, L, d, f), "w_up": normal(ks[5], d, L, d, f),
        "w_down": normal(ks[6], f, L, f, d),
        "attn": {
            "wq": normal(ks[0], d, n_a, d, nh * hd), "wk": normal(ks[1], d, n_a, d, nkv * hd),
            "wv": normal(ks[2], d, n_a, d, nkv * hd), "wo": normal(ks[3], nh * hd, n_a, nh * hd, d),
        },
        "mamba": {
            "w_in": normal(km[0], d, n_m, d, 2 * I), "taps": normal(km[1], K, n_m, K, I),
            "conv_bias": jnp.zeros((n_m, I), jnp.float32),
            "w_x": normal(km[2], I, n_m, I, R + 2 * N), "w_dt": normal(km[3], R, n_m, R, I),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (n_m, I, N)),
            "D": ones(n_m, I), "w_out": normal(km[5], I, n_m, I, d),
            "dt_norm": ones(n_m, R), "b_norm": ones(n_m, N), "c_norm": ones(n_m, N),
        },
    }


# ---- the layers -------------------------------------------------------------


def _dot(eq: str, a, b, precision: str):
    """``reference._dot``; the bfloat16-state control multiplies as float32,
    and ``"stated"`` as the config states the PROGRAM does: float32 operands
    at the backend's DEFAULT precision (on the TPU bfloat16-rounded operands,
    float32 sums; off it float32)."""
    if precision == "stated":
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.DEFAULT)
    return reference._dot(eq, a, b, "float32" if precision == "state_bf16" else precision)


def selective_scan(u, delta, B, C, A, D, state0=None, round_state=False):
    """The recurrence, one token at a time. u, delta [t, I], B, C [t, N], A
    [I, N], D [I] -> (y [t, I], the last state [I, N]). ``round_state`` rounds
    the state to bfloat16's 8 exponent and 7 mantissa bits after every token
    (the bfloat16-state control), by an op no compiler pass may fold away."""
    if state0 is None:
        state0 = jnp.zeros(A.shape, jnp.float32)

    def token(S, x):
        # products and sums written out elementwise: float32 multiply-adds
        u_t, d_t, b_t, c_t = x
        S = jnp.exp(d_t[:, None] * A) * S + (d_t * u_t)[:, None] * b_t[None, :]
        if round_state:
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.sum(S * c_t[None, :], axis=1) + D * u_t

    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    S, y = jax.lax.scan(token, f32(state0), tuple(map(f32, (u, delta, B, C))))
    return y, S


def recurrence_inputs(h, lw, sizes, precision: str):
    """h [t, d] (normed) -> (what the recurrence reads: u, delta [t, I], B, C
    [t, N], all float32; the gate z [t, I])."""
    t = h.shape[0]
    N, K, R = sizes["mamba_d_state"], sizes["mamba_d_conv"], sizes["mamba_dt_rank"]
    I = sizes["mamba_expand"] * sizes["d_model"]
    eps = sizes["norm_eps"]
    xz = _dot("td,dn->tn", h, lw["w_in"], precision)
    x, z = xz[:, :I], xz[:, I:]
    # tap j reads the input K - 1 - j positions back; zeros before the start
    padded = jnp.concatenate([jnp.zeros((K - 1, I), x.dtype), x])
    u = jax.nn.silu(sum(padded[j:j + t] * lw["taps"][j].astype(x.dtype)
                        for j in range(K)) + lw["conv_bias"].astype(x.dtype))
    dbc = _dot("ti,in->tn", u, lw["w_x"], precision).astype(jnp.float32)
    dt = rms_norm(dbc[:, :R], lw["dt_norm"], eps)
    B = rms_norm(dbc[:, R:R + N], lw["b_norm"], eps)
    C = rms_norm(dbc[:, R + N:], lw["c_norm"], eps)
    delta = jax.nn.softplus(
        _dot("tr,ri->ti", dt, lw["w_dt"], precision).astype(jnp.float32) + lw["dt_bias"])
    return (u.astype(jnp.float32), delta, B, C), z


def mamba_mixer(h, lw, sizes, precision: str):
    inputs, z = recurrence_inputs(h, lw, sizes, precision)
    y, _ = selective_scan(*inputs, -jnp.exp(lw["A_log"]), lw["D"],
                          round_state=precision == "state_bf16")
    return _dot("ti,id->td", y.astype(h.dtype) * jax.nn.silu(z), lw["w_out"], precision)


def attn_mixer(h, lw, sizes, precision: str, q_block: int):
    """Dense causal attention, multi-query, no positional encoding, query
    rows in blocks."""
    t, nh, nkv = h.shape[0], sizes["n_heads"], sizes["n_kv_heads"]
    hd = sizes["d_model"] // nh
    q = _dot("td,dn->tn", h, lw["wq"], precision).reshape(t, nkv, nh // nkv, hd)
    k = _dot("td,dn->tn", h, lw["wk"], precision).reshape(t, nkv, hd)
    v = _dot("td,dn->tn", h, lw["wv"], precision).reshape(t, nkv, hd)

    def block(qb, start):
        s = _dot("qkgd,tkd->kgqt", qb, k, precision).astype(jnp.float32) / math.sqrt(hd)
        seen = (start + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1).astype(h.dtype)
        return _dot("kgqt,tkd->qkgd", p, v, precision)

    nb = t // q_block if t % q_block == 0 and t > q_block else 1
    size = t // nb
    a = jnp.concatenate([block(q[i * size:(i + 1) * size], i * size)
                         for i in range(nb)])
    return _dot("tn,nd->td", a.reshape(t, nh * hd), lw["wo"], precision)


def _runs(kinds):
    """Consecutive layers of one kind: (kind, first layer, first index among
    the layers of the kind, how many)."""
    seen = {"mamba": 0, "attn": 0}
    runs = []
    for l, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][3] += 1
        else:
            runs.append([kind, l, seen[kind], 1])
        seen[kind] += 1
    return runs


def hidden_states(w, tokens, sizes, precision="float32", q_block=512):
    """tokens [t] -> final-norm hidden states [t, d] of ONE sequence."""
    dt = jnp.float32 if precision in ("float32", "state_bf16") else jnp.bfloat16
    eps = sizes["norm_eps"]
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731

    def layer(kind):
        def body(x, idx):
            l, i = idx
            h = rms_norm(x, w["mixer_norm"][l], eps)
            x = x + (mamba_mixer(h, at(w["mamba"], i), sizes, precision)
                     if kind == "mamba"
                     else attn_mixer(h, at(w["attn"], i), sizes, precision, q_block))
            h = rms_norm(x, w["mlp_norm"][l], eps)
            gate = _dot("td,df->tf", h, w["w_gate"][l], precision)
            up = _dot("td,df->tf", h, w["w_up"][l], precision)
            return x + _dot("tf,fd->td", jax.nn.silu(gate) * up, w["w_down"][l],
                            precision), None
        return body

    x = w["embed"][tokens].astype(dt)
    for kind, l0, i0, n in _runs(layer_kinds(sizes)):
        x, _ = jax.lax.scan(layer(kind), x, (l0 + jnp.arange(n), i0 + jnp.arange(n)))
    return rms_norm(x, w["final_norm"], eps)


def logits(w, tokens, sizes, precision="float32", q_block=512):
    """tokens [t] -> logits [t, vocab] float32 (the head is the embedding)."""
    h = hidden_states(w, tokens, sizes, precision, q_block)
    return _dot("td,vd->tv", h, w["embed"], precision).astype(jnp.float32)


@partial(jax.jit, static_argnames=("sizes_t", "precision"))
def _first_mamba(w, seq, sizes_t, precision):
    sizes = dict(sizes_t)
    assert layer_kinds(sizes)[0] == "mamba"
    lw = jax.tree_util.tree_map(lambda a: a[0], w["mamba"])
    dt = jnp.float32 if precision in ("float32", "state_bf16", "stated") else jnp.bfloat16
    h = rms_norm(w["embed"][seq].astype(dt), w["mixer_norm"][0], sizes["norm_eps"])
    inputs, _ = recurrence_inputs(h, lw, sizes, precision)
    A, D = -jnp.exp(lw["A_log"]), lw["D"]
    return inputs + (A, D), selective_scan(
        *inputs, A, D, round_state=precision == "state_bf16")[1]


def mamba_state(w, sizes, tokens: Sequence[int], precision: str = "float32"):
    """The FIRST layer's recurrence over ``tokens`` (its input is the normed
    embedding: no other layer's products stand before it) -> ((u, delta, B,
    C, A, D) as the recurrence reads them, the state [I, N] after the last
    token)."""
    return _first_mamba(w, jnp.asarray(tokens, jnp.int32), _static(sizes), precision)


# ---- serving: teacher-forced gaps (the surface of benchmarks/reference.py) ----


def _static(sizes):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in sizes.items()))


@partial(jax.jit, static_argnames=("sizes_t", "precision", "rows"))
def _rows_logits(w, seq, start, sizes_t, precision, rows):
    h = hidden_states(w, seq, dict(sizes_t), precision)
    h = jax.lax.dynamic_slice_in_dim(h, start, rows, axis=0)
    return _dot("td,vd->tv", h, w["embed"], precision).astype(jnp.float32)


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
