"""Gated DeltaNet (Yang, Kautz & Hatamizadeh, ICLR '25): the linear-attention
layer's recurrence as two forward kernels that take and return a STATE.

Per head, with keys of width d_k and values of width d_v, the state is a
matrix ``S [d_k, d_v]`` in float32, zero at a sequence's start::

    S_t = a_t · (I − b_t · k_t k_tᵀ) · S_{t−1} + b_t · k_t v_tᵀ
    o_t = S_tᵀ q_t

``a_t = exp(alpha_log_t) ∈ (0, 1]`` is the decay gate, ``b_t`` the write
strength (``[0, 1]``, or ``[0, 2]`` where the model allows the transition's
eigenvalues to turn negative). q and k come in as they enter the recurrence
(the layer has already normalised and scaled them).

``gated_delta_chunk`` is the CHUNKED form for many tokens of one sequence
(a prefill chunk, a whole training row): the sequence is cut into chunks of
``CHUNK`` (64) positions; inside a chunk, with ``g_i`` the running sum of
``alpha_log`` from the chunk's start and ``u_i = b_i (v_i − a_i S_{i−1}ᵀ
k_i)`` the value actually written at position i (so that ``S_i = a_i
S_{i−1} + k_i u_iᵀ``), the u solve ONE unit-lower-triangular system a chunk ::

    (I + A) U = b ⊙ (V − e^g ⊙ K S_0),   A_ij = b_i e^{g_i − g_j} k_i·k_j  (j < i)
    O = e^g ⊙ Q S_0 + (Q Kᵀ ⊙ e^{g_i − g_j}, j <= i) U
    S_C = e^{g_C} S_0 + (e^{g_C − g} ⊙ K)ᵀ U

and only S crosses chunks, in float32. Every exponent is of a difference
``g_i − g_j <= 0`` taken BEFORE the exp, so a strong decay underflows to 0
and nothing overflows. The system is inverted by block recursion, exactly:
with ``X_b`` the inverse of the diagonal blocks of size b of ``I + A`` and
``E`` the entries of A that join two b-blocks into one of 2b, ``X_2b = X_b −
X_b E X_b`` (X_1 = I), five doublings to 64 — ten small products on the
matrix unit and no row-by-row substitution.

``gated_delta_step`` is the RECURRENT form for one token of each of ``s``
slots (a decode step): it reads and writes every slot's state once. Given
the serve engine's whole state store ``[layers, slots, H, d_k, d_v]`` with
``layer`` and ``slots`` it updates the named slots IN PLACE (the store is
the kernel's aliased operand, layer and slot sit in the BlockSpec index
map: no layer of the store is sliced out or copied, as with the page pool
in ``flash_attention_decode``).

Rows flagged invalid (``valid`` False: the padding of a short chunk, an
inactive slot) take ``b = 0, a = 1``: they write nothing, decay nothing,
and their outputs are not to be read.

Dispatch follows ``ops/flash_attention.py``: the Pallas kernels
(``gdn_chunk_fwd``, ``gdn_step`` — the names the device trace carries) run
on a TPU, or anywhere under ``interpret=True`` (the CPU test path for the
kernel logic); off the TPU the same mathematics runs in plain ``jnp``; a
TPU run that is handed the ``jnp`` form says so once (``_say_reference``).
Forward only: nothing here defines a gradient for the kernels (the ``jnp``
form differentiates as any ``jnp`` code does).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from tf_operator_tpu.ops.flash_attention import NEG_INF, _say_reference

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST
_STEP_VMEM_BUDGET = 8 << 20  # of the 16 MiB a kernel may scope by default


def _mask_invalid(alpha_log, beta, valid):
    if valid is None:
        return alpha_log, beta
    keep = valid[:, None]
    return jnp.where(keep, alpha_log, 0.0), jnp.where(keep, beta, 0.0)


# ---------------------------------------------------------------------------
# the chunked form
# ---------------------------------------------------------------------------


def _chunk_jnp(q, k, v, g, b, state0):
    """All heads, chunk by chunk under ``lax.scan``. q, k [H, n, C, d_k],
    v [H, n, C, d_v], g (running sum inside each chunk), b [H, n, C],
    state0 [H, d_k, d_v] -> (o [H, n, C, d_v], state1)."""
    C = q.shape[2]
    i = jnp.arange(C)[:, None]
    j = jnp.arange(C)[None, :]
    eye = jnp.eye(C, dtype=jnp.float32)
    dot = functools.partial(jnp.einsum, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)

    def body(S, xs):
        qc, kc, vc, gc, bc = xs  # [H, C, ·]
        decay = jnp.exp(jnp.where(i >= j, gc[:, :, None] - gc[:, None, :], NEG_INF))
        A = jnp.where(i > j, bc[:, :, None] * decay * dot("hid,hjd->hij", kc, kc), 0.0)
        gam = jnp.exp(gc)[..., None]
        rhs = bc[..., None] * (vc - gam * dot("hcd,hdv->hcv", kc, S))
        U = jax.scipy.linalg.solve_triangular(
            eye + A, rhs, lower=True, unit_diagonal=True)
        o = gam * dot("hcd,hdv->hcv", qc, S) + dot(
            "hij,hjv->hiv", dot("hid,hjd->hij", qc, kc) * decay, U)
        g_last = gc[:, -1]
        kd = kc * jnp.exp(g_last[:, None] - gc)[..., None]
        S = jnp.exp(g_last)[:, None, None] * S + dot("hck,hcv->hkv", kd, U)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b))
    state1, o = jax.lax.scan(body, state0, xs)
    return jnp.moveaxis(o, 0, 1), state1


def _unit_lower_inverse(A, dot):
    """``(I + A)⁻¹`` for A [C, C] strictly lower triangular, C a power of
    two: the block recursion of the module docstring, every product a full
    [C, C] one under a mask (no slicing)."""
    C = A.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    X = jnp.where(row == col, 1.0, 0.0) - jnp.where(
        (row >> 1) == (col >> 1), A, 0.0)
    s = 1
    while (2 << s) <= C:  # blocks of 2**s -> 2**(s+1)
        join = ((row >> (s + 1)) == (col >> (s + 1))) & ((row >> s) != (col >> s))
        X = X - dot(dot(X, jnp.where(join, A, 0.0)), X)
        s += 1
    return X


def _chunk_kernel(g_ref, b_ref, q_ref, k_ref, v_ref, s0_ref, o_ref, s1_ref,
                  s_scr, *, chunk):
    """One head, one chunk: grid (H, chunks), the state in VMEM scratch
    across a head's chunks. g and b arrive as ROWS [1, C]; their column
    forms come off the diagonal of a broadcast (a masked lane reduce: no
    transpose in the kernel)."""
    from jax.experimental import pallas as pl

    C = chunk
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _load():
        s_scr[...] = s0_ref[0]

    def dot(a, b_, dims=(((1,), (0,)), ((), ()))):
        # float32 operands multiplied as float32: the kernel is not bound by
        # the matrix unit (0.41 ms a 256-token call against 0.40 with
        # bfloat16-rounded operands, which read 1e-3 off; PERF.md §6 PR 37)
        return jax.lax.dot_general(a, b_, dims, precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)

    rhs_t = (((1,), (1,)), ((), ()))  # a · b_ᵀ
    lhs_t = (((0,), (0,)), ((), ()))  # aᵀ · b_
    g_r, b_r = g_ref[0, 0], b_ref[0, 0]  # [1, C]
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    S = s_scr[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = row == col
    g_c = jnp.sum(jnp.where(eye, g_r, 0.0), axis=1, keepdims=True)  # [C, 1]
    b_c = jnp.sum(jnp.where(eye, b_r, 0.0), axis=1, keepdims=True)
    decay = jnp.exp(jnp.where(row >= col, g_c - g_r, NEG_INF))
    A = jnp.where(row > col, b_c * decay * dot(k, k, rhs_t), 0.0)
    T = _unit_lower_inverse(A, dot)
    gam = jnp.exp(g_c)
    U = dot(T, b_c * (v - gam * dot(k, S)))
    o_ref[0] = (gam * dot(q, S) + dot(dot(q, k, rhs_t) * decay, U)).astype(o_ref.dtype)
    g_last = jnp.min(g_r, axis=1, keepdims=True)  # g never rises: its last entry
    S = jnp.exp(g_last) * S + dot(k * jnp.exp(g_last - g_c), U, lhs_t)
    s_scr[...] = S

    @pl.when(c == pl.num_programs(1) - 1)
    def _store():
        s1_ref[0] = S


def _chunk_call(q, k, v, g, b, state0, interpret):
    """q, k [H, n, C, d_k], v [H, n, C, d_v], g, b [H, n, C] through the
    kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, n, C, dk = q.shape
    dv = v.shape[-1]
    rows = lambda a: a.reshape(H, n, 1, C)  # noqa: E731 — a row a (head, chunk)
    row_spec = pl.BlockSpec((1, 1, 1, C), lambda h, c: (h, c, 0, 0))
    by_chunk = lambda d: pl.BlockSpec((1, C, d), lambda h, c: (h, c, 0))  # noqa: E731
    state_spec = pl.BlockSpec((1, dk, dv), lambda h, c: (h, 0, 0))
    o, state1 = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=C),
        grid=(H, n),
        in_specs=[row_spec, row_spec, by_chunk(dk), by_chunk(dk), by_chunk(dv),
                  state_spec],
        out_specs=[by_chunk(dv), state_spec],
        out_shape=[jax.ShapeDtypeStruct((H, n * C, dv), v.dtype),
                   jax.ShapeDtypeStruct((H, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_chunk_fwd",
    )(rows(g), rows(b), q.reshape(H, n * C, dk), k.reshape(H, n * C, dk),
      v.reshape(H, n * C, dv), state0)
    return o.reshape(H, n, C, dv), state1


def gated_delta_chunk(q, k, v, alpha_log, beta, state0, *, valid=None,
                      interpret: Optional[bool] = None):
    """``t`` consecutive tokens of ONE sequence through the recurrence.

    q, k [t, H, d_k]; v [t, H, d_v]; alpha_log (log of the decay gate,
    <= 0), beta [t, H]; state0 [H, d_k, d_v] float32: the state the first
    token finds; valid [t] bool or None. Returns ``(o [t, H, d_v] in v's
    dtype, state1 [H, d_k, d_v] float32)``, state1 the state after the last
    VALID token. t is padded with invalid rows to whole chunks of ``CHUNK``.
    The kernel runs on a TPU (or under ``interpret=True``) when d_k and d_v
    are multiples of 8."""
    chunk = CHUNK
    t, H, dk = q.shape
    dv = v.shape[-1]
    alpha_log, beta = _mask_invalid(
        alpha_log.astype(jnp.float32), beta.astype(jnp.float32), valid)
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(a):  # [t, H, ...] -> [H, n, C, ...] float32, padded rows zero
        a = jnp.pad(a.astype(jnp.float32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return jnp.moveaxis(a, 0, 1).reshape((H, n, chunk) + a.shape[2:])

    qc, kc, vc, ac, bc = map(chunks, (q, k, v, alpha_log, beta))
    gc = jnp.cumsum(ac, axis=2)
    why_not = f"d_k={dk}, d_v={dv}: not multiples of 8" if dk % 8 or dv % 8 else None
    on_tpu = jax.default_backend() == "tpu"
    if why_not is None and (bool(interpret) or on_tpu):
        o, state1 = _chunk_call(qc, kc, vc, gc, bc, state0.astype(jnp.float32),
                                bool(interpret))
    else:
        if on_tpu:
            _say_reference("gated_delta_chunk", why_not)
        o, state1 = _chunk_jnp(qc, kc, vc, gc, bc, state0.astype(jnp.float32))
    o = jnp.moveaxis(o.reshape(H, n * chunk, dv), 0, 1)[:t]
    return o.astype(v.dtype), state1


# ---------------------------------------------------------------------------
# the recurrent form
# ---------------------------------------------------------------------------


def _step_math(q, k, v, a, b, S):
    """One token, any leading dims: q, k [..., d_k], v [..., d_v], a, b
    [...] (a the decay itself), S [..., d_k, d_v] -> (o, S)."""
    S = a[..., None, None] * S
    u = b[..., None] * (v - jnp.sum(k[..., None] * S, axis=-2))
    S = S + k[..., None] * u[..., None, :]
    return jnp.sum(q[..., None] * S, axis=-2), S


def _step_kernel(slot_ref, qk_ref, abv_ref, s_ref, o_ref, so_ref, *, heads):
    """One slot, ``heads`` heads, each a 2-D update on the vector unit: the
    state [d_k, d_v] is read once and written once. q and k arrive as
    COLUMNS (``qk`` [2, d_k, heads]: a head a lane) and are spread over the
    d_v lanes here; ``abv`` holds a head's decay and write strength spread
    over a row of d_v lanes beside its v (a scalar cannot be spread over
    lanes and sublanes inside the kernel). Nothing is transposed and the
    only reductions are the two over d_k."""
    q_cols, k_cols = qk_ref[0, 0, 0], qk_ref[0, 0, 1]  # [d_k, heads]
    for h in range(heads):
        a, b, v = (abv_ref[0, 0, i, h:h + 1] for i in range(3))  # [1, d_v]
        k = k_cols[:, h:h + 1]  # [d_k, 1]
        S = a * s_ref[0, 0, h]
        u = b * (v - jnp.sum(k * S, axis=0, keepdims=True))
        S = S + k * u
        so_ref[0, 0, h] = S
        o_ref[0, 0, h:h + 1] = jnp.sum(
            q_cols[:, h:h + 1] * S, axis=0, keepdims=True).astype(o_ref.dtype)


def _heads_per_step(H: int, dk: int, dv: int) -> int:
    """Heads one grid step of ``gdn_step`` takes: the largest divisor of H
    whose state blocks (read and written, double-buffered, the value width
    padded to whole lanes) stay inside ``_STEP_VMEM_BUDGET``."""
    per_head = 4 * 4 * dk * (-(-dv // 128) * 128)
    for hb in range(H, 0, -1):
        if H % hb == 0 and hb * per_head <= _STEP_VMEM_BUDGET:
            return hb
    return 0


def _step_call(q, k, v, a, b, store, layer, slots, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_n, H, dk = q.shape
    dv = v.shape[-1]
    hb = _heads_per_step(H, dk, dv)
    groups = H // hb
    grouped = lambda x: x.reshape(s_n, groups, hb, x.shape[-1])  # noqa: E731
    spread = lambda x: jnp.broadcast_to(x[..., None], v.shape)  # noqa: E731
    abv = jnp.stack([grouped(x) for x in (spread(a), spread(b), v)], axis=2)
    # [s, groups, 2, d_k, hb]: a head a lane, so a head's q or k is a column
    qk = jnp.stack([jnp.swapaxes(grouped(x), 2, 3) for x in (q, k)], axis=2)
    by_slot = lambda d: pl.BlockSpec(  # noqa: E731
        (1, 1, hb, d), lambda i, j, slot: (i, j, 0, 0))
    state_spec = pl.BlockSpec(
        (1, 1, hb, dk, dv), lambda i, j, slot: (layer, slot[i], j, 0, 0))
    o, store = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s_n, groups),
            in_specs=[pl.BlockSpec((1, 1, 2, dk, hb),
                                   lambda i, j, slot: (i, j, 0, 0, 0)),
                      pl.BlockSpec((1, 1, 3, hb, dv),
                                   lambda i, j, slot: (i, j, 0, 0, 0)),
                      state_spec],
            out_specs=[by_slot(dv), state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((s_n, groups, hb, dv), v.dtype),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        # operand 3 (the prefetched slots are operand 0) is the store: updated in place
        input_output_aliases={3: 1},
        interpret=interpret,
        name="gdn_step",
    )(slots.astype(jnp.int32), qk, abv, store)
    return o.reshape(s_n, H, dv), store


def gated_delta_step(q, k, v, alpha_log, beta, state, *, valid=None,
                     layer: Optional[int] = None, slots=None,
                     interpret: Optional[bool] = None):
    """ONE token of each of ``s`` sequences through the recurrence.

    q, k [s, H, d_k]; v [s, H, d_v]; alpha_log, beta [s, H]; valid [s] bool
    or None; ``state`` [s, H, d_k, d_v] float32, row i the state of
    sequence i — or the serve engine's whole store [layers, slots, H, d_k,
    d_v] with ``layer`` (a Python int) and ``slots`` [s] int32 naming each
    row's slot (rows may share a slot nobody reads: the trash slot).
    Returns ``(o [s, H, d_v] in v's dtype, the states in ``state``'s form)``;
    the store comes back updated in place when the caller donated it."""
    whole = layer is not None
    if whole != (slots is not None) or state.ndim != (5 if whole else 4):
        raise ValueError(
            f"step state: [s,H,dk,dv], or the store [layers,slots,H,dk,dv] "
            f"with layer= and slots= (got {state.shape}, layer={layer})")
    s_n, H, dk = q.shape
    dv = v.shape[-1]
    alpha_log, beta = _mask_invalid(
        alpha_log.astype(jnp.float32), beta.astype(jnp.float32), valid)
    a = jnp.exp(alpha_log)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    why_not = None
    if not _heads_per_step(H, dk, dv):
        why_not = f"one head's state [{dk}, {dv}] does not fit the kernel's VMEM"
    elif state.dtype != jnp.float32:
        why_not = f"state dtype {state.dtype}"
    on_tpu = jax.default_backend() == "tpu"
    if why_not is None and (bool(interpret) or on_tpu):
        store = state if whole else state[None]
        o, store = _step_call(
            f32(q), f32(k), f32(v), a, beta, store, layer if whole else 0,
            slots if whole else jnp.arange(s_n, dtype=jnp.int32), bool(interpret))
        return o.astype(v.dtype), store if whole else store[0]
    if on_tpu:
        _say_reference("gated_delta_step", why_not)
    S = state[layer, slots] if whole else state
    o, S = _step_math(f32(q), f32(k), f32(v), a, beta, f32(S))
    S = S.astype(state.dtype)
    return o.astype(v.dtype), state.at[layer, slots].set(S) if whole else S
