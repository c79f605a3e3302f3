"""Mamba-1's selective scan (Gu & Dao 2023): the state-space layer's
recurrence as two forward kernels that take and return a STATE.

Per channel c of the mixer's inner width I, with a state of N numbers, the
transition is DIAGONAL and input-dependent — a step size a channel a token,
no heads, no matrix product anywhere::

    S_t[n, c] = exp(Δ_t[c] · A[c, n]) · S_{t−1}[n, c] + Δ_t[c] · u_t[c] · B_t[n]
    y_t[c]    = Σ_n S_t[n, c] · C_t[n] + D[c] · u_t[c]

``A = −exp(A_log) < 0`` and ``Δ = softplus(·) >= 0``, so every exponent is
``<= 0``: nothing overflows. The state is float32 and is held ``[N, I]`` —
the state dimension on the sublanes, the CHANNELS ON THE LANES — which is the
serve engine's ``StateStore`` entry ``[heads 1, d_k N, d_v I]``; ``A`` comes
in as the layer keeps it, ``[I, N]``. u (the convolved, activated input), Δ,
B and C come in as they enter the recurrence: the layer has already run its
convolution, its three norms and the step's projection.

``selective_scan_chunk`` is the form for many tokens of one sequence (a
prefill chunk, a whole training row): the kernel's grid is (channel blocks,
position blocks); a channel block's state ``[N, 512]`` stays in registers
while the block's positions go by one at a time, and crosses position blocks
in VMEM scratch. A token's B and C arrive as ROWS ``[1, N]``; their column
forms come off the diagonal of a broadcast (a masked lane reduce, as
``gdn_chunk_fwd`` makes its columns: no transpose in the kernel).

``selective_scan_step`` is the form for one token of each of ``s`` slots (a
decode step): it reads and writes every slot's state once. Given the serve
engine's whole state store ``[layers, slots, 1, N, I]`` with ``layer`` and
``slots`` it updates the named slots IN PLACE (the store is the kernel's
aliased operand, layer and slot sit in the BlockSpec index map: no layer of
the store is sliced out or copied, as ``gated_delta_step`` and
``flash_attention_decode`` take theirs).

Rows flagged invalid (``valid`` False: the padding of a short chunk, an
inactive slot) take ``Δ = 0``: ``exp(0) = 1`` and nothing is written; their
outputs are not to be read.

Dispatch follows ``ops/gated_delta.py``: the Pallas kernels
(``ssm_chunk_fwd``, ``ssm_step`` — the names the device trace carries) run
on a TPU, or anywhere under ``interpret=True``; off the TPU the same
mathematics runs in plain ``jnp``; a TPU run that is handed the ``jnp`` form
says so once (``_say_reference``). Forward only: nothing here defines a
gradient for the kernels (the ``jnp`` form differentiates as any ``jnp`` code
does).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from tf_operator_tpu.ops.flash_attention import _say_reference

_ROWS = 8  # positions a loop iteration of ``ssm_chunk_fwd`` unrolls: one sublane tile
_CHUNK_ROWS = 256  # positions a grid step of ``ssm_chunk_fwd`` takes at most
_STEP_VMEM_BUDGET = 8 << 20  # of the 16 MiB a kernel may scope by default


def _mask_invalid(delta, valid):
    return delta if valid is None else jnp.where(valid[:, None], delta, 0.0)


def _why_not(N: int, I: int, state_dtype) -> Optional[str]:
    if N % 8 or I % 128:
        return f"d_state={N}, channels={I}: not multiples of 8 and 128"
    if state_dtype != jnp.float32:
        return f"state dtype {state_dtype}"
    return None


def _column(row, eye):
    """A row [1, N] as a column [N, 1], off the diagonal of its broadcast."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _eye(N: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))


def _token(S, u, d, b_col, c_col, A, D):
    """One token of the recurrence. S, A [N, c]; u, d, D rows [1, c]; b_col,
    c_col [N, 1] -> (S, y [1, c])."""
    S = jnp.exp(d * A) * S + (d * u) * b_col
    return S, jnp.sum(S * c_col, axis=-2, keepdims=True) + D * u


# ---------------------------------------------------------------------------
# many tokens of one sequence
# ---------------------------------------------------------------------------


def _chunk_jnp(u, delta, B, C, A_t, D, state0):
    """Token by token under ``lax.scan``. u, delta [t, I]; B, C [t, N]; A_t
    [N, I]; D [I]; state0 [N, I] -> (y [t, I], state1)."""

    def body(S, x):
        u_t, d_t, b_t, c_t = x
        S, y = _token(S, u_t[None], d_t[None], b_t[:, None], c_t[:, None],
                      A_t, D[None])
        return S, y[0]

    state1, y = jax.lax.scan(body, state0, (u, delta, B, C))
    return y, state1


def _chunk_kernel(u_ref, d_ref, b_ref, c_ref, a_ref, dd_ref, s0_ref,
                  y_ref, s1_ref, s_scr, *, rows):
    """One channel block, one block of ``rows`` positions: grid (channel
    blocks, position blocks), the state in VMEM scratch across a channel
    block's position blocks and in the loop's carry inside one."""
    from jax.experimental import pallas as pl

    p = pl.program_id(1)

    @pl.when(p == 0)
    def _load():
        s_scr[...] = s0_ref[...]

    A, D = a_ref[...], dd_ref[...]
    eye = _eye(A.shape[0])

    def eight(i, S):
        at = pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS)
        u8, d8, b8, c8 = u_ref[at, :], d_ref[at, :], b_ref[at, :], c_ref[at, :]
        ys = []
        for j in range(_ROWS):
            S, y = _token(S, u8[j:j + 1], d8[j:j + 1], _column(b8[j:j + 1], eye),
                          _column(c8[j:j + 1], eye), A, D)
            ys.append(y)
        y_ref[at, :] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
        return S

    S = jax.lax.fori_loop(0, rows // _ROWS, eight, s_scr[...])
    s_scr[...] = S

    @pl.when(p == pl.num_programs(1) - 1)
    def _store():
        s1_ref[...] = S


def _channel_block(I: int) -> int:
    """Channels a grid step of ``ssm_chunk_fwd`` carries: at 512 a state of
    16 numbers a channel is 8 vector registers, and stays in them."""
    return next(c for c in (512, 256, 128) if I % c == 0)


def _chunk_call(u, delta, B, C, A_t, D, state0, rows, interpret):
    """u, delta [t, I] (t whole blocks of ``rows``), B, C [t, N] through the
    kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, I = u.shape
    N = B.shape[1]
    bc = _channel_block(I)
    by_pos = pl.BlockSpec((rows, bc), lambda c, p: (p, c))
    small = pl.BlockSpec((rows, N), lambda c, p: (p, 0))
    by_channel = lambda n: pl.BlockSpec((n, bc), lambda c, p: (0, c))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_chunk_kernel, rows=rows),
        grid=(I // bc, t // rows),
        in_specs=[by_pos, by_pos, small, small, by_channel(N), by_channel(1),
                  by_channel(N)],
        out_specs=[by_pos, by_channel(N)],
        out_shape=[jax.ShapeDtypeStruct((t, I), u.dtype),
                   jax.ShapeDtypeStruct((N, I), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_chunk_fwd",
    )(u, delta, B, C, A_t, D[None], state0)


def selective_scan_chunk(u, delta, B, C, A, D, state0, *, valid=None,
                         interpret: Optional[bool] = None):
    """``t`` consecutive tokens of ONE sequence through the recurrence.

    u, delta [t, I]; B, C [t, N]; A [I, N] (negative); D [I]; state0 [N, I]
    float32: the state the first token finds; valid [t] bool or None.
    Returns ``(y [t, I] in u's dtype, state1 [N, I] float32)``, state1 the
    state after the last VALID token. t is padded with invalid rows to whole
    position blocks. The kernel runs on a TPU (or under ``interpret=True``)
    when N is a multiple of 8 and I of 128."""
    t, I = u.shape
    N = B.shape[1]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    delta = _mask_invalid(f32(delta), valid)
    A_t = f32(A).T
    why_not = _why_not(N, I, jnp.float32)
    on_tpu = jax.default_backend() == "tpu"
    if why_not is None and (bool(interpret) or on_tpu):
        rows = min(_CHUNK_ROWS, -(-t // _ROWS) * _ROWS)
        pad = lambda x: jnp.pad(f32(x), ((0, -t % rows), (0, 0)))  # noqa: E731
        y, state1 = _chunk_call(pad(u), pad(delta), pad(B), pad(C), A_t, f32(D),
                                f32(state0), rows, bool(interpret))
        y = y[:t]
    else:
        if on_tpu:
            _say_reference("selective_scan_chunk", why_not)
        y, state1 = _chunk_jnp(f32(u), delta, f32(B), f32(C), A_t, f32(D),
                               f32(state0))
    return y.astype(u.dtype), state1


# ---------------------------------------------------------------------------
# one token of each of s slots
# ---------------------------------------------------------------------------


def _step_kernel(slot_ref, ud_ref, bc_ref, a_ref, dd_ref, s_ref, y_ref, so_ref):
    """One slot, one channel block: the state [N, c] is read once and written
    once, everything on the vector unit."""
    u, d = ud_ref[0, 0:1], ud_ref[0, 1:2]  # [1, c]
    eye = _eye(a_ref.shape[0])
    S, y = _token(s_ref[0, 0, 0], u, d, _column(bc_ref[0, 0:1], eye),
                  _column(bc_ref[0, 1:2], eye), a_ref[...], dd_ref[...])
    so_ref[0, 0, 0] = S
    y_ref[0] = y.astype(y_ref.dtype)


def _step_channels(N: int, I: int) -> int:
    """Channels one grid step of ``ssm_step`` takes: all of them, or the
    largest share in whole lanes whose state blocks (read and written,
    double-buffered) stay inside ``_STEP_VMEM_BUDGET``."""
    for parts in range(1, I // 128 + 1):
        if I % (128 * parts) == 0 and 4 * 4 * N * (I // parts) <= _STEP_VMEM_BUDGET:
            return I // parts
    return 0


def _step_call(u, delta, B, C, A_t, D, store, layer, slots, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_n, I = u.shape
    N = B.shape[1]
    bc = _step_channels(N, I)
    state_spec = pl.BlockSpec(
        (1, 1, 1, N, bc), lambda i, j, slot: (layer, slot[i], 0, 0, j))
    row_spec = pl.BlockSpec((1, 2, bc), lambda i, j, slot: (i, 0, j))
    by_channel = lambda n: pl.BlockSpec((n, bc), lambda i, j, slot: (0, j))  # noqa: E731
    y, store = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s_n, I // bc),
            in_specs=[row_spec,
                      pl.BlockSpec((1, 2, N), lambda i, j, slot: (i, 0, 0)),
                      by_channel(N), by_channel(1), state_spec],
            out_specs=[pl.BlockSpec((1, 1, bc), lambda i, j, slot: (i, 0, j)),
                       state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((s_n, 1, I), u.dtype),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        # operand 5 (the prefetched slots are operand 0) is the store: updated in place
        input_output_aliases={5: 1},
        interpret=interpret,
        name="ssm_step",
    )(slots.astype(jnp.int32), jnp.stack([u, delta], axis=1),
      jnp.stack([B, C], axis=1), A_t, D[None], store)
    return y[:, 0], store


def selective_scan_step(u, delta, B, C, A, D, state, *, valid=None,
                        layer: Optional[int] = None, slots=None,
                        interpret: Optional[bool] = None):
    """ONE token of each of ``s`` sequences through the recurrence.

    u, delta [s, I]; B, C [s, N]; A [I, N]; D [I]; valid [s] bool or None;
    ``state`` [s, N, I] float32, row i the state of sequence i — or the serve
    engine's whole store [layers, slots, 1, N, I] with ``layer`` (a Python
    int) and ``slots`` [s] int32 naming each row's slot (rows may share a
    slot nobody reads: the trash slot). Returns ``(y [s, I] in u's dtype, the
    states in ``state``'s form)``; the store comes back updated in place when
    the caller donated it."""
    whole = layer is not None
    if whole != (slots is not None) or state.ndim != (5 if whole else 3):
        raise ValueError(
            f"step state: [s,N,I], or the store [layers,slots,1,N,I] with "
            f"layer= and slots= (got {state.shape}, layer={layer})")
    s_n, I = u.shape
    N = B.shape[1]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    delta = _mask_invalid(f32(delta), valid)
    A_t = f32(A).T
    why_not = _why_not(N, I, state.dtype)
    if why_not is None and not _step_channels(N, I):
        why_not = f"a lane tile of the state [{N}, 128] does not fit the kernel's VMEM"
    on_tpu = jax.default_backend() == "tpu"
    if why_not is None and (bool(interpret) or on_tpu):
        store = state if whole else state[None, :, None]
        y, store = _step_call(
            f32(u), delta, f32(B), f32(C), A_t, f32(D), store,
            layer if whole else 0,
            slots if whole else jnp.arange(s_n, dtype=jnp.int32), bool(interpret))
        return y.astype(u.dtype), store if whole else store[0, :, 0]
    if on_tpu:
        _say_reference("selective_scan_step", why_not)
    S = state[layer, slots, 0] if whole else state
    S, y = _token(f32(S), f32(u)[:, None], delta[:, None], f32(B)[..., None],
                  f32(C)[..., None], A_t, f32(D)[None])
    S = S.astype(state.dtype)
    return y[:, 0].astype(u.dtype), state.at[layer, slots, 0].set(S) if whole else S
