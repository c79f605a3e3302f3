"""Grouped (block-diagonal) matmul as a Pallas TPU kernel — MoE experts
without capacity padding.

The capacity-queue formulation pads every expert's token queue to
cf·k·T/E rows, so expert FLOPs scale with cf (2x the active FLOPs at the
quality-safe cf=2 — the top structural term in the r4 MoE decomposition,
BASELINE.md). ``jax.lax.ragged_dot`` removes the padding in principle but
its XLA lowering measured ~19 TFLOP/s at moe-small bench shapes vs the
~50 TFLOP/s the same chip sustains on the equivalent dense matmul (r5
probe) — the lowering runs full-height masked matmuls per group. This
kernel is the Megablocks-style alternative the VERDICT asked for:

- Tokens arrive SORTED by expert and padded only to the row-block
  granularity B (total rows R = T·k rounded up per expert: overhead
  E·B/(T·k) worst case — 12.5% at B=256 on the bench shapes, vs 100%
  for cf=2).
- The grid walks (row-block i, col-tile j); a scalar-prefetched
  ``block_expert[i]`` array steers the WEIGHT BlockSpec index map, so
  each step loads exactly its expert's [k, bn] weight tile into VMEM —
  no [NB, k, n] gathered-weight materialization (the XLA block-diagonal
  einsum formulation measured slower than the padded vmap for exactly
  that traffic).
- r6, ep sharding: ``block_expert`` entries may be ``-1`` — SENTINEL
  blocks. The static grid still visits them (XLA needs static shapes;
  the ep all_to_all hands each shard a worst-case-sized buffer whose
  occupancy is data-dependent) but the kernel skips the dot and writes
  zeros, so sentinel blocks cost a VMEM zero-fill instead of MXU FLOPs
  — compute scales with OCCUPIED blocks, not the static bound. A caller
  whose buffer never crosses an exchange does not hand the kernels its
  bound at all: one chip's share of a layer (parallel.moe._expert_walk)
  calls them a SEGMENT at a time, as many segments as hold an occupied
  block, and only the last one's tail is sentinel.
- r6, fused combine epilogue: ``row_scale`` (one f32 per row) multiplies
  the output rows INSIDE the kernel. The MoE combine is
  out[t] = Σ_k w[t,k]·expert(x)[slot[t,k]]; scaling the down-projection's
  output rows by their gate weight in the epilogue turns the combine
  into a pure gather+sum and retires the separate f32 [T,k,d]
  weighted-reduction pass the einsum combine paid per layer.
- r6, dw grid: (expert, col-tile, block-walk) with scalar-prefetched
  per-expert block LISTS, so the output tile's index map depends only on
  grid indices — the f32 [k, bn] accumulator stays resident in VMEM
  across an expert's whole block walk. The previous grid steered the
  output window by ``block_expert[i]`` per step, which is data-dependent:
  the pipeline must conservatively round-trip the accumulator tile
  HBM↔VMEM at every step (k=768, bn=3072 ⇒ ~9 MB x2 per 256-row block —
  the dw walk the r5 roofline named as the kernel's remaining headroom).
  Walk steps beyond an expert's real block count are skipped
  (``l < nblocks[e]``) and their input index maps repeat the last valid
  block so the window doesn't change (no re-DMA); every (expert,
  col-tile) tile is ZEROED at walk step 0, so an expert with zero blocks
  gets an exact-zero gradient rather than uninitialized output memory.

Everything is differentiable through a custom_vjp: dx is the same kernel
with transposed weights (sentinel blocks write zero cotangents, which
keeps the upstream gather/scatter transposes clean), dw the accumulation
kernel, and row_scale's cotangent reuses the dx kernel's unscaled
product (ds[r] = x[r]·(dy[r]@Wᵀ) = dy[r]·(x[r]@W) — no extra matmul).
``gmm_grads`` is those cotangents without the vjp around them, dw still
in the accumulator's float32, for a caller that sums several calls'
before it rounds (the segment walk). The sort/pad bookkeeping lives in
parallel.moe (_moe_single_gmm / _moe_local_gmm).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp


def gmm_block_rows() -> int:
    """The block quantum B of every gmm dispatch: rows a grid step, and the
    unit the MoE buffers round to. ``TPUJOB_GMM_BLOCK_ROWS`` (default 256,
    the measured-fastest tile; tests set 8 so that partial blocks occur at
    test sizes), read when the caller TRACES, not when its program runs."""
    return int(os.environ.get("TPUJOB_GMM_BLOCK_ROWS", "256"))


def _pick_cols(n: int, target: int) -> int:
    """Largest 128-aligned divisor of n not exceeding target (falls back
    to n itself for small/odd widths — one tile)."""
    if n <= target:
        return n
    for cand in range(target - target % 128, 127, -128):
        if n % cand == 0:
            return cand
    return n


# Scoped VMEM a kernel gets without asking (v5e/v6e default) and the most
# it may ask for: the chip's VMEM is 128 MiB; a quarter stays with the
# compiler for its own temporaries (the f32 dot result, spills).
_VMEM_SCOPED_DEFAULT = 16 * 2**20
_VMEM_ASK_MAX = 96 * 2**20


def _resident_bytes(block_rows: int, k: int, bn: int, x_bytes: int,
                    w_bytes: int, out_bytes: int, scaled: bool,
                    acc_rows: int) -> int:
    """VMEM one grid step keeps resident: the [block_rows, k] row tile,
    the [k, bn] weight tile (fwd/dx) or f32 accumulator (dw), the
    [block_rows, bn] output (fwd/dx) or dy (dw) tile and, when scaled,
    the [block_rows, 1] scale column padded to a lane tile — each TWICE,
    because the pipeline double-buffers every blocked operand — plus the
    f32 [acc_rows, bn] product the dot leaves before the cast (fwd/dx:
    block_rows) or the accumulate (dw: k)."""
    tiles = (
        block_rows * k * x_bytes
        + k * bn * w_bytes
        + block_rows * bn * out_bytes
        + (block_rows * 128 * 4 if scaled else 0)
    )
    return 2 * tiles + 4 * acc_rows * bn


def _plan_cols(n: int, k: int, block_rows: int, x_bytes: int, w_bytes: int,
               out_bytes: int, scaled: bool, acc_rows: int, block_cols=None):
    """(column tile, vmem_limit_bytes or None) for one gmm kernel.

    The tile starts from the measured rule — a ~4 MB [k, bn] weight tile
    (fwd/dx) or f32 accumulator (dw); wider is faster: full-width tiles
    measured 60.6 TFLOP/s vs 52.0 at bn=512 on the moe-small shapes —
    and narrows (128-aligned divisors of n) until EVERYTHING the step
    keeps resident fits the scoped default. Where even the narrowest
    tile does not (k=14336 at mixtral-8x7b widths: the double-buffered
    [256, k] row tile alone is 14.7 MB) the kernel asks the compiler for
    what it needs, up to _VMEM_ASK_MAX; above that it is refused here,
    by name, not by a Mosaic allocation error."""
    budget = _VMEM_SCOPED_DEFAULT * 7 // 8
    if block_cols is not None:
        cands = [_pick_cols(n, block_cols)]
    else:
        bn = _pick_cols(n, max(128, (4 * 2**20) // (w_bytes * k)))
        cands = [bn] + [c for c in range(bn - bn % 128, 127, -128)
                        if c < bn and n % c == 0]
    for bn in cands:
        need = _resident_bytes(block_rows, k, bn, x_bytes, w_bytes,
                               out_bytes, scaled, acc_rows)
        if need <= budget:
            return bn, None
    if need * 5 // 4 > _VMEM_ASK_MAX:
        raise ValueError(
            f"gmm: k={k}, n={n}, block_rows={block_rows} keeps "
            f"{need / 2**20:.1f} MiB resident in VMEM at its narrowest "
            f"column tile ({bn}); the kernel has no contraction tiling, so "
            f"it is good up to ~{_VMEM_ASK_MAX // 2**20} MiB — lower "
            "block_rows or the contraction width"
        )
    return bn, need * 5 // 4


def _compiler_params(vmem_limit):
    from jax.experimental.pallas import tpu as pltpu

    if vmem_limit is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit))


def gmm(x, w, block_expert, *, row_scale=None, block_rows: int = 256,
        block_cols: int | None = None, interpret: bool = False):
    """y[r] = x[r] @ w[block_expert[r // block_rows]]  (· row_scale[r]).

    x: [R, k] with R % block_rows == 0, rows grouped so every row-block
    maps to ONE expert; w: [E, k, n]; block_expert: [R // block_rows]
    int32 — entries may be ``-1`` (sentinel: the block's output rows are
    written as zeros and no FLOPs are spent; used by the ep-sharded
    dispatch whose statically-sized all-to-all buffers are partially
    occupied). ``row_scale``: optional [R] f32 applied to the output
    rows inside the kernel (the fused MoE combine epilogue).
    Returns [R, n] in x.dtype (f32 MXU accumulation inside).
    Differentiable in x, w and row_scale (not in block_expert — routing
    indices). ``block_cols`` None = VMEM-budgeted auto (the
    measured-fastest full-width tiles where they fit). ``interpret``
    runs the Pallas interpreter (CPU test path)."""
    if row_scale is None:
        return _gmm(x, w, block_expert, block_rows, block_cols,
                    bool(interpret))
    return _gmm_scaled(x, w, row_scale, block_expert, block_rows,
                       block_cols, bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(x, w, block_expert, block_rows, block_cols, interpret):
    return _gmm_call(x, w, None, block_expert, block_rows, block_cols,
                     interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gmm_scaled(x, w, row_scale, block_expert, block_rows, block_cols,
                interpret):
    return _gmm_call(x, w, row_scale, block_expert, block_rows, block_cols,
                     interpret)


def _gmm_fwd_kernel(be_ref, x_ref, w_ref, o_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    e = be_ref[i]

    @pl.when(e >= 0)
    def _compute():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)

    @pl.when(e < 0)
    def _sentinel():
        # sentinel blocks still own output rows (static shapes): write
        # zeros so downstream gathers/transposes never see uninitialized
        # memory, but spend no MXU work
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_fwd_scaled_kernel(be_ref, x_ref, w_ref, s_ref, o_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    e = be_ref[i]

    @pl.when(e >= 0)
    def _compute():
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # the combine epilogue: gate-weight each output row while the
        # tile is still in VMEM — the [T,k,d] weighted-reduction pass
        # this replaces is pure HBM traffic
        o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)

    @pl.when(e < 0)
    def _sentinel():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_call(x, w, row_scale, block_expert, block_rows, block_cols,
              interpret, name="gmm_fwd"):
    """One grouped product. ``name`` is the kernel's name in a profiler
    trace and in the compiled text: ``gmm_fwd`` / ``gmm_fwd_scaled`` for
    the forward products, ``gmm_dx`` for the same kernel against
    transposed weights in the backward pass (``gmm_dw`` /
    ``gmm_dw_scaled``: _gmm_dw)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, k = x.shape
    E, k2, n = w.shape
    if k2 != k:
        raise ValueError(f"contraction mismatch: x k={k} vs w k={k2}")
    if R % block_rows:
        raise ValueError(f"rows {R} not divisible by block_rows {block_rows}")
    # Budget on the operands' own element sizes (an f32 x/w doubles
    # every tile) and on every tile the step keeps, not the weight alone.
    bn, vmem_limit = _plan_cols(
        n, k, block_rows, x.dtype.itemsize, w.dtype.itemsize,
        x.dtype.itemsize, row_scale is not None, block_rows, block_cols,
    )
    nb = R // block_rows

    in_specs = [
        pl.BlockSpec((block_rows, k), lambda i, j, be: (i, 0),
                     memory_space=pltpu.VMEM),
        # sentinel blocks (-1) clamp to expert 0's tile — a dead DMA the
        # skipped dot never reads
        pl.BlockSpec((1, k, bn),
                     lambda i, j, be: (jnp.maximum(be[i], 0), 0, j),
                     memory_space=pltpu.VMEM),
    ]
    operands = [block_expert, x, w]
    kernel = _gmm_fwd_kernel
    if row_scale is not None:
        in_specs.append(
            pl.BlockSpec((block_rows, 1), lambda i, j, be: (i, 0),
                         memory_space=pltpu.VMEM)
        )
        operands.append(row_scale.astype(jnp.float32).reshape(R, 1))
        kernel = _gmm_fwd_scaled_kernel
        name += "_scaled"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, n // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, bn), lambda i, j, be: (i, j),
                               memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, n), x.dtype),
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
        name=name,
    )(*operands)


def _dw_kernel(nb_ref, bl_ref, x_ref, dy_ref, dw_ref):
    from jax.experimental import pallas as pl

    e = pl.program_id(0)
    l = pl.program_id(2)  # block-walk step — INNERMOST (accumulation dim)

    @pl.when(l == 0)
    def _zero():
        # every (expert, col-tile) zeroes at walk start — an expert with
        # ZERO blocks gets an exact-zero dw tile, never uninitialized
        # kernel output memory
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(l < nb_ref[e])
    def _accum():
        dw_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...],
            (((0,), (0,)), ((), ())),  # [bR,k]ᵀ·[bR,bn] -> [k,bn]
            preferred_element_type=jnp.float32,
        )[None]


def _dw_scaled_kernel(nb_ref, bl_ref, x_ref, dy_ref, s_ref, dw_ref):
    from jax.experimental import pallas as pl

    e = pl.program_id(0)
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(l < nb_ref[e])
    def _accum():
        # dw_e = Σ (s⊙x)ᵀ·dy — the scale rides the x rows so the scaled
        # forward's weight cotangent needs no [R,d] pre-scaled copy of x
        dw_ref[...] += jax.lax.dot_general(
            x_ref[...] * s_ref[...], dy_ref[...],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )[None]


def _expert_block_lists(block_expert, n_experts: int, nb: int):
    """Per-expert block lists from a block→expert map: blist[e, l] = the
    l-th row-block of expert e (walk entries past an expert's count
    repeat its LAST valid block so the input window never changes on
    skipped steps — no re-DMA), nblocks[e] = its real count. Sentinel
    (-1) blocks belong to no expert."""
    be = block_expert.astype(jnp.int32)
    bucket = jnp.where(be >= 0, be, n_experts)  # sentinels into a spare bucket
    order = jnp.argsort(bucket, stable=True).astype(jnp.int32)
    cnt = jnp.bincount(bucket, length=n_experts + 1)[:n_experts].astype(jnp.int32)
    starts = jnp.cumsum(cnt) - cnt  # [E]
    walk = jnp.minimum(jnp.arange(nb, dtype=jnp.int32)[None, :],
                       jnp.maximum(cnt[:, None] - 1, 0))
    idx = jnp.clip(starts[:, None] + walk, 0, nb - 1)
    return cnt, order[idx].reshape(-1)  # nblocks [E], blist [E*nb]


def _gmm_dw(x, dy, w_shape, block_expert, block_rows, block_cols, interpret,
            row_scale=None):
    """dw[e] = Σ_{blocks of e} x_blᵀ @ dy_bl — grid (expert, col-tile,
    block-walk) over scalar-prefetched per-expert block lists. The
    output tile's index map is (e, 0, j): grid-only, so the f32
    accumulator stays in VMEM for the whole inner walk instead of
    round-tripping per step behind a data-dependent window."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, k = x.shape
    E, k2, n = w_shape
    # dw accumulates in an f32 [k, bn] output tile held across the inner
    # block walk — budget on 4 bytes, not the bf16 fwd tile
    bn, vmem_limit = _plan_cols(
        n, k, block_rows, x.dtype.itemsize, 4, dy.dtype.itemsize,
        row_scale is not None, k, block_cols,
    )
    nb = R // block_rows
    nblocks, blist = _expert_block_lists(block_expert, E, nb)

    def x_map(e, j, l, nbr, blr):
        return (blr[e * nb + l], 0)

    def dy_map(e, j, l, nbr, blr):
        return (blr[e * nb + l], j)

    in_specs = [
        pl.BlockSpec((block_rows, k), x_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((block_rows, bn), dy_map, memory_space=pltpu.VMEM),
    ]
    operands = [nblocks, blist, x, dy]
    kernel = _dw_kernel
    if row_scale is not None:
        in_specs.append(
            pl.BlockSpec((block_rows, 1), x_map, memory_space=pltpu.VMEM)
        )
        operands.append(row_scale.astype(jnp.float32).reshape(R, 1))
        kernel = _dw_scaled_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(E, n // bn, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, k, bn), lambda e, j, l, nbr, blr: (e, 0, j),
                               memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, k, n), jnp.float32),
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
        name="gmm_dw" if row_scale is None else "gmm_dw_scaled",
    )(*operands)


def gmm_grads(x, w_t, block_expert, dy, *, row_scale=None, block_rows: int = 256,
              block_cols: int | None = None, interpret: bool = False):
    """The cotangents of one ``gmm`` call as the kernels leave them:
    ``(dx, dw)``, with ``row_scale`` ``(dx, dw, ds)``. ``w_t`` is
    ``swapaxes(w, 1, 2)`` ([E, n, k]; a caller that walks segments past the
    same weights transposes once), ``dw`` stays the accumulator's FLOAT32
    (the custom_vjp rules below round it to the weights' dtype; the segment
    walk of parallel.moe sums its segments first), ``dx`` comes in
    ``x.dtype``.

    dx is the same grouped matmul against transposed weight tiles. With a
    scale ONE unscaled transposed product serves two cotangents:
      t = dy @ w_eᵀ  ⇒  dx = s ⊙ t   and   ds[r] = x[r]·t[r]
    (x·(dy@wᵀ) = (x@w)·dy — the scale's cotangent without recomputing
    the forward or saving an unscaled copy of y)."""
    E, n, k = w_t.shape
    t = _gmm_call(dy, w_t, None, block_expert, block_rows, block_cols,
                  interpret, name="gmm_dx")
    dw = _gmm_dw(x, dy, (E, k, n), block_expert, block_rows, block_cols,
                 interpret, row_scale=row_scale)
    if row_scale is None:
        return t.astype(x.dtype), dw
    t = t.astype(jnp.float32)
    dx = row_scale.astype(jnp.float32)[:, None] * t
    ds = jnp.sum(x.astype(jnp.float32) * t, axis=-1)
    return dx.astype(x.dtype), dw, ds.astype(row_scale.dtype)


def _gmm_fwd_rule(x, w, block_expert, block_rows, block_cols, interpret):
    y = _gmm_call(x, w, None, block_expert, block_rows, block_cols, interpret)
    return y, (x, w, block_expert)


def _gmm_bwd_rule(block_rows, block_cols, interpret, res, dy):
    x, w, block_expert = res
    # The [E, n, k] transpose materializes once per call (~2 copies of w in
    # HBM traffic — ~0.3 ms at moe-small shapes, negligible next to the
    # padded-FLOP term this kernel retires).
    dx, dw = gmm_grads(x, jnp.swapaxes(w, 1, 2), block_expert, dy,
                       block_rows=block_rows, block_cols=block_cols,
                       interpret=interpret)
    return dx, dw.astype(w.dtype), None


_gmm.defvjp(_gmm_fwd_rule, _gmm_bwd_rule)


def _gmm_scaled_fwd_rule(x, w, row_scale, block_expert, block_rows,
                         block_cols, interpret):
    y = _gmm_call(x, w, row_scale, block_expert, block_rows, block_cols,
                  interpret)
    return y, (x, w, row_scale, block_expert)


def _gmm_scaled_bwd_rule(block_rows, block_cols, interpret, res, dy):
    x, w, row_scale, block_expert = res
    dx, dw, ds = gmm_grads(x, jnp.swapaxes(w, 1, 2), block_expert, dy,
                           row_scale=row_scale, block_rows=block_rows,
                           block_cols=block_cols, interpret=interpret)
    return dx, dw.astype(w.dtype), ds, None


_gmm_scaled.defvjp(_gmm_scaled_fwd_rule, _gmm_scaled_bwd_rule)


# ---- the combine: a segment's rows added onto their tokens, in place -------


def _token_tile(tokens: int, d: int, rows_bytes: int):
    """(token tile, vmem_limit_bytes or None) for ``combine_rows``: the
    largest tile of at most 512 tokens that divides ``tokens`` (all of them
    where none does) and whose resident set fits what a kernel may ask for.
    Resident: the f32 [tile, d] carry tile in and out, each double-buffered,
    the product the dot leaves before it is added (twice: one being summed
    while the next is made), and the double-buffered row chunks
    (``rows_bytes``)."""
    for tile in [t for t in (512, 256, 128, 64, 32, 16, 8)
                 if tokens % t == 0] or [tokens]:
        need = 6 * tile * d * 4 + 2 * rows_bytes
        if need * 5 // 4 <= _VMEM_ASK_MAX:
            break
    else:
        raise ValueError(
            f"combine_rows: d={d} keeps {need / 2**20:.1f} MiB resident in "
            f"VMEM at a tile of {tile} tokens; the kernel has no column "
            f"tiling, so it is good up to ~{_VMEM_ASK_MAX // 2**20} MiB")
    if need <= _VMEM_SCOPED_DEFAULT * 7 // 8:
        return tile, None
    return tile, need * 5 // 4


def _combine_pairs(tok_lanes, tokens: int, tile: int, groups: int):
    """The (token tile, row chunk) pairs of one combine, tile-major, as
    (pair_tile [P], pair_chunk [P], n_pairs [1]) for the scalar prefetch,
    from ``tok_lanes`` [n_chunks, 1, chunk]: a row's token, -1 where invalid.
    A chunk lies inside ONE group's ascending rows, so the tiles it meets
    are the run from its first valid token's to its last valid token's, and
    consecutive chunks of a group share at most one tile: a group lists at
    most ``n_tiles - 1`` pairs beyond its chunks, which makes
    P = groups · n_tiles + n_chunks a bound no routing exceeds. Entries
    behind the last pair repeat it — the same blocks, so nothing is
    fetched for a step that adds nothing."""
    n_tiles, n_chunks = tokens // tile, tok_lanes.shape[0]
    # a chunk with no valid row: first = n_tiles, last = -1, meets nothing
    first = jnp.min(jnp.where(tok_lanes < 0, tokens, tok_lanes), axis=(1, 2)) // tile
    last = jnp.max(tok_lanes, axis=(1, 2)) // tile
    t = jnp.arange(n_tiles, dtype=jnp.int32)[:, None]
    meets = (first[None, :] <= t) & (t <= last[None, :])  # [n_tiles, n_chunks]
    upto = jnp.cumsum(meets.reshape(-1).astype(jnp.int32))
    n_pairs = upto[-1]
    n_max = groups * n_tiles + n_chunks
    nth = jnp.minimum(jnp.arange(1, n_max + 1, dtype=jnp.int32),
                      jnp.maximum(n_pairs, 1))
    at = jnp.minimum(
        jnp.searchsorted(upto, nth, side="left", method="compare_all"),
        n_tiles * n_chunks - 1).astype(jnp.int32)
    return at // n_chunks, at % n_chunks, n_pairs.reshape(1)


def _bf16_parts(x):
    """``x`` as bfloat16 arrays whose float32 sum is ``x``: itself, or a
    wider dtype's three 8-bit slices of the 24-bit significand, so that a
    0/1 matrix selects rows of any dtype EXACTLY through the MXU."""
    if x.dtype == jnp.bfloat16:
        return (x,)
    parts, rest = [], x.astype(jnp.float32)
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(jnp.float32)
    return tuple(parts)


def _combine_kernel(tile_ref, chunk_ref, n_ref, tok_ref, acc_ref, *refs):
    from jax.experimental import pallas as pl

    *rows_refs, o_ref = refs
    p = pl.program_id(0)
    tile = tile_ref[p]

    @pl.when(jnp.logical_or(p == 0, tile != tile_ref[jnp.maximum(p - 1, 0)]))
    def _enter():
        # a tile's pairs are one run of the grid: the carry's tile comes in
        # once, here, and leaves once, when the run ends
        o_ref[...] = acc_ref[...]

    @pl.when(p < n_ref[0])
    def _add():
        rows_in_tile, chunk = o_ref.shape[0], tok_ref.shape[-1]
        # [tile, chunk] 0/1: row r of the chunk goes to token tok[r]; an
        # invalid row's token is -1 and a token outside this tile matches
        # no line of the iota, so both are selected by nothing
        onto = jax.lax.broadcasted_iota(jnp.int32, (rows_in_tile, chunk), 0) == (
            tok_ref[0] - tile * rows_in_tile)
        onto = jnp.where(onto, 1.0, 0.0).astype(jnp.bfloat16)
        add = None
        for rows_ref in rows_refs:
            for part in _bf16_parts(rows_ref[...]):
                picked = jax.lax.dot_general(
                    onto, part, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                add = picked if add is None else add + picked
        o_ref[...] += add


def _combine_call(acc, rows, tok, valid, groups, block_rows, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, d = acc.shape
    n_rows = tok.shape[0]
    if acc.dtype != jnp.float32:
        raise ValueError(f"combine_rows sums in float32; acc is {acc.dtype}")
    if n_rows % block_rows or any(r.shape != (n_rows, d) for r in rows):
        raise ValueError(
            f"combine_rows: rows {[r.shape for r in rows]} for {n_rows} "
            f"tokens' indices in blocks of {block_rows}, onto {acc.shape}")
    # the MXU contracts 128 deep: a longer chunk selects no more rows a pass
    chunk = 128 if block_rows % 128 == 0 else block_rows
    tile, vmem_limit = _token_tile(
        tokens, d, sum(chunk * d * r.dtype.itemsize for r in rows))
    tok_lanes = jnp.where(valid, tok, -1).astype(jnp.int32).reshape(
        n_rows // chunk, 1, chunk)
    pair_tile, pair_chunk, n_pairs = _combine_pairs(
        tok_lanes, tokens, tile, groups)

    def rows_map(p, tiles, chunks, n):
        return (chunks[p], 0)

    def acc_map(p, tiles, chunks, n):
        return (tiles[p], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(pair_tile.shape[0],),
        in_specs=[
            pl.BlockSpec((1, 1, chunk), lambda p, tiles, chunks, n: (chunks[p], 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, d), acc_map, memory_space=pltpu.VMEM),
            *(pl.BlockSpec((chunk, d), rows_map, memory_space=pltpu.VMEM)
              for _ in rows),
        ],
        out_specs=pl.BlockSpec((tile, d), acc_map, memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={4: 0},  # acc, behind the three scalars and tok
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
        name="moe_combine",
    )(pair_tile, pair_chunk, n_pairs, tok_lanes, acc, *rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _combine(acc, rows, tok, valid, groups, block_rows, interpret):
    return _combine_call(acc, rows, tok, valid, groups, block_rows, interpret)


def _combine_fwd_rule(acc, rows, tok, valid, groups, block_rows, interpret):
    out = _combine_call(acc, rows, tok, valid, groups, block_rows, interpret)
    # of the rows only their dtypes are kept: what their cotangents come in
    return out, (tok, valid, tuple(jnp.zeros((), r.dtype) for r in rows))


def _combine_bwd_rule(groups, block_rows, interpret, res, g):
    tok, valid, like = res
    g_rows = jnp.where(valid[:, None], g[tok], 0)
    return g, tuple(g_rows.astype(r.dtype) for r in like), None, None


_combine.defvjp(_combine_fwd_rule, _combine_bwd_rule)


def combine_rows(acc, rows, tok, valid, *, groups: int, block_rows: int = 256,
                 interpret: bool = False):
    """``acc`` [T, d] float32 with ``rows[i]`` added at ``acc[tok[i]]`` for
    every ``valid[i]`` — the MoE combine (and its mirror, ``dx``) as ONE
    kernel, ``moe_combine``, in place of an XLA scatter-add.

    ``rows``: [R, d] in any float dtype, or a tuple of such arrays whose
    SUM is what is added (the backward's two input cotangents: summed in
    float32 inside, never written out as one); ``tok`` [R] int32, ``valid``
    [R] bool. The caller's order is what the kernel uses in place of a sort:
    every ``block_rows`` rows belong to ONE of at most ``groups`` groups (an
    expert), groups lie one behind the other, and the valid tokens of a
    group ascend (gmm's layout: a stable sort of t-major choices). An
    invalid row adds nothing, whatever its ``tok`` (its value must be
    finite: it meets a 0 of the selection, not a mask).

    The grid walks (token tile, row chunk) pairs, tile-major
    (_combine_pairs): a tile of ``acc`` stays in VMEM while every chunk of
    128 rows that holds one of its tokens is fetched by a plain block DMA
    and added through a 0/1 [tile, chunk] product on the MXU — exact
    selection of bfloat16 rows (_bf16_parts for wider ones), float32
    accumulation, in the pairs' order, which the routing alone decides: no
    atomics, nothing that differs run to run. ``acc`` is read and written
    once, in place (aliased to the result). Differentiable in ``acc`` (the
    cotangent itself) and ``rows`` (its rows gathered at ``tok``, zero where
    invalid). The token tile is the widest that fits (_token_tile)."""
    many = isinstance(rows, (tuple, list))
    return _combine(acc, tuple(rows) if many else (rows,), tok, valid,
                    int(groups), int(block_rows), bool(interpret))
