"""Flash attention as a Pallas TPU kernel (forward + custom-VJP backward).

Why a kernel at all: dense attention materializes the [t, t] score matrix
in HBM — O(t²) bytes of traffic on the op XLA cannot fuse away. The
flash/online-softmax formulation streams K/V blocks through VMEM and keeps
only [block_q, d] / [block_k, d] tiles plus per-row (m, l) accumulators
resident, so HBM traffic is O(t·d) and the MXU stays fed. The backward
pass recomputes P from the saved logsumexp instead of storing it (the
standard flash recipe), trading FLOPs for HBM exactly as TPUs want.

Kernel structure: TWO kernels, ``flash_fwd`` and ``flash_bwd_dqkv``. The
contraction dimension is a GRID dimension, not a VMEM-resident loop —
grid (b, h_kv, nq, nk) for the forward and (b, h_kv, nk, nq) for the
backward, with the running (m, l, acc) / (dk, dv) state in VMEM scratch
that persists across the innermost grid dimension (TPU grids iterate the
last dimension sequentially, which is what makes carried scratch sound).
VMEM holds only one block of each operand at a time, so sequence length
is bounded by HBM, not by the ~16 MB VMEM budget. Causal grids skip
above-diagonal blocks with `pl.when` (zero compute, still one grid
step). The backward is ONE kernel over (k block, q block) pairs: a live
pair builds s, the mask, p, dp and ds once and feeds all three products
(five dots; a dq kernel beside a dk/dv kernel ran seven and the f32
vector work twice). dk/dv are the inner walk's scratch; dq, whose q
block comes round again an inner walk later, accumulates in f32 HBM
tiles the kernel reads and writes back itself (``_bwd_kernel``).

GQA is folded into the q tile: the grid's head dimension iterates K/V
heads, and each step's q tile is [g·block_q, d] — the g query heads of
the group stacked on the sublane dim (g = h // h_kv, 1 for classic MHA).
One K/V block load therefore serves every query head of its group, so
in-kernel K/V HBM reads scale with h_kv, not h — the whole point of GQA
(llama2-70b's 64q/8kv shape reads 8x less K/V than a repeat would), and
the s = q·kᵀ contraction sees a g·block_q-row tile, which feeds the MXU
better than g separate block_q-row tiles.

Layout: q/k/v are [b, t, h, d] (the model layout), transposed to
[b, h, t, d] so seq is the sublane dim and head_dim the lane dim. The
kernel path engages on TPU when t divides into 8-aligned blocks and
either d % 128 == 0 (any length) or d % 64 == 0 with t >= 2048 — the
measured END-TO-END crossover for hd=64 models (gpt-small/bert-base):
in-model the kernel wins 1.49x at t=2048 but loses to dense at t=512
under full remat, even though the isolated attention probe favors it at
every length (an earlier installation's reading; BASELINE.md). Off-TPU
the entry falls back to a jnp reference (same math, same f32 softmax) so
one model config runs everywhere; ``interpret=True`` forces the Pallas
interpreter — the CPU test path for the kernel logic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: exp() of a whole masked
                 # row must give 0 without generating inf-inf = nan
LSE_LANES = 128  # lse/delta carry a full lane dim to satisfy TPU tiling


_said: set = set()


def _say_reference(op: str, why: str) -> None:
    """A TPU run that was handed the jnp reference instead of the kernel
    says so, once per distinct reason — a fallback nobody can see is how
    a slow program gets measured as if it were the kernel's."""
    if (op, why) not in _said:
        _said.add((op, why))
        import logging

        logging.getLogger(__name__).warning(
            "%s: running the jnp reference on a TPU, not the Pallas "
            "kernel (%s)", op, why,
        )


def _use_kernel(t: int, d: int, block_q: int, block_k: int, interpret: bool) -> bool:
    if t % block_q or t % block_k:
        return False  # kernels assume exact tiling; odd lengths fall back
    if block_q % 8 or block_k % 8:
        return False  # clamped blocks (short t) must stay sublane-aligned
    if interpret:
        return True
    if jax.default_backend() != "tpu":
        return False
    if d % 128 == 0:
        return True
    # hd=64 (gpt-small, bert-base): the kernel serves long context —
    # measured END-TO-END in the model it wins from t=2048 (train MFU
    # 28.9% vs 19.4% dense, 1.49x; isolated attention 1.60x @ 2048 up to
    # 27x @ 8192 where dense spills) but loses at t=512 under full remat
    # (36.4% vs 38.0% — the in-model remat interaction the r1 fwd-only
    # probe couldn't see). Gate on the measured crossover.
    return d % 64 == 0 and t >= 2048


def reference_attention(q, k, v, causal: bool = False, window: int = 0):
    """Dense attention, f32 softmax — the correctness oracle and the
    off-TPU fallback (same contract as the kernel path). GQA-native: k/v
    may carry fewer heads than q (h % h_kv == 0); the grouped einsum
    keeps the group dim in the contraction instead of materializing
    repeated K/V heads. One implementation: softmax(s) == exp(s − lse),
    so this is the lse variant with the lse dropped. ``window`` > 0
    (causal only): query i sees key j iff j <= i and i - j < window."""
    return reference_attention_lse(q, k, v, causal=causal, window=window)[0]


def _check_window(causal: bool, window: int) -> None:
    if window and not causal:
        raise ValueError("a sliding window needs causal=True")


def _causal_mask(s, qi, kb, block_q, block_k, window=0):
    """Causal mask for an s tile whose rows may stack g group members:
    row r is sequence position qi*block_q + (r % block_q) — members share
    the same q sequence block, so position repeats per member (for g=1,
    r % block_q == r and this is the classic tile mask). ``window`` > 0
    also hides keys at or beyond ``window`` positions behind the query."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % block_q
    qpos = qi * block_q + rows
    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    visible = kpos <= qpos
    if window:
        visible = visible & (qpos - kpos < window)
    return jnp.where(visible, s, NEG_INF)


def _block_live(qb, kb, block_q, block_k, causal, window):
    """Whether the (q block, k block) pair holds any visible score: under
    causal masking blocks right of the diagonal hold none, and with a
    window neither do blocks whose LAST key lies ``window`` or more
    behind the q block's FIRST row. Dead blocks are still grid steps;
    they run no dot."""
    if not causal:
        return True
    live = kb * block_k <= qb * block_q + block_q - 1
    if window:
        live = live & (kb * block_k + block_k - 1 > qb * block_q - window)
    return live


def _nearest_live_q(ki, qb, block_q, block_k, nq, window):
    """The q block nearest ``qb`` whose pair with k block ``ki`` is live
    under causal masking — ``_block_live`` solved for the q block: the
    first holds the k block's first key, the last (with a window) the
    last row that still sees its last key. A dead pair runs nothing, so
    it should fetch nothing either: a backward grid step whose pair is
    dead names THIS block for its q-side tiles — a block whose index did
    not change is not fetched, and the dead steps in front of a k block's
    first live pair prefetch it."""
    first = (ki * block_k) // block_q
    last = nq - 1
    if window:
        last = jnp.minimum(last, (ki * block_k + block_k + window - 2) // block_q)
    return jnp.clip(qb, first, last)


def _gqa_specs(g, block_q, block_k, q_grid_dim, q_block=lambda ki, qb: qb):
    """BlockSpec factories shared by both folded-GQA grids, each over
    its operand's last (lane) dim: q, k and dq/dk carry the q/k width, v,
    o, do and dv the v width (the two differ under latent attention).

    Query-side tiles are (1, g, block_q, last) — the g query heads of kv
    head ``hk`` (contiguous in the h dim) stacked over one sequence
    block. ``q_grid_dim`` says which innermost grid dim walks q blocks:
    2 for the forward's (b, h_kv, nq, nk) grid, 3 for the backward's
    (b, h_kv, nk, nq); the other innermost dim walks K/V blocks.
    ``q_block(ki, qb)`` (backward grid only) is the q block a step's
    q-side tiles hold: its own, unless ``_bwd`` spares dead pairs the
    fetch. Returns (q_spec_factory, kv_spec_factory)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if q_grid_dim == 2:
        q_idx = lambda bi, hk, qi, kb: (bi, hk, qi, 0)
        kv_idx = lambda bi, hk, qi, kb: (bi, hk, kb, 0)
    else:
        q_idx = lambda bi, hk, ki, qb: (bi, hk, q_block(ki, qb), 0)
        kv_idx = lambda bi, hk, ki, qb: (bi, hk, ki, 0)

    def q_spec(shape_last):
        return pl.BlockSpec(
            (1, g, block_q, shape_last), q_idx, memory_space=pltpu.VMEM
        )

    def kv_spec(shape_last):
        return pl.BlockSpec(
            (1, 1, block_k, shape_last), kv_idx, memory_space=pltpu.VMEM
        )

    return q_spec, kv_spec


# ---------------------------------------------------------------------------
# forward kernel — grid (b, h_kv, nq, nk), carry (m, l, acc) in scratch
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal, block_q, block_k, scale, g, window=0):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    kb = pl.program_id(3)
    nkb = pl.num_programs(3)
    d, dv = q_ref.shape[-1], v_ref.shape[-1]
    rows = g * block_q

    @pl.when(kb == 0)
    def _init():
        m_scr[:, :] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:, :] = jnp.zeros_like(l_scr)
        acc_scr[:, :] = jnp.zeros_like(acc_scr)

    # Above-diagonal blocks contribute nothing under causal masking (every
    # group member in the tile shares the same q sequence block), nor do
    # blocks wholly left of the window. A row whose keys in a live block
    # are ALL outside its window adds exp(0) terms to l and acc; the
    # first block that holds one of its keys (its diagonal at the latest)
    # rescales both by alpha = exp(NEG_INF - m) = 0, so nothing survives.
    live = _block_live(qi, kb, block_q, block_k, causal, window)

    @pl.when(live)
    def _step():
        q = q_ref[0].reshape(rows, d).astype(jnp.float32) * scale  # [g·bq, d]
        k = k_ref[0, 0, :, :].astype(jnp.float32)                  # [bk, d]
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [g·bq, bk]
        if causal:
            s = _causal_mask(s, qi, kb, block_q, block_k, window)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:, :] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:, :] = l_scr[:, :] * alpha[:, None] + jnp.sum(p, axis=1)[:, None]
        acc_scr[:, :] = acc_scr[:, :] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kb == nkb - 1)
    def _finish():
        l = l_scr[:, 0]
        o_ref[0] = (acc_scr[:, :] / l[:, None]).reshape(g, block_q, dv).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            (m_scr[:, 0] + jnp.log(l))[:, None], (rows, LSE_LANES)
        ).reshape(g, block_q, LSE_LANES)


def _fwd(q, k, v, causal, block_q, block_k, interpret, window=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    h_kv, dv = k.shape[2], v.shape[3]  # dv: the v (and o) width, d: q/k's
    g = h // h_kv  # GQA group: g query heads fold into one q tile
    scale = d**-0.5
    # [b, t, h, d] -> [b, h, t, d]: sequence in the sublane dim, head_dim in
    # lanes — the MXU-native layout for the q·kᵀ and p·v contractions.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    q_by_qi, kv_by_kb = _gqa_specs(g, block_q, block_k, q_grid_dim=2)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
        scale=scale, g=g, window=window,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h_kv, t // block_q, t // block_k),
        in_specs=[q_by_qi(d), kv_by_kb(d), kv_by_kb(dv)],
        out_specs=[q_by_qi(dv), q_by_qi(LSE_LANES)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g * block_q, LSE_LANES), jnp.float32),  # running max m
            pltpu.VMEM((g * block_q, LSE_LANES), jnp.float32),  # running sum l
            pltpu.VMEM((g * block_q, dv), jnp.float32),         # output accumulator
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    # Residuals carry the COMPACT [b, h, t] lse (the kernel's LSE_LANES
    # lane-broadcast is rebuilt in _bwd): saved residuals under a
    # selective-remat policy would otherwise store 128x the lse bytes.
    return o.transpose(0, 2, 1, 3), (qt, kt, vt, o, lse[..., 0])


# ---------------------------------------------------------------------------
# backward kernel — grid (b, h_kv, nk, nq): dk/dv in scratch, dq in HBM
# ---------------------------------------------------------------------------


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_hbm, dk_ref, dv_ref, dk_scr, dv_scr, dq_buf, sem, state,
                *, causal, block_q, block_k, scale, g, window=0):
    """dq, dk and dv of one (q block, k block) pair from ONE s, mask, p,
    dp and ds: five dots. The q tile stacks the g query heads of the group
    ([g·block_q, d]), so the row contractions pᵀ·do and dsᵀ·q sum every
    group member in one matmul.

    The k block is the OUTER walk: dk/dv accumulate in [block_k, ·] f32
    scratch across the innermost q-block dim and are written once at its
    end. dq's accumulator belongs to the q block, which comes round again
    a whole inner walk later, so it lives in HBM as f32 tiles
    ``dq_hbm[b, h_kv, nq, g·block_q, d in whole lanes]`` (space ANY) that the
    kernel reads, adds to and writes back itself: the read is started
    before the pair's first dot and waited for after its fourth, the
    write-back is waited for one live pair later, just before the next
    one starts — at most one read and one write are in flight. A tile's
    read must not meet its own write-back, and which tile the live pair
    BEFORE wrote is a matter of the mask, not of the grid's walk: dead
    steps run nothing, so the last live pair of one k row and the first of
    the next are neighbours in time (causal with block_q >= block_k, a
    window of a q block or less, an inner walk of one block: all name the
    same q block twice running). So ``state`` remembers the q block of the
    write in flight, and a pair that is about to read that very tile waits
    for the write first; every other pair pays nothing. A q block's FIRST
    live pair (k block 0, or the first inside the window) writes without
    reading, so the buffer needs no zeros; dead pairs move nothing.
    ``state`` (SMEM) = [a write is in flight, the buffer slot the next
    pair takes, the q block of the write in flight]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bi, hk = pl.program_id(0), pl.program_id(1)
    ki, qb = pl.program_id(2), pl.program_id(3)
    nqb = pl.num_programs(3)
    d, dv = q_ref.shape[-1], v_ref.shape[-1]
    rows = g * block_q
    ids = (bi, hk, ki, qb)

    @pl.when(sum(ids) == 0)
    def _start():
        state[0] = 0
        state[1] = 0
        state[2] = -1

    @pl.when(qb == 0)
    def _init():
        dk_scr[:, :] = jnp.zeros_like(dk_scr)
        dv_scr[:, :] = jnp.zeros_like(dv_scr)

    def wait_write():
        @pl.when(state[0] == 1)
        def _():
            pltpu.make_async_copy(dq_buf.at[0], dq_hbm.at[0, 0, 0], sem.at[1]).wait()
            state[0] = 0

    # Causal: q-blocks strictly before this k-block see none of it; with
    # a window, neither do q-blocks wholly past it.
    live = _block_live(qb, ki, block_q, block_k, causal, window)
    first = ki == 0
    if window:
        first |= ~_block_live(qb, ki - 1, block_q, block_k, causal, window)

    @pl.when(live)
    def _step():
        slot = state[1]
        tile, buf = dq_hbm.at[bi, hk, qb], dq_buf.at[slot]
        read = pltpu.make_async_copy(tile, buf, sem.at[0])

        @pl.when(~first)
        def _():
            # the live pair before wrote this very tile: it was this
            # (b, h_kv)'s, whose first live pair reads nothing
            @pl.when(state[2] == qb)
            def _():
                wait_write()

            read.start()

        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        q = q_ref[0].reshape(rows, d).astype(jnp.float32) * scale
        do = do_ref[0].reshape(rows, dv).astype(jnp.float32)
        lse = lse_ref[0].reshape(rows, LSE_LANES)[:, :1]      # value replicated on lanes
        delta = delta_ref[0].reshape(rows, LSE_LANES)[:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [g·bq, bk]
        if causal:
            s = _causal_mask(s, qb, ki, block_q, block_k, window)
        p = jnp.exp(s - lse)  # [g·bq, bk]
        dv_scr[:, :] = dv_scr[:, :] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, dv] — row contraction sums the whole group
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [g·bq, bk]
        ds = p * (dp - delta)
        dk_scr[:, :] = dk_scr[:, :] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, d]
        dq = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [g·bq, d]

        @pl.when(first)
        def _():
            buf[:, :d] = dq

        @pl.when(~first)
        def _():
            read.wait()
            buf[:, :d] = buf[:, :d] + dq

        wait_write()  # of the pair before, from the other slot
        pltpu.make_async_copy(buf, tile, sem.at[1]).start()
        state[0] = 1
        state[1] = 1 - slot
        state[2] = qb

    @pl.when(qb == nqb - 1)
    def _finish():
        dk_ref[0, 0, :, :] = dk_scr[:, :].astype(dk_ref.dtype)  # q pre-scaled
        dv_ref[0, 0, :, :] = dv_scr[:, :].astype(dv_ref.dtype)

    @pl.when(sum(ids) == sum(pl.num_programs(i) - 1 for i in range(4)))
    def _drain():
        wait_write()


def _bwd(causal, block_q, block_k, interpret, residuals, g, dlse=None,
         window=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qt, kt, vt, o, lse_c = residuals
    b, h, t, d = qt.shape
    h_kv, dv = kt.shape[1], vt.shape[3]
    grp = h // h_kv  # GQA group size (1 = classic MHA)
    nq, rows = t // block_q, grp * block_q
    d_tile = -(-d // LSE_LANES) * LSE_LANES  # a dq tile is whole lanes wide
    scale = d**-0.5
    # Rebuild the kernel's lane-broadcast lse layout from the compact
    # [b, h, t] residual (transient — lives only through the bwd kernel).
    lse = jnp.broadcast_to(lse_c[..., None], (b, h, t, LSE_LANES))
    do = g.transpose(0, 2, 1, 3)
    # delta_i = rowsum(do_i * o_i) — the softmax-jacobian correction term —
    # lane-broadcast to the same [b,h,t,LSE_LANES] layout as lse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        # lse cotangent (the flash_attention_lse entry): ∂lse_i/∂s_ij = p_ij,
        # so the s gradient gains p_ij·g_i — algebraically ds = p·(dp −
        # (delta − g)), i.e. the whole lse-gradient path folds into the
        # delta term and the kernel runs UNCHANGED. dlse arrives [b, t, h].
        delta = delta - dlse.astype(jnp.float32).transpose(0, 2, 1)
    delta = jnp.broadcast_to(delta[..., None], (b, h, t, LSE_LANES))

    # Query-side tiles fold the group ([grp·block_q, d] rows), so one K/V
    # block load serves all grp query heads and the scratch accumulates
    # the whole group per grid step (see _bwd_kernel). Under causal
    # masking a dead pair's step names the nearest live q block's tiles.
    q_block = functools.partial(
        _nearest_live_q, block_q=block_q, block_k=block_k, nq=nq,
        window=window) if causal else (lambda ki, qb: qb)
    q_by_qb, kv_by_ki = _gqa_specs(grp, block_q, block_k, q_grid_dim=3,
                                   q_block=q_block)

    kernel = functools.partial(
        _bwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
        scale=scale, g=grp, window=window,
    )
    dq, dk, dv_ = pl.pallas_call(
        kernel,
        grid=(b, h_kv, t // block_k, nq),
        in_specs=[q_by_qb(d), kv_by_ki(d), kv_by_ki(dv), q_by_qb(dv),
                  q_by_qb(LSE_LANES), q_by_qb(LSE_LANES)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), kv_by_ki(d),
                   kv_by_ki(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h_kv, nq, rows, d_tile), jnp.float32),
            jax.ShapeDtypeStruct((b, h_kv, t, d), kt.dtype),
            jax.ShapeDtypeStruct((b, h_kv, t, dv), vt.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
            pltpu.VMEM((2, rows, d_tile), jnp.float32),  # dq: read / write-back
            pltpu.SemaphoreType.DMA((2,)),          # dq read, dq write-back
            pltpu.SMEM((3,), jnp.int32),
        ],
        interpret=interpret,
        name="flash_bwd_dqkv",
    )(qt, kt, vt, do, lse, delta)

    # dq's tiles [b, h_kv, nq, grp·block_q, d] → the model's [b, t, h, d]:
    # scaled and rounded once, as the scratch's last write was.
    dq = (dq[..., :d] * scale).astype(qt.dtype).reshape(
        b, h_kv, nq, grp, block_q, d)
    dq = dq.transpose(0, 2, 4, 1, 3, 5).reshape(b, t, h, d)
    return dq, dk.transpose(0, 2, 1, 3), dv_.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


# Selective rematerialization contract: every residual of the custom-VJP
# is a model-layout value with a checkpoint_name a policy can save — the
# q/k/v INPUTS (tagged in the public entries, outside the call) and the
# two OUTPUTS (o and the compact [b, t, h] f32 lse, tagged INSIDE the fwd
# rule). A policy saving flash_q/k/v retires the qkv projection recompute
# (the residual q/k/v are literally the saved tagged values); one saving
# flash_o/flash_lse retires the replay of the flash forward itself, ~2 of
# the 31 per-layer fwd matmul units at gqa-2048 shapes: the backward
# kernel reads the o and lse the forward wrote instead of an identical
# second copy. Per-layer HBM cost: flash_o b·t·h·dv in the activation
# dtype (50.3 MB at gqa-2048 b=6, the size of flash_q or resid_mid),
# flash_lse b·t·h·4 B (0.8 MB). The model's ``*_mid`` remat tiers name
# both (models/transformer.py _REMAT_SAVE_SETS).
#
# What r5 measured as "the boundary is opaque on the output side"
# (print_saved_residuals showing only the arguments, compiled FLOPs
# identical with and without internal tags) was ``optimize_remat=True``:
# that wraps the fwd rule in an opaque remat-optimised call whose inner
# names no policy sees. The vjp is registered WITHOUT it — the fwd's two
# outputs come from one kernel, so there was nothing for that
# optimisation to drop — and the inner tags are then visible (jax 0.9.0).
# Tags outside the call do nothing for the outputs either way: the
# residual is the fwd rule's own value, not the caller's copy of it.
# The bwd pays three cheap re-transposes to kernel layout (<1% of step
# time; the fwd does not store its transposed copies).
FLASH_SAVE_NAMES = ("flash_q", "flash_k", "flash_v", "flash_o", "flash_lse")


def _tag_inputs(q, k, v):
    from jax.ad_checkpoint import checkpoint_name

    return (
        checkpoint_name(q, "flash_q"),
        checkpoint_name(k, "flash_k"),
        checkpoint_name(v, "flash_v"),
    )


def _tag_outputs(out, lse_pub):
    """The (o, lse) a names policy may keep for the backward: the
    model-layout output and the compact [b, t, h] f32 row-logsumexp (not
    the kernel's 128-lane copy). Identities unless a policy names them."""
    from jax.ad_checkpoint import checkpoint_name

    return (
        checkpoint_name(out, "flash_o"),
        checkpoint_name(lse_pub, "flash_lse"),
    )


def _to_kernel_res(q, k, v, o, lse_pub):
    """Model-layout residuals → the kernel-layout tuple _bwd consumes."""
    tr = lambda a: a.transpose(0, 2, 1, 3)
    return tr(q), tr(k), tr(v), tr(o), lse_pub.transpose(0, 2, 1)


def _lse_public(lse_c):
    """Compact kernel residual [b, h, t] → the public [b, t, h] f32
    row-logsumexp."""
    return lse_c.transpose(0, 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, block_q, block_k, interpret, window=0):
    out, res = _fwd(q, k, v, causal, block_q, block_k, interpret, window)
    return out, _lse_public(res[4])


def _flash_lse_fwd(q, k, v, causal, block_q, block_k, interpret, window=0):
    out, res = _fwd(q, k, v, causal, block_q, block_k, interpret, window)
    out, lse_pub = _tag_outputs(out, _lse_public(res[4]))
    # model-layout residuals: q/k/v are the (possibly checkpoint_name-
    # tagged) INPUTS and out/lse the tagged outputs — under a names policy
    # they are saved values, so the backward reconstruction replays
    # neither the qkv projections nor this kernel.
    return (out, lse_pub), (q, k, v, out, lse_pub)


def _flash_lse_bwd(causal, block_q, block_k, interpret, window, residuals, cts):
    do, dlse = cts
    res = _to_kernel_res(*residuals)
    return _bwd(causal, block_q, block_k, interpret, res, do, dlse=dlse,
                window=window)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def reference_attention_lse(q, k, v, causal: bool = False, window: int = 0):
    """Dense (o, lse) — the fallback for flash_attention_lse. lse is the
    row logsumexp of the scaled (masked) scores, [b, t, h] f32; rows with
    every key masked get lse = NEG_INF — the finite -1e30 sentinel, NOT
    -inf (their o is the uniform-softmax artifact over NEG_INF scores).
    Downstream merges must treat lse <= NEG_INF/2 as masked/weight-0 the
    way ring_attention's _merge_partials does; an isinf check will NOT
    catch it."""
    _check_window(causal, window)
    b, tq, hq, d = q.shape
    h_kv = k.shape[2]
    scale = d**-0.5
    if hq != h_kv:
        g = hq // h_kv
        q5 = q.reshape(b, tq, h_kv, g, d)
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q5, k, preferred_element_type=jnp.float32
        ) * scale
    else:
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
    if causal:
        tk = k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        if window:
            mask = mask & ~jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq - window)
        s = jnp.where(mask.reshape((1,) * (s.ndim - 2) + mask.shape), s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)  # [b,h,q] or [b,h_kv,g,q]
    p = jnp.exp(s - lse[..., None]).astype(q.dtype)
    if hq != h_kv:
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(
            b, tq, hq, v.shape[-1])
        lse = lse.reshape(b, h_kv * (hq // h_kv), tq)  # head hi = hk·g + gi
    else:
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return out, lse.transpose(0, 2, 1)


def flash_attention_lse(
    q,
    k,
    v,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    force_kernel: Optional[bool] = None,
    window: int = 0,
):
    """flash_attention returning ``(o, lse)`` with lse [b, t, h] f32 —
    the row logsumexp of scaled scores. This is the composition surface
    for blockwise/distributed attention (ring attention's per-hop local
    compute): normalized partial outputs merge exactly across key blocks
    via their lse. Gradients are exact THROUGH lse — the lse cotangent
    folds into the backward kernel's delta term (see _bwd), so callers
    may use lse in differentiable math. Same dispatch gate and fallback
    as flash_attention — including the explicit-block clamp/rounding
    documented there. Fully-masked rows report the finite NEG_INF
    sentinel, not -inf (see reference_attention_lse)."""
    use, block_q, block_k = _dispatch(q, k, v, block_q, block_k, interpret,
                                      force_kernel)
    _check_window(causal, window)
    q, k, v = _tag_inputs(q, k, v)
    if not use:
        # the dense fallback's (o, lse) under the kernel path's names, so
        # that a name means the same on both paths
        return _tag_outputs(
            *reference_attention_lse(q, k, v, causal=causal, window=window))
    return _flash_lse(q, k, v, causal, block_q, block_k, bool(interpret),
                      int(window))


def _pick_block(t: int, target: int) -> int:
    """Largest 8-aligned divisor of t not exceeding target (grid overhead
    falls with block size: 512/1024 blocks measured 2.2x faster than
    128/128 at t=2048 on v5e). A why_not target is first rounded down
    to a multiple of 8 — the candidate scan steps by 8, so an unaligned
    start would only ever visit unaligned candidates and the gate would
    silently reject the kernel (the g=3/5/12 GQA default targets hit
    exactly this). Returns target when none divides — the _use_kernel
    gate then routes to the dense fallback."""
    target = max(8, target - target % 8)
    if t <= target:
        return t
    for cand in range(target, 7, -8):
        if t % cand == 0:
            return cand
    return target


def _dispatch(q, k, v, block_q, block_k, interpret, force_kernel):
    """Shared entry logic: validate head shapes, pick group-bounded
    blocks, and decide kernel-vs-fallback. Returns (use, block_q,
    block_k)."""
    t, d = q.shape[1], q.shape[3]
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"q heads {q.shape[2]} not a multiple of kv heads {k.shape[2]}"
        )
    if k.shape[2] != v.shape[2]:
        raise ValueError(f"k/v head mismatch: {k.shape[2]} vs {v.shape[2]}")
    if k.shape[3] != d:
        raise ValueError(f"q/k width mismatch: {d} vs {k.shape[3]}")
    grp = q.shape[2] // k.shape[2]
    # Folded tiles and scratch scale as grp*block_q rows, so the q-block
    # target is bounded by the group: default lands on the measured
    # 512-row sweet spot, and an EXPLICIT block_q is clamped to 1024 rows
    # — without the clamp a block size that compiled fine pre-fold (per-
    # query-head tiles) would blow VMEM at large g instead of running.
    block_q = _pick_block(
        t, max(8, min(block_q or (512 // grp), 1024 // grp))
    )
    block_k = _pick_block(t, block_k or 1024)
    use = _use_kernel(t, d, block_q, block_k, bool(interpret))
    dv = v.shape[3]
    if dv != d:  # the v width passes the same lane gate on its own
        use = use and _use_kernel(t, dv, block_q, block_k, bool(interpret))
    if force_kernel is not None:
        # HARD constraints still bind (exact tiling; a compiled Pallas TPU
        # kernel cannot run on CPU — off-TPU only the interpreter engages).
        # The d % 128 lane HEURISTIC is deliberately overridden: the kernel
        # is correct at any d (Mosaic pads the lane dim) — d % 128 is a
        # performance gate, and measuring shapes on the other side of it
        # is exactly what this hook is for.
        use = force_kernel and not (
            t % block_q or t % block_k or block_q % 8 or block_k % 8
        ) and (bool(interpret) or jax.default_backend() == "tpu")
    return use, block_q, block_k


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    force_kernel: Optional[bool] = None,
    window: int = 0,
):
    """Self-attention over [b, t, h, d] with softmax(q·kᵀ/√d)·v semantics.

    v may be narrower or wider than q and k (latent attention: q/k 192
    wide, v 128): q, k [b, t, h(_kv), d], v [b, t, h_kv, dv], the result
    [b, t, h, dv], the scale 1/√d of the q/k width. The kernels read each
    operand at its own width (no padded copy of v in HBM); at dv == d they
    are the kernels they were.

    ``window`` > 0 (causal only) is a sliding window: query i sees key j
    iff j <= i and i - j < window. The kernels mask inside the diagonal
    and trailing blocks and run no dot in blocks wholly outside the
    window; 0 is today's causal / full attention, unchanged.

    GQA-native (r3): k/v may carry h_kv < h heads (h % h_kv == 0, the
    llama2-70b 64q/8kv shape). Neither path materializes repeated K/V —
    the kernel folds the g = h/h_kv group members into its q tile
    ([g·block_q, d] rows per K/V block load, grid over K/V heads), so
    both the repeated-K/V TENSOR and the in-kernel K/V HBM re-reads per
    query head are gone: K/V traffic scales with h_kv. The dense
    fallback contracts through a grouped einsum. The default q-block
    target shrinks by g so the folded tile stays within the measured
    512-row sweet spot (and VMEM).

    Dispatches to the Pallas kernel on TPU when shapes tile cleanly
    (t divisible by both block sizes, blocks 8-aligned, d a lane-friendly
    multiple — see _use_kernel); otherwise the jnp reference (identical
    math). Blocks default to the largest divisors of t up to 512/g (q) /
    1024 (k) — measured optimum on v5e. ``interpret=True`` forces the
    kernel through the Pallas interpreter — the CPU test path for kernel
    logic. ``force_kernel`` overrides the dispatch heuristic both ways
    (tiling constraints still apply) — the hook for measuring a shape on
    the other side of the heuristic.

    An EXPLICIT ``block_q``/``block_k`` is a TARGET, not a verbatim
    config: block_q is clamped to 1024//g rows (VMEM bound for folded
    GQA tiles), both are rounded down to a multiple of 8 and then to a
    divisor of t when one exists (_pick_block) — the resolved blocks may
    differ from what was passed. Callers probing an exact configuration
    should treat a changed block as "that config cannot run", not as a
    measurement of it."""
    _check_window(causal, window)
    use, block_q, block_k = _dispatch(q, k, v, block_q, block_k, interpret,
                                      force_kernel)
    q, k, v = _tag_inputs(q, k, v)
    if not use:
        if force_kernel is None and jax.default_backend() == "tpu":
            _say_reference(
                "flash_attention",
                f"t={q.shape[1]} d={q.shape[3]} blocks=({block_q},{block_k}) "
                "does not tile, or is under the hd=64 crossover",
            )
        return _tag_outputs(
            *reference_attention_lse(q, k, v, causal=causal, window=window))[0]
    # One custom-vjp entry serves both public surfaces (the lse output is
    # a residual either way, so dropping it here costs nothing).
    return _flash_lse(q, k, v, causal, block_q, block_k, bool(interpret),
                      int(window))[0]


# ---------------------------------------------------------------------------
# paged attention (serving: decode step and prefill chunk)
# ---------------------------------------------------------------------------
#
# Attention of a TILE of consecutive query positions of one sequence over
# that sequence's PAGED K/V cache (serve/kvcache.py): K/V live in
# fixed-size pages of a preallocated pool and each sequence owns an
# ordered page table. Nothing materializes a contiguous [t, d] K/V tensor
# on TPU — the kernel walks the page table as its innermost grid
# dimension and DMAs one page per step (of as many kv-heads as its VMEM
# holds, ``_kv_heads_per_step``), with page ids resolved through
# scalar-prefetch (the page table is in SMEM before the grid runs, so the
# K/V BlockSpec index_map can compute each step's HBM source block from
# it). The q tile of one (sequence, kv-head) pair is [rows·g, d]: the
# ``rows`` query positions times the g heads of the GQA group, stacked on
# the sublane dim as in the forward kernel, with one row of the
# online-softmax carry (m, l, acc) each and a causal limit per query
# position. A decode step is the tile at rows = 1 over every slot; a
# prefill chunk is ONE sequence at rows = chunk, whose pages are walked
# once for all its positions.


def _tile_q(q, h_kv):
    """q [s, r, h, d] -> [s, h_kv, r·g, d]: row ``i·g + j`` of a tile is
    query position i, head j of the group (a free reshape at r = 1)."""
    s_n, r, h, d = q.shape
    g = h // h_kv
    return jnp.swapaxes(q.reshape(s_n, r, h_kv, g, d), 1, 2).reshape(
        s_n, h_kv, r * g, d)


def _untile_o(o, r):
    """[s, h_kv, r·g, d] -> [s, r, h, d], ``_tile_q`` undone."""
    s_n, h_kv, n, d = o.shape
    return jnp.swapaxes(o.reshape(s_n, h_kv, r, n // r, d), 1, 2).reshape(
        s_n, r, h_kv * (n // r), d)


def _tile_visible(kpos, row, q_start, length, g):
    """Which keys a tile's rows see: row ``i·g + j`` is the query at
    position ``q_start + i``; it sees keys ``<= q_start + i`` if that
    position lies inside the sequence (``< length``), none otherwise (a
    padded row, or an inactive slot's). Written without the division
    ``row // g``: g·(kpos − q_start) is a multiple of g, so it is
    ``<= g·i`` exactly when it is ``<= row``."""
    return (g * (kpos - q_start) <= row) & (row < g * (length - q_start))


def paged_decode_reference(q, k_pages, v_pages, page_table, seq_lens,
                           layer: Optional[int] = None, q_start=None):
    """Pure-JAX paged attention — the correctness oracle and the off-TPU
    fallback (same contract as the kernel).

    q [s, h, d] (one query token per sequence) or [s, r, h, d] (r
    consecutive positions of each), k_pages/v_pages
    [n_pages, h_kv, page_size, d] — or the whole [n_layers, n_pages,
    h_kv, page_size, d] pool with ``layer`` naming the one to read —
    page_table [s, p] int32 (page ids in
    sequence order; rows padded with any valid id past the live prefix),
    seq_lens [s] int32 = valid K/V tokens per sequence INCLUDING the
    last query position, q_start [s] int32 = position of each sequence's
    first query row (default: the rows are the sequence's last).
    Gathers pages to [s, h_kv, p·page_size, d] ONCE for all rows (one
    gather straight out of the pool: no layer is sliced off first), masks
    per row (``_tile_visible``) with the NEG_INF sentinel, f32 softmax.
    Rows that see nothing (seq_len == 0, a padded row) produce the
    uniform-softmax artifact (see reference_attention_lse) — callers mask
    them out."""
    flat = q.ndim == 3
    if flat:
        q = q[:, None]
    s_n, r, h, d = q.shape
    h_kv, page_size = k_pages.shape[-3:-1]
    p = page_table.shape[1]
    g = h // h_kv
    scale = d**-0.5
    if q_start is None:
        q_start = seq_lens - r

    def gather(pages):  # [s, p, h_kv, page, d] -> [s, h_kv, p·page, d]
        got = pages[page_table] if layer is None else pages[layer, page_table]
        return jnp.swapaxes(got, 1, 2).reshape(s_n, h_kv, p * page_size, d)

    k, v = gather(k_pages), gather(v_pages)
    q5 = _tile_q(q, h_kv).astype(jnp.float32) * scale
    s = jnp.einsum(
        "shgd,shtd->shgt", q5, k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )  # [s, h_kv, r·g, t]
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    per_seq = (slice(None), None, None, None)
    s = jnp.where(
        _tile_visible(kpos, row, q_start[per_seq], seq_lens[per_seq], g),
        s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    pr = jnp.exp(s - m)
    l = jnp.sum(pr, axis=-1, keepdims=True)
    out = jnp.einsum(
        "shgt,shtd->shgd", pr / l, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    out = _untile_o(out, r).astype(q.dtype)
    return out[:, 0] if flat else out


def _paged_kernel(pt_ref, sl_ref, qs_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size, g, scale):
    """One (sequence, group of ``hb`` kv-heads) pair streams its pages
    through VMEM past its q tiles [hb, rows·g, d]: a grid step is one page
    of ALL the group's heads (one contiguous slab of the pool) and runs
    the one-head step on all of them at once — the heads are the batch
    dimension of its two dots, and every head has rows of the carry of
    its own. The innermost grid dim walks page-table
    SLOTS; slots past the sequence's live prefix are skipped with pl.when
    (and fetch nothing: their index map repeats the last live page).
    Inside a live page every row masks what it may not see to NEG_INF
    (``_tile_visible``), so a sequence ending mid-page and a chunk that
    starts or ends mid-page are exact (the cases
    tests/test_flash_decode.py pins). A row that has seen no key yet
    keeps m at the sentinel, where exp(s - m) of a masked key would be 1:
    p is zeroed by the same mask, so padded rows end with l == 0."""
    from jax.experimental import pallas as pl

    si = pl.program_id(0)
    pi = pl.program_id(2)
    npi = pl.num_programs(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = sl_ref[si]
    q_start = qs_ref[si]
    live = pi * page_size < length

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale  # [hb, rows·g, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [hb, page_size, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [hb, rows·g, page_size]
        kpos = pi * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = _tile_visible(kpos, row, q_start, length, g)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pi == npi - 1)
    def _finish():
        # a row that saw no key (seq_len == 0, a padded row) leaves l at
        # 0 — guard the divide so it emits zeros, not nan.
        l = l_scr[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


_TILE_VMEM_BUDGET = 12 << 20  # of the 16 MiB a kernel may scope by default


def _tile_vmem_bytes(n: int, d: int) -> int:
    """What ``_paged_call`` keeps resident for a q tile of n rows, in f32:
    q and o double-buffered, the accumulator, m and l a lane row each."""
    return 4 * n * (5 * d + 2 * LSE_LANES)


def _kv_heads_per_step(h_kv: int, n: int, d: int, page_size: int,
                       itemsize: int, at_most: Optional[int] = None) -> int:
    """How many KV heads ``hb`` one grid step of ``_paged_call`` takes:
    the largest divisor of h_kv whose step stays inside
    ``_TILE_VMEM_BUDGET`` — the hb q tiles of n rows with their carries
    and the K and V blocks [hb, page_size, d] of the pool's dtype, double-
    buffered. Read from the shapes in hand (8 for a decode step at the
    -serve1 shapes, 4 for its 512-row prefill tile); 0 when not even one
    head fits, which sends the call to the gather reference. ``at_most``
    is the tests' handle on it (1 is the one-head step the others must
    equal bit for bit); no caller in the package passes it."""
    for hb in range(min(h_kv, at_most or h_kv), 0, -1):
        step = _tile_vmem_bytes(hb * n, d) + 4 * hb * page_size * d * itemsize
        if h_kv % hb == 0 and step <= _TILE_VMEM_BUDGET:
            return hb
    return 0


def _rows_per_tile(r: int, g: int, h_kv: int, d: int, page_size: int,
                   itemsize: int) -> int:
    """Query positions ONE q tile of ``_paged_call`` takes: all ``r`` where
    the tile fits (the shapes every cell before the multi-query one has:
    their calls are what they were), else the largest divisor of r whose
    tile — rows·g a multiple of 8 — does: a chunk at 20 query heads a KV
    head is 5,120 rows at 256 positions, 18 MB of tile against the 12 MiB
    budget, and goes as two tiles of 128. 0 when no divisor fits."""
    for rows in range(r, 0, -1):
        if r % rows == 0 and (rows == r or (rows * g) % 8 == 0) and \
                _kv_heads_per_step(h_kv, rows * g, d, page_size, itemsize):
            return rows
    return 0


def _row_tiles(q, page_table, seq_lens, q_start, rows: int):
    """q [s, r, h, d] cut into r / rows tiles of ``rows`` consecutive
    positions, each a sequence of its own for the kernel's grid: tile j of
    sequence i starts at ``q_start[i] + j·rows``, walks the same page-table
    row, and sees the sequence only as far as its own last row (``seq_lens``
    cut there: a tile walks no page that all its rows mask; a tile wholly
    past the sequence's length walks none)."""
    s_n, r, h, d = q.shape
    nb = r // rows
    starts = q_start[:, None] + rows * jnp.arange(nb, dtype=q_start.dtype)[None]
    lens = jnp.minimum(seq_lens[:, None], starts + rows)
    lens = jnp.where(lens > starts, lens, 0)
    return (q.reshape(s_n * nb, rows, h, d), jnp.repeat(page_table, nb, axis=0),
            lens.reshape(-1), starts.reshape(-1))


def _paged_call(q, k_pool, v_pool, layer, page_table, seq_lens, q_start,
                interpret):
    """q [s, r, h, d] through the kernel: grid (s, h_kv / hb, page slots)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_n, r, h, d = q.shape
    _, _, h_kv, page_size, _ = k_pool.shape
    p = page_table.shape[1]
    g = h // h_kv
    n = r * g
    hb = _kv_heads_per_step(h_kv, n, d, page_size, k_pool.dtype.itemsize)
    qt = _tile_q(q, h_kv)

    # Scalar-prefetch args (page_table, seq_lens, q_start) reach the
    # index_maps as TRAILING refs after the grid indices — the K/V source
    # block for grid step (si, hg, pi) is whatever page the table names,
    # which is the whole paging trick. One K/V block is one page of hb
    # kv-heads, [hb, page_size, d]: contiguous in the pool — the reason
    # the pools are laid out [n_layers, n_pages, h_kv, page_size, d] —
    # and with the tiled minor dims Mosaic requires of a block
    # (sublane-aligned page, whole head_dim). A grid step costs its
    # bookkeeping on the scalar core and the latency of its two DMAs
    # whatever it carries, and most steps of a decode run are dead slots,
    # so a step takes as many heads as VMEM holds, not one
    # (``_kv_heads_per_step``). The kernel takes the WHOLE pool and
    # ``layer`` (a Python int) sits in the index map: handing it
    # ``pool[layer]`` makes XLA materialise that layer (a slice of the
    # whole layer, 84 MB at the -serve1 shapes) before every call. A slot past the last live page names
    # that page again — a block whose index did not change is not
    # fetched — and the table is clamped HERE, once a call, not in the
    # index map, which runs at every grid step on the scalar core.
    last = jnp.maximum(pl.cdiv(seq_lens, page_size) - 1, 0)
    page_table = jnp.take_along_axis(
        page_table,
        jnp.minimum(jnp.arange(p, dtype=jnp.int32)[None], last[:, None]),
        axis=1)

    def pool_index(si, hg, pi, pt, sl, qs):
        return (layer, pt[si, pi], hg, 0, 0)

    def tile_index(si, hg, pi, pt, sl, qs):
        return (si, hg, 0, 0)

    pool_spec = pl.BlockSpec((1, 1, hb, page_size, d), pool_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_n, h_kv // hb, p),
        in_specs=[pl.BlockSpec((1, hb, n, d), tile_index), pool_spec,
                  pool_spec],
        out_specs=pl.BlockSpec((1, hb, n, d), tile_index),
        scratch_shapes=[
            pltpu.VMEM((hb, n, LSE_LANES), jnp.float32),  # running max m
            pltpu.VMEM((hb, n, LSE_LANES), jnp.float32),  # running sum l
            pltpu.VMEM((hb, n, d), jnp.float32),          # output accumulator
        ],
    )
    kernel = functools.partial(
        _paged_kernel, page_size=page_size, g=g, scale=d**-0.5
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q_start.astype(jnp.int32), qt, k_pool, v_pool)
    return _untile_o(o, r)


def flash_attention_decode(
    q,
    k_pages,
    v_pages,
    page_table,
    seq_lens,
    interpret: Optional[bool] = None,
    force_kernel: Optional[bool] = None,
    layer: Optional[int] = None,
    q_start=None,
):
    """Paged attention: a tile of query positions per sequence against a
    paged K/V cache — one token per sequence (a decode step) or ``r``
    consecutive positions of each (a prefill chunk: ONE walk over the
    sequence's pages serves all its rows).

    q [s, h, d] or [s, r, h, d]; k_pages/v_pages
    [n_pages, h_kv, page_size, d] (the
    serve/kvcache.py pool layout — head-major so one (page, kv-head)
    slab is a tile-aligned [page_size, d] block the TPU compiler
    accepts, and a page's heads are one contiguous piece), or the whole
    pool [n_layers, n_pages, h_kv, page_size, d] with ``layer`` (a
    Python int) naming the layer to read: the serve engine's form. The kernel always takes a 5-D pool and puts the layer
    in its BlockSpec index map, so no ``pool[layer]`` is ever cut out of
    the pool in front of it (a 4-D pool is the one-layer case, a free
    reshape); page_table [s, max_pages] int32;
    seq_lens [s] int32 (valid K/V length per sequence, INCLUDING the
    just-written query positions — a query attends to itself);
    q_start [s] int32, the position of each sequence's first query row
    (default ``seq_lens - r``: the rows are the sequence's last). Row i
    sees keys ``<= q_start + i``; a row at or past ``seq_lens`` (the
    padding of a short chunk) sees none. Returns q's shape in q's dtype.
    GQA-native: h % h_kv folds into the q tile exactly as in the full
    kernel. One grid step of the kernel is a page of as many KV heads as
    its VMEM holds beside their q tiles (read from the shapes; a row's
    result does not depend on it).

    Dispatch mirrors flash_attention: the Pallas kernel engages on TPU
    (or under ``interpret=True`` — the CPU test path) when the page size
    is sublane-aligned for the pool dtype (8 rows of f32, 16 of bf16)
    and a tile of several positions is too (r·g a multiple of 8; a tile too
    large to stay in VMEM with its carry is cut into tiles of fewer
    positions that run in turn over the same pages, ``_rows_per_tile``);
    otherwise the pure-JAX gather reference (same math, same f32
    softmax, same NEG_INF masking) — the off-TPU path, so the serve
    engine runs everywhere. A TPU run that takes the reference says so
    once in the log (_say_reference). ``force_kernel``
    overrides the heuristic both ways (alignment still binds). Rows that
    see no key (seq_lens == 0: an inactive slot; a padded row) come back
    garbage-but-finite on both paths (zeros from the kernel, the uniform
    artifact from the reference) — callers mask, never read."""
    if q.ndim not in (3, 4) or k_pages.ndim != (4 if layer is None else 5):
        raise ValueError(
            f"decode shapes: q [s,h,d] or [s,r,h,d] (got {q.shape}), pages "
            f"[n,h_kv,page,d], or [layers,n,h_kv,page,d] with layer= "
            f"(got {k_pages.shape}, layer={layer})"
        )
    if k_pages.shape != v_pages.shape:
        raise ValueError(f"k/v pool mismatch: {k_pages.shape} vs {v_pages.shape}")
    h, h_kv, page_size = q.shape[-2], *k_pages.shape[-3:-1]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    r = 1 if q.ndim == 3 else q.shape[1]
    sublanes = 8 * max(1, 4 // jnp.dtype(k_pages.dtype).itemsize)
    tile = f"a q tile of {r} positions x {h // h_kv} heads a group"
    why_not = None
    if page_size % sublanes:
        why_not = (f"page_size={page_size} is not a multiple of {sublanes} "
                   f"({jnp.dtype(k_pages.dtype).name} sublanes)")
    elif r > 1 and (r * h // h_kv) % 8:
        why_not = f"{tile} is not a multiple of 8 rows"
    else:
        rows = _rows_per_tile(r, h // h_kv, h_kv, q.shape[-1], page_size,
                              jnp.dtype(k_pages.dtype).itemsize)
        if not rows:
            why_not = f"{tile} does not fit the kernel's VMEM, cut or whole"
    on_tpu = jax.default_backend() == "tpu"
    use = why_not is None and (bool(interpret) or on_tpu)
    if force_kernel is not None:
        use = force_kernel and use
    if not use:
        if on_tpu and force_kernel is None:
            _say_reference("flash_attention_decode", why_not)
        return paged_decode_reference(
            q, k_pages, v_pages, page_table, seq_lens, layer, q_start
        )
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    if q_start is None:
        q_start = seq_lens - r
    tiles = q.reshape(q.shape[0], r, h, q.shape[-1])
    if rows < r:
        tiles, page_table, seq_lens, q_start = _row_tiles(
            tiles, page_table, seq_lens, q_start, rows)
    o = _paged_call(tiles, k_pages, v_pages, layer, page_table, seq_lens,
                    q_start, bool(interpret))
    return o.reshape(q.shape)
