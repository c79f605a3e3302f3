"""Decode-path correctness oracle (r10): flash_attention_decode (paged,
incremental) against the full flash_attention on the same prefix —
ragged sequence lengths, page-boundary crossings, GQA, and the
interpret-mode kernel (scalar-prefetch page walk) vs the gather
reference."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tf_operator_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention,
    flash_attention_decode,
    paged_decode_reference,
    reference_attention,
)
from tf_operator_tpu.serve.kvcache import (  # noqa: E402
    PagePool,
    SequencePages,
    pages_needed,
    write_rows,
)


def _paged_prefix(lengths, page_size, h_kv, d, seed=0, scramble=False):
    """Scatter per-sequence K/V prefixes into a paged pool. Returns
    (k_seqs, v_seqs, k_pages, v_pages, page_table, seq_lens) with the
    pool sized to hold everything plus the trash page."""
    rng = np.random.RandomState(seed)
    num_pages = sum(pages_needed(L, page_size) for L in lengths) + 2
    pool = PagePool(num_pages)
    if scramble:
        # Hand pages out in shuffled order so the table indirection is
        # genuinely exercised (sequential ids would also pass a broken
        # identity mapping).
        pool._free = list(rng.permutation(num_pages))
    k_pages = np.zeros((num_pages + 1, h_kv, page_size, d), np.float32)
    v_pages = np.zeros((num_pages + 1, h_kv, page_size, d), np.float32)
    max_p = max(pages_needed(L, page_size) for L in lengths)
    table = np.full((len(lengths), max_p), pool.trash_page - 1, np.int32)
    k_seqs, v_seqs = [], []
    for i, L in enumerate(lengths):
        sp = SequencePages(page_size)
        sp.ensure(L, pool)
        table[i, : len(sp.pages)] = sp.pages
        k_seq = rng.randn(L, h_kv, d).astype(np.float32)
        v_seq = rng.randn(L, h_kv, d).astype(np.float32)
        for t in range(L):
            k_pages[sp.pages[t // page_size], :, t % page_size] = k_seq[t]
            v_pages[sp.pages[t // page_size], :, t % page_size] = v_seq[t]
        k_seqs.append(k_seq)
        v_seqs.append(v_seq)
    return (
        k_seqs, v_seqs, jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(table), jnp.asarray(np.asarray(lengths, np.int32)),
    )


def _full_oracle(q_last, k_seq, v_seq):
    """Last-row output of the full (causal) attention entry over the
    same prefix — what the paged decode step must reproduce."""
    L, h_kv, d = k_seq.shape
    h = q_last.shape[0]
    g = h // h_kv
    # the decode query is the final position; build the full [1, L, h, d]
    # problem with arbitrary earlier queries — causal masking makes only
    # the last row comparable, which is the one we read.
    q_full = np.zeros((1, L, h, d), np.float32)
    q_full[0, -1] = q_last
    out = flash_attention(
        jnp.asarray(q_full), jnp.asarray(k_seq[None]), jnp.asarray(v_seq[None]),
        causal=True,
    )
    return np.asarray(out)[0, -1]


# lengths chosen to hit: mid-page end (5), exact page boundary (16),
# boundary crossing (23 = 2 pages + 7), single token (1)
RAGGED = [5, 16, 23, 1]
PAGE = 8


@pytest.mark.parametrize("h,h_kv", [(4, 4), (4, 2)], ids=["mha", "gqa"])
def test_decode_matches_full_prefix_ragged(h, h_kv):
    d = 16
    k_seqs, v_seqs, kp, vp, table, lens = _paged_prefix(
        RAGGED, PAGE, h_kv, d, seed=1
    )
    rng = np.random.RandomState(2)
    q = rng.randn(len(RAGGED), h, d).astype(np.float32)
    out = np.asarray(
        flash_attention_decode(jnp.asarray(q), kp, vp, table, lens)
    )
    for i, L in enumerate(RAGGED):
        want = _full_oracle(q[i], k_seqs[i], v_seqs[i])
        np.testing.assert_allclose(out[i], want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pool", ["4d", "5d"])
def test_decode_kernel_interpret_matches_reference(pool):
    """The Pallas decode kernel (scalar-prefetch page walk, interpret
    mode off-TPU) against the pure-JAX gather reference — same ragged
    lengths, scrambled page ids so the index_map indirection is real.
    ``5d``: the serve engine's form — the kernel and the reference get
    the WHOLE [layers, pages, ...] pool and the layer to read (the
    kernel through its BlockSpec index map), held against the 4-D read
    of ``pool[layer]``; the other layers hold other values."""
    h, h_kv, d = 4, 2, 128  # lane-width head_dim: the kernel's home turf
    k_seqs, v_seqs, kp, vp, table, lens = _paged_prefix(
        RAGGED, PAGE, h_kv, d, seed=3, scramble=True
    )
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(len(RAGGED), h, d).astype(np.float32))
    ref = np.asarray(paged_decode_reference(q, kp, vp, table, lens))
    if pool == "5d":
        kp5 = jnp.stack([kp + 1.0, kp, kp * 2.0])
        vp5 = jnp.stack([vp - 1.0, vp, vp * 0.5])
        krn = np.asarray(flash_attention_decode(
            q, kp5, vp5, table, lens, interpret=True, layer=1))
        ref5 = np.asarray(
            paged_decode_reference(q, kp5, vp5, table, lens, layer=1))
        np.testing.assert_array_equal(ref5, ref)
        # bit for bit the 4-D kernel read of that layer, and not another's
        np.testing.assert_array_equal(krn, np.asarray(flash_attention_decode(
            q, kp5[1], vp5[1], table, lens, interpret=True)))
        other = np.asarray(flash_attention_decode(
            q, kp5, vp5, table, lens, interpret=True, layer=2))
        assert np.abs(other - ref).max() > 1e-3
        with pytest.raises(ValueError, match="layer="):
            flash_attention_decode(q, kp5, vp5, table, lens)
    else:
        krn = np.asarray(
            flash_attention_decode(q, kp, vp, table, lens, interpret=True)
        )
    np.testing.assert_allclose(krn, ref, atol=2e-5, rtol=2e-5)
    # and both against the full-attention oracle
    for i, L in enumerate(RAGGED):
        want = _full_oracle(np.asarray(q)[i], k_seqs[i], v_seqs[i])
        np.testing.assert_allclose(krn[i], want, atol=2e-5, rtol=2e-5)


def test_decode_incremental_accumulation():
    """Token-by-token cache growth: after writing position t, decoding
    with seq_len t+1 must equal row t of the full causal attention —
    the incremental contract the serve engine's step loop relies on."""
    L, h, h_kv, d, page = 21, 2, 2, 16, 8  # crosses two page boundaries
    rng = np.random.RandomState(5)
    q_all = rng.randn(L, h, d).astype(np.float32)
    k_all = rng.randn(L, h_kv, d).astype(np.float32)
    v_all = rng.randn(L, h_kv, d).astype(np.float32)
    full = np.asarray(
        flash_attention(
            jnp.asarray(q_all[None]), jnp.asarray(k_all[None]),
            jnp.asarray(v_all[None]), causal=True,
        )
    )[0]
    pool = PagePool(pages_needed(L, page) + 1)
    sp = SequencePages(page)
    kp = np.zeros((pool.num_pages + 1, h_kv, page, d), np.float32)
    vp = np.zeros_like(kp)
    for t in range(L):
        sp.ensure(t + 1, pool)
        kp[sp.pages[t // page], :, t % page] = k_all[t]
        vp[sp.pages[t // page], :, t % page] = v_all[t]
        table = np.full((1, pages_needed(L, page)), 0, np.int32)
        table[0, : len(sp.pages)] = sp.pages
        out = np.asarray(
            flash_attention_decode(
                jnp.asarray(q_all[t][None]), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(table), jnp.asarray([t + 1], np.int32),
            )
        )[0]
        np.testing.assert_allclose(out, full[t], atol=2e-5, rtol=2e-5)


# A prefill chunk: ``rows`` consecutive positions of ONE sequence as one
# query tile. (rows, start, n_valid, g, page) — the tile path engages when
# rows·g is a multiple of 8.
TILES = {
    "from-0-over-two-pages": (16, 0, 16, 4, 8),
    "mid-page-start": (16, 5, 16, 4, 8),           # pages 0..2, both ends ragged
    "page-boundary-start": (16, 16, 16, 4, 8),
    "padded-rows": (16, 8, 11, 4, 8),              # n_valid < rows
    "padded-to-one-row": (8, 13, 1, 4, 8),
    "deep-mid-page-page16": (16, 37, 9, 2, 16),
    "mha-g1": (16, 5, 12, 1, 8),
    "one-row-is-decode": (1, 22, 1, 4, 8),
    "misaligned-takes-the-reference": (3, 6, 3, 2, 8),  # rows·g = 6
    "oversize-takes-the-reference": (16, 5, 16, 4, 8),  # VMEM budget cut to 1 KB
}
SAID = {"misaligned": "not a multiple of 8 rows", "oversize": "does not fit"}


@pytest.mark.parametrize("case", sorted(TILES))
def test_prefill_tile_matches_causal_prefix(case, caplog, monkeypatch):
    """The tiled path (interpret mode) against ``reference_attention(
    causal=True)`` on the contiguous prefix: every valid row of the chunk
    is row ``start + i`` of the full causal attention, whatever page the
    chunk starts or ends in; rows past ``n_valid`` come back zero from the
    kernel. Pages are scrambled and read out of a 3-layer pool at
    ``layer=1``. A tile of one row IS a decode step, bit for bit; a tile
    the kernel cannot take (rows·g off the sublane grid, or more rows than
    VMEM holds with their carry) goes to the gather reference, which a TPU
    run says once in the log."""
    import importlib
    import logging
    from unittest import mock

    rows, start, n_valid, g, page = TILES[case]
    h_kv, d = 2, 128
    h, L = h_kv * g, start + n_valid
    k_seqs, v_seqs, kp, vp, table, lens = _paged_prefix(
        [L, 3], page, h_kv, d, seed=rows + start, scramble=True)
    kp5 = jnp.stack([kp + 1.0, kp, kp * 2.0])
    vp5 = jnp.stack([vp - 1.0, vp, vp * 0.5])
    table, lens = table[:1], lens[:1]
    # the engine's table rows are max_pages wide: slots past the live
    # prefix (here two more) name a valid page nobody may read
    table = jnp.concatenate([table, jnp.full((1, 2), int(table[0, 0]))], axis=1)
    rng = np.random.RandomState(7)
    q = rng.randn(1, rows, h, d).astype(np.float32)
    q_start = jnp.asarray([start], jnp.int32)

    def run(**kw):
        return np.asarray(flash_attention_decode(
            jnp.asarray(q), kp5, vp5, table, lens, layer=1, q_start=q_start,
            **kw))[0]

    q_full = np.zeros((1, L, h, d), np.float32)
    q_full[0, start:] = q[0, :n_valid]
    want = np.asarray(reference_attention(
        jnp.asarray(q_full), jnp.asarray(k_seqs[0][None]),
        jnp.asarray(v_seqs[0][None]), causal=True))[0, start:]
    ref = np.asarray(paged_decode_reference(
        jnp.asarray(q), kp5, vp5, table, lens, 1, q_start))[0]
    np.testing.assert_allclose(ref[:n_valid], want, atol=2e-5, rtol=2e-5)
    assert np.isfinite(ref).all()

    if case.split("-")[0] in SAID:
        fa = importlib.import_module("tf_operator_tpu.ops.flash_attention")
        fa._said.clear()
        if case.startswith("oversize"):
            monkeypatch.setattr(fa, "_TILE_VMEM_BUDGET", 1 << 10)
        with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
                caplog.at_level(logging.WARNING):
            got, again = run(), run()
        np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(again, ref)
        said = [r.getMessage() for r in caplog.records
                if "flash_attention_decode" in r.getMessage()]
        assert len(said) == 1 and SAID[case.split("-")[0]] in said[0]
        return
    got = run(interpret=True)
    np.testing.assert_allclose(got[:n_valid], want, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(got[n_valid:], 0.0)
    if rows == 1:  # q [s, h, d], no q_start: the decode step's own call
        np.testing.assert_array_equal(got, np.asarray(flash_attention_decode(
            jnp.asarray(q[:, 0]), kp5, vp5, table, lens, interpret=True,
            layer=1)))


# A grid step of the kernel is one page of ``hb`` KV heads, hb read from
# the shapes against the VMEM budget. (h_kv, g, rows, page, pool form,
# pool dtype, K/V length a sequence, valid rows a sequence, how many heads
# the budget is cut to hold — None: the real budget —, the hb that must
# come of it.) A decode step's slots are ragged with an EMPTY one among
# them; a chunk starts and ends mid-page.
F32, BF16 = "float32", "bfloat16"
RAGGED0 = [5, 0, 23, 16, 1]  # RAGGED with an empty slot among them
HEADS_A_STEP = {
    "decode-gqa-all-heads": (4, 4, 1, 8, "5d", F32, RAGGED0, None, None, 4),
    "decode-gqa-budget-for-two": (4, 4, 1, 8, "5d", F32, RAGGED0, None, 2, 2),
    "decode-gqa-budget-for-one": (4, 4, 1, 8, "5d", F32, RAGGED0, None, 1, 1),
    "decode-mha-g1-4d-pool": (4, 1, 1, 8, "4d", F32, [9, 24, 0, 2], None, None, 4),
    # four heads would fit, four does not divide six: three a step
    "decode-six-heads-budget-for-four": (
        6, 2, 1, 8, "5d", F32, [17, 0, 8], None, 4, 3),
    "decode-bf16-page16-4d-pool": (
        2, 4, 1, 16, "4d", BF16, [5, 0, 37, 32], None, None, 2),
    "chunk-mid-page-both-ends-all-heads": (
        2, 4, 16, 8, "5d", F32, [19], [14], None, 2),
    "chunk-deep-page16-budget-for-two": (
        4, 2, 16, 16, "5d", F32, [46], [9], 2, 2),
    "chunk-padded-rows-budget-for-one": (
        2, 4, 8, 8, "4d", F32, [16], [3], 1, 1),
    "chunks-of-two-sequences-one-empty": (
        2, 1, 16, 8, "5d", F32, [21, 0], [16, 0], None, 2),
    "chunk-mha-g1-budget-for-two": (4, 1, 8, 8, "4d", F32, [13], [6], 2, 2),
}


@pytest.mark.parametrize("case", sorted(HEADS_A_STEP))
def test_kv_heads_a_grid_step_leave_every_row_as_one_head_a_step_did(
        case, monkeypatch):
    """The kernel in interpret mode, at the hb its VMEM predicate picks —
    all KV heads, a proper divisor, one — (a) against
    ``paged_decode_reference`` on every row that sees a key, zeros on the
    others, and (b) BIT FOR BIT against the same call with the predicate
    held to one head a step, the kernel as it was: a head's dots, mask,
    carry and page order do not depend on which heads share its step. The
    grid of the traced call is slots x h_kv / hb x page slots.

    One exception to (b), and it is the interpreter's: a q tile of ONE row
    (an MHA decode step). The heads are the batch dimension of the step's
    dots, and XLA's CPU backend computes a one-row product of a batch of
    one in another order than of a batch of several (1 ulp; two rows or
    more agree exactly, ``jnp`` alone shows it), so that case is held to
    1e-6. On the chip the kernel equalled the one-head kernel bit for bit,
    one-row tiles too (PERF.md §6 "PR 29")."""
    import functools
    import importlib

    from tf_operator_tpu.serve.engine import pallas_grid_steps

    fa = importlib.import_module("tf_operator_tpu.ops.flash_attention")
    h_kv, g, rows, page, form, dtype, lengths, n_valid, fits, hb = (
        HEADS_A_STEP[case])
    d, h, s_n = 128, h_kv * g, len(lengths)
    _, _, kp, vp, table, lens = _paged_prefix(
        [max(L, 1) for L in lengths], page, h_kv, d, seed=len(case),
        scramble=True)
    lens = jnp.asarray(np.asarray(lengths, np.int32))
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    # the engine's rows are max_pages wide: two more slots nobody may read
    table = jnp.concatenate(
        [table, jnp.broadcast_to(table[:, :1], (s_n, 2))], axis=1)
    rng = np.random.RandomState(11)
    if rows == 1:  # the decode step's own call: q [s, h, d], no q_start
        q = jnp.asarray(rng.randn(s_n, h, d), dtype)
        valid = np.asarray(lengths) > 0
        kw = {}
    else:
        q = jnp.asarray(rng.randn(s_n, rows, h, d), dtype)
        valid = np.arange(rows)[None] < np.asarray(n_valid)[:, None]
        kw = {"q_start": lens - jnp.asarray(n_valid, jnp.int32)}
    if form == "5d":
        kp, vp = jnp.stack([kp + 1, kp, kp * 2]), jnp.stack([vp - 1, vp, vp / 2])
        kw["layer"] = 1
    if fits is not None:
        monkeypatch.setattr(
            fa, "_TILE_VMEM_BUDGET",
            fa._tile_vmem_bytes(fits * rows * g, d)
            + 4 * fits * page * d * kp.dtype.itemsize)
    assert fa._kv_heads_per_step(
        h_kv, rows * g, d, page, kp.dtype.itemsize) == hb

    def run(q):
        return flash_attention_decode(q, kp, vp, table, lens, interpret=True,
                                      **kw)

    def grid_steps():  # a new function a trace: none is answered from a cache
        return pallas_grid_steps(jax.make_jaxpr(lambda q: run(q))(q).jaxpr)

    assert grid_steps() == s_n * (h_kv // hb) * table.shape[1]
    got = np.asarray(run(q).astype(jnp.float32))
    ref = np.asarray(paged_decode_reference(
        q, kp, vp, table, lens, kw.get("layer"), kw.get("q_start")
    ).astype(jnp.float32))
    tol = 2e-5 if dtype == F32 else 2e-2
    np.testing.assert_allclose(got[valid], ref[valid], atol=tol, rtol=tol)
    np.testing.assert_array_equal(got[~valid], 0.0)
    monkeypatch.setattr(
        fa, "_kv_heads_per_step",
        functools.partial(fa._kv_heads_per_step, at_most=1))
    assert grid_steps() == s_n * h_kv * table.shape[1]
    one_head = np.asarray(run(q).astype(jnp.float32))
    if rows * g == 1:
        np.testing.assert_allclose(got, one_head, atol=1e-6, rtol=0)
    else:
        np.testing.assert_array_equal(got, one_head)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("page", [16, 64])
def test_write_rows_matches_the_scatter_it_replaces(page, program):
    """``write_rows`` against ``kp.at[l, pid, :, row].set(k)``, the write
    it replaced (same values, another scatter: PERF.md §6, PR 25), with
    the indices the engine's two programs build — a decode step with
    inactive slots, a prefill chunk that crosses a page boundary and ends
    in padding, both steering what is masked to the trash page. Bit for
    bit on every page but the trash page, whose rows nobody reads; the
    other layers untouched."""
    L, P, h_kv, d, l = 3, 9, 2, 128, 1
    trash = P
    rng = np.random.RandomState(page)
    kp = jnp.asarray(rng.randn(L, P + 1, h_kv, page, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(L, P + 1, h_kv, page, d).astype(np.float32))
    if program == "decode":  # engine.decode_step's pid / row
        table = jnp.asarray(rng.permutation(P)[:8].reshape(4, 2).astype(np.int32))
        pos = jnp.asarray([page - 1, 0, page + 3, 5], jnp.int32)
        active = jnp.asarray([True, False, True, False])
        pid = jnp.where(active, table[jnp.arange(4), pos // page], trash)
    else:  # engine.prefill_chunk's: one sequence, positions start..start+c
        table_row = jnp.asarray([4, 7, 2], jnp.int32)
        c, n_valid = 24, 19
        pos = (page - 10) + jnp.arange(c)
        pid = jnp.where(jnp.arange(c) < n_valid, table_row[pos // page], trash)
    row = pos % page
    n = int(pos.shape[0])
    k = jnp.asarray(rng.randn(n, h_kv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(n, h_kv, d).astype(np.float32))

    def old(kp, vp, k, v, pid, row):
        return kp.at[l, pid, :, row].set(k), vp.at[l, pid, :, row].set(v)

    want = jax.jit(old)(kp, vp, k, v, pid, row)
    got = jax.jit(lambda *a: write_rows(a[0], a[1], l, *a[2:]))(
        kp, vp, k, v, pid, row)
    for g, w, before in zip(got, want, (kp, vp)):
        g, w, before = np.asarray(g), np.asarray(w), np.asarray(before)
        np.testing.assert_array_equal(g[:, :trash], w[:, :trash])
        assert (g[l, :trash] != before[l, :trash]).any()  # something landed
        np.testing.assert_array_equal(g[[0, 2]], before[[0, 2]])


def test_pagepool_alloc_free_leak():
    pool = PagePool(8)
    assert pool.free_count == 8
    a = pool.alloc(3)
    b = pool.alloc(5)
    assert pool.free_count == 0
    with pytest.raises(Exception):
        pool.alloc(1)  # PoolExhausted
    pool.free(a)
    # copy-free reuse: freed pages are immediately allocatable
    c = pool.alloc(3)
    assert sorted(c) == sorted(a)
    pool.free(c)
    pool.free(b)
    assert pool.free_count == 8  # the leak invariant
    with pytest.raises(ValueError):
        pool.free([0])  # double free
