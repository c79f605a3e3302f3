"""Kernel-level units for ops/grouped_matmul (r6): sentinel blocks, the
fused combine epilogue (row_scale), and the regridded dw accumulation —
all through the Pallas interpreter against dense references, including
gradients (the custom_vjp is hand-derived; these pins are what license
the ep-sharded dispatch to trust it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_operator_tpu.ops.grouped_matmul import (_combine_pairs, _token_tile,
                                                 combine_rows, gmm)

B = 8  # small block quantum so tests exercise multi-block experts cheaply


def _mk(seed=0, R=64, k=16, n=32, E=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (R, k), jnp.float32)
    w = jax.random.normal(ks[1], (E, k, n), jnp.float32) * 0.1
    s = jax.nn.sigmoid(jax.random.normal(ks[2], (R,), jnp.float32))
    return x, w, s


def _ref(x, w, be, s=None):
    """Dense reference: per-block matmul, zeros for sentinel blocks."""
    R, n = x.shape[0], w.shape[-1]
    out = []
    for i, e in enumerate(np.asarray(be)):
        xr = x[i * B:(i + 1) * B]
        if e < 0:
            out.append(jnp.zeros((B, n)))
            continue
        y = xr @ w[e]
        if s is not None:
            y = y * s[i * B:(i + 1) * B, None]
        out.append(y)
    return jnp.concatenate(out)


def test_sentinel_blocks_write_zeros_not_garbage():
    x, w, _ = _mk()
    be = jnp.array([0, 0, 1, -1, 2, 2, -1, 3], jnp.int32)
    y = gmm(x, w, be, block_rows=B, interpret=True)
    np.testing.assert_allclose(y, _ref(x, w, be), rtol=1e-5, atol=1e-5)
    # the sentinel rows specifically: exact zeros (uninitialized output
    # memory here would poison any downstream transpose/gather)
    np.testing.assert_array_equal(np.asarray(y[3 * B:4 * B]), 0.0)
    np.testing.assert_array_equal(np.asarray(y[6 * B:7 * B]), 0.0)


def test_row_scale_epilogue_matches_post_multiply():
    x, w, s = _mk()
    be = jnp.array([0, 1, 1, 2, 2, 2, 3, 0], jnp.int32)
    got = gmm(x, w, be, row_scale=s, block_rows=B, interpret=True)
    want = gmm(x, w, be, block_rows=B, interpret=True) * s[:, None]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("scaled", [False, True])
def test_grads_match_dense_reference(scaled):
    x, w, s = _mk()
    be = jnp.array([0, 0, 1, -1, 2, 2, -1, 3], jnp.int32)

    def loss_gmm(x, w, s):
        y = gmm(x, w, be, row_scale=s if scaled else None, block_rows=B,
                interpret=True)
        return jnp.sum(y ** 2)

    def loss_ref(x, w, s):
        return jnp.sum(_ref(x, w, be, s if scaled else None) ** 2)

    got = jax.grad(loss_gmm, argnums=(0, 1, 2))(x, w, s)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, s)
    for a, b, name in zip(got, want, "xws"):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("scaled", [False, True])
def test_gmm_grads_keeps_dw_in_float32_and_sums_over_segments(scaled):
    """``gmm_grads`` on bfloat16 operands: dw is the accumulator's float32
    (the vjp's is that, rounded), and two SEGMENTS of a buffer summed in
    float32 give the whole buffer's dw — what the expert walk relies on."""
    from tf_operator_tpu.ops.grouped_matmul import gmm_grads

    x, w, s = _mk()
    x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    dy = jax.random.normal(jax.random.PRNGKey(7), (64, 32)).astype(jnp.bfloat16)
    be = jnp.array([0, 0, 1, -1, 2, 2, 0, 3], jnp.int32)
    scale = s if scaled else None
    w_t = jnp.swapaxes(w, 1, 2)
    whole = gmm_grads(x, w_t, be, dy, row_scale=scale, block_rows=B, interpret=True)
    assert whole[1].dtype == jnp.float32 and whole[0].dtype == jnp.bfloat16
    args = (x, w) + ((s,) if scaled else ())
    _, vjp = jax.vjp(lambda *a: gmm(a[0], a[1], be, row_scale=a[2] if scaled else None,
                                    block_rows=B, interpret=True), *args)
    cots = vjp(dy)
    np.testing.assert_array_equal(np.asarray(cots[1]), np.asarray(whole[1].astype(w.dtype)))
    np.testing.assert_array_equal(np.asarray(cots[0]), np.asarray(whole[0]))
    halves = [gmm_grads(x[h], w_t, be[b], dy[h], block_rows=B, interpret=True,
                        row_scale=None if scale is None else scale[h])
              for h, b in ((slice(0, 32), slice(0, 4)), (slice(32, 64), slice(4, 8)))]
    np.testing.assert_allclose(halves[0][1] + halves[1][1], whole[1], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(h[0]) for h in halves]), np.asarray(whole[0]))


def test_unvisited_expert_dw_is_exact_zero():
    """The regridded dw kernel zeroes every (expert, col-tile) output at
    walk step 0, so an expert no block maps to gets dw == 0 — not
    uninitialized kernel output memory. (The r5 grid only wrote tiles a
    step visited; parallel.moe had to allocate garbage blocks to paper
    over that. r6 makes the guarantee kernel-level.)"""
    x, w, _ = _mk()
    be = jnp.zeros((x.shape[0] // B,), jnp.int32)  # everything on expert 0
    gw = jax.grad(
        lambda w: jnp.sum(gmm(x, w, be, block_rows=B, interpret=True) ** 2)
    )(w)
    assert np.isfinite(np.asarray(gw)).all()
    np.testing.assert_array_equal(np.asarray(gw[1:]), 0.0)
    assert np.abs(np.asarray(gw[0])).sum() > 0  # the visited one is real


def test_noncontiguous_same_expert_blocks_accumulate():
    """The dw walk follows per-expert block LISTS, so an expert whose
    blocks are interleaved with other experts' still accumulates every
    one of them (the list, not block adjacency, defines the walk)."""
    x, w, s = _mk()
    be = jnp.array([0, 1, 0, 1, 0, 1, 0, 1], jnp.int32)  # interleaved

    def loss_gmm(w):
        return jnp.sum(gmm(x, w, be, block_rows=B, interpret=True) ** 2)

    def loss_ref(w):
        return jnp.sum(_ref(x, w, be) ** 2)

    np.testing.assert_allclose(
        jax.grad(loss_gmm)(w), jax.grad(loss_ref)(w), rtol=1e-4, atol=1e-5)


def test_row_count_must_divide_block_rows():
    x, w, _ = _mk(R=60)  # 60 % 8 != 0
    with pytest.raises(ValueError, match="divisible"):
        gmm(x, w, jnp.zeros((8,), jnp.int32), block_rows=B, interpret=True)


# -- VMEM tile plan (PR 21) --------------------------------------------------


@pytest.mark.parametrize(
    "name,args,want",
    [
        # (n, k, block_rows, x_bytes, w_bytes, out_bytes, scaled, acc_rows)
        # moe-small (E=8, 768<->3072, bf16): the measured tile choices,
        # unchanged by counting every resident tile, inside the default
        ("moe-small up fwd", (3072, 768, 256, 2, 2, 2, False, 256), (1536, False)),
        ("moe-small down fwd", (768, 3072, 256, 2, 2, 2, True, 256), (384, False)),
        ("moe-small up dw", (3072, 768, 256, 2, 4, 2, False, 768), (1024, False)),
        ("moe-small down dw", (768, 3072, 256, 2, 4, 2, True, 3072), (256, False)),
        # mixtral-8x7b (4096<->14336): the double-buffered [256, 14336] row
        # tile alone is 14.7 MB — narrowest tile AND a raised limit
        ("mixtral up fwd", (14336, 4096, 256, 2, 2, 2, False, 256), (512, False)),
        ("mixtral up dx", (4096, 14336, 256, 2, 2, 2, False, 256), (128, True)),
        ("mixtral down dw", (4096, 14336, 256, 2, 4, 2, False, 14336), (128, True)),
    ],
)
def test_plan_cols_counts_every_resident_tile(name, args, want):
    from tf_operator_tpu.ops.grouped_matmul import (
        _VMEM_ASK_MAX,
        _VMEM_SCOPED_DEFAULT,
        _plan_cols,
        _resident_bytes,
    )

    bn, limit = _plan_cols(*args)
    assert (bn, limit is not None) == want, name
    n, k, br, xb, wb, ob, scaled, acc = args
    need = _resident_bytes(br, k, bn, xb, wb, ob, scaled, acc)
    if limit is None:
        assert need <= _VMEM_SCOPED_DEFAULT
    else:
        assert _VMEM_SCOPED_DEFAULT < need < limit <= _VMEM_ASK_MAX


# ---- combine_rows: a segment's rows added onto their tokens ----------------


def _segment(seed, tokens, k_top, experts, held, n_blocks=None, never=()):
    """(tok, valid) of one segment in gmm's layout: every token's ``k_top``
    distinct choices of ``experts`` (none of ``never``), the held experts'
    choices sorted stably by expert, each expert padded to the block quantum
    B (invalid slots read token 0, as the walk's do) and the tail filled
    with sentinel blocks up to ``n_blocks``."""
    rng = np.random.default_rng(seed)
    open_to = [e for e in range(experts) if e not in never]
    chosen = np.stack([rng.choice(open_to, k_top, replace=False)
                       for _ in range(tokens)]).reshape(-1)
    order = np.argsort(chosen, kind="stable")
    tok, valid = [], []
    for e in range(held):
        mine = order[chosen[order] == e] // k_top
        pad = -len(mine) % B
        tok += [*mine, *[0] * pad]
        valid += [*[True] * len(mine), *[False] * pad]
    tail = (n_blocks or 0) * B - len(tok)
    assert tail >= 0 or n_blocks is None
    tok += [0] * max(tail, 0)
    valid += [False] * max(tail, 0)
    return jnp.asarray(tok, jnp.int32), jnp.asarray(valid)


COMBINE_CASES = {
    # tokens, d, k_top, experts, held + what the case is there for; the token
    # tile is the op's own choice, the widest of 512 ... 8 that divides the
    # tokens: 64 is one tile, 80 five of 16, 40 five of 8, 1024 two of 512
    "bf16-d128": dict(shape=(64, 128, 2, 8, 4), dtype=jnp.bfloat16),
    "f32-d512": dict(shape=(64, 512, 2, 8, 4), dtype=jnp.float32),
    "bf16-d512-five-tiles-of-16": dict(shape=(80, 512, 3, 8, 8),
                                       dtype=jnp.bfloat16, tile=16),
    # every token is named by every held expert: each group meets each tile,
    # which is the most pairs a routing can list
    "a-token-in-every-experts-blocks": dict(
        shape=(40, 128, 4, 4, 4), dtype=jnp.bfloat16, tile=8),
    "tail-of-sentinel-blocks": dict(shape=(64, 128, 2, 8, 4),
                                    dtype=jnp.bfloat16, n_blocks=24),
    "an-expert-with-no-row": dict(shape=(80, 128, 2, 8, 4),
                                  dtype=jnp.float32, never=(1,), tile=16),
    "valid-all-false": dict(shape=(80, 128, 2, 8, 4), dtype=jnp.bfloat16,
                            none_valid=True, tile=16),
    "the-backwards-two-operands": dict(shape=(64, 128, 2, 8, 4),
                                       dtype=jnp.bfloat16, operands=2),
    "f32-and-bf16-operands-24-tokens": dict(
        shape=(24, 128, 2, 4, 2), dtype=jnp.float32, operands=2, tile=8),
    # the cells' own tile, twice: ~130 chunks of 8 rows against 2 tiles
    "two-tiles-of-512": dict(shape=(1024, 128, 2, 8, 4), dtype=jnp.bfloat16,
                             tile=512),
}


@pytest.mark.parametrize("case", sorted(COMBINE_CASES))
def test_combine_rows_is_the_scatter_add_and_its_vjp(case):
    """``combine_rows`` (the ``moe_combine`` kernel's body, through the
    interpreter) against ``acc.at[tok].add(where(valid, rows, 0))``: the
    result, and the cotangents of ``acc`` and of every row operand against
    ``jax.grad`` of the scatter form. The selection is exact and a token's
    rows are added in the slots' order, which is the order XLA's CPU scatter
    applies them in, so the two agree to the last bit of float32 but for
    the association of the operands' sum."""
    spec = COMBINE_CASES[case]
    tokens, d, k_top, experts, held = spec["shape"]
    tok, valid = _segment(3, tokens, k_top, experts, held,
                          spec.get("n_blocks"), spec.get("never", ()))
    if spec.get("none_valid"):
        valid = jnp.zeros_like(valid)
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    acc = jax.random.normal(keys[0], (tokens, d), jnp.float32)
    dtypes = [spec["dtype"], jnp.bfloat16][:spec.get("operands", 1)]
    rows = tuple(jax.random.normal(k, (tok.shape[0], d), jnp.float32).astype(dt)
                 for k, dt in zip(keys[1:], dtypes))
    weigh = jax.random.normal(keys[3], (tokens, d), jnp.float32)
    run = dict(groups=held, block_rows=B, interpret=True)

    def kernel(acc, rows):
        return combine_rows(acc, rows if len(rows) > 1 else rows[0], tok,
                            valid, **run)

    def scatter(acc, rows):
        add = sum(r.astype(jnp.float32) for r in rows)
        return acc.at[tok].add(jnp.where(valid[:, None], add, 0))

    tile, _ = _token_tile(tokens, d, B * d * sum(
        jnp.dtype(dt).itemsize for dt in dtypes))
    assert tile == spec.get("tile", tokens)
    _, _, n_pairs = _combine_pairs(
        jnp.where(valid, tok, -1).reshape(-1, 1, B), tokens, tile, held)
    assert int(n_pairs[0]) <= held * (tokens // tile) + tok.shape[0] // B
    if spec.get("none_valid"):
        assert int(n_pairs[0]) == 0
    np.testing.assert_allclose(kernel(acc, rows), scatter(acc, rows),
                               rtol=1e-6, atol=1e-6)
    got = jax.grad(lambda a, r: jnp.sum(kernel(a, r) * weigh), (0, 1))(acc, rows)
    want = jax.grad(lambda a, r: jnp.sum(scatter(a, r) * weigh), (0, 1))(acc, rows)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
