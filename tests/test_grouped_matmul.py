"""Kernel-level units for ops/grouped_matmul (r6): sentinel blocks, the
fused combine epilogue (row_scale), and the regridded dw accumulation —
all through the Pallas interpreter against dense references, including
gradients (the custom_vjp is hand-derived; these pins are what license
the ep-sharded dispatch to trust it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_operator_tpu.ops.grouped_matmul import gmm

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
