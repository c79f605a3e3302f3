"""The flash kernels (forward and fused backward) with a v width of its own (latent attention: q/k
192 wide, v 128) in interpret mode against the jnp oracle — values and
gradients, causal, one key a query head and grouped — and equality with
the equal-width kernels: v zero-padded to the q/k width goes through the
kernels as they were, and its first columns are the narrow call's."""

import importlib

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

fa = importlib.import_module("tf_operator_tpu.ops.flash_attention")


def operands(h, h_kv, d, dv, t=64, b=2):
    ks = jax.random.split(jax.random.PRNGKey(h * 100 + d), 4)
    return (jax.random.normal(ks[0], (b, t, h, d), jnp.float32),
            jax.random.normal(ks[1], (b, t, h_kv, d), jnp.float32),
            jax.random.normal(ks[2], (b, t, h_kv, dv), jnp.float32),
            jax.random.normal(ks[3], (b, t, h, dv), jnp.float32))


def kernel(q, k, v, causal=True):
    return fa.flash_attention(q, k, v, causal=causal, interpret=True,
                              block_q=16, block_k=32)


@pytest.mark.parametrize(
    "h,h_kv,d,dv,causal",
    [(4, 4, 48, 32, True), (4, 2, 24, 32, False)],
    ids=["mha-192:128-causal", "grouped-full"])
def test_unequal_widths_match_the_oracle_values_and_gradients(h, h_kv, d, dv, causal):
    q, k, v, w = operands(h, h_kv, d, dv)
    out = kernel(q, k, v, causal)
    assert out.shape == (2, 64, h, dv)
    want = fa.reference_attention(q, k, v, causal=causal)
    assert float(jnp.abs(out - want).max()) < 2e-5
    got = jax.grad(lambda *a: jnp.sum(kernel(*a, causal) * w), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(fa.reference_attention(*a, causal=causal) * w),
                   (0, 1, 2))(q, k, v)
    for g, r in zip(got, ref):
        assert g.shape == r.shape and float(jnp.abs(g - r).max()) < 5e-5
    # the scale is the q/k width's, and the lse surface agrees
    o2, lse = fa.flash_attention_lse(q, k, v, causal=causal, interpret=True,
                                     block_q=16, block_k=32)
    _, lse_ref = fa.reference_attention_lse(q, k, v, causal=causal)
    assert float(jnp.abs(lse - lse_ref).max()) < 2e-5 and bool(jnp.all(o2 == out))


def test_narrow_v_is_bit_equal_to_the_equal_width_kernels_on_padded_v():
    """Forward, bit for bit (a column of the result never meets another);
    the gradients to rounding (the padded dP sums 16 zeros more, in an order
    of the backend's choosing)."""
    q, k, v, w = operands(4, 2, 48, 32)
    pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, 16),))  # noqa: E731
    narrow, wide = kernel(q, k, v), kernel(q, k, pad(v))
    assert bool(jnp.all(wide[..., :32] == narrow)) and bool(jnp.all(wide[..., 32:] == 0))
    g_n = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))(q, k, v)
    g_w = jax.grad(lambda q, k, v: jnp.sum(kernel(q, k, pad(v)) * pad(w)), (0, 1, 2))(q, k, v)
    for a, b in zip(g_n, g_w):
        assert float(jnp.abs(a - b).max()) < 1e-5


def test_dispatch_refuses_mismatched_q_k_widths_and_gates_each_width():
    q, k, v, _ = operands(2, 2, 16, 40)
    with pytest.raises(ValueError, match="q/k width"):
        fa.flash_attention(q, k[..., :8], v, interpret=True)
    # off the TPU without interpret: the oracle, at any pair of widths (a v
    # WIDER than q and k here)
    out = fa.flash_attention(q, k, v, causal=True)
    assert out.shape == (2, 64, 2, 40)
    assert bool(jnp.all(out == fa.reference_attention(q, k, v, causal=True)))


# ---- the fused backward at unequal widths (PR 43) --------------------------
# dq's HBM tiles are whole lanes wide (192 → 256 on the chip; 48 → 128 here),
# dk is q/k wide and dv v wide, from one pair's p and ds.

LATENT_BWD_CASES = {
    "causal": dict(),
    "full": dict(causal=False),
    "g4-window-2.5-blocks": dict(h=8, window=160),
    "walks-of-1": dict(t=64),
    "walks-of-2": dict(t=128),
    "lse-cotangent-window-one-block": dict(dlse=True, window=64),
    "v-wider-than-q": dict(widths=(32, 48)),
}


def _latent_case(name):
    from tests.test_flash_backward import fused_bwd_case

    differs = dict(LATENT_BWD_CASES[name])
    d, dv = differs.pop("widths", (48, 32))
    return fused_bwd_case(name, differs, d=d, dv=dv)


@pytest.mark.parametrize("name", sorted(LATENT_BWD_CASES))
def test_fused_backward_at_unequal_widths_matches_autodiff(name):
    from tests.test_flash_backward import check_fused_bwd_against_autodiff

    check_fused_bwd_against_autodiff(_latent_case(name))


@pytest.mark.parametrize("name", sorted(LATENT_BWD_CASES))
def test_fused_backward_at_unequal_widths_equals_the_two_kernels(name):
    from tests.test_flash_backward import check_fused_bwd_against_two_kernels

    check_fused_bwd_against_two_kernels(_latent_case(name))
