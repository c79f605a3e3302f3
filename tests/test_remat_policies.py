"""Selective-remat policy ladder (r5): parsing, wiring, and semantics.

The policy names must (a) parse, (b) actually mark the intended values
saveable (checked through jax.ad_checkpoint.saved_residuals — the same
introspection print_saved_residuals uses), and (c) be semantically
IDENTITY: a names policy changes what is stored vs recomputed, never the
math. These tests pin the machinery itself on the CPU backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_operator_tpu.models.transformer import (
    _REMAT_SAVE_SETS,
    init_transformer,
    lm_loss,
    preset,
    remat_save_names,
)


def test_remat_save_names_parsing():
    for alias, names in _REMAT_SAVE_SETS.items():
        assert remat_save_names(alias) == names
    assert remat_save_names("save:resid_mid, mlp_up") == ("resid_mid", "mlp_up")
    assert remat_save_names(True) is None
    assert remat_save_names("dots") is None
    assert remat_save_names(False) is None


def test_unknown_remat_mode_rejected():
    cfg = preset("tiny", remat="save_everything_twice")
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="unknown remat mode"):
        lm_loss(params, tok, cfg)


def _saved_residual_report(fn, *args) -> str:
    """print_saved_residuals output as a string (saved_residuals itself
    is not exported from jax.ad_checkpoint in this jax version)."""
    import contextlib
    import io

    from jax.ad_checkpoint import print_saved_residuals

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        print_saved_residuals(fn, *args)
    return buf.getvalue()


def test_named_values_become_saved_residuals():
    """Under save:resid_mid the saved-residual set grows beyond full
    remat's (the report prints shapes/provenance, not tag names — the
    policy's effect is the extra stored entries)."""
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 256)

    def residual_lines(remat):
        cfg = preset("tiny", remat=remat, max_seq=32)
        params = init_transformer(jax.random.PRNGKey(0), cfg)
        report = _saved_residual_report(lambda p: lm_loss(p, tok, cfg), params)
        return [ln for ln in report.splitlines() if ln.strip()]

    full = residual_lines(True)
    pol = residual_lines("save:resid_mid")
    assert len(pol) > len(full), (full, pol)


def test_flash_input_names_are_policy_visible():
    """The flash custom-vjp residuals are its model-layout inputs, tagged
    in the public entry — so a names policy can save them (the receipt
    that the r5 restructure actually made the boundary transparent on the
    input side). Pallas runs in interpreter mode on CPU."""
    from tf_operator_tpu.ops.flash_attention import flash_attention

    b, t, h, d = 1, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.float32)
    W = jax.random.normal(ks[3], (d, d), jnp.float32)

    def f(W):
        qq = (q.reshape(b * t * h, d) @ W).reshape(b, t, h, d)
        o = flash_attention(qq, k, v, causal=True, interpret=True)
        return jnp.sum(o * o)

    pol = jax.checkpoint_policies.save_only_these_names(
        "flash_q", "flash_k", "flash_v"
    )
    # the report prints each saved value's provenance: the tagged inputs
    # surface as outputs of the _tag_inputs checkpoint_name site
    assert "_tag_inputs" not in _saved_residual_report(jax.checkpoint(f), W)
    assert "_tag_inputs" in _saved_residual_report(
        jax.checkpoint(f, policy=pol), W
    )

    # and the policy is semantically identity
    g_pol = jax.grad(jax.checkpoint(f, policy=pol))(W)
    g_full = jax.grad(jax.checkpoint(f))(W)
    np.testing.assert_allclose(g_pol, g_full, rtol=1e-5, atol=1e-6)


def test_policy_grads_match_full_remat():
    """Names policies store-instead-of-recompute; grads must match full
    remat to the same tolerance full-vs-none remat exhibits (bf16 fusion
    reassociation noise — measured ~1e-2 relative on this config)."""
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)

    def grads(remat):
        cfg = preset("tiny", remat=remat, max_seq=32)
        params = init_transformer(jax.random.PRNGKey(0), cfg)
        return jax.grad(lambda p: lm_loss(p, tok, cfg))(params)

    g_full = grads(True)
    for mode in ("save_mlp_mid", "save:resid_mid"):
        g = grads(mode)
        for a, b_ in zip(jax.tree_util.tree_leaves(g_full),
                         jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(a, b_, rtol=3e-2, atol=3e-3)
