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
    _remat_wrap,
    checkpoint_name,
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
    assert remat_save_names("save:flash_o,flash_lse") == ("flash_o", "flash_lse")
    with pytest.raises(ValueError, match="flash_0"):
        remat_save_names("save:flash_0,flash_lse")  # a typo saves nothing
    # every ``*_mid`` tier keeps the flash forward's own outputs where it
    # kept resid_mid (in place of it: a step at its memory limit had no room
    # for all three); the old and the larger set stay one ``save:`` away
    for alias, names in _REMAT_SAVE_SETS.items():
        assert ("flash_o" in names) == ("flash_lse" in names) == (
            "_mid" in alias), alias
        assert "resid_mid" not in names, alias
    assert remat_save_names("save:resid_mid,flash_o,flash_lse") == (
        "resid_mid", "flash_o", "flash_lse")


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


@pytest.mark.parametrize("remat", ["save:resid_mid", "save_mid"])
def test_named_values_become_saved_residuals(remat):
    """Under a names policy the saved-residual set grows beyond full
    remat's (the policy's effect is the extra stored entries) — on the
    model's DENSE attention path too, whose output carries ``flash_o``:
    ``save_mid`` must not fall to full remat where no kernel runs."""
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 256)

    def residual_lines(remat):
        cfg = preset("tiny", remat=remat, max_seq=32)
        params = init_transformer(jax.random.PRNGKey(0), cfg)
        report = _saved_residual_report(lambda p: lm_loss(p, tok, cfg), params)
        return [ln for ln in report.splitlines() if ln.strip()]

    full = residual_lines(True)
    pol = residual_lines(remat)
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


# ---- the flash forward's (o, lse) as nameable residuals -------------------


def _scanned_flash_layers(remat, force_kernel=True, layers=3):
    """loss(W) of attention layers (projections, the flash kernel in
    interpret mode or its dense fallback, the wo product, the ``resid_mid``
    tag) under the model's own remat wrapper inside ``lax.scan``, as the
    train step holds them."""
    from tf_operator_tpu.ops.flash_attention import flash_attention

    b, t, h, d = 1, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, t, h * d), jnp.float32)
    W = {n: 0.1 * jax.random.normal(k, (layers, h * d, h * d), jnp.float32)
         for n, k in zip(("wq", "wk", "wv", "wo"), ks[1:])}

    def layer(x, w):
        q, k, v = ((x @ w[n]).reshape(b, t, h, d) for n in ("wq", "wk", "wv"))
        o = flash_attention(q, k, v, causal=True, interpret=force_kernel,
                            force_kernel=force_kernel)
        return checkpoint_name(x + o.reshape(b, t, h * d) @ w["wo"], "resid_mid")

    f = _remat_wrap(layer, preset("tiny", remat=remat))

    def loss(W):
        if layers == 1:  # no scan: its residuals print as the scan's outputs
            out = f(x, jax.tree_util.tree_map(lambda w: w[0], W))
        else:
            out, _ = jax.lax.scan(lambda c, w: (f(c, w), None), x, W)
        return jnp.sum(out * out)

    return loss, W


def _pallas_calls(fn, *args) -> dict:
    import re

    text = str(jax.make_jaxpr(fn)(*args))
    return {name: len(re.findall(rf"name={name}\b", text))
            for name in ("flash_fwd", "flash_bwd_dqkv")}


@pytest.mark.parametrize("remat, fwd_runs", [
    ("save_mid", 1), ("save_qkv_mid", 1), ("save:flash_o,flash_lse", 1),
    ("full", 2), ("save:resid_mid", 2), ("save_qkv", 2)])
def test_mid_tiers_keep_the_flash_outputs_and_replay_no_forward(remat, fwd_runs):
    """The gradient of a scanned, rematerialised flash layer holds
    ``flash_fwd`` once under every tier that names ``flash_o`` /
    ``flash_lse`` (the backward kernel reads what the forward wrote) and
    twice under the modes that do not (the program they always were)."""
    loss, W = _scanned_flash_layers(remat)
    assert _pallas_calls(jax.grad(loss), W) == {
        "flash_fwd": fwd_runs, "flash_bwd_dqkv": 1}


@pytest.mark.parametrize("remat", ["save_mid", "save:flash_o,flash_lse"])
def test_saved_flash_outputs_give_full_remats_gradients_exactly(remat):
    """The same kernels on the same inputs, one launch fewer: not a bit of
    any gradient differs from full remat's."""
    loss, W = _scanned_flash_layers(remat)
    g = jax.jit(jax.grad(loss))(W)
    loss_full, _ = _scanned_flash_layers("full")
    g_full = jax.jit(jax.grad(loss_full))(W)
    for name in W:
        np.testing.assert_array_equal(g[name], g_full[name], err_msg=name)


@pytest.mark.parametrize("force_kernel", [True, False], ids=["kernel", "dense"])
def test_flash_output_names_are_policy_visible(force_kernel):
    """``flash_o`` / ``flash_lse`` are tagged INSIDE the custom-vjp's
    forward rule (registered without ``optimize_remat``, which hid them)
    and on the dense fallback under the same names: the saved-residual
    report gains them ([b, t, h, dv] and the compact f32 [b, t, h]) under
    a policy that names them."""
    def saved(remat):
        loss, W = _scanned_flash_layers(remat, force_kernel, layers=1)
        return _saved_residual_report(loss, W)

    named = saved("save:flash_o,flash_lse")
    assert "f32[1,64,2,16]" in named, named
    # flash_attention() drops the lse: the kernel path keeps it as the
    # backward kernel's residual, the dense path has no reader for it
    assert ("f32[1,64,2] named 'flash_lse'" in named) == force_kernel, named
    old_set = saved("save:resid_mid")
    assert "flash_lse" not in old_set and "f32[1,64,2,16]" not in old_set, old_set
