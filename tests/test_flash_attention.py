"""Flash-attention kernel tests: Pallas interpreter on CPU vs the dense
reference — forward and the custom-VJP backward, causal and full."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tf_operator_tpu.ops.flash_attention import flash_attention, reference_attention


def _qkv(key, b=2, t=256, h=2, d=64, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (b, t, h, d), dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    want = reference_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(1), b=1, t=128, h=2, d=32)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
        return jnp.sum(out ** 2)

    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for name, w, g in zip("qkv", want, got):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_uneven_lengths_fall_back_to_reference():
    q, k, v = _qkv(jax.random.PRNGKey(2), t=100)  # not block-divisible
    want = reference_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)  # silently dense
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_blockwise_equals_singleblock():
    """Online-softmax accumulation across many k-blocks must equal the
    single-block computation exactly (up to float assoc.)."""
    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, t=256, h=1, d=32)
    one = flash_attention(q, k, v, block_q=256, block_k=256, interpret=True)
    many = flash_attention(q, k, v, block_q=64, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(many), np.asarray(one), atol=2e-5, rtol=2e-5)


def test_short_sequences_stay_sublane_aligned():
    """Clamping blocks to a short t must not defeat the alignment gate:
    t=100 gives 100-row blocks (not 8-aligned) and must fall back rather
    than hand Mosaic an untileable shape."""
    from tf_operator_tpu.ops.flash_attention import _use_kernel

    assert not _use_kernel(t=100, d=128, block_q=100, block_k=100, interpret=False)
    assert not _use_kernel(t=100, d=128, block_q=100, block_k=100, interpret=True)
    assert _use_kernel(t=256, d=128, block_q=64, block_k=64, interpret=True)


def test_flash_under_sharded_trainer():
    """attn_impl='flash' must work through the sharded Trainer on a dp×tp
    mesh (the shard_map wrap; kernel itself falls back to reference on
    CPU, which exercises the same partitioning contract)."""
    from tf_operator_tpu.models.transformer import init_transformer, lm_loss, preset
    from tf_operator_tpu.models.transformer import transformer_logical_axes
    from tf_operator_tpu.parallel import build_mesh
    from tf_operator_tpu.train import Trainer, TrainerConfig

    mesh = build_mesh({"dp": 2, "tp": 4})
    cfg = preset("tiny", dtype=jnp.float32, attn_impl="flash")
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, b, e: lm_loss(p, b, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    state, m = trainer.step(state, tokens)
    assert np.isfinite(float(m["loss"]))


def test_transformer_flash_impl_matches_dense():
    """attn_impl='flash' in the model must match attn_impl='dense'."""
    from tf_operator_tpu.models.transformer import (
        init_transformer,
        preset,
        transformer_forward,
    )

    cfg_d = preset("tiny", dtype=jnp.float32, attn_impl="dense")
    cfg_f = preset("tiny", dtype=jnp.float32, attn_impl="flash")
    params = init_transformer(jax.random.PRNGKey(0), cfg_d)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg_d.vocab)
    dense = transformer_forward(params, tokens, cfg_d)
    flash = transformer_forward(params, tokens, cfg_f)
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(dense), atol=2e-4, rtol=2e-4
    )


# ---------------------------------------------------------------------------
# GQA (r3): no repeated-K/V materialization on either path
# ---------------------------------------------------------------------------


def _gqa_qkv(key, b=2, t=128, h=8, h_kv=2, d=32, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype)
    k = jax.random.normal(ks[1], (b, t, h_kv, d), dtype)
    v = jax.random.normal(ks[2], (b, t, h_kv, d), dtype)
    return q, k, v


def _repeat_oracle(q, k, v, causal):
    """The pre-r3 formulation: materialized repeated K/V heads through
    ordinary MHA — the semantics GQA must reproduce exactly."""
    g = q.shape[2] // k.shape[2]
    return reference_attention(
        q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), causal=causal
    )


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_reference_matches_repeat_oracle(causal):
    q, k, v = _gqa_qkv(jax.random.PRNGKey(3))
    want = _repeat_oracle(q, k, v, causal)
    got = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,h_kv", [(8, 2), (4, 1), (6, 6)])
def test_gqa_kernel_forward_matches_oracle(causal, h, h_kv):
    q, k, v = _gqa_qkv(jax.random.PRNGKey(4), h=h, h_kv=h_kv)
    want = _repeat_oracle(q, k, v, causal)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# (o, lse) entry — the blockwise/ring composition surface
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,h_kv", [(4, 4), (6, 2)])
def test_lse_entry_matches_reference(causal, h, h_kv):
    from tf_operator_tpu.ops.flash_attention import (
        flash_attention_lse, reference_attention_lse)

    q, k, v = _gqa_qkv(jax.random.PRNGKey(8), b=2, t=64, h=h, h_kv=h_kv, d=32)
    ow, lw = reference_attention_lse(q, k, v, causal=causal)
    ok_, lk = flash_attention_lse(q, k, v, causal=causal, block_q=32,
                                  block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(ok_), np.asarray(ow),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lw),
                               atol=2e-5, rtol=2e-5)
    # lse must also equal the repeat-oracle's logsumexp head-for-head
    # (pins the hk*g+gi head ordering of both layouts)
    _, l_rep = reference_attention_lse(
        q, jnp.repeat(k, h // h_kv, axis=2), jnp.repeat(v, h // h_kv, axis=2),
        causal=causal)
    np.testing.assert_allclose(np.asarray(lw), np.asarray(l_rep),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_lse_entry_grads_through_lse(causal):
    """Gradients THROUGH the lse output: the lse cotangent folds into the
    backward kernel's delta term (ds = p·(dp − (delta − g))) — the
    contract ring attention's merge relies on. Tolerances are f32-rounding
    scale: both paths sit ~1e-2 relative from the f64 truth on the
    squared-sum scalar (measured; the kernel is marginally CLOSER), so
    kernel-vs-reference comparisons cannot be tighter."""
    from tf_operator_tpu.ops.flash_attention import (
        flash_attention_lse, reference_attention_lse)

    q, k, v = _gqa_qkv(jax.random.PRNGKey(9), b=1, t=64, h=4, h_kv=2, d=32)

    def scal(r):
        return jnp.sum(r[0] ** 2) + jnp.sum(jnp.tanh(r[1]))

    def loss_ref(q, k, v):
        return scal(reference_attention_lse(q, k, v, causal=causal))

    def loss_ker(q, k, v):
        return scal(flash_attention_lse(q, k, v, causal=causal, block_q=32,
                                        block_k=32, interpret=True))

    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss_ker, argnums=(0, 1, 2))(q, k, v)
    for name, w, g in zip("qkv", want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-2, rtol=1e-2,
                                   err_msg=f"d{name} mismatch")


def test_lse_only_grads_are_tight():
    """With ONLY the lse cotangent live (o unused), the delta-adjustment
    path is isolated and f32 agreement is tight — separates 'lse path
    correct' from the looser o-path rounding above."""
    from tf_operator_tpu.ops.flash_attention import (
        flash_attention_lse, reference_attention_lse)

    q, k, v = _gqa_qkv(jax.random.PRNGKey(10), b=1, t=64, h=4, h_kv=4, d=32)

    def loss(fn, **kw):
        def f(q, k, v):
            return jnp.sum(jnp.tanh(fn(q, k, v, causal=False, **kw)[1]))
        return f

    want = jax.grad(loss(reference_attention_lse), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(flash_attention_lse, block_q=32, block_k=32,
                        interpret=True), argnums=(0, 1, 2))(q, k, v)
    for name, w, g in zip("qkv", want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("g", [3, 5, 12])
def test_gqa_default_blocks_stay_kernel_eligible(g):
    """Non-power-of-two group sizes: the default q-block target 512//g is
    not 8-aligned, and _pick_block's candidate scan steps by 8 from the
    target — an unaligned start would only visit unaligned candidates, so
    the gate would silently drop to the dense fallback at EVERY t (the
    regression this pins). The target must round down to 8-aligned
    first."""
    from tf_operator_tpu.ops.flash_attention import _pick_block, _use_kernel

    t = 2048
    bq = _pick_block(t, max(8, 512 // g))
    assert bq % 8 == 0 and t % bq == 0, (g, bq)
    assert _use_kernel(t, 128, bq, _pick_block(t, 1024), True)


def test_gqa_g3_kernel_matches_oracle():
    """End-to-end through flash_attention's DEFAULT block selection for a
    g=3 shape (t divisible only by 8-aligned blocks): the kernel must
    engage and agree with the repeat oracle."""
    q, k, v = _gqa_qkv(jax.random.PRNGKey(7), b=1, t=64, h=6, h_kv=2, d=32)
    want = _repeat_oracle(q, k, v, True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_kernel_grads_match_oracle(causal):
    """dk/dv must accumulate ALL query heads of a group (the fused
    (group, q-block) grid dim in _bwd_kernel) — a missed member
    under-counts dk/dv by its contribution."""
    q, k, v = _gqa_qkv(jax.random.PRNGKey(5), b=1, t=64, h=4, h_kv=2, d=32)

    def loss_ref(q, k, v):
        return jnp.sum(_repeat_oracle(q, k, v, causal) ** 2)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True)
        return jnp.sum(out ** 2)

    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for name, w, g in zip("qkv", want, got):
        assert g.shape == w.shape, f"d{name} shape"
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_gqa_head_mismatch_rejected():
    q, k, v = _gqa_qkv(jax.random.PRNGKey(6), h=6, h_kv=4)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, v)


def test_gqa_transformer_never_materializes_repeated_kv():
    """The model-level guarantee: a GQA config's jaxpr contains no
    [b, t, n_heads, hd]-shaped K/V produced by repeat on the dense/flash
    paths (transformer.py no longer calls jnp.repeat there)."""
    from tf_operator_tpu.models.transformer import lm_loss, preset, init_transformer

    cfg = preset("tiny", n_heads=4, n_kv_heads=2, remat=False,
                 attn_impl="dense", fused_xent=False)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    jaxpr = jax.make_jaxpr(lambda p, t: lm_loss(p, t, cfg))(params, tokens)
    # repeat lowers to broadcast_in_dim+reshape of a [b,t,nkv,hd] operand to
    # [b,t,nh,hd]; assert no eqn output carries the repeated-KV shape from
    # a gather/broadcast of the KV projection
    b, t, nh, nkv, hd = 2, 16, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bad = []
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name in ("broadcast_in_dim", "gather", "concatenate"):
            for out in eqn.outvars:
                if tuple(getattr(out.aval, "shape", ())) == (b, t, nh, hd):
                    bad.append(eqn)
    assert not bad, f"repeated-KV materialization found: {bad}"
