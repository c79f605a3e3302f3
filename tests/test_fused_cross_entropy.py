"""Fused blockwise cross-entropy: value/gradient parity with the naive
materialize-the-logits path, weighting, padding, and the lm_loss toggle."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_operator_tpu.ops.fused_cross_entropy import fused_cross_entropy


def naive_xent(x, embed, targets, weights=None):
    logits = jnp.dot(x, embed.astype(x.dtype).T, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    if weights is None:
        return -jnp.mean(ll)
    w = weights.astype(jnp.float32)
    return -jnp.sum(ll * w) / jnp.maximum(jnp.sum(w), 1.0)


def data(n=48, d=16, v=37, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (jax.random.normal(ks[0], (n, d)) * 0.7).astype(dtype)
    embed = jax.random.normal(ks[1], (v, d), jnp.float32) * 0.3
    targets = jax.random.randint(ks[2], (n,), 0, v)
    return x, embed, targets


def test_value_matches_naive_f32():
    x, embed, targets = data()
    got = fused_cross_entropy(x, embed, targets, row_block=16)
    want = naive_xent(x, embed, targets)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_value_row_padding():
    # n not divisible by row_block: pad rows must not contribute
    x, embed, targets = data(n=41)
    got = fused_cross_entropy(x, embed, targets, row_block=16)
    want = naive_xent(x, embed, targets)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_single_block():
    x, embed, targets = data(n=8)
    got = fused_cross_entropy(x, embed, targets, row_block=1024)
    want = naive_xent(x, embed, targets)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_weighted_value_and_zero_weights():
    x, embed, targets = data()
    w = (jnp.arange(48) % 3 == 0).astype(jnp.float32)
    got = fused_cross_entropy(x, embed, targets, weights=w, row_block=16)
    want = naive_xent(x, embed, targets, weights=w)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # all-zero weights: denom clamps to 1, loss is 0, grads finite
    z = jnp.zeros((48,), jnp.float32)
    val, grads = jax.value_and_grad(
        lambda x, e: fused_cross_entropy(x, e, targets, weights=z, row_block=16),
        argnums=(0, 1),
    )(x, embed)
    assert float(val) == 0.0
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_grads_match_naive_f32():
    x, embed, targets = data()
    w = jax.random.uniform(jax.random.PRNGKey(9), (48,))
    gf = jax.grad(
        lambda x, e: fused_cross_entropy(x, e, targets, weights=w, row_block=16),
        argnums=(0, 1),
    )(x, embed)
    gn = jax.grad(
        lambda x, e: naive_xent(x, e, targets, weights=w), argnums=(0, 1)
    )(x, embed)
    np.testing.assert_allclose(gf[0], gn[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gf[1], gn[1], rtol=1e-5, atol=1e-6)


def test_grads_match_naive_f32_with_padding():
    x, embed, targets = data(n=41)
    gf = jax.grad(
        lambda x, e: fused_cross_entropy(x, e, targets, row_block=16), argnums=(0, 1)
    )(x, embed)
    gn = jax.grad(lambda x, e: naive_xent(x, e, targets), argnums=(0, 1))(x, embed)
    assert gf[0].shape == x.shape
    np.testing.assert_allclose(gf[0], gn[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gf[1], gn[1], rtol=1e-5, atol=1e-6)


def test_bf16_hidden_states():
    x, embed, targets = data(dtype=jnp.bfloat16)
    val, (dx, de) = jax.value_and_grad(
        lambda x, e: fused_cross_entropy(x, e, targets, row_block=16),
        argnums=(0, 1),
    )(x, embed)
    want = naive_xent(x, embed, targets)
    np.testing.assert_allclose(float(val), float(want), rtol=2e-2)
    assert dx.dtype == jnp.bfloat16
    assert de.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(de)))


def test_under_jit_and_grad_jit():
    """Value AND gradient under jit, with targets/weights as traced jit
    arguments (the production shape: trainer.step closes the whole loss,
    tokens included, under one jit)."""
    x, embed, targets = data()
    w = jax.random.uniform(jax.random.PRNGKey(3), (48,))
    f = jax.jit(
        lambda x, e, t, w: fused_cross_entropy(x, e, t, weights=w, row_block=16)
    )
    np.testing.assert_allclose(
        f(x, embed, targets, w), naive_xent(x, embed, targets, weights=w), rtol=1e-6
    )
    g = jax.jit(
        jax.grad(
            lambda x, e, t, w: fused_cross_entropy(x, e, t, weights=w, row_block=16),
            argnums=(0, 1),
        )
    )
    gf = g(x, embed, targets, w)
    gn = jax.grad(
        lambda x, e: naive_xent(x, e, targets, weights=w), argnums=(0, 1)
    )(x, embed)
    np.testing.assert_allclose(gf[0], gn[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gf[1], gn[1], rtol=1e-5, atol=1e-6)


def test_empty_rows_raise():
    x, embed, targets = data(n=8)
    with pytest.raises(ValueError, match="at least one row"):
        fused_cross_entropy(x[:0], embed, targets[:0])


# ---- lm_loss integration --------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
def test_lm_loss_fused_matches_unfused(causal):
    from tf_operator_tpu.models.transformer import init_transformer, lm_loss, preset

    cfg = preset("tiny", causal=causal, dtype=jnp.float32)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    key = jax.random.PRNGKey(2)
    fused = lm_loss(params, tokens, cfg, key=key)
    unfused = lm_loss(
        params, tokens, preset("tiny", causal=causal, dtype=jnp.float32,
                               fused_xent=False), key=key,
    )
    np.testing.assert_allclose(float(fused), float(unfused), rtol=1e-5)


def test_lm_loss_fused_grads_close_to_unfused():
    from tf_operator_tpu.models.transformer import init_transformer, lm_loss, preset

    cfg_f = preset("tiny", dtype=jnp.float32)
    cfg_u = preset("tiny", dtype=jnp.float32, fused_xent=False)
    params = init_transformer(jax.random.PRNGKey(0), cfg_f)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg_f.vocab)
    gf = jax.grad(lambda p: lm_loss(p, tokens, cfg_f))(params)
    gu = jax.grad(lambda p: lm_loss(p, tokens, cfg_u))(params)
    for a, b in zip(jax.tree_util.tree_leaves(gf), jax.tree_util.tree_leaves(gu)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_fused_trainer_step_on_mesh():
    """Full sharded train step over the 8-device CPU mesh (dp x tp: the tp
    axis shards the vocab dim of embed through the fused loss)."""
    from tf_operator_tpu.models.transformer import (
        init_transformer, lm_loss, preset, transformer_logical_axes,
    )
    from tf_operator_tpu.parallel import build_mesh
    from tf_operator_tpu.train import Trainer, TrainerConfig

    cfg = preset("tiny")
    mesh = build_mesh({"dp": 2, "tp": 4})
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, extra: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    losses = []
    for _ in range(3):
        state, metrics = trainer.step(state, tokens)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # it learns the (fixed) batch


# ---- the op's own partition on a mesh of data axes (PR 31) ----------------

_MESH_CFG = dict(d_model=256, vocab=4096, n_layers=2, n_heads=4, d_ff=512,
                 max_seq=2048, dtype=jnp.float32)


@functools.lru_cache(maxsize=None)
def _unmeshed_loss_and_grads():
    """{causal: (cfg, loss, grads)} of one tiny float32 model on one batch,
    no mesh; 1,100 columns, so a chip's 1,099 (1,100 masked) rows are two
    blocks, the second mostly padding."""
    from tf_operator_tpu.models.transformer import init_transformer, lm_loss, preset

    cfgs = {c: preset("tiny", causal=c, max_seq=2048, dtype=jnp.float32)
            for c in (True, False)}
    params = jax.jit(lambda k: init_transformer(k, cfgs[True]))(jax.random.PRNGKey(0))
    tokens = np.random.default_rng(1).integers(0, cfgs[True].vocab, (4, 1100), np.int32)
    out = {}
    for causal, cfg in cfgs.items():  # False: the MLM mask rides as weights
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: lm_loss(p, t, cfg, key=jax.random.PRNGKey(2))))(params, tokens)
        out[causal] = (cfg, loss, grads)
    return params, tokens, out


@pytest.mark.parametrize(
    "axes",
    [{"fsdp": 4}, {"dp": 2, "fsdp": 2}, {"dp": 4}, {"fsdp": 1}, {"dp": 2, "tp": 4}],
    ids=lambda a: "x".join(f"{k}{v}" for k, v in a.items()))
def test_partition_follows_the_mesh(axes):
    """On a mesh that only splits the batch the compiled Trainer step walks
    each chip's own rows past a head gathered once: no collective carries a
    [rows, vocab] tile, none sits in the CE's loops, the head is gathered
    once and its gradient reduced once an axis; loss and gradients are the
    unmeshed call's. One chip, or a mesh that shards anything else (tp), keeps
    the one walk over all rows."""
    from tf_operator_tpu.models.transformer import (
        init_transformer, lm_loss, preset, transformer_logical_axes,
    )
    from tf_operator_tpu.ops.fused_cross_entropy import data_parallel_axes
    from tf_operator_tpu.parallel import build_mesh
    from tf_operator_tpu.parallel.collectives import (
        collectives_summary, compiled_collectives,
    )
    from tf_operator_tpu.train import Trainer, TrainerConfig

    n_dev = int(np.prod(list(axes.values())))
    mesh = build_mesh(axes, devices=jax.devices()[:n_dev])
    data_axes = tuple(a for a in ("dp", "fsdp") if axes.get(a, 1) > 1)
    engages = "tp" not in axes and bool(data_axes)
    assert data_parallel_axes(mesh) == (data_axes if engages else ())

    cfg = preset("tiny", **_MESH_CFG)
    vocab, d = cfg.vocab, cfg.d_model
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, extra: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    text = trainer.compile_step(jax.ShapeDtypeStruct((4, 2048), "int32")).as_text()
    ops = compiled_collectives(text)
    assert trainer.step_collectives == collectives_summary(ops)
    assert trainer.step_kernels == {}  # a CPU program holds no Mosaic kernel
    assert trainer.step_remats == 0  # nor a clone made for want of memory
    in_map = [op for op in ops if "shard_map" in op["op_name"]]
    if not engages:
        assert not in_map
        if n_dev == 1:
            assert trainer.step_collectives == {}
        return

    tiles = [op for op in ops if any(s.endswith(f",{vocab}]") for s in op["shapes"])]
    assert not tiles, tiles
    in_ce_loops = [op for op in ops if "fused_xent" in op["op_name"] and op["in_loop"]]
    assert not in_ce_loops, in_ce_loops
    # what the CE itself put in: the head's gather (before its cast the CPU
    # backend gathers f32), the gather's transpose over fsdp on the f32
    # accumulator, one all-reduce of what is left over dp, two scalar psums
    fsdp = axes.get("fsdp", 1)
    ce = [op for op in ops if "fused_xent" in op["op_name"]]
    assert all(op["runs"] == 1 and "shard_map" in op["op_name"] for op in ce), ce
    big = sorted((op["kind"], op["shapes"][0]) for op in ce if op["bytes"] > 64)
    assert big == sorted(
        [("all-gather", f"f32[{vocab},{d}]"),
         ("reduce-scatter", f"f32[{vocab},{d}]")] * (fsdp > 1)
        + [("all-reduce", f"f32[{vocab},{d // fsdp}]")] * ("dp" in data_axes)), ce
    assert len(ce) <= len(big) + 2, ce  # XLA may merge a psum with a neighbour

    params, tokens, unmeshed = _unmeshed_loss_and_grads()
    params = jax.device_put(params, trainer.param_shardings)
    tokens = jax.device_put(tokens, trainer.batch_sharding)
    for cfg_c, loss0, grads0 in unmeshed.values():
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: lm_loss(p, t, cfg_c, mesh=mesh, key=jax.random.PRNGKey(2))
        ))(params, tokens)
        np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)
        for g, g0 in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(grads0)):
            np.testing.assert_allclose(
                g, g0, rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(g0))))
