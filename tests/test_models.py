"""Model family tests: transformer (dense + ring attention paths) and
ResNet, plus the sharded Trainer on multi-axis meshes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_operator_tpu.models import (
    ResNetConfig,
    TransformerConfig,
    init_resnet,
    init_transformer,
    lm_loss,
    resnet_forward,
    transformer_forward,
    transformer_logical_axes,
)
from tf_operator_tpu.models.transformer import PRESETS, preset
from tf_operator_tpu.parallel import build_mesh
from tf_operator_tpu.train import Trainer, TrainerConfig

TINY = PRESETS["tiny"]


def tokens(batch=4, seq=32, vocab=TINY.vocab, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, vocab)


# ---- transformer ---------------------------------------------------------


def test_transformer_forward_shape_and_dtype():
    params = init_transformer(jax.random.PRNGKey(0), TINY)
    logits = transformer_forward(params, tokens(), TINY)
    assert logits.shape == (4, 32, TINY.vocab)
    assert logits.dtype == jnp.float32


def test_logical_axes_match_param_tree():
    params = init_transformer(jax.random.PRNGKey(0), TINY)
    axes = transformer_logical_axes(TINY)
    # must be tree_map-compatible and rank-consistent
    checked = jax.tree_util.tree_map(
        lambda p, a: p.ndim == len(a), params, axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )
    assert all(jax.tree_util.tree_leaves(checked))


def test_causal_masking_is_causal():
    # changing a future token must not change earlier logits
    params = init_transformer(jax.random.PRNGKey(0), TINY)
    t1 = tokens(batch=1)
    t2 = t1.at[0, -1].set((t1[0, -1] + 1) % TINY.vocab)
    l1 = transformer_forward(params, t1, TINY)
    l2 = transformer_forward(params, t2, TINY)
    np.testing.assert_allclose(
        np.asarray(l1[0, :-1]), np.asarray(l2[0, :-1]), rtol=1e-3, atol=1e-3
    )


def test_bidirectional_encoder_sees_future():
    cfg = preset("tiny", causal=False)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    t1 = tokens(batch=1)
    t2 = t1.at[0, -1].set((t1[0, -1] + 1) % cfg.vocab)
    l1 = transformer_forward(params, t1, cfg)
    l2 = transformer_forward(params, t2, cfg)
    assert not np.allclose(np.asarray(l1[0, 0]), np.asarray(l2[0, 0]), atol=1e-5)


def test_ring_attention_path_matches_dense():
    mesh = build_mesh({"dp": 2, "cp": 4})
    cfg_dense = preset("tiny", remat=False, dtype=jnp.float32)
    cfg_ring = preset("tiny", remat=False, dtype=jnp.float32, attn_impl="ring")
    params = init_transformer(jax.random.PRNGKey(0), cfg_dense)
    toks = tokens(batch=2, seq=64)
    with jax.sharding.use_mesh(mesh) if hasattr(jax.sharding, "use_mesh") else _nullcontext():
        dense = transformer_forward(params, toks, cfg_dense)
        ring = transformer_forward(params, toks, cfg_ring, mesh=mesh)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), rtol=5e-3, atol=5e-3)


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def test_n_params_formula_matches_actual():
    params = init_transformer(jax.random.PRNGKey(0), TINY)
    actual = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert TINY.n_params() == actual


# ---- trainer -------------------------------------------------------------


def test_trainer_lm_loss_decreases_dp_tp():
    mesh = build_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    cfg = TINY

    def loss_fn(params, batch, extra):
        del extra
        return lm_loss(params, batch, cfg)

    trainer = Trainer(
        mesh,
        loss_fn=loss_fn,
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-2, grad_clip=1.0),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    # params actually sharded: embed over fsdp, mlp over tp
    embed_sh = state.params["embed"].sharding
    assert "fsdp" in str(embed_sh.spec) or embed_sh.spec == jax.sharding.PartitionSpec()
    batch = jax.device_put(tokens(batch=8, seq=32), trainer.batch_sharding)
    losses = []
    for _ in range(8):
        state, m = trainer.step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    assert int(state.step) == 8


def test_optimizer_state_shardings_match_params_despite_shape_collision():
    # tiny has n_heads*head_dim == d_model, so wq (L,d,d) and wo (L,d,d)
    # have identical shapes but transposed shardings on an fsdp x tp mesh —
    # optimizer moments must follow their OWN param's sharding.
    mesh = build_mesh({"fsdp": 4, "tp": 2})
    cfg = TINY

    trainer = Trainer(
        mesh,
        loss_fn=lambda p, b, e: lm_loss(p, b, cfg),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw"),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    mu = state.opt_state[1][0].mu  # chain(clip, adamw) -> adamw ScaleByAdam
    for name in ("wq", "wo", "w_gate", "w_down"):
        assert (
            mu["layers"][name].sharding == state.params["layers"][name].sharding
        ), name


def test_mlm_loss_trains_bidirectional_encoder():
    mesh = build_mesh({"dp": 8})
    cfg = preset("tiny", causal=False)

    def loss_fn(params, batch, extra):
        del extra
        return lm_loss(params, batch, cfg, key=jax.random.PRNGKey(7))

    trainer = Trainer(
        mesh,
        loss_fn=loss_fn,
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=5e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    batch = jax.device_put(tokens(batch=8, seq=32), trainer.batch_sharding)
    losses = []
    for _ in range(10):
        state, m = trainer.step(state, batch)
        losses.append(float(m["loss"]))
    # MLM on random tokens can't reach ~0 (identity would); it should still
    # optimize the masked prediction objective downward.
    assert losses[-1] < losses[0], losses


def test_bn_fused_stats_matches_two_pass_variance():
    """bn_fused_stats=True (one-pass E[x]/E[x²] statistics, the TPU-fast
    path) must agree with the textbook mean-then-var formulation — same
    forward output and same running-stat update, within f32 tolerance."""
    from tf_operator_tpu.models.resnet import _batch_norm

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 6, 6, 16), jnp.float32) * 3.0 + 1.5
    p = {"scale": jnp.linspace(0.5, 2.0, 16), "bias": jnp.linspace(-1.0, 1.0, 16)}
    s = {"mean": jnp.zeros((16,)), "var": jnp.ones((16,))}
    y_fused, s_fused = _batch_norm(x, p, s, train=True, fused_stats=True)
    y_exact, s_exact = _batch_norm(x, p, s, train=True, fused_stats=False)
    assert np.allclose(np.asarray(y_fused), np.asarray(y_exact), rtol=1e-4, atol=1e-4)
    assert np.allclose(np.asarray(s_fused["mean"]), np.asarray(s_exact["mean"]), rtol=1e-5)
    assert np.allclose(np.asarray(s_fused["var"]), np.asarray(s_exact["var"]), rtol=1e-4)
    # The production path is bf16 activations (cfg.dtype): the fused form
    # reduces bf16 with f32 accumulation — including a nasty large-mean /
    # small-variance channel where E[x²]-E[x]² cancellation would show up.
    xb = x.astype(jnp.bfloat16)
    xb = xb.at[..., 0].set(jnp.bfloat16(40.0) + xb[..., 0] * jnp.bfloat16(0.1))
    yb_fused, sb_fused = _batch_norm(xb, p, s, train=True, fused_stats=True)
    yb_exact, sb_exact = _batch_norm(xb, p, s, train=True, fused_stats=False)
    assert yb_fused.dtype == jnp.bfloat16
    # Near-centered channels (the real BN regime — conv outputs): outputs
    # agree. Channel 0 is excluded from the y comparison: with |mean|≈40
    # the folded bf16 affine (x·a at magnitude ~66, ulp 0.25) quantizes a/b
    # differently between the two stats paths in BOTH variants — that is
    # the documented in_act_dtype precision tradeoff, not a fused-stats
    # defect.
    assert np.allclose(
        np.asarray(yb_fused[..., 1:], dtype=np.float32),
        np.asarray(yb_exact[..., 1:], dtype=np.float32),
        rtol=0.05, atol=0.05,
    )
    # The cancellation-sensitive quantity is the variance itself: on the
    # large-mean channel E[x²]-E[x]² must still match the two-pass var.
    assert np.allclose(
        np.asarray(sb_fused["var"]), np.asarray(sb_exact["var"]), rtol=0.02, atol=1e-3
    )
    # the offset channel kept a sane, non-degenerate variance
    assert np.asarray(sb_fused["var"])[0] > 0.0


def test_trainer_resnet_with_bn_state():
    mesh = build_mesh({"dp": 8})
    cfg = ResNetConfig(stage_sizes=(1, 1), widths=(8, 16), num_classes=10, dtype=jnp.float32)

    def init_fn(key):
        return init_resnet(key, cfg)

    def loss_fn(params, batch, state):
        images, labels = batch
        logits, new_state = resnet_forward(params, state, images, cfg, train=True)
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
        return loss, new_state

    trainer = Trainer(
        mesh,
        loss_fn=loss_fn,
        init_fn=init_fn,
        config=TrainerConfig(optimizer="sgd", learning_rate=0.05),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    images = jax.random.normal(jax.random.PRNGKey(1), (16, 32, 32, 3))
    labels = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 10)
    batch = (
        jax.device_put(images, trainer.batch_sharding),
        jax.device_put(labels, trainer.batch_sharding),
    )
    bn_before = np.asarray(state.extra["stem"]["mean"])
    losses = []
    for _ in range(6):
        state, m = trainer.step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    # BN running stats moved
    assert not np.allclose(bn_before, np.asarray(state.extra["stem"]["mean"]))


def test_resnet50_shapes():
    cfg = ResNetConfig.resnet50(num_classes=1000)
    params, state = init_resnet(jax.random.PRNGKey(0), cfg)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert 25e6 < n < 26e6, n  # ResNet-50 ≈ 25.5M params
    # one compiled call, as the trainer's step is: run eagerly the 53 layers
    # dispatch op by op, 14 s alone for 2 s compiled (PR 32)
    logits, _ = jax.jit(lambda p, s, x: resnet_forward(p, s, x, cfg, train=True))(
        params, state, jnp.zeros((2, 64, 64, 3))
    )
    assert logits.shape == (2, 1000)
