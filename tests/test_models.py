"""Model family tests: transformer (dense + ring attention paths) and
ResNet, plus the sharded Trainer on multi-axis meshes."""

import hashlib

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


# ---- the leaf table ------------------------------------------------------
# One tiny config a mechanism: dense; experts; latent attention + dense lead +
# shared expert + sigmoid-bias router + MTP + untied head (JoyAI, as
# test_joyai_model.py cuts it); window / NoPE pattern with a share of the
# experts (SmallThinker, as test_smallthinker.py); linear layers + q/k norms
# + post-norm (Olmo-hybrid, as test_olmo_hybrid.py).

ARCHS = {
    "tiny": lambda: preset("tiny"),
    "tiny-moe": lambda: preset("tiny-moe"),
    "joyai": lambda: preset(
        "joyai-llm-flash", vocab=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4, d_ff=32, d_ff_dense=96, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, n_experts=8, moe_top_k=2,
        experts_held=8, expert_first=0, max_seq=64, dtype=jnp.float32,
        attn_impl="dense", remat=False),
    "smallthinker": lambda: TransformerConfig(
        vocab=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=32,
        d_head=32, max_seq=64, remat="save_mid", attn_impl="flash",
        n_experts=8, moe_top_k=6, moe_dispatch="gmm",
        layer_pattern=((0, False), (16, True), (16, True), (16, True)),
        expert_act="relu", router_input="attn_norm", router_f32=True,
        experts_held=2, expert_first=2, rope_theta=1.5e6, norm_eps=1e-6,
        dtype=jnp.float32),
    "olmo": lambda: preset(
        "olmo-hybrid-7b", vocab=256, d_model=64, n_layers=8, n_heads=4,
        n_kv_heads=4, d_ff=128, max_seq=192, lin_heads=4, lin_dk=8, lin_dv=16),
}


def leaf_digests(tree):
    """{"a/b": sha256 of the leaf's dtype, shape and bytes}."""
    return {
        "/".join(k.key for k in path): hashlib.sha256(
            f"{leaf.dtype}{leaf.shape}".encode() + np.asarray(leaf).tobytes()
        ).hexdigest()
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_init_draws_the_weights_the_parent_of_pr46_drew(name):
    """Every leaf bit for bit: the same key of the same split, shape, dtype
    and scale as the hand-written init the leaf table replaced (PR 46). The
    expert cells' throughput follows their routers' weights, and the
    benchmark's references draw the same leaves on their own."""
    got = leaf_digests(init_transformer(jax.random.PRNGKey(0), ARCHS[name]()))
    assert got == INIT_SHA256[name]


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_logical_axes_have_the_params_structure_and_each_leafs_rank(name):
    cfg = ARCHS[name]()
    shapes = jax.eval_shape(lambda k: init_transformer(k, cfg), jax.random.PRNGKey(0))
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    axes = transformer_logical_axes(cfg)
    assert (jax.tree_util.tree_structure(axes, is_leaf=is_axes)
            == jax.tree_util.tree_structure(shapes))
    ranks = jax.tree_util.tree_map(
        lambda a, s: len(a) == len(s.shape), axes, shapes, is_leaf=is_axes)
    assert all(jax.tree_util.tree_leaves(ranks)), ranks


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_n_params_is_the_leaves_total(name):
    cfg = ARCHS[name]()
    shapes = jax.eval_shape(lambda k: init_transformer(k, cfg), jax.random.PRNGKey(0))
    assert cfg.n_params() == sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    assert cfg.n_active_params() <= cfg.n_params()


@pytest.mark.parametrize("mesh_axes", ["tp", "ep"])
def test_stage_specs_follow_the_leaves_axes(mesh_axes):
    """The pipeline's stage specs, derived from the table: with tp the
    Megatron split; under ep-in-stage the experts' own dimension over ep and
    the router whole."""
    from jax.sharding import PartitionSpec as P

    from tf_operator_tpu.models.transformer import _pp_param_specs

    if mesh_axes == "tp":
        col, row, whole = P("pp", None, None, "tp"), P("pp", None, "tp", None), P("pp", None, None)
        assert _pp_param_specs(preset("tiny"), "tp", None) == {
            "attn_norm": whole, "wq": col, "wk": col, "wv": col, "wo": row,
            "mlp_norm": whole, "w_gate": col, "w_up": col, "w_down": row}
    else:
        specs = _pp_param_specs(preset("tiny-moe"), None, "ep")
        assert {n: tuple(s) for n, s in specs.items() if "ep" in tuple(s)} == {
            n: ("pp", None, "ep", None, None) for n in ("w_gate", "w_up", "w_down")}
        assert tuple(specs["w_router"]) == ("pp", None, None, None)


def test_config_surface_is_the_fields_less_five():
    """CONFIG_OVERRIDE_FIELDS is computed from the dataclass and is the set
    the hand-kept list held before PR 46; the axis names are no fields."""
    from tf_operator_tpu.models.transformer import CONFIG_OVERRIDE_FIELDS

    assert CONFIG_OVERRIDE_FIELDS == {
        "vocab", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff",
        "max_seq", "causal", "remat", "fused_xent", "n_experts",
        "moe_top_k", "capacity_factor", "moe_aux_weight", "moe_zloss_weight",
        "moe_dispatch", "pp_microbatches", "pp_schedule",
        "d_head", "layer_pattern", "expert_act", "router_input", "router_f32",
        "experts_held", "expert_first",
        "attn_kind", "q_lora_rank", "kv_lora_rank", "qk_nope_dim",
        "qk_rope_dim", "v_head_dim", "n_dense_lead", "d_ff_dense",
        "router_score", "router_bias", "router_bias_rate", "router_scale",
        "router_groups", "n_shared_experts", "mtp_depth", "mtp_weight",
        "tied_head",
        "lin_heads", "lin_dk", "lin_dv", "lin_conv", "lin_neg_eigval",
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",  # PR 47
        "norm_order", "qk_norm",
    }
    for gone in ("cp_axis", "ep_axis", "pp_axis"):
        with pytest.raises(TypeError):
            TransformerConfig(**{gone: "x"})


# sha256 per leaf of init_transformer(PRNGKey(0), cfg) at the parent of PR 46
INIT_SHA256 = {
    "joyai": {
        "embed":
            "837d10b26fbf5c0309371b018bf75da1c5da4e2a97a82bcfadb4d6995f71ca32",
        "final_norm":
            "800ed95cb408e02d1497865afd90e6432a04d8ba3c568c32f3f6615cba610583",
        "head":
            "85d23d8b5ca67cfd64b51a0bffe34417e717e4372687f5d9a7e4dbc8399c1dd6",
        "layers/attn_norm":
            "811e6f3d4f8e2fca91c9df15f1c78901633545a4e03de3a9ca250b96cac0af0f",
        "layers/kv_norm":
            "eb9759a2d6d77f2e2b542e032d086fea976dcce5812ab29f5667a8799211b1b1",
        "layers/mlp_norm":
            "811e6f3d4f8e2fca91c9df15f1c78901633545a4e03de3a9ca250b96cac0af0f",
        "layers/q_norm":
            "737405f41b700931c55c201d58c465bd6b746795f3418c5527a088de88a69f3f",
        "layers/w_down":
            "37ac22ba19ce46a643f5d99e35ff78d17db05962916bd3ef6aaef33257c7dcfd",
        "layers/w_gate":
            "4b57464d8c9887897d8645fddd1bba2b00ae0cb1e62186f763d47ac768cfda83",
        "layers/w_router":
            "870be72f2657eab9c4e8cc9bf23880f568fd45e264641d6e1143fd4f27703f03",
        "layers/w_up":
            "ad9168e225db963c55efea7314eee44f0c323f46565f841f9e44fe83ab8943fa",
        "layers/wkv_a":
            "f9705ac02ebb89983668177593cf1ceda4948b72c7508f9aea14df9b2b328e87",
        "layers/wkv_b":
            "17fba7564842c5e55ba9095d72a1724fb63f881e4688cc1b05731298eb61dc62",
        "layers/wo":
            "f636ba982d80db74066d8296adbd2cabd5195cdb18b1ca2735ebb5eecc1b560d",
        "layers/wq_a":
            "0c8ff8dcfe660415d431abed479262e797aae1b00d63baed7c805ae8d4cd0f60",
        "layers/wq_b":
            "2198dab46fce9450c5964588bab75c6a7509a1776179d9ddeabd5b21eabbf7ad",
        "layers/ws_down":
            "325e3f8e6364e6ac1d70c813b68319909b67210172be7b8291865c34f100dbad",
        "layers/ws_gate":
            "a57a972a6881d424abd6e868ec26e45dd41b05e0a53950a5613aec533ed17b81",
        "layers/ws_up":
            "2217cd70dda0e83cfb13e55e2193f617d29c26a08f17948997aa2bd27d8db6a8",
        "lead/attn_norm":
            "811e6f3d4f8e2fca91c9df15f1c78901633545a4e03de3a9ca250b96cac0af0f",
        "lead/kv_norm":
            "eb9759a2d6d77f2e2b542e032d086fea976dcce5812ab29f5667a8799211b1b1",
        "lead/mlp_norm":
            "811e6f3d4f8e2fca91c9df15f1c78901633545a4e03de3a9ca250b96cac0af0f",
        "lead/q_norm":
            "737405f41b700931c55c201d58c465bd6b746795f3418c5527a088de88a69f3f",
        "lead/w_down":
            "af30239f7ef3bd4a63071f8eb4b3da03d3b47121ce82896a79d6adf36a1cb292",
        "lead/w_gate":
            "d93292edd47d17c02d93d8d8cdca97c34f2124ca069f7a82c17d19a170f1f5a6",
        "lead/w_up":
            "9d3c239b4dae81742593948d24573aea7ea5351841a7ae63cb6a2f353aff3be6",
        "lead/wkv_a":
            "f9705ac02ebb89983668177593cf1ceda4948b72c7508f9aea14df9b2b328e87",
        "lead/wkv_b":
            "17fba7564842c5e55ba9095d72a1724fb63f881e4688cc1b05731298eb61dc62",
        "lead/wo":
            "f636ba982d80db74066d8296adbd2cabd5195cdb18b1ca2735ebb5eecc1b560d",
        "lead/wq_a":
            "0c8ff8dcfe660415d431abed479262e797aae1b00d63baed7c805ae8d4cd0f60",
        "lead/wq_b":
            "2198dab46fce9450c5964588bab75c6a7509a1776179d9ddeabd5b21eabbf7ad",
        "mtp/final_norm":
            "800ed95cb408e02d1497865afd90e6432a04d8ba3c568c32f3f6615cba610583",
        "mtp/layer/attn_norm":
            "811e6f3d4f8e2fca91c9df15f1c78901633545a4e03de3a9ca250b96cac0af0f",
        "mtp/layer/kv_norm":
            "eb9759a2d6d77f2e2b542e032d086fea976dcce5812ab29f5667a8799211b1b1",
        "mtp/layer/mlp_norm":
            "811e6f3d4f8e2fca91c9df15f1c78901633545a4e03de3a9ca250b96cac0af0f",
        "mtp/layer/q_norm":
            "737405f41b700931c55c201d58c465bd6b746795f3418c5527a088de88a69f3f",
        "mtp/layer/w_down":
            "d1a2bd2d748c80f9269f913172b4ed524ab3bf4a0663e517c1e088d0e7550d9f",
        "mtp/layer/w_gate":
            "33f36475071904d6a8f73bb5e7b91a2e6be07391c56072c5895fc1e0ff176fa0",
        "mtp/layer/w_router":
            "87536f8e761b60fab837edbdc6cda0640ec1b56c6cd702e2c32d91c2e59933a3",
        "mtp/layer/w_up":
            "79c6b6277d117fa8044fd970717f87b2ac6e9fda25bf0c778da1e70087c5b30d",
        "mtp/layer/wkv_a":
            "169ea9fe8e46a56ee1dbf8b5034222c4e960d86de983a113f13191e9fd6b87f8",
        "mtp/layer/wkv_b":
            "122c1db06f95c80226f87ebf8ca2b810c9e432b7abd4529756bebd4ed6126dfb",
        "mtp/layer/wo":
            "2e5b8ae462699d9842dfc3dfbf835aeb6fccb9bf0adbd97552dd992fbb58d3b1",
        "mtp/layer/wq_a":
            "706d0842c27be3fb7f6f826ab93554a95930c7f1fbfe637d0605d585ab3dd5bf",
        "mtp/layer/wq_b":
            "eaf36848bc39ae4cf24478684d84746b3e05cd90eb304ee19aefdaeb97a4c5a4",
        "mtp/layer/ws_down":
            "ba9322442818af02e93aea70881abf2a9c9249f2983b47314ba853c22c0fc440",
        "mtp/layer/ws_gate":
            "606b4aeda4e414d3b12fa80946f6b3f2bc8ef9c4393e9bfe42b6ee00efc6bdbc",
        "mtp/layer/ws_up":
            "c27f0e2abf35505c29c5b943b65385d05b41ac42e76644ccd393cbe522cbc71c",
        "mtp/norm_e":
            "800ed95cb408e02d1497865afd90e6432a04d8ba3c568c32f3f6615cba610583",
        "mtp/norm_h":
            "800ed95cb408e02d1497865afd90e6432a04d8ba3c568c32f3f6615cba610583",
        "mtp/w_eh":
            "9a98b8f7d967ba0eeec6dbe447efdfcb3c5daed1362bcf5be5988f049b6a6580",
    },
    "olmo": {
        "embed":
            "837d10b26fbf5c0309371b018bf75da1c5da4e2a97a82bcfadb4d6995f71ca32",
        "final_norm":
            "800ed95cb408e02d1497865afd90e6432a04d8ba3c568c32f3f6615cba610583",
        "head":
            "85d23d8b5ca67cfd64b51a0bffe34417e717e4372687f5d9a7e4dbc8399c1dd6",
        "layers/attn_norm":
            "f68a75cfd50a274e11ac5e8c936fa0d748585e86709476743a65912d329a8002",
        "layers/k_norm":
            "eae42367fcc43b923cf8b77b56c2c4d115c3f4f1c572f4562073993dfcd55a98",
        "layers/lin_A_log":
            "8630b2652ddba9b7caa3ba0a0ba36727b4d3bb712ca4b8814fc2301d0a72bc25",
        "layers/lin_conv":
            "29fa320d153a679001d7d69817832a99a1e43ac2a9dec381ee2b13edb913e334",
        "layers/lin_dt_bias":
            "2a229466aa8b86c6d0d416d0ed15eb2c9b79b390ceb2ceb61de01d413195e0c5",
        "layers/lin_norm":
            "7273b51ea9347f5cdb806c2cb9787055c2edfe4618159d0744630c14bc6c2521",
        "layers/lin_wba":
            "5535ef3791cc59cd52247d4b9d009b65ba82ef8e0b20cfccfbf48cf7561ee59b",
        "layers/lin_wo":
            "2a776201a160c6c104de03ff56669a5559239b3ef6861987f99c51c37a4e8f3b",
        "layers/lin_wqkv":
            "bdb2266cb3dff4ce3f5d5e1b4660ff719c94a2e5d47f950ef9c0a3c2610dd379",
        "layers/lin_wz":
            "c79601895fff47a8df1cdf2a143d61fe42c1ac02f3d94027301388638b25c4ff",
        "layers/mlp_norm":
            "f68a75cfd50a274e11ac5e8c936fa0d748585e86709476743a65912d329a8002",
        "layers/q_norm":
            "eae42367fcc43b923cf8b77b56c2c4d115c3f4f1c572f4562073993dfcd55a98",
        "layers/w_down":
            "3aee0bef5425851b153d9b749c350f47f2538041e31fa220fb16bad54bdf5314",
        "layers/w_gate":
            "9c23bb53823157a77e173b9b2b895209a29b63eb234d48e0593a9e56fd25fe16",
        "layers/w_up":
            "e08102a0df919a756c127b212375c8d130dc3ddeaf1198f51dba26b349e57635",
        "layers/wk":
            "dc51b99e0b43d1beea2feb3cfb589c34b53c7063392f693a1f333b8f6c74cba2",
        "layers/wo":
            "d5a29752f3c8a74aefa2c3bdcb6013683c55fc90bfb8bdf4e02b5b2302f48ce1",
        "layers/wq":
            "c7c578001897aec13a2371e39e98f4a71e12c95eb384f5be04c8e0da7795671c",
        "layers/wv":
            "83da5b3fae861278664b8d0a4d40310dc2fa1a655e925d65543329c0a6d38b7c",
    },
    "smallthinker": {
        "embed":
            "837d10b26fbf5c0309371b018bf75da1c5da4e2a97a82bcfadb4d6995f71ca32",
        "final_norm":
            "800ed95cb408e02d1497865afd90e6432a04d8ba3c568c32f3f6615cba610583",
        "layers/attn_norm":
            "1f67df6d4373f8c015bca5146034691607339b3c970fbcc52f9510da56eaec75",
        "layers/mlp_norm":
            "1f67df6d4373f8c015bca5146034691607339b3c970fbcc52f9510da56eaec75",
        "layers/w_down":
            "6f4a7f80afcaf0417a8dde6d123ceac98b826deb8de67d3c2b502bae7aae22f5",
        "layers/w_gate":
            "ca54bacb6f7955547ea13801598a7bef840361758bc4be3fc85b0b107d0167c3",
        "layers/w_router":
            "f7fe2cc24cdc367b767f8d35caf7a86fe7182532fde488c28af44ac948d148b2",
        "layers/w_up":
            "48318e2c12376d687fec3b28bafe583782a19d5de820857d60e5bf0167813a7e",
        "layers/wk":
            "e85190966d1d21c5fff879ff0e27e81fdeaf50654843165bef54eb1d9d950436",
        "layers/wo":
            "8c7fa416c2d8d2432de697923b281259f7bf22ee288579f2e763a93546f4df01",
        "layers/wq":
            "1cc61f70b08e17f30fe7142d4e7119f64b5d723ef99917ec75f8c06aaa2f20dd",
        "layers/wv":
            "e4c8df1df21a6e76a11ae31d1b1b195f0c5717d33346eb33e5364a2a3f20b177",
    },
    "tiny": {
        "embed":
            "837d10b26fbf5c0309371b018bf75da1c5da4e2a97a82bcfadb4d6995f71ca32",
        "final_norm":
            "800ed95cb408e02d1497865afd90e6432a04d8ba3c568c32f3f6615cba610583",
        "layers/attn_norm":
            "eae42367fcc43b923cf8b77b56c2c4d115c3f4f1c572f4562073993dfcd55a98",
        "layers/mlp_norm":
            "eae42367fcc43b923cf8b77b56c2c4d115c3f4f1c572f4562073993dfcd55a98",
        "layers/w_down":
            "d99d2045e95896107dfb8e16c7add362d2e775c53f6a2e1d37c3ad2fd63b60cd",
        "layers/w_gate":
            "0c9e23ae6665ec62aec3c1b6d5de01a0bf9f26a7b187e19b9424f9569e8892e4",
        "layers/w_up":
            "7e79eafc35bd82dc93c9932a447fd9a03bed20e24da313a4f1cdc09cd5ddf12e",
        "layers/wk":
            "468aba31d4eb2d11ec9907bbe6cd79e9e35ea50b1773b544c75f8ae00e373e70",
        "layers/wo":
            "d5a29752f3c8a74aefa2c3bdcb6013683c55fc90bfb8bdf4e02b5b2302f48ce1",
        "layers/wq":
            "c7c578001897aec13a2371e39e98f4a71e12c95eb384f5be04c8e0da7795671c",
        "layers/wv":
            "7c469163b3cb9baab16ee4c703b8c39b0e602ac152ba814d8cedc94254fbf73d",
    },
    "tiny-moe": {
        "embed":
            "837d10b26fbf5c0309371b018bf75da1c5da4e2a97a82bcfadb4d6995f71ca32",
        "final_norm":
            "800ed95cb408e02d1497865afd90e6432a04d8ba3c568c32f3f6615cba610583",
        "layers/attn_norm":
            "eae42367fcc43b923cf8b77b56c2c4d115c3f4f1c572f4562073993dfcd55a98",
        "layers/mlp_norm":
            "eae42367fcc43b923cf8b77b56c2c4d115c3f4f1c572f4562073993dfcd55a98",
        "layers/w_down":
            "4e3577210ea76721d89b5b755d0fd86b9fa1e3bc4b54baae52109ed4281f991c",
        "layers/w_gate":
            "7b17158c1c5c1a2788a7b20ec0e1742a4efe94a59b7c0c5380fa98af79320dca",
        "layers/w_router":
            "2836812f0ff5e799e7b89ceb28cd108f0533e7875b41efd7477c31516cd7776a",
        "layers/w_up":
            "9c39c87be9ea5182436d6160ee24a7185670bdae81f2b1ebcb1d88de6d9f96a4",
        "layers/wk":
            "468aba31d4eb2d11ec9907bbe6cd79e9e35ea50b1773b544c75f8ae00e373e70",
        "layers/wo":
            "d5a29752f3c8a74aefa2c3bdcb6013683c55fc90bfb8bdf4e02b5b2302f48ce1",
        "layers/wq":
            "c7c578001897aec13a2371e39e98f4a71e12c95eb384f5be04c8e0da7795671c",
        "layers/wv":
            "7c469163b3cb9baab16ee4c703b8c39b0e602ac152ba814d8cedc94254fbf73d",
    },
}
