"""MoE transformer: Switch-style expert MLP as a model-family variant
(routing math in parallel/moe.py; here its integration into the
transformer — params, logical axes, layer body, trainer, ep sharding).

Whatever runs on a mesh, or through the interpreted gmm kernel, runs as one
compiled call (``hidden`` / ``loss_grads`` below, ``conftest.jit_*``), the
way the trainer's step does: eagerly a ``shard_map`` body is executed
primitive by primitive on the 8 virtual devices, 10 - 25 x the cost (PR 32)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import jit_out_and_grads, jit_value_and_grad
from tf_operator_tpu.models.transformer import (
    init_transformer,
    lm_loss,
    lm_loss_and_metrics,
    preset,
    transformer_forward,
    transformer_hidden,
    transformer_logical_axes,
)
from tf_operator_tpu.parallel import build_mesh
from tf_operator_tpu.train import Trainer, TrainerConfig


def tokens(batch=4, seq=32, vocab=256, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, vocab)


def hidden(params, tok, cfg, mesh, **kw):
    """``transformer_hidden`` as one compiled call."""
    return jax.jit(lambda p: transformer_hidden(p, tok, cfg, mesh, **kw))(params)


def loss_grads(params, tok, cfg, mesh):
    """``lm_loss``'s parameter gradients from one compiled call."""
    return jit_value_and_grad(lambda p: lm_loss(p, tok, cfg, mesh=mesh), params)[1]


def ce_and_grads(params, tok, cfg, mesh):
    """(``ce_loss``, the step's metrics), and the CE's parameter gradients,
    from one compiled call."""
    def ce(p):
        m = lm_loss_and_metrics(p, tok, cfg, mesh=mesh)[1]
        return m["ce_loss"], m

    return jit_value_and_grad(ce, params, has_aux=True)


def test_moe_forward_shape_and_finite():
    cfg = preset("tiny-moe", dtype=jnp.float32)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    logits = transformer_forward(params, tokens(), cfg)
    assert logits.shape == (4, 32, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_moe_param_and_axes_trees_match():
    cfg = preset("tiny-moe", dtype=jnp.float32)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    axes = transformer_logical_axes(cfg)
    checked = jax.tree_util.tree_map(
        lambda p, a: p.ndim == len(a), params, axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )
    assert all(jax.tree_util.tree_leaves(checked))
    assert params["layers"]["w_gate"].shape == (2, 4, 64, 128)  # [L, E, d, f]


def test_single_expert_matches_dense_mlp():
    """n_experts=1 with capacity >= tokens is mathematically the dense
    model (softmax over one expert = weight 1.0, nothing dropped): exact
    layer-parity check of the whole forward."""
    dense_cfg = preset("tiny", dtype=jnp.float32, remat=False)
    moe_cfg = preset(
        "tiny", dtype=jnp.float32, remat=False, n_experts=1, capacity_factor=1.0
    )
    moe_params = init_transformer(jax.random.PRNGKey(0), moe_cfg)
    # dense params = expert 0's weights (drop the router, squeeze E dim)
    dense_params = jax.tree_util.tree_map(lambda a: a, moe_params)
    layers = dict(dense_params["layers"])
    layers.pop("w_router")
    for k in ("w_gate", "w_up", "w_down"):
        layers[k] = layers[k][:, 0]
    dense_params["layers"] = layers

    tok = tokens()
    got = transformer_forward(moe_params, tok, moe_cfg)
    want = transformer_forward(dense_params, tok, dense_cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_moe_n_params_accounting():
    cfg = preset("tiny-moe")
    dense = preset("tiny")
    assert cfg.n_params() > dense.n_params()
    assert cfg.n_active_params() < cfg.n_params()
    # active ≈ dense + routers
    routers = cfg.n_layers * cfg.d_model * cfg.n_experts
    assert cfg.n_active_params() == dense.n_params() + routers


def test_moe_trains_over_ep_mesh():
    """Sharded training with experts over ep and batch over dp: the
    all-to-all dispatch path through the full Trainer."""
    cfg = preset("tiny-moe", dtype=jnp.float32)
    mesh = build_mesh({"dp": 2, "ep": 4})
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, extra: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    # expert weights must actually shard over ep
    w_gate = state.params["layers"]["w_gate"]
    assert "ep" in {
        ax for axes in w_gate.sharding.spec if axes for ax in (
            axes if isinstance(axes, tuple) else (axes,)
        )
    }
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    losses = []
    for _ in range(4):
        state, metrics = trainer.step(state, tok)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_moe_via_workload_config():
    from tf_operator_tpu.models.transformer import preset_from_workload

    cfg = preset_from_workload({"preset": "tiny", "n_experts": 4})
    assert cfg.n_experts == 4


def test_dropped_tokens_leave_residual_untouched():
    """Switch rule in the model: a capacity-dropped token's layer output
    must be x + attention only — NOT x + attention + rms_norm(x) (the bug
    mode where moe passthrough leaks the normed hidden into the residual).
    With zero expert+router weights and capacity for only some tokens,
    every token — kept (expert output 0) or dropped — must match a model
    whose MoE contributes nothing."""
    cfg = preset(
        "tiny", dtype=jnp.float32, remat=False, n_experts=1, capacity_factor=1e-9
    )
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    zeroed = dict(params)
    layers = dict(params["layers"])
    for k in ("w_router", "w_gate", "w_up", "w_down"):
        layers[k] = jnp.zeros_like(layers[k])
    zeroed["layers"] = layers

    tok = tokens()
    got = transformer_forward(zeroed, tok, cfg)

    # reference: same weights with capacity covering every token — all kept,
    # expert output 0, so MoE contributes exactly 0 everywhere
    cfg_all = preset(
        "tiny", dtype=jnp.float32, remat=False, n_experts=1, capacity_factor=10.0
    )
    want = transformer_forward(zeroed, tok, cfg_all)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_top2_moe_trains():
    cfg = preset("tiny-moe", dtype=jnp.float32, moe_top_k=2)
    assert cfg.n_active_params() > preset("tiny-moe").n_active_params()
    mesh = build_mesh({"dp": 2, "ep": 4})
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, extra: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    losses = []
    for _ in range(3):
        state, metrics = trainer.step(state, tok)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def _train_router_ablation(moe_aux_weight, moe_zloss_weight, steps=100):
    """Train tiny-moe from a router init skewed toward expert 0, fresh
    random batches each step (memorizable fixed batches mask the routing
    dynamics). Returns (expert_entropy, drop_frac) on held-out tokens."""
    cfg = preset(
        "tiny-moe", dtype=jnp.float32,
        moe_aux_weight=moe_aux_weight, moe_zloss_weight=moe_zloss_weight,
    )
    mesh = build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, e: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=3e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    wr = state.params["layers"]["w_router"]
    # +2.0 skew (r3, was +1.0): the GQA-native grouped attention einsum
    # changed reduction order enough that the old razor-edge skew no
    # longer collapses the no-aux router at this seed; the stronger skew
    # restores a robust separation (no-aux collapses, aux repairs).
    state.params["layers"]["w_router"] = wr.at[..., 0].set(wr[..., 0] + 2.0)
    key = jax.random.PRNGKey(1)
    for _ in range(steps):
        key, k2 = jax.random.split(key)
        batch = jax.device_put(
            jax.random.randint(k2, (8, 32), 0, cfg.vocab), trainer.batch_sharding
        )
        state, _ = trainer.step(state, batch)
    held_out = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(99), (8, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    _, m = jax.jit(lambda p, t: lm_loss_and_metrics(p, t, cfg, mesh=mesh))(
        state.params, held_out
    )
    return float(m["moe_expert_entropy"]), float(m["moe_drop_frac"])


def test_aux_losses_repair_router_imbalance_where_no_aux_collapses():
    """The load-balance + z losses are what make MoE *trainable at
    quality* (VERDICT #4): from an imbalanced router init, 200 training
    steps WITH the aux losses drive expert-assignment entropy back toward
    uniform (ln 4 ≈ 1.386) with near-zero capacity drops, while the
    no-aux ablation stays collapsed and drops a fifth of its tokens.
    Calibrated values (seeded, deterministic per backend; CPU test env,
    r3 skew=2.0/steps=200: no-aux ≈ (0.79, 0.21), aux ≈ (1.08, 0.0))."""
    ent_no_aux, drop_no_aux = _train_router_ablation(0.0, 0.0, steps=200)
    ent_aux, drop_aux = _train_router_ablation(0.05, 1e-3, steps=200)
    assert ent_no_aux < 0.95, (ent_no_aux, drop_no_aux)
    assert drop_no_aux > 0.08, (ent_no_aux, drop_no_aux)
    assert ent_aux > 1.05, (ent_aux, drop_aux)
    assert drop_aux < 0.05, (ent_aux, drop_aux)
    assert ent_aux > ent_no_aux + 0.15


def test_lm_loss_metrics_expose_router_stats():
    """lm_loss_and_metrics surfaces router telemetry; the scalar lm_loss
    includes the weighted aux terms (ablation: zero weights give pure CE)."""
    cfg = preset("tiny-moe", dtype=jnp.float32)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    toks = tokens()
    total, m = lm_loss_and_metrics(params, toks, cfg)
    for key in ("ce_loss", "moe_lb_loss", "moe_z_loss", "moe_expert_entropy",
                "moe_drop_frac"):
        assert key in m, key
    # total = ce + weighted aux terms, all finite
    expect = (
        m["ce_loss"]
        + cfg.moe_aux_weight * m["moe_lb_loss"]
        + cfg.moe_zloss_weight * m["moe_z_loss"]
    )
    np.testing.assert_allclose(float(total), float(expect), rtol=1e-6)
    # zero-weight config: scalar loss is pure CE
    cfg0 = preset("tiny-moe", dtype=jnp.float32, moe_aux_weight=0.0,
                  moe_zloss_weight=0.0)
    np.testing.assert_allclose(
        float(lm_loss(params, toks, cfg0)), float(m["ce_loss"]), rtol=1e-6
    )
    # near-uniform routing at init: lb_loss ~ 1, entropy near ln(E)
    assert 0.8 < float(m["moe_lb_loss"]) < 1.3
    assert float(m["moe_expert_entropy"]) > 1.0


def test_moe_stats_agree_between_single_and_sharded_paths():
    """Aggregate router stats (load, mean gate) must agree between the
    single-device and ep-sharded paths — drop PATTERNS may differ (see
    moe_apply docstring) but the aggregate view is layout-invariant when
    nothing drops."""
    from tf_operator_tpu.parallel.moe import moe_apply

    n_experts, d, tok = 8, 8, 64
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (tok, d), jnp.float32)
    gate_logits = jax.random.normal(jax.random.PRNGKey(1), (tok, n_experts))
    w = {"w": jax.random.normal(jax.random.PRNGKey(2), (n_experts, d, d)) * 0.1}
    expert_fn = lambda wp, t: t @ wp["w"]  # noqa: E731

    def stats(mesh):
        return jax.jit(lambda x, gl, w: moe_apply(
            x, gl, w, expert_fn, mesh,
            capacity_factor=float(n_experts), return_stats=True,
        ))(x, gate_logits, w)[1]

    s_single = stats(None)
    s_shard = stats(build_mesh({"ep": jax.device_count()}))
    np.testing.assert_allclose(
        np.asarray(s_single["expert_load"]), np.asarray(s_shard["expert_load"]),
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(s_single["mean_gate"]), np.asarray(s_shard["mean_gate"]),
        atol=1e-6,
    )
    assert float(s_single["drop_frac"]) == 0.0
    assert float(s_shard["drop_frac"]) == 0.0


def test_moe_lb_gradient_agrees_between_single_and_sharded_paths():
    """The load-balance gradient must be layout-invariant: shard_map's
    transpose of the replicated (P()) stats outputs must not rescale the
    mean_gate cotangent — otherwise multi-chip MoE training would apply a
    silently mis-scaled balance pressure vs the CPU-tested path."""
    from tf_operator_tpu.parallel.moe import moe_apply

    n_experts, d, tok = 8, 8, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (tok, d), jnp.float32)
    gate_logits0 = jax.random.normal(jax.random.PRNGKey(1), (tok, n_experts))
    w = {"w": jax.random.normal(jax.random.PRNGKey(2), (n_experts, d, d)) * 0.1}
    expert_fn = lambda wp, t: t @ wp["w"]  # noqa: E731

    def lb_loss(gate_logits, mesh):
        _, stats = moe_apply(
            x, gate_logits, w, expert_fn, mesh,
            capacity_factor=float(n_experts), return_stats=True,
        )
        return n_experts * jnp.sum(stats["expert_load"] * stats["mean_gate"])

    mesh = build_mesh({"ep": jax.device_count()})
    _, g_single = jit_value_and_grad(lambda gl: lb_loss(gl, None), gate_logits0)
    _, g_shard = jit_value_and_grad(lambda gl: lb_loss(gl, mesh), gate_logits0)
    np.testing.assert_allclose(
        np.asarray(g_single), np.asarray(g_shard), atol=1e-6
    )
    assert float(jnp.max(jnp.abs(g_single))) > 0  # the probe isn't vacuous


# ---------------------------------------------------------------------------
# Pipeline-parallel transformer (VERDICT #5: a REAL model through
# pipeline_apply — toy tanh retired)
# ---------------------------------------------------------------------------


def test_pipeline_transformer_matches_single_device_oracle():
    """pp=4 GPipe forward of the tiny transformer == the plain scan
    forward, exactly (same stacked-params math, f32)."""
    cfg_pp = preset("tiny", dtype=jnp.float32, remat=False, pp_microbatches=4)
    cfg_1d = preset("tiny", dtype=jnp.float32, remat=False)
    # 4 layers so pp=4 gives one layer per stage; tiny has 2 — widen it
    cfg_pp = preset("tiny", dtype=jnp.float32, remat=False, pp_microbatches=4,
                    n_layers=4)
    cfg_1d = preset("tiny", dtype=jnp.float32, remat=False, n_layers=4)
    params = init_transformer(jax.random.PRNGKey(0), cfg_pp)
    tok = tokens(batch=8)
    mesh = build_mesh({"pp": 4, "dp": 2})
    got = hidden(params, tok, cfg_pp, mesh)
    want = hidden(params, tok, cfg_1d, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )


def test_pipeline_transformer_trains_through_trainer():
    """The VERDICT done-bar: a transformer TRAINS through the pipeline —
    full Trainer over a pp x dp mesh, layer params sharded over pp
    (logical "layers" -> pp rule), loss decreasing, gradients real."""
    cfg = preset("tiny", dtype=jnp.float32, remat=False, n_layers=4,
                 pp_microbatches=4)
    mesh = build_mesh({"pp": 4, "dp": 2})
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, e: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    # layer-stacked params actually shard over pp
    wq = state.params["layers"]["wq"]
    spec_axes = {
        ax for axes in wq.sharding.spec if axes for ax in (
            axes if isinstance(axes, tuple) else (axes,)
        )
    }
    assert "pp" in spec_axes, wq.sharding
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    losses = []
    for _ in range(4):
        state, metrics = trainer.step(state, tok)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_moe_forward_matches_single_device(schedule):
    """MoE + pipeline (r3): experts replicated per stage through the
    no-ep routing path — the pp forward must equal the plain scan.

    capacity_factor is raised so nothing drops: expert capacity is
    computed per MICROBATCH under pp (each microbatch routes alone), so
    at tight capacity the dropped-token sets legitimately differ from
    full-batch routing — with headroom the math is exactly equal."""
    cfg_pp = preset("tiny-moe", dtype=jnp.float32, pp_microbatches=4,
                    pp_schedule=schedule, capacity_factor=8.0)
    cfg_1d = preset("tiny-moe", dtype=jnp.float32, capacity_factor=8.0)
    params = init_transformer(jax.random.PRNGKey(0), cfg_pp)
    tok = tokens(batch=16)
    mesh = build_mesh({"pp": 2, "dp": 4})
    got, aux = hidden(params, tok, cfg_pp, mesh, with_aux=True)
    want, aux_1d = hidden(params, tok, cfg_1d, None, with_aux=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )
    # aux z-loss is microbatch-invariant (per-token logsumexp mean);
    # lb_loss differs only through per-microbatch load fractions
    np.testing.assert_allclose(
        float(aux["z_loss"]), float(aux_1d["z_loss"]), rtol=1e-3
    )
    assert aux["expert_load"] is None  # telemetry not carried through pp


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_moe_trains_with_router_gradient(schedule):
    """MoE TRAINS through the pipeline with the aux losses active: loss
    decreases and the ROUTER receives gradient through the pp aux channel
    (a broken channel would zero it — routing then collapses silently)."""
    cfg = preset("tiny-moe", dtype=jnp.float32, pp_microbatches=4,
                 pp_schedule=schedule)
    mesh = build_mesh({"pp": 2, "dp": 4})
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, e: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    g = loss_grads(state.params, tok, cfg, mesh)
    assert float(jnp.max(jnp.abs(g["layers"]["w_router"]))) > 0.0
    losses = []
    for _ in range(4):
        state, metrics = trainer.step(state, tok)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses


def test_pipeline_moe_grads_match_single_device():
    """Full lm_loss gradient parity for pp+MoE (1f1b): the aux-channel
    cotangent path (run_bwd feeds g_aux into every valid tick's vjp) must
    reproduce the plain scan's gradients — router included. Drop-free
    capacity (see the forward oracle), and lb weight 0: the load-balance
    fractions are per-MICROBATCH under pp (mean-of-products != full-batch
    product), so only the z-loss — whose per-token mean IS microbatch-
    invariant — admits an exact cross-layout gradient oracle; lb gradient
    flow is covered by test_pipeline_moe_trains_with_router_gradient."""
    cfg_pp = preset("tiny-moe", dtype=jnp.float32, pp_microbatches=4,
                    capacity_factor=8.0, moe_aux_weight=0.0)
    cfg_1d = preset("tiny-moe", dtype=jnp.float32, capacity_factor=8.0,
                    moe_aux_weight=0.0)
    params = init_transformer(jax.random.PRNGKey(0), cfg_pp)
    tok = tokens(batch=16)
    mesh = build_mesh({"pp": 2, "dp": 4})
    g_pp = loss_grads(params, tok, cfg_pp, mesh)
    g_1d = loss_grads(params, tok, cfg_1d, None)
    for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(g_pp)[0],
        jax.tree_util.tree_leaves(g_1d),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_pipeline_moe_invalid_meshes_rejected():
    """r4: ep INSIDE a pipeline stage is now supported (see the pp x ep
    oracle below) — only MoE + tp-within-stage and indivisible expert
    counts remain rejections."""
    cfg_tp = preset("tiny-moe", dtype=jnp.float32, pp_microbatches=2,
                    n_heads=4, n_kv_heads=2)
    params = init_transformer(jax.random.PRNGKey(0), cfg_tp)
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        transformer_hidden(
            params, tokens(), cfg_tp, build_mesh({"pp": 2, "tp": 2, "dp": 2})
        )
    cfg3 = preset("tiny-moe", dtype=jnp.float32, pp_microbatches=2,
                  n_experts=3)
    params3 = init_transformer(jax.random.PRNGKey(0), cfg3)
    with pytest.raises(ValueError, match="divisible"):
        transformer_hidden(
            params3, tokens(), cfg3, build_mesh({"pp": 2, "ep": 4})
        )


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_schedule_forward_oracle(schedule):
    """Both pipeline schedules produce the exact plain-scan forward."""
    cfg_pp = preset("tiny", dtype=jnp.float32, remat=False, pp_microbatches=4,
                    n_layers=4, pp_schedule=schedule)
    cfg_1d = preset("tiny", dtype=jnp.float32, remat=False, n_layers=4)
    params = init_transformer(jax.random.PRNGKey(0), cfg_pp)
    tok = tokens(batch=8)
    mesh = build_mesh({"pp": 4, "dp": 2})
    got = hidden(params, tok, cfg_pp, mesh)
    want = hidden(params, tok, cfg_1d, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )


def test_pipeline_tp_within_stage_matches_oracle():
    """pp x tp (VERDICT r2 #4): stage weights shard Megatron-style over tp
    (_pp_param_specs), _layer psums its row-parallel products — the
    forward must equal the single-device scan exactly."""
    cfg_pp = preset("tiny", dtype=jnp.float32, remat=False, pp_microbatches=4,
                    n_layers=2, n_heads=4, n_kv_heads=2)
    cfg_1d = preset("tiny", dtype=jnp.float32, remat=False,
                    n_layers=2, n_heads=4, n_kv_heads=2)
    params = init_transformer(jax.random.PRNGKey(0), cfg_pp)
    tok = tokens(batch=8)
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})
    got = hidden(params, tok, cfg_pp, mesh)
    want = hidden(params, tok, cfg_1d, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )


def test_pipeline_tp_trains_through_trainer():
    """pp=2 x tp=2 x dp=2 TRAINS: full Trainer, loss decreasing, stage
    params sharded over BOTH pp and tp."""
    cfg = preset("tiny", dtype=jnp.float32, remat=False, n_layers=2,
                 n_heads=4, n_kv_heads=2, pp_microbatches=4)
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, e: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    losses = []
    for _ in range(4):
        state, metrics = trainer.step(state, tok)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_pipeline_tp_indivisible_heads_rejected():
    cfg = preset("tiny", dtype=jnp.float32, n_layers=2, n_heads=4,
                 n_kv_heads=1, pp_microbatches=2)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})
    with pytest.raises(ValueError, match="n_kv_heads"):
        transformer_hidden(params, tokens(), cfg, mesh)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_tp_grads_match_single_device(schedule):
    """pp x tp GRADIENT parity (the bug class the forward oracle cannot
    see): raw lax.psum in the tp region is silently wrong under direct
    jax.vjp (its transpose-is-psum convention inflates cotangents by tp,
    compounding per layer) — _layer must route tp activations through the
    Megatron f/g pair (collectives.tp_region_enter/exit). Full lm_loss
    grads, pp=2 x tp=2 x dp=2 vs the plain single-device scan, BOTH
    schedules."""
    cfg_pp = preset("tiny", dtype=jnp.float32, remat=False, n_layers=2,
                    n_heads=4, n_kv_heads=2, pp_microbatches=4,
                    pp_schedule=schedule)
    cfg_1d = preset("tiny", dtype=jnp.float32, remat=False,
                    n_layers=2, n_heads=4, n_kv_heads=2)
    params = init_transformer(jax.random.PRNGKey(0), cfg_pp)
    tok = tokens(batch=8)
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})

    g_pp = loss_grads(params, tok, cfg_pp, mesh)
    g_1d = loss_grads(params, tok, cfg_1d, None)
    flat_pp = jax.tree_util.tree_flatten_with_path(g_pp)[0]
    flat_1d = jax.tree_util.tree_leaves(g_1d)
    for (path, a), b in zip(flat_pp, flat_1d):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_pipeline_interleaved_transformer_matches_oracle():
    """Interleaved 1F1B in the model (pp_chunks=2): 4 layers as 4 virtual
    stages on pp=2 devices (layer j on device j mod 2) — forward equals
    the plain scan exactly."""
    cfg_pp = preset("tiny", dtype=jnp.float32, remat=False, pp_microbatches=4,
                    n_layers=4, pp_chunks=2)
    cfg_1d = preset("tiny", dtype=jnp.float32, remat=False, n_layers=4)
    params = init_transformer(jax.random.PRNGKey(0), cfg_pp)
    tok = tokens(batch=16)
    mesh = build_mesh({"pp": 2, "dp": 4})
    got = hidden(params, tok, cfg_pp, mesh)
    want = hidden(params, tok, cfg_1d, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )


def test_pipeline_interleaved_tp_matches_oracle():
    """Interleaved (pp_chunks=2) composed with tp-within-stage: the
    [v, S]-reshaped Megatron param specs still shard each chunk's weights
    over tp; forward equals the single-device scan."""
    kw = dict(dtype=jnp.float32, remat=False, n_layers=4, n_heads=4,
              n_kv_heads=2)
    cfg_pp = preset("tiny", pp_microbatches=4, pp_chunks=2, **kw)
    cfg_1d = preset("tiny", **kw)
    params = init_transformer(jax.random.PRNGKey(0), cfg_pp)
    tok = tokens(batch=8)
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})
    got = hidden(params, tok, cfg_pp, mesh)
    want = hidden(params, tok, cfg_1d, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )


def test_pipeline_interleaved_trains_through_trainer():
    """Interleaved 1F1B TRAINS end to end: full Trainer on pp=2 x dp=4,
    4 layers as 2 chunks/device, loss decreasing."""
    cfg = preset("tiny", dtype=jnp.float32, remat=False, n_layers=4,
                 pp_microbatches=4, pp_chunks=2)
    mesh = build_mesh({"pp": 2, "dp": 4})
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, e: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    losses = []
    for _ in range(4):
        state, m = trainer.step(state, tok)
        losses.append(float(m["loss"] if isinstance(m, dict) else m))
    assert losses[-1] < losses[0], losses


# ---- flagship MoE sharding: ep x fsdp (r4, VERDICT r3 #5) -----------------


def test_moe_apply_ep_fsdp_matches_single_device_oracle():
    """Expert weights sharded over ep (expert dim) AND fsdp (embed dim),
    tokens over (dp, fsdp, ep) — the mixtral-8x7b layout — must match
    the single-device moe_apply exactly, fwd and grads. capacity 8.0:
    no drops, so the per-shard-queue caveat doesn't apply and parity is
    exact."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tf_operator_tpu.parallel.moe import moe_apply

    T, d, f, E = 64, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    gl = jax.random.normal(ks[1], (T, E), jnp.float32)
    wp = {
        "w_gate": jax.random.normal(ks[2], (E, d, f)) * 0.1,
        "w_up": jax.random.normal(ks[3], (E, d, f)) * 0.1,
        "w_down": jax.random.normal(ks[4], (E, f, d)) * 0.1,
    }

    def expert_fn(w, t):
        return (jax.nn.silu(t @ w["w_gate"]) * (t @ w["w_up"])) @ w["w_down"]

    mesh = build_mesh({"dp": 2, "fsdp": 2, "ep": 2})
    xs = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp", "ep"))))
    gls = jax.device_put(gl, NamedSharding(mesh, P(("dp", "fsdp", "ep"))))
    wps = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("ep", "fsdp"))), wp
    )

    def run(fn_mesh, gl_, x_, wp_):
        return jit_out_and_grads(
            lambda x_, wp_: moe_apply(x_, gl_, wp_, expert_fn, fn_mesh,
                                      capacity_factor=8.0, k_top=2),
            x_, wp_, argnums=(0, 1))

    # mesh path closes over the SHARDED gating logits (gls) so the
    # backward through sharded routing is what's tested
    got, got_g = run(mesh, gls, xs, wps)
    want, want_g = run(None, gl, x, wp)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5)


def test_moe_transformer_trains_ep_fsdp_dp():
    """Full Trainer on the dp x fsdp x ep mesh: expert weights must be
    STORED sharded over both ep and fsdp (no per-dp-replica expert
    replication — the flagship memplan depends on it) and the model must
    train."""
    cfg = preset("tiny-moe", dtype=jnp.float32, remat=False, moe_top_k=2)
    mesh = build_mesh({"dp": 2, "fsdp": 2, "ep": 2})
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, e: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=3e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    spec = tuple(state.params["layers"]["w_gate"].sharding.spec)
    assert "ep" in spec and "fsdp" in spec, spec
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    losses = []
    for _ in range(10):
        state, m = trainer.step(state, tok)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses


# ---- ep INSIDE the pipeline (r4, VERDICT r3 #5 stretch) -------------------


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_ep_in_stage_matches_single_device(schedule):
    """pp x ep x dp: experts shard over ep INSIDE each pipeline stage
    (pipeline_apply's one shard_map binds every mesh axis; the stage body
    runs parallel.moe._moe_local against the bound ep name — no nested
    shard_map). CE forward and grads must match the single-device oracle
    exactly at no-drop capacity; the total loss differs only by the
    documented per-microbatch/per-shard aux estimators. The 1f1b arm
    additionally pins the backward's per-leaf data-axis reduction — a
    uniform psum over data axes scrambles ep-sharded expert grads."""
    cfg = preset("tiny-moe", dtype=jnp.float32, remat=False, n_layers=4,
                 pp_microbatches=2, capacity_factor=8.0, moe_top_k=2,
                 pp_schedule=schedule)
    mesh = build_mesh({"pp": 2, "ep": 2, "dp": 2})
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab)

    (ce_pp, m_pp), g_got = ce_and_grads(params, tok, cfg, mesh)
    (ce_sd, m_sd), g_want = ce_and_grads(params, tok, cfg, None)
    np.testing.assert_allclose(float(ce_pp), float(ce_sd), rtol=2e-5)
    for (pa, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g_got),
                               jax.tree_util.tree_leaves_with_path(g_want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6,
            err_msg=jax.tree_util.keystr(pa))
    # aux losses: finite and same order as single-device (different
    # estimator — per microbatch x ep shard)
    assert np.isfinite(float(m_pp["moe_lb_loss"]))
    np.testing.assert_allclose(float(m_pp["moe_lb_loss"]),
                               float(m_sd["moe_lb_loss"]), rtol=0.2)


def test_pipeline_ep_in_stage_trains():
    """Full Trainer over pp=2 x ep=2 x dp=2 — the flagship-MoE pipeline
    mesh end to end, expert weights stored sharded over (pp, ep)."""
    cfg = preset("tiny-moe", dtype=jnp.float32, remat=False, n_layers=4,
                 pp_microbatches=2, moe_top_k=2)
    mesh = build_mesh({"pp": 2, "ep": 2, "dp": 2})
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, tok, e: lm_loss(p, tok, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=3e-3),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    losses = []
    for _ in range(8):
        state, m = trainer.step(state, tok)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses


@pytest.mark.parametrize("k_top", [1, 2])
def test_gmm_dispatch_matches_sort_at_no_drop_capacity(k_top):
    """dispatch_impl="gmm" (r5 — padding-free grouped expert matmuls, no
    capacity) must equal the sort path when the sort path's capacity is
    large enough that nothing drops: with no drops both compute
    out[t] = sum_k w_k * expert_k(x[t]). This is the oracle pin
    BASELINE.md's r5 MoE row cites."""
    from tf_operator_tpu.parallel.moe import moe_apply

    T, d, f, E = 64, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    gl = jax.random.normal(ks[1], (T, E), jnp.float32)
    ep = {
        "w_gate": jax.random.normal(ks[2], (E, d, f)) * 0.1,
        "w_up": jax.random.normal(ks[3], (E, d, f)) * 0.1,
        "w_down": jax.random.normal(ks[4], (E, f, d)) * 0.1,
    }

    def efn(wp, t):
        return (jax.nn.silu(t @ wp["w_gate"]) * (t @ wp["w_up"])) @ wp["w_down"]

    def run(**kw):
        return jit_out_and_grads(
            lambda ew: moe_apply(x, gl, ew, efn, None, k_top=k_top,
                                 return_stats=True, **kw), ep)

    (out_sort, _), g_sort = run(capacity_factor=float(E), dropped="zero",
                                dispatch_impl="sort")
    (out, stats), g = run(dispatch_impl="gmm")
    np.testing.assert_allclose(out_sort, out, atol=1e-5)
    assert float(stats["drop_frac"]) == 0.0  # never drops
    for name in g:
        np.testing.assert_allclose(g[name], g_sort[name], atol=1e-4,
                                   err_msg=name)


def test_gmm_zero_token_expert_gets_zero_grad():
    """An expert with ZERO routed tokens still owns one (all-garbage)
    block, so its dw tile is written (zeroed + accumulated) rather than
    returned as uninitialized kernel output memory — and the garbage
    rows' cotangents are zeros, so the gradient is exactly 0."""
    from tf_operator_tpu.parallel.moe import moe_apply

    T, d, f, E = 32, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    # route EVERY token to expert 0 (logits hugely favor it)
    gl = jnp.zeros((T, E)).at[:, 0].set(100.0)
    ep = {
        "w_gate": jax.random.normal(ks[1], (E, d, f)) * 0.1,
        "w_up": jax.random.normal(ks[2], (E, d, f)) * 0.1,
        "w_down": jax.random.normal(ks[3], (E, f, d)) * 0.1,
    }

    def efn(wp, t):
        return (jax.nn.silu(t @ wp["w_gate"]) * (t @ wp["w_up"])) @ wp["w_down"]

    _, g = jit_out_and_grads(
        lambda ew: moe_apply(x, gl, ew, efn, None, k_top=1,
                             dispatch_impl="gmm"), ep)
    for name in g:
        # experts 1..3 got nothing: their grads must be exactly zero
        np.testing.assert_array_equal(np.asarray(g[name][1:]), 0.0)
        assert np.isfinite(np.asarray(g[name])).all()


def test_gmm_rejects_non_swiglu_expert_params():
    from tf_operator_tpu.parallel.moe import moe_apply

    x = jnp.zeros((8, 4))
    gl = jnp.zeros((8, 2))
    with pytest.raises(ValueError, match="gmm"):
        moe_apply(x, gl, {"w": jnp.zeros((2, 4, 4))}, lambda w, t: t, None,
                  dispatch_impl="gmm")


def test_unknown_moe_dispatch_raises():
    """A removed or misspelt dispatch is refused, not run as something
    else: through the layer and through the model config."""
    from tf_operator_tpu.parallel.moe import moe_apply

    with pytest.raises(ValueError, match="unknown dispatch_impl 'ragged'"):
        moe_apply(jnp.zeros((8, 4)), jnp.zeros((8, 2)),
                  {"w": jnp.zeros((2, 4, 4))}, lambda w, t: t, None,
                  dispatch_impl="ragged")
    cfg = preset("tiny-moe", moe_dispatch="ragged")
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    with pytest.raises(ValueError, match="unknown dispatch_impl 'ragged'"):
        lm_loss_and_metrics(params, tok, cfg)
