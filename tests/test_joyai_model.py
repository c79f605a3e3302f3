"""JoyAI-LLM-Flash's mechanisms at a tiny size on the CPU, in float32:
the program against ``benchmarks/reference_joyai.py`` on seeded weights
(loss, both partial losses, every gradient leaf, the returned bias; whole
model and one share), the share tied to the model, the prediction module's
shift, the bias rule, and the state through a checkpoint."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmarks import reference, reference_joyai as rj  # noqa: E402
from tf_operator_tpu.models import transformer as tr  # noqa: E402

# d 64, ranks 32 / 16, 4 heads of 16 | 8 | 16, 8 experts top-2, vocab 256
SIZES = dict(vocab=256, d_model=64, n_layers=2, n_dense=1, n_heads=4, q_rank=32,
             kv_rank=16, nope=16, rope=8, v_dim=16, d_ff=32, d_ff_dense=96,
             rope_theta=3.2e7, norm_eps=1e-6, n_experts=8, top_k=2, held=8,
             first=0, n_shared=1, scale=2.5, bias_rate=0.001, mtp_weight=0.3)


def config(held=8, first=0, **kw):
    kw = dict(dict(attn_impl="dense", remat=False), **kw)  # flash: test_flash_latent, the cell
    return tr.preset(
        "joyai-llm-flash", vocab=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4, d_ff=32, d_ff_dense=96, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, n_experts=8, moe_top_k=2,
        experts_held=held, expert_first=first, max_seq=64, dtype=jnp.float32, **kw)


def tokens(seed=1, shape=(2, 32)):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 256))


def by_leaf(tree):
    return dict(zip(reference.leaf_names(tree), jax.tree_util.tree_leaves(tree)))


def seeded(held, first, seed=5):
    """The program's config and parameters, the reference's sizes and weights
    (the same seeded draw, checked), a bias that matters, two rows."""
    cfg, sizes = config(held, first), dict(SIZES, held=held, first=first)
    w = rj.init_weights(seed, sizes)
    params = jax.jit(lambda k: tr.init_transformer(k, cfg))(jax.random.PRNGKey(seed))
    for (a, x), (b, y) in zip(by_leaf(params).items(), by_leaf(w).items()):
        assert a == b and np.allclose(x, y, rtol=1e-5, atol=1e-8), a
    bias = rj.zero_bias(sizes)
    bias["layers"] = bias["layers"].at[0, 3].set(0.02)
    extra = dict(tr.zero_moe_counters(cfg), router_bias=bias)
    return cfg, sizes, w, bias, extra, tokens()


def held_to(new, bias, counts, sizes, main, mtp):
    """Both partial losses, the returned bias and the counters of a step
    against the reference's."""
    first, held = sizes["first"], sizes["held"]
    assert float(new["loss_main"]) == pytest.approx(main, abs=2e-6)
    assert float(new["loss_mtp"]) == pytest.approx(mtp, abs=2e-6)
    after = rj.bias_update(bias, counts, sizes)
    for k in ("layers", "mtp"):
        assert np.array_equal(np.asarray(new["router_bias"][k]), np.asarray(after[k]))
    assert float(new["moe_routed_here"]) == counts[:, first:first + held].sum()
    assert float(new["moe_bias_abs_max"]) == pytest.approx(
        max(np.abs(np.asarray(v)).max() for v in after.values()))
    load = counts.astype(np.float64)
    assert float(new["moe_all_load_max_over_mean"]) == pytest.approx(
        load.max(-1).sum() / load.mean(-1).sum())


def test_one_share_loss_every_gradient_leaf_and_bias_match_the_reference():
    cfg, sizes, w, bias, extra, tok = seeded(held=2, first=2)
    with jax.default_matmul_precision("highest"):
        (loss, new), grad = jax.jit(jax.value_and_grad(
            lambda p: tr.lm_loss_with_counters(p, jnp.asarray(tok), cfg, extra=extra),
            has_aux=True))(w)
    total, main, mtp, ref_grad, counts = rj.loss_and_grad(w, bias, tok, sizes)
    assert float(loss) == pytest.approx(total, abs=2e-6)
    assert float(loss) == pytest.approx(main + 0.3 * mtp, abs=2e-6)
    held_to(new, bias, counts, sizes, main, mtp)
    got, want = by_leaf(grad), by_leaf(ref_grad)
    assert got.keys() == want.keys() and len(want) == 51
    for k, r in want.items():
        gap = float(jnp.linalg.norm(got[k] - r) / jnp.linalg.norm(r))
        assert gap < 2e-5, (k, gap)


def test_whole_model_losses_counters_and_bias_match_the_reference():
    """All 8 experts here: the forward quantities (the gradients are held on
    the share, and the share to the whole by the test below)."""
    cfg, sizes, w, bias, extra, tok = seeded(held=8, first=0)
    with jax.default_matmul_precision("highest"):
        loss, new = jax.jit(lambda p: tr.lm_loss_with_counters(
            p, jnp.asarray(tok), cfg, extra=extra))(w)
    rows = jax.jit(lambda w, row: rj.row_losses(w, row, bias, sizes))
    parts = [rows(w, jnp.asarray(r)) for r in tok]
    main = sum(float(p[0]) for p in parts) / (2 * 31)
    mtp = sum(float(p[1]) for p in parts) / (2 * 30)
    assert float(loss) == pytest.approx(main + 0.3 * mtp, abs=2e-6)
    held_to(new, bias, sum(np.asarray(p[2], np.int64) for p in parts), sizes, main, mtp)


def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The model-configs guide's test of a share: the parts of an expert
    layer's result that the four chips of a group give (experts 2i, 2i+1
    each), with the shared expert — which every chip computes alike —
    counted ONCE, are the uncut reference's layer."""
    w = rj.init_weights(9, SIZES)
    lw = jax.tree_util.tree_map(lambda a: a[0], w["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 64), jnp.float32)
    bias = jnp.zeros((8,)).at[5].set(0.05)
    pos = jnp.arange(32)
    held = ("w_gate", "w_up", "w_down")

    @jax.jit
    def reference_layer(lw):
        whole, _ = rj._layer(x[0], lw, bias, pos, SIZES, "float32", 256)
        none = {k: (v[:0] if k in held else v) for k, v in lw.items()}
        base, _ = rj._layer(x[0], none, bias, pos, dict(SIZES, held=0), "float32", 256)
        return whole, base  # base: attention + the shared expert, no routed one

    whole, base = reference_layer(lw)
    parts = []
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            cfg = config(held=2, first=2 * i)
            share = {k: (v[2 * i:2 * i + 2] if k in held else v) for k, v in lw.items()}
            y, _ = jax.jit(lambda lp, cfg=cfg: tr._layer(x, lp, cfg, None))(
                dict(share, router_bias=bias))
            parts.append(y[0] - base)  # the share's routed experts alone
    assert float(jnp.abs(base + sum(parts) - whole).max()) < 2e-5
    assert float(jnp.abs(whole - base).max()) > 1e-2  # the experts do something


def test_the_module_scores_the_token_two_ahead_and_the_main_loss_the_next(monkeypatch):
    """What each pass through the head is scored against: the main pass
    t_{i+1} on T - 1 positions, the module's t_{i+2} with weight on the
    first T - 2; the module's pass against PERMUTED targets is another
    number, the main loss is not touched by it."""
    import importlib

    fce = importlib.import_module("tf_operator_tpu.ops.fused_cross_entropy")
    real, calls, permuted = fce.fused_cross_entropy, [], []

    def spy(x, head, targets, weights=None, **kw):
        calls.append((targets, weights))  # tracers of the one trace below
        if weights is not None:
            permuted.append(real(  # every position's target, reversed
                x, head, targets.ravel()[::-1].reshape(targets.shape), weights, **kw))
        return real(x, head, targets, weights, **kw)

    monkeypatch.setattr(fce, "fused_cross_entropy", spy)
    cfg = config()
    params = jax.jit(lambda k: tr.init_transformer(k, cfg))(jax.random.PRNGKey(0))
    tok = tokens(3)

    @jax.jit
    def losses(p):  # what the spy saw leaves the trace beside the losses
        _, m = tr.lm_loss_and_metrics(p, tok, cfg)
        (main_t, main_w), (mtp_t, mtp_w) = calls
        assert main_w is None
        return m["loss_main"], m["loss_mtp"], permuted[0], main_t, mtp_t, mtp_w

    main, mtp, mtp_permuted, main_t, mtp_t, mtp_w = map(np.asarray, losses(params))
    assert np.array_equal(main_t, tok[:, 1:])  # [b, t - 1]: the op flattens
    assert np.array_equal(mtp_t.reshape(2, 32)[:, :30], tok[:, 2:])
    assert np.array_equal(mtp_w.reshape(2, 32), np.tile(np.arange(32) < 30, (2, 1)))
    assert abs(float(mtp_permuted) - float(mtp)) > 1e-3
    monkeypatch.setattr(fce, "fused_cross_entropy", real)
    plain = jax.jit(lambda p: tr.lm_loss_and_metrics(p, tok, cfg)[1]["loss_main"])(params)
    assert float(plain) == float(main)


def test_selection_reads_score_plus_bias_weights_read_the_score():
    from tf_operator_tpu.parallel.moe import moe_apply

    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (16, 8), jnp.float32)
    logits = jax.random.normal(jax.random.fold_in(key, 1), (16, 4), jnp.float32)
    logits = logits.at[:, 3].set(-4.0)  # expert 3: the smallest score of every token
    wp = {"w_gate": jax.random.normal(jax.random.fold_in(key, 2), (4, 8, 8)) * 0.3,
          "w_up": jax.random.normal(jax.random.fold_in(key, 3), (4, 8, 8)) * 0.3,
          "w_down": jax.random.normal(jax.random.fold_in(key, 5), (4, 8, 8)) * 0.3}

    @jax.jit
    def run(bias):
        return moe_apply(x, logits, wp, None, None, k_top=2, dispatch_impl="gmm",
                         return_stats=True, dropped="zero", score="sigmoid",
                         bias=bias, scale=2.5)

    _, plain = run(jnp.zeros((4,)))
    assert int(plain["expert_count"][3]) == 0
    out, steered = run(jnp.zeros((4,)).at[3].set(10.0))
    assert int(steered["expert_count"][3]) == 16  # chosen by all: s + b decides
    s = jax.nn.sigmoid(logits)
    left = jnp.where(jnp.arange(4) == 3, -jnp.inf, s)
    other = jnp.argmax(left, axis=-1)
    gates = jnp.zeros_like(s).at[:, 3].set(s[:, 3]).at[jnp.arange(16), other].set(
        s[jnp.arange(16), other])
    gates = gates / gates.sum(-1, keepdims=True) * 2.5  # s alone: no 10.0 in a weight

    def expert(e):
        return (jax.nn.silu(x @ wp["w_gate"][e]) * (x @ wp["w_up"][e])) @ wp["w_down"][e]

    want = sum(gates[:, e:e + 1] * expert(e) for e in range(4))
    assert float(jnp.abs(out - want).max()) < 1e-5
    with pytest.raises(ValueError, match="gmm"):
        moe_apply(x, logits, wp, lambda w, t: t, None, k_top=2, score="sigmoid")


def test_an_overloaded_experts_bias_falls_by_exactly_the_rate():
    cfg = config()
    params = jax.jit(lambda k: tr.init_transformer(k, cfg))(jax.random.PRNGKey(0))
    bias = tr.zero_router_bias(cfg)
    bias["layers"] = bias["layers"].at[0, 6].set(1.0)  # every token's first choice
    tok = jnp.asarray(tokens())
    new = jax.jit(lambda p, b: tr.lm_loss_and_metrics(
        p, tok, cfg, router_bias=b)[1]["router_bias"])(params, bias)
    layer = np.asarray(new["layers"])[0]
    assert layer[6] == np.float32(1.0) - np.float32(0.001)
    # 64 tokens x 2 choices over 8 outputs: 64 went to expert 6, the other 64
    # over seven outputs, most of which drew under the mean of 16 and rise
    others = np.delete(layer, 6)
    assert set(np.round(others / 0.001).astype(int)) <= {-1, 0, 1} and (others > 0).sum() >= 4
    assert np.asarray(new["mtp"]).shape == (1, 8)


def test_counts_axes_and_refusals():
    cfg = config(held=2)
    shapes = jax.eval_shape(lambda k: tr.init_transformer(k, cfg), jax.random.PRNGKey(0))
    assert cfg.n_params() == sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    axes = tr.transformer_logical_axes(cfg)
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(
        axes, is_leaf=is_axes)
    for leaf, ax in zip(jax.tree_util.tree_leaves(shapes),
                        jax.tree_util.tree_leaves(axes, is_leaf=is_axes)):
        assert len(ax) == leaf.ndim
    # the cell's sizes: ISSUE 30's arithmetic
    cell = tr.preset("joyai-llm-flash", n_layers=5, vocab=16160, experts_held=16)
    assert cell.n_params() == 680_439_808
    full = tr.preset("joyai-llm-flash")
    assert abs(full.n_params() - (48.94e9 + 1.25e9)) < 0.02e9
    assert full.n_active_params() < 3.5e9
    for bad in (dict(attn_impl="ring"), dict(layer_pattern=((64, True),)),
                dict(pp_microbatches=2), dict(router_groups=8),
                dict(moe_dispatch="sort"), dict(mtp_depth=2)):
        with pytest.raises(ValueError):
            config(**bad)
    assert set(tr.moe_counter_names(cfg)) >= {
        "loss_main", "loss_mtp", "moe_bias_abs_max", "moe_all_load_max_over_mean"}


def test_the_bias_is_state_and_a_checkpoint_keeps_it(tmp_path):
    """``extra`` carries the bias beside the scalars; what a job saved is
    what its next incarnation routes with. (That a step threads it is the
    cell's two followed steps against the reference, tests/test_joyai_cell.)"""
    from tf_operator_tpu.parallel.mesh import build_mesh
    from tf_operator_tpu.train.checkpoint import CheckpointManager
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

    cfg = config(held=2)
    mesh = build_mesh({"fsdp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, t, extra: tr.lm_loss_with_counters(
            p, t, cfg, mesh=mesh, extra=extra),
        init_fn=lambda k: (tr.init_transformer(k, cfg), tr.zero_moe_counters(cfg)),
        logical_axes=tr.transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=3e-4))
    state = trainer.init(jax.random.PRNGKey(0))
    assert set(state.extra) == set(tr.moe_counter_names(cfg)) | {"router_bias"}
    assert float(jnp.abs(state.extra["router_bias"]["layers"]).max()) == 0.0
    moved = jax.tree_util.tree_map(
        lambda b: b + 0.001 * jnp.arange(b.size, dtype=b.dtype).reshape(b.shape),
        state.extra["router_bias"])
    state = state._replace(extra=dict(state.extra, router_bias=moved)) if hasattr(
        state, "_replace") else type(state)(
            state.params, state.opt_state, state.step + 2, dict(state.extra, router_bias=moved))
    manager = CheckpointManager(str(tmp_path), async_save=False)
    manager.save(2, state)
    resumed = trainer.restore_or_init(jax.random.PRNGKey(1), manager)
    for k in ("layers", "mtp"):
        assert np.array_equal(np.asarray(resumed.extra["router_bias"][k]),
                              np.asarray(moved[k]))
