"""SmallThinker-21BA3B's mechanisms at tiny sizes on the CPU: the model
through ``lm_loss`` against the plain reference, the chip's share of an
expert layer, windowed flash attention, and the period scan's pattern of
one against today's dense stack."""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import reference_smallthinker as ref  # noqa: E402
from tf_operator_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    init_transformer,
    lm_loss,
    lm_loss_and_metrics,
    preset,
    preset_from_workload,
)

fa = importlib.import_module("tf_operator_tpu.ops.flash_attention")

PATTERN = ((0, False), (16, True), (16, True), (16, True))


def tiny(**over) -> TransformerConfig:
    """The published shape at toy widths: the same period, head width its own
    number (4 x 32 != 64), a float32 pre-attention router, top-6 of 8 ReGLU
    experts of which 2 are held. float32 activations: the comparison with
    the reference is then free of bfloat16's near-tie flips."""
    base = dict(
        vocab=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=32,
        d_head=32, max_seq=64, remat="save_mid", attn_impl="flash",
        n_experts=8, moe_top_k=6, moe_dispatch="gmm", layer_pattern=PATTERN,
        expert_act="relu", router_input="attn_norm", router_f32=True,
        experts_held=2, expert_first=2, rope_theta=1.5e6, norm_eps=1e-6,
        dtype=jnp.float32)
    base.update(over)
    return TransformerConfig(**base)


def sizes_of(cfg: TransformerConfig):
    return dict(
        vocab=cfg.vocab, d_model=cfg.d_model, n_layers=cfg.n_layers,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        d_ff=cfg.d_ff, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
        n_experts=cfg.n_experts, top_k=cfg.moe_top_k, held=cfg.n_held,
        first=cfg.expert_first, pattern=cfg.pattern,
        aux_weight=cfg.moe_aux_weight, zloss_weight=cfg.moe_zloss_weight)


# ---- (i) the model against the reference -------------------------------------


def test_model_matches_reference_loss_and_every_gradient_leaf():
    n_layers = 8  # two periods of the pattern through the period scan
    cfg = tiny(n_layers=n_layers)
    sizes = sizes_of(cfg)
    seed = 3
    params = init_transformer(jax.random.PRNGKey(seed), cfg)
    w = ref.init_weights(seed, sizes)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(w)):
        np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=str(path))
    assert params["layers"]["w_gate"].shape == (n_layers, 2, 64, 32)
    assert params["layers"]["w_router"].shape == (n_layers, 64, 8)
    assert params["layers"]["wq"].shape == (n_layers, 64, 128)  # 4 x 32 != 64

    tokens = np.random.default_rng(0).integers(0, 256, (2, 48), dtype=np.int32)
    (loss, metrics), grad = jax.jit(jax.value_and_grad(
        lambda p: lm_loss_and_metrics(p, jnp.asarray(tokens), cfg), has_aux=True))(params)
    ref_loss, ref_grad, held_counts = ref.loss_and_grad(w, tokens, sizes)
    assert float(loss) == pytest.approx(ref_loss, abs=2e-5)
    # every choice of a held expert is counted and computed, none dropped
    assert float(metrics["moe_routed_here"]) == held_counts.sum()
    assert float(metrics["moe_drop_frac"]) == 0.0
    for name, g, r in zip(ref.leaf_names(grad), jax.tree_util.tree_leaves(grad),
                          jax.tree_util.tree_leaves(ref_grad)):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g, r, atol=2e-4 * scale, rtol=2e-3, err_msg=name)


def test_config_file_builds_the_share_the_issue_sized():
    with open(os.path.join(
            REPO, "benchmarks/configs/smallthinker-21ba3b-ep4share-train1.json")) as f:
        config = json.load(f)
    cfg = preset_from_workload(config["workload"])
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff) == (
        2560, 28, 4, 128, 768)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.n_held, cfg.expert_first) == (64, 6, 16, 0)
    assert cfg.pattern == ((0, False), (4096, True), (4096, True), (4096, True))
    assert (cfg.rope_theta, cfg.norm_eps) == (config["rope_theta"], config["rms_norm_eps"])
    assert cfg.n_params() == 4 * 115_512_320 + 37_984 * 2560 + 2560 == 559_290_880
    # a token's choices land on a held expert a quarter of the time
    assert cfg.n_active_params() == cfg.n_params() - 4 * int((16 - 1.5) * 5_898_240)
    full = preset("smallthinker-21ba3b")
    assert full.n_held == 64 and full.n_layers == 52
    # presets without the new fields are what they were
    assert preset("llama2-7b").head_dim == 128 and preset("llama2-7b").pattern == ((0, True),)
    mixtral = 32000 * 4096 + 4096 + 32 * (
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 8 * 3 * 4096 * 14336 + 4096 * 8 + 2 * 4096)
    assert preset("mixtral-8x7b").n_params() == mixtral
    assert preset("mixtral-8x7b").n_active_params() == mixtral - 32 * 6 * 3 * 4096 * 14336


# ---- (ii) the share ----------------------------------------------------------


def _layer_inputs(t=96, d=64, f=32, n=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = jax.random.normal(ks[0], (t, d), jnp.float32)
    r = jax.random.normal(ks[1], (t, n), jnp.float32)
    lw = {"w_gate": jax.random.normal(ks[2], (n, d, f)) * d ** -0.5,
          "w_up": jax.random.normal(ks[3], (n, d, f)) * d ** -0.5,
          "w_down": jax.random.normal(ks[4], (n, f, d)) * f ** -0.5}
    return h, r, lw


def _share(h, r, lw, first, held, block_rows=None, monkeypatch=None):
    from tf_operator_tpu.parallel.moe import moe_apply

    part = {k: v[first:first + held] for k, v in lw.items()}
    return moe_apply(h, r, part, None, None, k_top=6, dropped="zero",
                     return_stats=True, dispatch_impl="gmm", expert_act="relu",
                     expert_first=first)


@pytest.mark.parametrize("block_rows", ["256", "32"])
def test_four_shares_add_up_to_the_uncut_reference_layer(block_rows, monkeypatch):
    monkeypatch.setenv("TPUJOB_GMM_BLOCK_ROWS", block_rows)
    h, r, lw = _layer_inputs()
    gates, chosen = ref.route(r, 6)
    whole = ref.expert_mix(h, gates, lw)  # all 8 experts: the uncut layer
    parts, routed = [], 0.0
    for first in (0, 2, 4, 6):
        out, stats = _share(h, r, lw, first, 2)
        np.testing.assert_allclose(  # each share is ITS experts' part
            out, ref.expert_mix(h, gates[:, first:first + 2],
                                {k: v[first:first + 2] for k, v in lw.items()}),
            atol=2e-5)
        # the router's view is of all 8 experts on every chip
        np.testing.assert_allclose(stats["expert_load"], chosen.mean(0) / 6, atol=1e-7)
        assert float(stats["rows_computed"]) % int(block_rows) == 0
        assert float(stats["routed_here"]) <= float(stats["rows_computed"])
        routed += float(stats["routed_here"])
        parts.append(out)
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5)
    assert routed == 96 * 6  # every choice is computed on exactly one chip


def test_share_with_no_choice_routed_gives_exact_zeros_and_zero_expert_grads():
    h, r, lw = _layer_inputs()
    r = r.at[:, 6:].set(-1e9)  # top-6 of 8 never reaches experts 6 and 7
    part = {k: v[6:8] for k, v in lw.items()}

    def f(part, h):
        from tf_operator_tpu.parallel.moe import moe_apply

        out, stats = moe_apply(h, r, part, None, None, k_top=6, dropped="zero",
                               return_stats=True, dispatch_impl="gmm",
                               expert_act="relu", expert_first=6)
        return jnp.sum(out * jnp.arange(out.shape[-1])), (out, stats)

    (_, (out, stats)), (g_part, g_h) = jax.value_and_grad(f, (0, 1), has_aux=True)(part, h)
    assert float(stats["routed_here"]) == 0.0 == float(stats["rows_computed"])
    assert not np.any(np.asarray(out)) and not np.any(np.asarray(g_h))
    for k, g in g_part.items():
        assert not np.any(np.asarray(g)), k


def test_share_loses_no_choice_when_every_token_picks_the_held_experts():
    """The lossless bound: all T·k choices could land here."""
    from tf_operator_tpu.parallel.moe import moe_apply

    h, r, lw = _layer_inputs(n=8)
    r = r.at[:, :6].add(100.0)  # every token's six choices are experts 0-5
    part = {k: v[:6] for k, v in lw.items()}
    out, stats = moe_apply(h, r, part, None, None, k_top=6, dropped="zero",
                           return_stats=True, dispatch_impl="gmm",
                           expert_act="relu", expert_first=0)
    assert float(stats["routed_here"]) == 96 * 6
    assert float(stats["held_load_max"]) == 96 == float(stats["held_load_mean"])
    gates, _ = ref.route(r, 6)
    np.testing.assert_allclose(out, ref.expert_mix(h, gates[:, :6], part), atol=5e-5)


# ---- (ii b) the segment walk ---------------------------------------------------

WALK_T, WALK_E, WALK_K, WALK_B = 100, 8, 2, 8
# load -> (first, held, what is added to the held experts' router scores)
WALK_LOADS = {
    "even": (2, 2, (0.0, 0.0)),
    "every_choice_held": (2, 2, (100.0, 100.0)),   # all trips run: the lossless case
    "no_choice_held": (2, 2, (-1e9, -1e9)),         # 0 trips
    "one_held_expert_takes_all": (2, 2, (100.0, -1e9)),
    "whole_layer": (0, WALK_E, (0.0,) * WALK_E),    # held == E: one segment, no loop
}


def _walk_gates(r, router):
    """[T, E] gate weights, zero where an expert was not chosen: the oracle's
    own router (k rounds of argmax; softmax over the chosen, or sigmoid
    scores chosen by score + bias, over their sum, times the scale)."""
    score, bias, scale = router
    s = jax.nn.sigmoid(r) if score == "sigmoid" else r
    left, chosen = s + (0.0 if bias is None else bias), jnp.zeros(r.shape, bool)
    for _ in range(WALK_K):
        pick = jax.nn.one_hot(jnp.argmax(left, axis=-1), r.shape[-1], dtype=bool)
        chosen, left = chosen | pick, jnp.where(pick, -jnp.inf, left)
    if score == "sigmoid":
        return jnp.where(chosen, s, 0.0) / jnp.sum(
            jnp.where(chosen, s, 0.0), axis=-1, keepdims=True) * scale, chosen
    return jax.nn.softmax(jnp.where(chosen, r, -jnp.inf), axis=-1), chosen


def _walk_oracle_layer(h, w_router, part, steer, first, router):
    """Every held expert densely over every token, weighted by its gate."""
    gates, chosen = _walk_gates(h @ w_router + steer, router)
    out = jnp.zeros_like(h)
    for e in range(part["w_gate"].shape[0]):
        z = jax.nn.relu(h @ part["w_gate"][e]) * (h @ part["w_up"][e])
        out = out + gates[:, first + e, None] * (z @ part["w_down"][e])
    return out, chosen


def _walk_layer(h, w_router, part, steer, first, router):
    from tf_operator_tpu.parallel.moe import moe_apply

    score, bias, scale = router
    return moe_apply(h, h @ w_router + steer, part, None, None, k_top=WALK_K,
                     dropped="zero", return_stats=True, dispatch_impl="gmm",
                     expert_act="relu", expert_first=first, score=score,
                     bias=bias, scale=scale)


def _as_the_trainer_runs_it(layer):
    """Two residual layers under ``lax.scan``, each under ``jax.checkpoint``
    with the ``save_mid`` tier's policy."""
    from tf_operator_tpu.models.transformer import remat_save_names

    policy = jax.checkpoint_policies.save_only_these_names(
        *remat_save_names("save_mid"))

    def run(h, w_router, part, *rest):
        def body(h, _):
            out, aux = layer(h, w_router, part, *rest)
            return h + 0.1 * out, aux

        h, aux = jax.lax.scan(jax.checkpoint(body, policy=policy), h, None, length=2)
        return h, jax.tree_util.tree_map(lambda a: a[0], aux)
    return run


WALK_CASES = [(load, "softmax", wrap) for load in WALK_LOADS
              for wrap in ("alone", "scan_remat")] + [
    ("even", "sigmoid", "alone"), ("even", "sigmoid", "scan_remat")]


@pytest.mark.parametrize("load,score,wrap", WALK_CASES)
def test_the_walk_equals_the_dense_oracle_in_output_and_every_gradient(
        load, score, wrap, monkeypatch):
    """The expert layer as a walk over segments (``_expert_walk``; one
    segment under plain autodiff where the share is the whole layer): output
    and the gradients of x, the router weights (through the gate weights)
    and the three expert stacks against every held expert computed densely,
    at five loads, alone and as the trainer runs it; and the two counters
    that say how far it walked."""
    from conftest import jit_value_and_grad

    monkeypatch.setenv("TPUJOB_GMM_BLOCK_ROWS", str(WALK_B))
    first, held, add = WALK_LOADS[load]
    h, _, lw = _layer_inputs(t=WALK_T, n=WALK_E, seed=3)
    w_router = jax.random.normal(jax.random.PRNGKey(4), (64, WALK_E)) * 0.3
    part = {k: v[first:first + held] for k, v in lw.items()}
    steer = jnp.zeros((WALK_E,)).at[first:first + held].set(jnp.asarray(add))
    router = (score, jnp.linspace(-0.2, 0.2, WALK_E), 2.5) if score == "sigmoid" \
        else ("softmax", None, 1.0)
    weigh = jax.random.normal(jax.random.PRNGKey(5), h.shape)

    def loss_of(layer):
        fn = _as_the_trainer_runs_it(layer) if wrap == "scan_remat" else layer

        def loss(h, w_router, part):
            out, aux = fn(h, w_router, part, steer, first, router)
            return jnp.sum(out * weigh), (out, aux)
        return loss

    walk = loss_of(_walk_layer)
    (_, (out, stats)), grads = jit_value_and_grad(
        walk, h, w_router, part, argnums=(0, 1, 2), has_aux=True)
    (_, (want, chosen)), want_grads = jit_value_and_grad(
        loss_of(_walk_oracle_layer), h, w_router, part, argnums=(0, 1, 2),
        has_aux=True)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=1e-5)
    for got, ref_g in zip(jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(got, ref_g, atol=2e-4, rtol=1e-4)

    # how far it walked (the first layer's routing, in both wraps)
    counts = np.asarray(chosen).sum(0)[first:first + held]
    occupied = int(np.sum(-(-counts // WALK_B)))
    tk = WALK_T * WALK_K
    nb = -(-tk // WALK_B) + held
    seg_blocks = -(-(tk * held) // (WALK_E * WALK_B)) + held
    assert float(stats["routed_here"]) == counts.sum()
    assert float(stats["rows_computed"]) == occupied * WALK_B
    assert float(stats["rows_bound"]) == nb * WALK_B
    assert float(stats["rows_walked"]) == -(-occupied // seg_blocks) * seg_blocks * WALK_B
    text = str(jax.make_jaxpr(lambda *a: _walk_layer(*a, steer, first, router))(
        h, w_router, part))
    if load == "whole_layer":
        assert seg_blocks == nb and "while" not in text
    else:
        assert "while" in text and -(-nb // seg_blocks) == 3
    if load == "every_choice_held":  # no choice drops: every trip the bound allows
        assert occupied == 26 and float(stats["rows_walked"]) == 3 * seg_blocks * WALK_B
    if load == "no_choice_held":  # 0 trips: zeros out, exact-zero gradients
        assert float(stats["rows_walked"]) == 0.0
        g_h, g_router, g_part = grads
        for k, g in g_part.items():
            assert not np.any(np.asarray(g)), k
        if wrap == "alone":  # under the scan the residual stream passes through
            assert not np.any(np.asarray(out)) and not np.any(np.asarray(g_h))
            assert not np.any(np.asarray(g_router))


# ---- (iii) windowed flash attention ------------------------------------------


def _explicit_attention(q, k, v, window):
    """Row by row, from the definition: key j is visible to query i iff
    j <= i and (no window or i - j < window)."""
    b, t, h, d = q.shape
    g = h // k.shape[2]
    out = np.zeros(q.shape, np.float64)
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    for hi in range(h):
        for i in range(t):
            lo = max(0, i - window + 1) if window else 0
            s = q[0, i, hi] @ k[0, lo:i + 1, hi // g].T / np.sqrt(d)
            p = np.exp(s - s.max())
            out[0, i, hi] = (p / p.sum()) @ v[0, lo:i + 1, hi // g]
    return out


@pytest.mark.parametrize("window", [24, 64, 100], ids=["lt-t", "eq-t", "gt-t"])
@pytest.mark.parametrize("path,group", [("kernel", 1), ("kernel", 7), ("jnp", 7)])
def test_windowed_flash_forward_and_backward_match_the_reference(window, group, path):
    t, h_kv, d = 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(window + group), 4)
    q = jax.random.normal(ks[0], (1, t, h_kv * group, d))
    k = jax.random.normal(ks[1], (1, t, h_kv, d))
    v = jax.random.normal(ks[2], (1, t, h_kv, d))
    do = jax.random.normal(ks[3], q.shape)
    kernel = path == "kernel"

    def flash(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, window=window, block_q=16,
                               block_k=32, interpret=kernel,
                               force_kernel=True if kernel else None)
        return jnp.sum(o * do), o

    def oracle(q, k, v):
        o = fa.reference_attention(q, k, v, causal=True, window=window)
        return jnp.sum(o * do), o

    (_, o), grads = jax.value_and_grad(flash, (0, 1, 2), has_aux=True)(q, k, v)
    (_, o_ref), grads_ref = jax.value_and_grad(oracle, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(o_ref, _explicit_attention(q, k, v, window), atol=2e-5)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    for a, b in zip(grads, grads_ref):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_window_needs_causal_and_window_zero_is_plain_causal():
    q = jnp.ones((1, 16, 2, 8))
    with pytest.raises(ValueError):
        fa.flash_attention(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError):
        fa.reference_attention(q, q, q, causal=False, window=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 8))
    assert jnp.array_equal(fa.reference_attention(x, x, x, causal=True, window=0),
                           fa.reference_attention(x, x, x, causal=True))
    assert jnp.array_equal(fa.reference_attention(x, x, x, causal=True, window=32),
                           fa.reference_attention(x, x, x, causal=True))


def test_dead_blocks_are_those_wholly_outside_the_window():
    live = lambda qb, kb, w: bool(fa._block_live(qb, kb, 16, 32, True, w))  # noqa: E731
    assert live(3, 1, 0) and not live(1, 1, 0)  # rows 48-63 see keys 32-63; rows 16-31 none
    assert live(4, 1, 8)       # rows 64-79, window 8: row 64 sees keys 57-64
    assert not live(5, 1, 8)   # rows 80-95 see keys 73.. only
    assert live(5, 1, 18)      # row 80 sees key 63
    assert not live(5, 1, 17)  # row 80 sees keys 64..80


# ---- (iv) a pattern of one is today's dense stack ----------------------------


def _loss_and_grad(cfg, params, tokens):
    return jax.jit(jax.value_and_grad(lambda p: lm_loss(p, tokens, cfg)))(params)


def test_pattern_of_one_is_the_dense_path_bit_for_bit_at_tiny():
    dense = preset("tiny")
    patterned = preset("tiny", layer_pattern=((0, True),))
    params = init_transformer(jax.random.PRNGKey(1), dense)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, dense.vocab)
    for a, b in zip(jax.tree_util.tree_leaves(_loss_and_grad(dense, params, tokens)),
                    jax.tree_util.tree_leaves(_loss_and_grad(patterned, params, tokens))):
        assert jnp.array_equal(a, b)
    text = lambda cfg: jax.jit(jax.grad(  # noqa: E731 — and it is the same program
        lambda p: lm_loss(p, tokens, cfg))).lower(params).as_text()
    assert text(dense) == text(patterned)


def test_period_of_two_global_layers_is_the_dense_stack():
    """The period scan itself: two layers a scan step, unrolled, against one
    layer a step. float32, so that where XLA rounds to bfloat16 between the
    two unrolled layers cannot differ."""
    dense = preset("tiny", dtype=jnp.float32, n_layers=4)
    patterned = preset("tiny", dtype=jnp.float32, n_layers=4,
                       layer_pattern=((0, True), (0, True)))
    params = init_transformer(jax.random.PRNGKey(1), dense)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, dense.vocab)
    for a, b in zip(jax.tree_util.tree_leaves(_loss_and_grad(dense, params, tokens)),
                    jax.tree_util.tree_leaves(_loss_and_grad(patterned, params, tokens))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    with pytest.raises(ValueError, match="whole number of periods"):
        preset("tiny", n_layers=3, layer_pattern=((0, True), (0, True)))
