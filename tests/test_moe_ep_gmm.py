"""MoE transformer, the ep-SHARDED gmm dispatch: the second half of
tests/test_moe_transformer.py as a file of its own, so that ``--dist
loadfile`` hands the two halves to two workers (together they were one
worker's 1,400 s, the whole tier-1 run's length; PR 31).

Every case evaluates its path and its oracle through ONE compiled call each
(``conftest.jit_out_and_grads`` / ``jit_value_and_grad``), as the trainer's
step does: run eagerly, the ``shard_map`` body and the interpreted gmm kernel
in it cost 18 s a forward and 41 s a gradient at these sizes, against 3.8 s
for the jitted ``value_and_grad`` (PR 32). The jitted callables are built
inside each test, after it has set ``TPUJOB_GMM_BLOCK_ROWS``: the block
quantum is read at trace time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import jit_out_and_grads, jit_value_and_grad
from tf_operator_tpu.models.transformer import init_transformer, preset
from tf_operator_tpu.parallel import build_mesh


# ---- ep-SHARDED gmm dispatch (r6 tentpole) --------------------------------
# dispatch_impl="gmm" no longer degrades to capacity queues under an ep
# axis: count exchange + block-quantum a2a buffers + sentinel-skipped
# kernel blocks (parallel.moe._moe_local_gmm). Oracle = the capacity
# path at no-drop capacity (identical math when nothing drops).


@pytest.mark.parametrize("k_top", [1, 2])
def test_ep_gmm_matches_capacity_oracle_on_flagship_mesh(k_top, monkeypatch):
    """moe_apply level, the mixtral dp x fsdp x ep layout, k_top 1 and 2,
    fwd AND grads (x, router logits, expert weights). block_rows=8 so
    the per-(source, expert) block-quantum rounding actually engages at
    test sizes (256 would make every expert a single partial block)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tf_operator_tpu.parallel.moe import moe_apply

    monkeypatch.setenv("TPUJOB_GMM_BLOCK_ROWS", "8")
    T, d, f, E = 64, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    gl = jax.random.normal(ks[1], (T, E), jnp.float32)
    wp = {
        "w_gate": jax.random.normal(ks[2], (E, d, f)) * 0.1,
        "w_up": jax.random.normal(ks[3], (E, d, f)) * 0.1,
        "w_down": jax.random.normal(ks[4], (E, f, d)) * 0.1,
    }

    def efn(w, t):
        return (jax.nn.silu(t @ w["w_gate"]) * (t @ w["w_up"])) @ w["w_down"]

    mesh = build_mesh({"dp": 2, "fsdp": 2, "ep": 2})
    xs = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp", "ep"))))
    gls = jax.device_put(gl, NamedSharding(mesh, P(("dp", "fsdp", "ep"))))
    wps = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("ep", "fsdp"))), wp)

    def run(**kw):
        return jit_out_and_grads(
            lambda x_, gl_, wp_: moe_apply(x_, gl_, wp_, efn, mesh, k_top=k_top,
                                           return_stats=True, **kw),
            xs, gls, wps, argnums=(0, 1, 2))

    (want, wstats), g2 = run(capacity_factor=8.0, dropped="zero")
    (got, stats), g1 = run(dispatch_impl="gmm")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # router telemetry agrees with the capacity path and drops are
    # structurally impossible
    np.testing.assert_allclose(np.asarray(stats["expert_load"]),
                               np.asarray(wstats["expert_load"]),
                               atol=1e-6)
    assert float(stats["drop_frac"]) == 0.0
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_ep_gmm_through_transformer_moe_fsdp(monkeypatch):
    """Config surface on the moe-fsdp dryrun mesh: moe_dispatch="gmm"
    must match BOTH the sharded capacity oracle and the single-device
    gmm path, CE and parameter grads."""
    from tf_operator_tpu.models.transformer import lm_loss_and_metrics

    monkeypatch.setenv("TPUJOB_GMM_BLOCK_ROWS", "8")
    cfg = preset("tiny-moe", dtype=jnp.float32, remat=False, moe_top_k=2,
                 moe_dispatch="gmm")
    cfg_sort = preset("tiny-moe", dtype=jnp.float32, remat=False,
                      moe_top_k=2, capacity_factor=8.0)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    mesh = build_mesh({"dp": 2, "fsdp": 2, "ep": 2})

    def ce(c, m):
        return jit_value_and_grad(
            lambda p: lm_loss_and_metrics(p, tok, c, mesh=m)[1]["ce_loss"],
            params)

    got, g1 = ce(cfg, mesh)
    want, g2 = ce(cfg_sort, mesh)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    np.testing.assert_allclose(
        float(got),
        float(jax.jit(lambda p: lm_loss_and_metrics(
            p, tok, cfg, mesh=None)[1]["ce_loss"])(params)), rtol=2e-5)
    for (pa, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g1),
                               jax.tree_util.tree_leaves_with_path(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-6,
                                   err_msg=jax.tree_util.keystr(pa))


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_ep_gmm_pipeline_in_stage(schedule, monkeypatch):
    """ep INSIDE the pipeline (the moe-pipeline dryrun mesh, pp x ep x
    dp): the stage body routes cfg.moe_dispatch="gmm" through
    _moe_local's gmm branch against the BOUND ep axis — both schedules,
    CE and grads against the sharded capacity oracle."""
    from tf_operator_tpu.models.transformer import lm_loss_and_metrics

    monkeypatch.setenv("TPUJOB_GMM_BLOCK_ROWS", "8")
    cfg = preset("tiny-moe", dtype=jnp.float32, remat=False, n_layers=4,
                 pp_microbatches=2, moe_top_k=2, pp_schedule=schedule,
                 moe_dispatch="gmm")
    cfg_sort = preset("tiny-moe", dtype=jnp.float32, remat=False, n_layers=4,
                      pp_microbatches=2, moe_top_k=2, pp_schedule=schedule,
                      capacity_factor=8.0)
    mesh = build_mesh({"pp": 2, "ep": 2, "dp": 2})
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab)

    def ce(c):
        return jit_value_and_grad(
            lambda p: lm_loss_and_metrics(p, tok, c, mesh=mesh)[1]["ce_loss"],
            params)

    got, g1 = ce(cfg)
    want, g2 = ce(cfg_sort)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    for (pa, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g1),
                               jax.tree_util.tree_leaves_with_path(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-6,
                                   err_msg=jax.tree_util.keystr(pa))


def test_ep_gmm_zero_token_expert_gets_zero_grad_across_shards(monkeypatch):
    """Route every token to expert 0 (shard 0's expert) on an ep=2 mesh:
    shard 1's experts see ZERO tokens from every source — their weight
    grads must be exactly 0 and finite (the dw kernel zero-initializes
    every expert tile; no garbage block needed on the remote shard)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tf_operator_tpu.parallel.moe import moe_apply

    monkeypatch.setenv("TPUJOB_GMM_BLOCK_ROWS", "8")
    T, d, f, E = 32, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    gl = jnp.zeros((T, E)).at[:, 0].set(100.0)
    wp = {
        "w_gate": jax.random.normal(ks[1], (E, d, f)) * 0.1,
        "w_up": jax.random.normal(ks[2], (E, d, f)) * 0.1,
        "w_down": jax.random.normal(ks[3], (E, f, d)) * 0.1,
    }

    def efn(w, t):
        return (jax.nn.silu(t @ w["w_gate"]) * (t @ w["w_up"])) @ w["w_down"]

    mesh = build_mesh({"dp": 2, "ep": 2}, devices=jax.devices()[:4])
    xs = jax.device_put(x, NamedSharding(mesh, P(("dp", "ep"))))
    gls = jax.device_put(gl, NamedSharding(mesh, P(("dp", "ep"))))
    wps = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("ep"))), wp)

    _, g = jit_out_and_grads(
        lambda w: moe_apply(xs, gls, w, efn, mesh, k_top=1,
                            dispatch_impl="gmm"), wps)
    for name in g:
        np.testing.assert_array_equal(np.asarray(g[name][1:]), 0.0)
        assert np.isfinite(np.asarray(g[name])).all()
        assert np.abs(np.asarray(g[name][0])).sum() > 0


@pytest.mark.parametrize("k_top", [1, 2])
def test_ep_gmm_uneven_shard_loads_block_quantum_edge(k_top, monkeypatch):
    """The block-quantum padding edge: skew the router so per-(source,
    expert) counts are UNEVEN and not multiples of the block quantum
    (partial last blocks + empty (source, expert) pairs on the same
    shard), then pin against the no-drop capacity oracle."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tf_operator_tpu.parallel.moe import moe_apply

    monkeypatch.setenv("TPUJOB_GMM_BLOCK_ROWS", "8")
    T, d, f, E = 64, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(42), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    # strong skew: most tokens to experts 0 and 3, a trickle to 1, none
    # to 2 from many sources
    bias = jnp.array([3.0, -1.0, -6.0, 2.0])
    gl = jax.random.normal(ks[1], (T, E)) + bias[None, :]
    wp = {
        "w_gate": jax.random.normal(ks[2], (E, d, f)) * 0.1,
        "w_up": jax.random.normal(ks[3], (E, d, f)) * 0.1,
        "w_down": jax.random.normal(ks[4], (E, f, d)) * 0.1,
    }

    def efn(w, t):
        return (jax.nn.silu(t @ w["w_gate"]) * (t @ w["w_up"])) @ w["w_down"]

    mesh = build_mesh({"dp": 2, "fsdp": 2, "ep": 2})
    xs = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp", "ep"))))
    gls = jax.device_put(gl, NamedSharding(mesh, P(("dp", "fsdp", "ep"))))
    wps = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("ep", "fsdp"))), wp)

    def run(**kw):
        return jit_out_and_grads(
            lambda w: moe_apply(xs, gls, w, efn, mesh, k_top=k_top, **kw), wps)

    want, g2 = run(capacity_factor=float(E), dropped="zero")
    got, g1 = run(dispatch_impl="gmm")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for name in g1:
        np.testing.assert_allclose(np.asarray(g1[name]),
                                   np.asarray(g2[name]), rtol=5e-5,
                                   atol=5e-5, err_msg=name)
