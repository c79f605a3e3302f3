"""Gradient accumulation (TrainerConfig.grad_accum).

The reference trains whatever batch fits the pod; on TPU the per-chip
activation budget caps the direct batch, so accumulation is the lever
that keeps a recipe's global batch when memory doesn't (VERDICT r2 #6 —
e.g. the llama2-70b fsdp=32 x tp=8 memplan). The oracle: accumulated
steps must match full-batch steps exactly (mean-of-microbatch-means ==
full-batch mean for equal microbatches), composed with the device loop
and donation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_operator_tpu.parallel import build_mesh
from tf_operator_tpu.train.trainer import Trainer, TrainerConfig


def _make_trainer(mesh, accum, optimizer="sgd", extra=False):
    def init_fn(key):
        params = {
            "w": jax.random.normal(key, (8, 4), jnp.float32) * 0.1,
            "b": jnp.zeros((4,), jnp.float32),
        }
        if extra:
            return params, {"count": jnp.zeros((), jnp.float32)}
        return params

    def loss_fn(params, batch, ex):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        loss = jnp.mean((pred - y) ** 2)
        if extra:
            return loss, {"count": ex["count"] + 1.0}
        return loss

    return Trainer(
        mesh,
        loss_fn=loss_fn,
        init_fn=init_fn,
        config=TrainerConfig(
            optimizer=optimizer, learning_rate=0.05, grad_accum=accum
        ),
    )


def _batch(key, b=16):
    kx, ky = jax.random.split(key)
    return (
        jax.random.normal(kx, (b, 8), jnp.float32),
        jax.random.normal(ky, (b, 4), jnp.float32),
    )


@pytest.mark.parametrize("accum", [2, 4])
@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
def test_accum_matches_full_batch(accum, optimizer):
    mesh = build_mesh({"dp": 1}, devices=jax.devices()[:1])
    full = _make_trainer(mesh, 1, optimizer)
    acc = _make_trainer(mesh, accum, optimizer)
    s_full = full.init(jax.random.PRNGKey(0))
    s_acc = acc.init(jax.random.PRNGKey(0))
    for i in range(4):
        batch = _batch(jax.random.PRNGKey(i))
        s_full, m_full = full.step(s_full, batch)
        s_acc, m_acc = acc.step(s_acc, batch)
        np.testing.assert_allclose(
            float(m_acc["loss"]), float(m_full["loss"]), rtol=1e-5
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_acc.params),
        jax.tree_util.tree_leaves(s_full.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-7)


def test_accum_on_sharded_mesh():
    """Composes with dp sharding: the microbatch reshape keeps every
    device an equal slice (with_sharding_constraint in _accum_grads)."""
    mesh = build_mesh({"dp": jax.device_count()})
    full = _make_trainer(mesh, 1)
    acc = _make_trainer(mesh, 4)
    batch = _batch(jax.random.PRNGKey(0), b=16)
    batch = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, full.batch_sharding), batch
    )
    s_full, m_full = full.step(full.init(jax.random.PRNGKey(0)), batch)
    s_acc, m_acc = acc.step(acc.init(jax.random.PRNGKey(0)), batch)
    np.testing.assert_allclose(float(m_acc["loss"]), float(m_full["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(s_acc.params),
        jax.tree_util.tree_leaves(s_full.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-7)


def test_accum_threads_extra_state():
    """Model state (BN-stats-shaped `extra`) advances once per microbatch,
    sequential-small-steps semantics."""
    mesh = build_mesh({"dp": 1}, devices=jax.devices()[:1])
    acc = _make_trainer(mesh, 4, extra=True)
    state = acc.init(jax.random.PRNGKey(0))
    state, _ = acc.step(state, _batch(jax.random.PRNGKey(0)))
    assert float(state.extra["count"]) == 4.0


def test_indivisible_batch_rejected():
    mesh = build_mesh({"dp": 1}, devices=jax.devices()[:1])
    acc = _make_trainer(mesh, 3)
    with pytest.raises(ValueError, match="grad_accum"):
        acc.step(acc.init(jax.random.PRNGKey(0)), _batch(jax.random.PRNGKey(0)))


def test_precompile_step_async_matches_jit_path():
    """The r4 submit-overlap path: a step through the background-
    precompiled (AOT) executable must produce exactly what the lazy jit
    path produces — same params, opt state, loss — and a sharding
    mismatch must fall back to the jit path, not crash."""
    mesh = build_mesh({"dp": 8})
    batch = (
        jnp.ones((16, 8), jnp.float32),
        jnp.zeros((16, 4), jnp.float32),
    )

    tr_pre = _make_trainer(mesh, accum=1)
    tr_jit = _make_trainer(mesh, accum=1)
    t = tr_pre.precompile_step_async(batch)
    t.join()
    assert tr_pre._step_compiled is not None

    s_pre = tr_pre.init(jax.random.PRNGKey(0))
    s_jit = tr_jit.init(jax.random.PRNGKey(0))
    staged = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, tr_pre.batch_sharding), batch
    )
    s_pre, m_pre = tr_pre.step(s_pre, staged)
    s_jit, m_jit = tr_jit.step(s_jit, staged)
    np.testing.assert_allclose(
        float(m_pre["loss"]), float(m_jit["loss"]), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(s_pre.params),
                    jax.tree_util.tree_leaves(s_jit.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # wrong-shape batch: the AOT call must fall back for THIS call only,
    # keeping the executable for the common shape (one odd final batch
    # must not force a cold recompile)
    other = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, tr_pre.batch_sharding),
        (jnp.ones((8, 8), jnp.float32), jnp.zeros((8, 4), jnp.float32)),
    )
    s_pre, m = tr_pre.step(s_pre, other)
    assert np.isfinite(float(m["loss"]))
    assert tr_pre._step_compiled is not None


def test_fast_init_key_distinct_and_deterministic():
    """fast_init_rng derives rbg keys from caller keys: same key -> same
    stream, different keys -> different params."""
    mesh = build_mesh({"dp": 8})
    tr = _make_trainer(mesh, accum=1)
    a = tr.init(jax.random.PRNGKey(0))
    b = tr.init(jax.random.PRNGKey(0))
    c = tr.init(jax.random.PRNGKey(1))
    wa, wb, wc = (np.asarray(s.params["w"]) for s in (a, b, c))
    np.testing.assert_array_equal(wa, wb)
    assert not np.array_equal(wa, wc)
