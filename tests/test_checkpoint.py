"""Checkpoint/resume tests (SURVEY.md §5: restart-based recovery).

Runs on the 8-device virtual CPU mesh from conftest; exercises both the
orbax and the dependency-free npy backends through one API.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tf_operator_tpu.models.transformer import (
    init_transformer,
    lm_loss,
    preset,
    transformer_logical_axes,
)
from tf_operator_tpu.parallel import build_mesh
from tf_operator_tpu.train import CheckpointManager, Trainer, TrainerConfig

BACKENDS = ["npy", "orbax"]


def _clone(state):
    """Fresh buffers: trainer.step donates params/opt_state, so tests that
    step from the shared fixture state must copy it first."""
    from tf_operator_tpu.train import TrainState

    return TrainState(
        *(
            jax.tree_util.tree_map(lambda a: a.copy(), part)
            for part in (state.params, state.opt_state, state.step, state.extra)
        )
    )


def _tiny_trainer(mesh):
    cfg = preset("tiny", dtype=jnp.float32)

    def loss_fn(params, tokens, extra):
        del extra
        return lm_loss(params, tokens, cfg, mesh=mesh)

    return (
        Trainer(
            mesh,
            loss_fn=loss_fn,
            init_fn=lambda k: init_transformer(k, cfg),
            logical_axes=transformer_logical_axes(cfg),
            config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
        ),
        cfg,
    )


@pytest.fixture(scope="module")
def sharded_state():
    mesh = build_mesh({"dp": 2, "tp": 4})
    trainer, cfg = _tiny_trainer(mesh)
    state = trainer.init(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab),
        trainer.batch_sharding,
    )
    state, _ = trainer.step(state, tokens)
    return mesh, trainer, state, tokens


@pytest.mark.parametrize("backend", BACKENDS)
def test_roundtrip_sharded(tmp_path, sharded_state, backend):
    mesh, trainer, state, _ = sharded_state
    mgr = CheckpointManager(tmp_path / backend, keep=2, backend=backend)
    assert mgr.latest_step() is None
    assert mgr.save(int(state.step), state)
    assert mgr.all_steps() == [1]

    restored = mgr.restore(trainer.state_template())
    assert int(restored.step) == int(state.step)
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(restored.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        # restored leaves land on the template shardings (same mesh here)
    for a, b in zip(
        jax.tree_util.tree_leaves(state.opt_state),
        jax.tree_util.tree_leaves(restored.opt_state),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    mgr.close()


@pytest.mark.parametrize("backend", BACKENDS)
def test_retention_and_latest(tmp_path, sharded_state, backend):
    _, trainer, state, tokens = sharded_state
    state = _clone(state)
    mgr = CheckpointManager(tmp_path / backend, keep=2, backend=backend)
    for _ in range(3):
        state, _ = trainer.step(state, tokens)
        mgr.save(int(state.step), state)
    mgr.wait_until_finished()  # retention runs in the async drain
    steps = mgr.all_steps()
    assert len(steps) == 2, steps  # keep=2 pruned the oldest
    assert mgr.latest_step() == steps[-1] == int(state.step)
    mgr.close()


@pytest.mark.parametrize("backend", BACKENDS)
def test_restore_onto_different_mesh(tmp_path, sharded_state, backend):
    """Resharding on restore: save under dp=2/tp=4, restore under dp=4/tp=2
    (elastic topology change between runs)."""
    _, trainer, state, _ = sharded_state
    mgr = CheckpointManager(tmp_path / backend, keep=2, backend=backend)
    mgr.save(int(state.step), state)

    mesh2 = build_mesh({"dp": 4, "tp": 2})
    trainer2, _ = _tiny_trainer(mesh2)
    restored = mgr.restore(trainer2.state_template())
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(restored.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    mgr.close()


def test_restore_or_init_resumes(tmp_path, sharded_state):
    mesh, trainer, state, tokens = sharded_state
    mgr = CheckpointManager(tmp_path / "resume", keep=3, backend="npy")
    # no checkpoint -> fresh init at step 0
    fresh = trainer.restore_or_init(jax.random.PRNGKey(0), mgr)
    assert int(fresh.step) == 0
    # checkpoint present -> resume at its step
    state, _ = trainer.step(_clone(state), tokens)
    mgr.save(int(state.step), state)
    resumed = trainer.restore_or_init(jax.random.PRNGKey(0), mgr)
    assert int(resumed.step) == int(state.step) > 0
    # and training continues from there
    resumed2, m = trainer.step(resumed, tokens)
    assert int(resumed2.step) == int(state.step) + 1
    assert np.isfinite(float(m["loss"]))


def test_restore_empty_raises(tmp_path):
    mgr = CheckpointManager(tmp_path / "empty", backend="npy")
    with pytest.raises(FileNotFoundError):
        mgr.restore(template={"x": jnp.zeros((2,))})


def test_save_same_step_is_noop(tmp_path, sharded_state):
    _, trainer, state, _ = sharded_state
    mgr = CheckpointManager(tmp_path / "dup", backend="npy")
    assert mgr.save(int(state.step), state)
    assert not mgr.save(int(state.step), state)
    assert mgr.all_steps() == [int(state.step)]


def test_npy_restore_rejects_tree_drift(tmp_path):
    """Restoring onto a template with a different tree structure must fail
    loudly, not silently load weights into the wrong slots."""
    mgr = CheckpointManager(tmp_path / "drift", backend="npy")
    mgr.save(1, {"a": jnp.ones((2,)), "b": jnp.zeros((3,))})
    with pytest.raises(ValueError, match="does not match"):
        mgr.restore({"a": jnp.ones((2,)), "c": jnp.zeros((3,))})


def test_npy_restore_rejects_shape_dtype_drift(tmp_path):
    """Same tree structure but a changed leaf shape (config drift, e.g.
    d_model bumped) or dtype must fail loudly at restore time."""
    mgr = CheckpointManager(tmp_path / "shape", backend="npy")
    mgr.save(1, {"w": jnp.ones((2, 4)), "b": jnp.zeros((4,))})
    with pytest.raises(ValueError, match="config changed"):
        mgr.restore({"w": jnp.ones((2, 8)), "b": jnp.zeros((8,))})
    with pytest.raises(ValueError, match="config changed"):
        mgr.restore(
            {"w": jnp.ones((2, 4), jnp.bfloat16), "b": jnp.zeros((4,), jnp.bfloat16)}
        )


def test_npy_orphan_tmp_dirs_swept(tmp_path):
    """A crash mid-save leaves .tmp_step_* behind; a fresh manager (new
    process incarnation) must sweep it."""
    import os

    root = tmp_path / "orphans"
    mgr = CheckpointManager(root, backend="npy")
    mgr.save(1, {"x": jnp.ones((2,))}, wait=True)
    orphan = root / ".tmp_step_9_12345"
    orphan.mkdir()
    (orphan / "leaf_0.npy").write_bytes(b"partial")
    mgr2 = CheckpointManager(root, backend="npy")
    assert not orphan.exists()
    assert mgr2.all_steps() == [1]


def test_workload_checkpointer_refuses_nan_save(tmp_path):
    """A periodic save must never checkpoint a diverged state — that would
    poison every restart's resume."""
    from tf_operator_tpu.train.checkpoint import WorkloadCheckpointer

    ckpt = WorkloadCheckpointer(
        {"checkpoint_dir": str(tmp_path / "nan"), "checkpoint_every": 1}
    )
    ckpt.advance({"x": jnp.ones((2,))}, loss=1.25)  # finite: saved
    assert ckpt.manager.all_steps() == [1]
    with pytest.raises(AssertionError, match="non-finite"):
        ckpt.advance({"x": jnp.ones((2,))}, loss=float("nan"))
    assert ckpt.manager.all_steps() == [1]  # nothing new written


def test_workload_checkpointer_is_complete_peeks_without_restore(tmp_path):
    """is_complete must answer from the manifest alone (before any restore)."""
    from tf_operator_tpu.train.checkpoint import WorkloadCheckpointer

    wl = {"checkpoint_dir": str(tmp_path / "peek"), "checkpoint_every": 1}
    ckpt = WorkloadCheckpointer(wl)
    # wait=True: models a COMPLETED prior incarnation (its last save is
    # fenced by final()); an unfenced async save is legitimately invisible
    # to a new process until committed.
    ckpt.manager.save(6, {"x": jnp.ones((2,))}, wait=True)
    fresh = WorkloadCheckpointer(wl)  # new incarnation, nothing restored
    assert fresh.is_complete(5)  # 6 >= 5 + 1 (warmup step)
    assert not fresh.is_complete(10)


def test_async_save_overlaps_and_fences(tmp_path, sharded_state):
    """Async orbax semantics (r3): save() returns with the write possibly
    still in flight; wait_until_finished commits it; the next save()
    self-fences (at most one write in flight); a fenced save is restorable
    by a FRESH manager (the cross-process visibility contract)."""
    _, trainer, state, _ = sharded_state
    mgr = CheckpointManager(tmp_path / "async", backend="orbax")
    assert mgr.async_save
    assert mgr.save(1, state)
    mgr.wait_until_finished()
    assert 1 in mgr.all_steps()
    # second save fences the first internally, then dispatches
    assert mgr.save(2, _clone(state), wait=True)
    mgr.close()
    fresh = CheckpointManager(tmp_path / "async", backend="orbax", readonly=True)
    assert fresh.latest_step() == 2
    restored = fresh.restore(trainer.state_template(), step=2)
    assert int(restored.step) == int(state.step)


def test_sync_save_opt_out(tmp_path, sharded_state):
    """async_save=False restores the r2 blocking behavior."""
    _, _, state, _ = sharded_state
    mgr = CheckpointManager(tmp_path / "sync", backend="orbax", async_save=False)
    assert mgr.save(3, state)
    fresh = CheckpointManager(tmp_path / "sync", backend="orbax", readonly=True)
    assert fresh.latest_step() == 3  # committed before save() returned
    mgr.close()


@pytest.mark.parametrize("backend", BACKENDS)
def test_reader_sees_external_saves_after_reload(tmp_path, sharded_state, backend):
    """The evaluator pattern: a READER manager constructed before any
    checkpoint exists must see another manager's saves after reload()
    (the orbax backend caches its step list at construction)."""
    mesh, trainer, state, tokens = sharded_state
    root = tmp_path / backend
    reader = CheckpointManager(root, backend=backend, readonly=True)
    writer = CheckpointManager(root, backend=backend)
    # wait=True: cross-manager visibility is committed-state only — the
    # live evaluator polls reload() until a save commits; the test pins
    # the discovery mechanics, not the polling.
    writer.save(2, _clone(state), wait=True)
    reader.reload()
    assert reader.latest_step() == 2
    writer.save(4, _clone(state), wait=True)
    reader.reload()
    assert reader.latest_step() == 4


@pytest.mark.parametrize("backend", BACKENDS)
def test_restore_params_only(tmp_path, sharded_state, backend):
    mesh, trainer, state, tokens = sharded_state
    mgr = CheckpointManager(tmp_path / backend, backend=backend)
    mgr.save(3, _clone(state))
    params = mgr.restore_params(trainer.state_template().params)
    for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(state.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_readonly_manager_refuses_save_and_preserves_tmp_dirs(tmp_path, sharded_state):
    mesh, trainer, state, tokens = sharded_state
    root = tmp_path / "ro"
    root.mkdir()
    # a live writer's in-flight tmp dir must survive a readonly reader
    live_tmp = root / ".tmp_step_9_12345"
    live_tmp.mkdir()
    ro = CheckpointManager(root, backend="npy", readonly=True)
    assert live_tmp.exists()
    with pytest.raises(RuntimeError, match="readonly"):
        ro.save(1, _clone(state))
    # a writable manager still sweeps it
    CheckpointManager(root, backend="npy")
    assert not live_tmp.exists()


def test_run_loop_matches_hand_driven_steps_and_saves_at_boundaries(tmp_path):
    """run_loop is trainer.step called ``1 + steps`` times: the same
    trajectory as a hand-driven loop, a save at every boundary plus the
    final one, and on_step after EVERY step (fault injection keys on it)."""
    from tf_operator_tpu.train.checkpoint import WorkloadCheckpointer

    mesh = build_mesh({"dp": 2, "tp": 4})
    trainer, cfg = _tiny_trainer(mesh)
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256),
        trainer.batch_sharding,
    )
    wl = {"checkpoint_dir": str(tmp_path / "loop"), "checkpoint_every": 2,
          "checkpoint_keep": 8}
    ckpt = WorkloadCheckpointer(wl)
    seen = []
    state, loss, timed, step_s = ckpt.run_loop(
        trainer, jax.random.PRNGKey(0), tok, 7, on_step=seen.append
    )
    assert seen == list(range(1, 9))  # warmup + 7, none skipped or late
    assert timed == 7 and step_s > 0 and int(state.step) == 8
    assert ckpt.manager.all_steps() == [2, 4, 6, 8]

    ref, _ = _tiny_trainer(mesh)
    s_ref = ref.init(jax.random.PRNGKey(0))
    for _ in range(8):
        s_ref, m_ref = ref.step(s_ref, tok)
    np.testing.assert_allclose(loss, float(m_ref["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(s_ref.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_run_loop_pulls_one_iterator_batch_per_step(tmp_path):
    from tf_operator_tpu.train.checkpoint import WorkloadCheckpointer
    from tf_operator_tpu.train.data import ArrayDataset, DeviceLoader

    mesh = build_mesh({"dp": 2, "tp": 4})
    trainer, cfg = _tiny_trainer(mesh)
    ds = ArrayDataset(
        {"t": np.random.default_rng(0).integers(0, 256, (64, 32), dtype=np.int32)},
        batch_size=4, shuffle=False,
    )
    ckpt = WorkloadCheckpointer({})
    pulled = []
    with DeviceLoader(ds, trainer.batch_sharding) as loader:
        it = (pulled.append(1) or b["t"] for b in loader)
        state, loss, timed, step_s = ckpt.run_loop(
            trainer, jax.random.PRNGKey(0), it, 6
        )
    # the warmup step trains on a batch of its own and stays untimed
    assert timed == 6 and int(state.step) == 7 and len(pulled) == 7
    assert np.isfinite(loss) and step_s > 0


def test_run_loop_resumes_at_the_saved_step(tmp_path):
    """A restarted worker re-enters run_loop: it restores the final save,
    times only the steps that remain, and on_step counts on from there."""
    from tf_operator_tpu.train.checkpoint import WorkloadCheckpointer

    mesh = build_mesh({"dp": 2, "tp": 4})
    wl = {"checkpoint_dir": str(tmp_path / "resume"), "checkpoint_every": 100}

    def run(steps):
        trainer, cfg = _tiny_trainer(mesh)
        tok = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256),
            trainer.batch_sharding,
        )
        ckpt = WorkloadCheckpointer(wl)
        seen = []
        if ckpt.is_complete(steps):
            return ckpt, None, 0, seen
        state, _, timed, _ = ckpt.run_loop(
            trainer, jax.random.PRNGKey(0), tok, steps, on_step=seen.append
        )
        return ckpt, state, timed, seen

    ckpt, state, timed, seen = run(3)
    assert int(state.step) == 4 and timed == 3 and seen == [1, 2, 3, 4]
    assert ckpt.manager.all_steps() == [4]  # no boundary crossed: the final save
    assert run(3)[1] is None  # already complete: no restore, no step
    ckpt, state, timed, seen = run(7)
    assert ckpt.start_step == 4 and timed == 3
    assert int(state.step) == 8 and seen == [5, 6, 7, 8]


# ---------------------------------------------------------------------------
# crash-mid-save (r8): a torn orbax step dir is never a resume point
# ---------------------------------------------------------------------------


def test_crash_mid_save_never_becomes_resume_point(tmp_path):
    """A bare numeric step dir without orbax's commit marker is a save
    cut by a crash: discovery must fall back to the newest COMPLETE step
    instead of handing the warm-restart env a corrupt checkpoint."""
    from tf_operator_tpu.train.checkpoint import latest_checkpoint_step

    d = tmp_path / "ckpt"
    mgr = CheckpointManager(str(d), backend="orbax")
    mgr.save(2, {"a": np.ones(3)}, wait=True)
    mgr.close()
    assert latest_checkpoint_step(str(d)) == 2
    # Crash mid-save at step 4: the dir exists (renamed into place or
    # partially written) but the commit marker never landed.
    torn = d / "4"
    torn.mkdir()
    (torn / "default").mkdir()
    assert latest_checkpoint_step(str(d)) == 2, "torn step 4 must not win"
    # Commit marker appears (the save finalizes): now it is the latest.
    (torn / "_CHECKPOINT_METADATA").write_text("{}")
    assert latest_checkpoint_step(str(d)) == 4


def test_npy_step_without_manifest_is_not_a_resume_point(tmp_path):
    from tf_operator_tpu.train.checkpoint import latest_checkpoint_step

    d = tmp_path / "ckpt"
    d.mkdir()
    (d / "step_3").mkdir()
    (d / "step_3" / "manifest.json").write_text("{}")
    (d / "step_5").mkdir()  # no manifest: torn npy save
    assert latest_checkpoint_step(str(d)) == 3


# ---- chunked async npy pipeline (r8) ------------------------------------


def test_async_npy_crash_never_yields_torn_resume_point(tmp_path):
    """Commit ordering: a crash at ANY phase of the async drain (mid-leaf,
    before the manifest, between the manifest and the rename) must never
    make the step discoverable by the controller's resume oracle
    (latest_checkpoint_step), and the failure must surface at the next
    fence — then a retry of the same step succeeds."""
    from tf_operator_tpu.train.checkpoint import latest_checkpoint_step

    for phase in ("leaf", "manifest", "commit"):
        root = tmp_path / f"crash-{phase}"
        mgr = CheckpointManager(root, backend="npy")
        assert mgr.save(1, {"x": jnp.ones((4,))}, wait=True)

        def boom(p, step, _phase=phase):
            if p == _phase and step == 2:
                raise RuntimeError(f"injected crash at {p}")

        mgr._fault_hook = boom
        assert mgr.save(2, {"x": jnp.full((4,), 2.0)})
        with pytest.raises(RuntimeError, match="never committed"):
            mgr.wait_until_finished()
        # The torn step is invisible to the warm-restart contract: the
        # controller would stamp TPUJOB_RESUME_STEP=1, never 2.
        assert latest_checkpoint_step(str(root)) == 1
        assert mgr.all_steps() == [1]
        # Retry (same incarnation) rebuilds its tmp from scratch and lands.
        mgr._fault_hook = None
        assert mgr.save(2, {"x": jnp.full((4,), 2.0)}, wait=True)
        assert latest_checkpoint_step(str(root)) == 2
        np.testing.assert_array_equal(
            np.asarray(mgr.restore({"x": jnp.zeros((4,))})["x"]),
            np.full((4,), 2.0),
        )


def test_async_npy_save_returns_before_commit(tmp_path):
    """Overlap receipt: save() hands back control while the drain is still
    running; until the commit rename, nothing on disk is discoverable (a
    crash in this window is a clean orphan, not a resume point)."""
    import threading

    from tf_operator_tpu.train.checkpoint import latest_checkpoint_step

    root = tmp_path / "overlap"
    mgr = CheckpointManager(root, backend="npy")
    gate = threading.Event()
    mgr._fault_hook = (
        lambda phase, step: gate.wait(timeout=30) if phase == "commit" else None
    )
    assert mgr.save(1, {"x": jnp.ones((1024,))})
    # save() already returned; the drain is parked just before the rename
    assert latest_checkpoint_step(str(root)) == 0
    assert mgr.last_save_stall_s < 30.0  # the caller never waited on the gate
    gate.set()
    mgr.wait_until_finished()
    assert latest_checkpoint_step(str(root)) == 1


def test_duplicate_step_save_never_fences_inflight_write(tmp_path):
    """The head-of-line fix: a duplicate-step save must answer from the
    step list WITHOUT fencing the previous in-flight write (here the
    in-flight drain is the SAME step, parked at the commit gate — a
    fencing implementation would block 30s)."""
    import threading
    import time as _time

    root = tmp_path / "hol"
    mgr = CheckpointManager(root, backend="npy")
    gate = threading.Event()
    mgr._fault_hook = (
        lambda phase, step: gate.wait(timeout=30) if phase == "commit" else None
    )
    assert mgr.save(3, {"x": jnp.ones((8,))})
    t0 = _time.perf_counter()
    assert mgr.save(3, {"x": jnp.ones((8,))}) is False
    assert _time.perf_counter() - t0 < 5.0, "duplicate save fenced the drain"
    gate.set()
    mgr.wait_until_finished()
    assert mgr.all_steps() == [3]


def test_waited_duplicate_save_fences_inflight_write(tmp_path):
    """wait=True must fence even when the save is rejected as a duplicate
    — the duplicate may BE the in-flight drain (final() re-saving the
    last periodic step), and returning unfenced would let process exit
    (daemon drain thread) tear the final checkpoint."""
    import threading

    from tf_operator_tpu.train.checkpoint import latest_checkpoint_step

    root = tmp_path / "dupfence"
    mgr = CheckpointManager(root, backend="npy")
    gate = threading.Event()
    mgr._fault_hook = (
        lambda phase, step: gate.wait(timeout=30) if phase == "commit" else None
    )
    assert mgr.save(5, {"x": jnp.ones((8,))})  # async, parked pre-rename
    threading.Timer(0.2, gate.set).start()
    assert mgr.save(5, {"x": jnp.ones((8,))}, wait=True) is False
    # The waited call returned only after the drain committed.
    assert latest_checkpoint_step(str(root)) == 5


def test_final_fences_duplicate_of_inflight_save(tmp_path):
    """The review scenario: steps % checkpoint_every == 0, so final()'s
    save is a duplicate of the accepted in-flight async save — it must
    still fence before returning (run_loop callers never close())."""
    import threading

    from tf_operator_tpu.train.checkpoint import (
        WorkloadCheckpointer,
        latest_checkpoint_step,
    )

    root = tmp_path / "finalfence"
    ckpt = WorkloadCheckpointer(
        {"checkpoint_dir": str(root), "checkpoint_every": 1}
    )
    gate = threading.Event()
    ckpt.manager._fault_hook = (
        lambda phase, step: gate.wait(timeout=30) if phase == "commit" else None
    )
    state = {"x": jnp.ones((2,))}
    ckpt.advance(state, loss=1.0)  # periodic save of step 1 accepted, parked
    assert latest_checkpoint_step(str(root)) == 0  # still in flight
    threading.Timer(0.2, gate.set).start()
    ckpt.final(state)  # duplicate of the in-flight step — must fence
    assert latest_checkpoint_step(str(root)) == 1


def test_failed_drain_cleans_its_tmp_dir(tmp_path):
    """A drain that dies must remove its partial .tmp_step_* dir NOW (the
    constructor sweep skips our own pid, so without this each failure
    pins a partial dir — and disk bytes — for the process lifetime)."""
    import os

    root = tmp_path / "drainfail"
    mgr = CheckpointManager(root, backend="npy")

    def boom(phase, step):
        if phase == "manifest":
            raise RuntimeError("disk full")

    mgr._fault_hook = boom
    assert mgr.save(1, {"x": jnp.ones((16,))})
    with pytest.raises(RuntimeError, match="never committed"):
        mgr.wait_until_finished()
    assert not [n for n in os.listdir(root) if n.startswith(".tmp_step_")]


def test_prefetch_falls_back_to_next_peer_then_disk(tmp_path, monkeypatch):
    """The promised fallback order: best peer dying mid-transfer must try
    the NEXT live peer holding the step before degrading to disk."""
    from types import SimpleNamespace

    from tf_operator_tpu.rendezvous import statechannel
    from tf_operator_tpu.rendezvous.statechannel import DepotClient, ShardDepot
    from tf_operator_tpu.train.checkpoint import (
        WorkloadCheckpointer,
        latest_checkpoint_step,
    )

    depot_a, depot_b = ShardDepot(), ShardDepot()
    try:
        src = tmp_path / "src"
        mgr = CheckpointManager(src, backend="npy")
        mgr.save(4, {"x": jnp.arange(4, dtype=jnp.float32)}, wait=True)
        client = DepotClient()
        assert client.push_step(depot_a.url, "ns", "job", 4, str(src / "step_4"))
        assert client.push_step(depot_b.url, "ns", "job", 4, str(src / "step_4"))

        real_fetch = statechannel.DepotClient.fetch_step

        def dying_first_peer(self, url, ns, job, step, dest_root):
            if url == depot_a.url:
                return None  # peer died mid-transfer
            return real_fetch(self, url, ns, job, step, dest_root)

        monkeypatch.setattr(
            statechannel.DepotClient, "fetch_step", dying_first_peer
        )
        dest = tmp_path / "dest"
        ctx = SimpleNamespace(
            namespace="ns", job_name="job", peer_depot="",
            restore_peers=[depot_a.url, depot_b.url],
        )
        ckpt = WorkloadCheckpointer({"checkpoint_dir": str(dest)}, ctx=ctx)
        assert ckpt.prefetch_from_peers() == "peer"
        assert latest_checkpoint_step(str(dest)) == 4
        # Every peer dead -> disk.
        monkeypatch.setattr(
            statechannel.DepotClient, "fetch_step",
            lambda self, *a, **k: None,
        )
        ckpt2 = WorkloadCheckpointer(
            {"checkpoint_dir": str(tmp_path / "dest2")}, ctx=ctx
        )
        assert ckpt2.prefetch_from_peers() == "disk"
    finally:
        depot_a.stop()
        depot_b.stop()


def test_workload_checkpointer_records_save_stall(tmp_path):
    """Every ACCEPTED periodic save contributes one stall sample (the
    bench artifact's p50/p99 source); skipped duplicates contribute none."""
    from tf_operator_tpu.train.checkpoint import WorkloadCheckpointer

    ckpt = WorkloadCheckpointer(
        {"checkpoint_dir": str(tmp_path / "stall"), "checkpoint_every": 1}
    )
    ckpt.advance({"x": jnp.ones((2,))}, loss=1.0)
    ckpt.advance({"x": jnp.ones((2,))}, loss=1.0)
    assert len(ckpt.save_stalls) == 2
    assert all(s >= 0.0 for s in ckpt.save_stalls)
    ckpt.manager.close()
