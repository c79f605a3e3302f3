"""Checkpoint/resume harness: sharded train-state save/restore.

Reference parity: the reference operator has NO checkpoint subsystem — user
workloads checkpoint to volumes/GCS through the PodTemplate and the
operator's own resume story is idempotent reconcile over CRD status
(/root/reference/tf_job_design_doc.md:73; SURVEY.md §5 "Checkpoint/resume").
The TPU build keeps that split but supplies the workload half as library
code: a checkpoint manager the training harness calls, so a gang restart
(controller deletes + recreates every process after a retryable failure)
resumes from the last saved step instead of step 0.

Two backends behind one API:

- **orbax** (preferred): ``orbax.checkpoint.CheckpointManager`` with
  ``StandardSave/StandardRestore`` — handles sharded arrays, multi-host
  coordination, and atomic finalization natively. Restoring onto a
  *different* mesh/sharding works by passing the target template (abstract
  arrays carrying NamedShardings). Saves are ASYNC by default (r3): the
  step loop only pays the device→host transfer; serialization overlaps
  subsequent steps, with a completion fence before the next save and on
  job end (the wrong default at v5p-128 scale is a synchronous save
  blocking the gang every checkpoint_every steps).
- **npy** (dependency-free fallback): one ``.npy`` per leaf plus a JSON
  tree manifest, written to a temp dir and atomically renamed. Requires
  fully-addressable arrays (single-host); restore ``device_put``s onto the
  template's shardings. With ``async_save`` (r8) the npy backend runs the
  chunked staging pipeline: ``save()`` only pays a device-side staging
  copy of the state (donation-safe — the step loop may immediately reuse
  the donated buffers), then a background drain moves staged leaves
  device→host and to disk in fixed-byte quanta, releasing each staging
  buffer as its leaf lands. The ONLY hard fence is the commit-marker
  write (``manifest.json`` written fsync'd-last into the temp dir, then
  one atomic rename) — the same contract ``latest_checkpoint_step()``
  already requires, so a crash anywhere in the pipeline leaves a
  ``.tmp_step_*`` orphan, never a resumable torn step.

Both are step-indexed directories with keep-N retention and
``latest_step()`` discovery, so "resume" is simply
``trainer.restore_or_init(key, manager)``. ``on_commit`` fires once per
step that actually COMMITTED — the seam the peer shard depot
(rendezvous/statechannel.py) feeds from, so peers only ever serve state a
crash could also have restored from disk.
"""

from __future__ import annotations

import json
import logging
import os
import re
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional

log = logging.getLogger("tpujob.checkpoint")

_STEP_DIR = re.compile(r"^step_(\d+)$")

# Completeness markers orbax leaves in a FINALIZED step directory:
# `_CHECKPOINT_METADATA` (modern orbax, written at commit) or
# `commit_success.txt` (the multihost/GCS-era marker). A bare numeric dir
# without either is a save torn mid-crash — orbax renames its tmp dir
# into place before the final metadata write, so "directory exists" alone
# is NOT a commit. Resuming from a torn step bricks the warm restart
# (restore raises, or worse, loads garbage), so discovery requires a
# marker and falls back to the newest COMPLETE step.
_ORBAX_COMMIT_MARKERS = ("_CHECKPOINT_METADATA", "commit_success.txt")


def _orbax_step_complete(step_dir: str) -> bool:
    return any(
        os.path.exists(os.path.join(step_dir, m)) for m in _ORBAX_COMMIT_MARKERS
    )


def checkpoint_world_size(directory: str, step: int) -> int:
    """World size recorded in a committed npy step's manifest (its commit
    marker), 0 when untagged (pre-r12 checkpoints, orbax steps) or absent.
    Dependency-free like :func:`latest_checkpoint_step` — the controller
    and the chaos checkers read it without importing jax."""
    try:
        with open(os.path.join(directory, f"step_{int(step)}", "manifest.json")) as f:
            return int(json.load(f).get("world_size", 0) or 0)
    except (OSError, ValueError, TypeError):
        return 0


def latest_checkpoint_step(directory: str) -> int:
    """Latest COMPLETE checkpointed step under ``directory``, 0 when none.

    Dependency-free filesystem scan (no orbax import, no manager
    construction): the control plane calls this on every gang (re)create
    to stamp the warm-restart env (``TPUJOB_RESUME_STEP``), so it must be
    cheap and must not pull jax/orbax into the controller process. Handles
    both on-disk layouts: the npy backend's ``step_N/manifest.json``
    (atomically renamed, so presence of the manifest is the commit) and
    orbax's bare numeric step directories, which count only when their
    commit marker exists (``_ORBAX_COMMIT_MARKERS``) — a save torn by a
    crash mid-write must never become a resume point; the newest complete
    step wins instead."""
    try:
        names = os.listdir(directory)
    except OSError:
        return 0
    best = 0
    for name in names:
        m = _STEP_DIR.match(name)
        if m and os.path.exists(os.path.join(directory, name, "manifest.json")):
            best = max(best, int(m.group(1)))
        elif (
            name.isdigit()
            and os.path.isdir(os.path.join(directory, name))
            and _orbax_step_complete(os.path.join(directory, name))
        ):
            best = max(best, int(name))
    return best


def _to_tree(state: Any) -> Any:
    """TrainState -> plain dict pytree (checkpoint wire format)."""
    from tf_operator_tpu.train.trainer import TrainState

    if isinstance(state, TrainState):
        return {
            "params": state.params,
            "opt_state": state.opt_state,
            "step": state.step,
            "extra": state.extra,
        }
    return state


def _from_tree(tree: Any, like: Any) -> Any:
    """Plain dict pytree -> same type as ``like`` (TrainState or dict)."""
    from tf_operator_tpu.train.trainer import TrainState

    if isinstance(like, TrainState) and isinstance(tree, dict):
        return TrainState(
            params=tree.get("params"),
            opt_state=tree.get("opt_state"),
            step=tree.get("step"),
            extra=tree.get("extra"),
        )
    return tree


class CheckpointManager:
    """Step-indexed sharded checkpoints under one directory.

    Args:
        directory: checkpoint root (created if missing).
        keep: retain at most this many checkpoints (oldest pruned).
        backend: "auto" (orbax if importable), "orbax", or "npy".
    """

    def __init__(
        self,
        directory: str,
        keep: int = 3,
        backend: str = "auto",
        readonly: bool = False,
        async_save: bool = True,
        chunk_bytes: int = 64 << 20,
        on_commit: Optional[Callable[[int, str], None]] = None,
        world_size: Optional[int] = None,
        allow_world_resize: bool = False,
    ) -> None:
        """``readonly=True`` is for consumers of someone else's checkpoint
        directory (evaluators): saves are refused and the npy orphan sweep
        is skipped — a live writer may legitimately own a .tmp dir.

        ``async_save``: device→host transfer overlaps subsequent training
        steps instead of stalling the step loop for the full fetch. With
        orbax, ``save()`` pays the device→host transfer (donated step
        buffers stay safe) and the disk write runs in orbax's background
        thread. With npy, ``save()`` pays only a device-side STAGING copy
        (bounded by HBM bandwidth, not PCIe) and a background drain moves
        staged leaves device→host→disk in ``chunk_bytes`` quanta,
        releasing each staging buffer as its leaf lands. In both cases at
        most one write is in flight; ``save(..., wait=True)`` /
        ``wait_until_finished()`` / ``close()`` fence completion — the
        final save of a job must be fenced or the process can exit with a
        torn checkpoint (WorkloadCheckpointer.final does).

        ``on_commit(step, step_dir)`` fires after a step COMMITS on disk
        (npy backend; after the atomic rename) — the peer shard depot's
        feed. Exceptions in the hook are logged, never raised: publishing
        to peers is best-effort, the disk commit already happened.

        ``last_save_stall_s`` after each accepted save is the wall time
        the CALLER was blocked — the step-loop stall the async pipeline
        exists to shrink.

        ``world_size`` (r12): the gang world size stamped into each npy
        manifest at save time (None ⇒ ``jax.process_count()``) and the
        world this manager expects at restore. Elastic trainers update it
        across resizes (``mgr.world_size = n``). A restore whose manifest
        tag disagrees with the declared world REFUSES loudly — a
        mixed-world resume must never materialize silently — unless
        ``allow_world_resize=True`` explicitly declares a resize restore
        (the elastic path, which re-shards onto the new world right
        after)."""
        self.directory = os.path.abspath(str(directory))
        self.keep = int(keep)
        self.readonly = bool(readonly)
        self.async_save = bool(async_save)
        self.chunk_bytes = max(1 << 20, int(chunk_bytes))
        self.on_commit = on_commit
        self.world_size = world_size
        self.allow_world_resize = bool(allow_world_resize)
        self.last_save_stall_s = 0.0
        # npy async pipeline state: at most one drain thread in flight.
        self._drain: Optional[threading.Thread] = None
        self._drain_step: Optional[int] = None
        self._drain_error: Optional[BaseException] = None
        # Test seam: called as _fault_hook(phase, step) with phase in
        # {"leaf", "manifest", "commit"} from inside the drain — lets
        # tests crash the pipeline between any two phases.
        self._fault_hook: Optional[Callable[[str, int], None]] = None
        os.makedirs(self.directory, exist_ok=True)
        if backend == "auto":
            try:
                import orbax.checkpoint  # noqa: F401

                backend = "orbax"
            except Exception:  # pragma: no cover - orbax is baked into CI
                backend = "npy"
        self.backend = backend
        self._ocp_mgr = None
        if backend == "npy" and not self.readonly:
            # Sweep partial-save orphans: a crash mid-_npy_save leaves a
            # .tmp_step_* dir that a restarted process (new PID) would
            # otherwise never clean. The npy backend is single-process
            # (enforced in _npy_save), so nothing live can own these —
            # except when we are a readonly reader of a live writer's dir.
            for name in os.listdir(self.directory):
                if not name.startswith(".tmp_step_"):
                    continue
                # Tmp names end in the writer's pid: skip OUR pid — a
                # second manager in this process may have an async drain
                # live in that dir right now (crashed writers restart
                # with a new pid, so their orphans still sweep; a pid
                # collision merely defers cleanup to that step's next
                # save, which re-creates its tmp from scratch).
                if name.endswith(f"_{os.getpid()}"):
                    continue
                shutil.rmtree(os.path.join(self.directory, name), ignore_errors=True)
        if backend == "orbax":
            import orbax.checkpoint as ocp

            self._ocp = ocp
            self._ocp_mgr = ocp.CheckpointManager(
                self.directory,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=self.keep,
                    create=True,
                    enable_async_checkpointing=self.async_save,
                ),
            )

    # ---- discovery ------------------------------------------------------

    def all_steps(self) -> List[int]:
        if self._ocp_mgr is not None:
            return sorted(self._ocp_mgr.all_steps())
        steps = set()
        for name in os.listdir(self.directory):
            m = _STEP_DIR.match(name)
            if m and os.path.exists(os.path.join(self.directory, name, "manifest.json")):
                steps.add(int(m.group(1)))
        # Read-your-own-writes, matching the orbax step cache: an ACCEPTED
        # async save counts as existing — it will commit, or its failure
        # surfaces (and the step vanishes from this list) at the next
        # fence. Restore paths fence before reading, so they only ever
        # load committed bytes.
        if self._drain_step is not None and self._drain_error is None:
            steps.add(self._drain_step)
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def reload(self) -> None:
        """Re-scan the directory for checkpoints written by ANOTHER
        process. The orbax manager caches its step list at construction,
        so a polling reader (the evaluator) must reload before
        latest_step() or it never sees new saves; npy scans the
        filesystem every call and needs nothing."""
        if self._ocp_mgr is not None:
            self._ocp_mgr.reload()

    # ---- save -----------------------------------------------------------

    def save(self, step: int, state: Any, wait: bool = False) -> bool:
        """Save ``state`` (TrainState or pytree) at ``step``. Returns True
        if written/accepted (False when this step already exists).

        With the async orbax backend the call returns once device arrays
        are safely on the host; the disk write completes in background.
        A fence on the previous save runs first (at most one write in
        flight), and ``wait=True`` fences this one too — required for the
        last save before process exit. ``wait=True`` fences even when the
        save is rejected as a duplicate: the duplicate may BE the
        in-flight async write (final() re-saving the last periodic step),
        and returning unfenced there would let process exit tear it."""
        if self.readonly:
            raise RuntimeError("CheckpointManager is readonly; refusing to save")
        step = int(step)
        tree = _to_tree(state)
        t0 = time.perf_counter()
        try:
            if self._ocp_mgr is not None:
                # Step check FIRST, against the cached step list: a
                # duplicate-step save (controllers re-drive saves
                # idempotently) must return without paying a completion
                # fence on the PREVIOUS in-flight write. The cache can
                # only miss a step that is itself mid-write — the fence
                # below, required anyway before starting a new write (at
                # most one in flight), makes the re-check authoritative.
                if step in self._ocp_mgr.all_steps():
                    if wait:
                        # The duplicate may be the in-flight write itself
                        # (the step cache counts accepted saves): a waited
                        # call must not return with it still unfenced.
                        self._ocp_mgr.wait_until_finished()
                    return False
                self._ocp_mgr.wait_until_finished()
                if step in self._ocp_mgr.all_steps():
                    return False  # the write just fenced WAS this step
                saved = self._ocp_mgr.save(step, args=self._ocp.args.StandardSave(tree))
                if wait or not self.async_save:
                    self._ocp_mgr.wait_until_finished()
                return bool(saved)
            if self.async_save:
                accepted = self._npy_save_async(step, tree)
                if wait:
                    # Fence even a rejected duplicate (all_steps counts the
                    # accepted in-flight drain): this is the seam final()
                    # relies on — and the fence surfaces any _drain_error.
                    self.wait_until_finished()
                return accepted
            return self._npy_save(step, tree)
        finally:
            self.last_save_stall_s = time.perf_counter() - t0

    def wait_until_finished(self) -> None:
        """Block until any in-flight async save is committed. Re-raises a
        background drain failure ONCE (then clears it): a save that died
        mid-pipeline never committed, and the caller deciding to exit or
        retry must hear about it at the next fence, not from a log line."""
        if self._ocp_mgr is not None:
            self._ocp_mgr.wait_until_finished()
            return
        drain = self._drain
        if drain is not None:
            drain.join()
            self._drain = None
            self._drain_step = None
        err, self._drain_error = self._drain_error, None
        if err is not None:
            raise RuntimeError(
                f"async checkpoint drain failed (step never committed): {err}"
            ) from err

    # -- world-size tagging (r12) -----------------------------------------

    def _writer_world_size(self) -> int:
        """World size stamped into manifests: the declared gang world when
        the caller set one (elastic trainers track the live directive),
        else the jax runtime's process count."""
        if self.world_size:
            return int(self.world_size)
        import jax

        return jax.process_count()

    def _check_restore_world(self, manifest: Dict[str, Any], step: int) -> None:
        """Refuse a silent mixed-world resume: a manifest tagged with a
        writing world size that disagrees with this manager's declared
        world raises unless the caller explicitly declared a resize
        restore (``allow_world_resize`` — the elastic path, which
        re-shards immediately after loading)."""
        saved = int(manifest.get("world_size", 0) or 0)
        expect = int(self.world_size or 0)
        if saved and expect and saved != expect and not self.allow_world_resize:
            raise ValueError(
                f"checkpoint at step {step} was written by a world of "
                f"{saved} but this restore targets a world of {expect}; "
                "a mixed-world resume must be an explicit resize "
                "(allow_world_resize=True), never silent"
            )

    # -- chunked async pipeline (npy backend) -----------------------------

    def _npy_save_async(self, step: int, tree: Any) -> bool:
        """Stage-and-drain save: the caller pays only the device-side
        staging copy; the device→host fetch and disk write overlap the
        caller's subsequent steps. Same step-check-then-fence order as
        the orbax path (a duplicate-step save never fences)."""
        if step in self.all_steps():
            return False
        self.wait_until_finished()  # at most one drain in flight
        if step in self.all_steps():
            return False  # the drain just fenced committed this step
        staged = _stage_tree(tree)  # donation-safe; THIS is the stall
        self._drain_step = step
        self._drain = threading.Thread(
            target=self._npy_drain, args=(step, staged), daemon=True,
            name=f"ckpt-drain-{step}",
        )
        self._drain.start()
        return True

    def _npy_drain(self, step: int, staged: Any) -> None:
        """Background half of the async save. Durability per phase:
        nothing before the final rename is discoverable (tmp dir name is
        dot-prefixed and latest_checkpoint_step requires the manifest), so
        a crash at ANY point here is an orphan sweep, not a torn resume
        point. Each staged device buffer is released as soon as its leaf
        reaches the host — peak staging memory decays during the drain."""
        import jax
        import numpy as np

        tmp = os.path.join(self.directory, f".tmp_step_{step}_{os.getpid()}")
        try:
            final = os.path.join(self.directory, f"step_{step}")
            shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp)
            leaves_with_path = jax.tree_util.tree_flatten_with_path(staged)[0]
            manifest: Dict[str, Any] = {
                "step": step,
                "world_size": self._writer_world_size(),
                "leaves": [],
            }
            for i, (path, leaf) in enumerate(leaves_with_path):
                if self._fault_hook is not None:
                    self._fault_hook("leaf", step)
                arr = np.asarray(leaf)  # device -> host, one leaf at a time
                _write_npy_chunked(
                    os.path.join(tmp, f"leaf_{i}.npy"), arr, self.chunk_bytes
                )
                if hasattr(leaf, "delete"):
                    try:
                        leaf.delete()  # release the staging copy early
                    except Exception:  # noqa: BLE001 — freeing is advisory
                        pass
                manifest["leaves"].append(
                    {
                        "path": jax.tree_util.keystr(path),
                        "index": i,
                        "shape": list(arr.shape),
                        "dtype": str(arr.dtype),
                    }
                )
            if self._fault_hook is not None:
                self._fault_hook("manifest", step)
            # Commit marker, fsync'd: the manifest is what makes the step
            # discoverable — it must be durable BEFORE the rename
            # publishes the directory.
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            if self._fault_hook is not None:
                self._fault_hook("commit", step)
            try:
                os.rename(tmp, final)  # THE commit
            except OSError:
                shutil.rmtree(tmp, ignore_errors=True)
                return  # lost a same-step race; theirs is complete
            self._npy_prune()
            self._fire_on_commit(step, final)
        except BaseException as exc:  # noqa: BLE001 — surfaced at next fence
            # Remove the partial tmp dir NOW: the constructor sweep skips
            # our own pid, and without this each distinct-step drain
            # failure would pin a partially-written dir for the process
            # lifetime — worsening exactly the disk pressure that likely
            # caused the failure. (No-op when the rename already landed.)
            shutil.rmtree(tmp, ignore_errors=True)
            self._drain_error = exc
            log.warning("async checkpoint drain for step %d failed: %s", step, exc)

    def _fire_on_commit(self, step: int, step_dir: str) -> None:
        if self.on_commit is None:
            return
        try:
            self.on_commit(step, step_dir)
        except Exception:  # noqa: BLE001 — peer publish is best-effort
            log.exception("on_commit hook failed for step %d", step)

    def _npy_save(self, step: int, tree: Any) -> bool:
        import jax
        import numpy as np

        if jax.process_count() > 1:
            # np.asarray on non-fully-addressable shards fails anyway, and
            # N processes racing on one tmp dir would corrupt the rename;
            # multi-host saving is what the orbax backend is for.
            raise RuntimeError(
                "npy checkpoint backend is single-process only "
                f"(process_count={jax.process_count()}); use backend='orbax'"
            )
        final = os.path.join(self.directory, f"step_{step}")
        if os.path.exists(final):
            return False
        tmp = os.path.join(self.directory, f".tmp_step_{step}_{os.getpid()}")
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        leaves_with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
        manifest: Dict[str, Any] = {
            "step": step,
            "world_size": self._writer_world_size(),
            "leaves": [],
        }
        for i, (path, leaf) in enumerate(leaves_with_path):
            arr = np.asarray(leaf)
            np.save(os.path.join(tmp, f"leaf_{i}.npy"), arr)
            manifest["leaves"].append(
                {
                    "path": jax.tree_util.keystr(path),
                    "index": i,
                    "shape": list(arr.shape),
                    "dtype": str(arr.dtype),
                }
            )
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        try:
            os.rename(tmp, final)
        except OSError:
            # lost a same-step race to another writer; theirs is complete
            shutil.rmtree(tmp, ignore_errors=True)
            return False
        self._npy_prune()
        self._fire_on_commit(step, final)
        return True

    def _npy_prune(self) -> None:
        steps = self.all_steps()
        for s in steps[: max(0, len(steps) - self.keep)]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s}"), ignore_errors=True)

    # ---- restore --------------------------------------------------------

    def restore(self, template: Any, step: Optional[int] = None) -> Any:
        """Restore the checkpoint at ``step`` (default: latest) onto the
        shapes/dtypes/shardings of ``template`` (a TrainState or pytree of
        arrays / ShapeDtypeStructs). Raises FileNotFoundError if none."""
        self.wait_until_finished()  # read-your-own-writes under async save
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        tmpl_tree = _to_tree(template)
        if self._ocp_mgr is not None:
            abstract = _abstractify(tmpl_tree)
            restored = self._ocp_mgr.restore(
                int(step), args=self._ocp.args.StandardRestore(abstract)
            )
            return _from_tree(restored, template)
        return _from_tree(self._npy_restore(int(step), tmpl_tree), template)

    def restore_params(self, template_params: Any, step: Optional[int] = None) -> Any:
        """Restore ONLY the params subtree of a TrainState checkpoint —
        what an evaluator needs. Skips the optimizer moments (2 extra
        param-sized trees under adamw), so restore I/O and device memory
        are ~1/3 of a full-state restore."""
        return self.restore_subtrees(
            {"params": template_params}, step=step
        )["params"]

    def restore_subtrees(
        self, templates: Dict[str, Any], step: Optional[int] = None
    ) -> Dict[str, Any]:
        """Restore a subset of a TrainState checkpoint's top-level items
        by name ({"params": tmpl} — or {"params": ..., "extra": ...},
        what a BatchNorm-model evaluator needs: the BN running stats live
        in ``extra`` and eval-mode inference is wrong without them, r4).
        Skips everything not named (the optimizer moments above all)."""
        self.wait_until_finished()  # the ephemeral manager below reads the
        # directory — an in-flight async write would present a torn item
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        wrapped = dict(templates)
        if self._ocp_mgr is not None:
            abstract = _abstractify(wrapped)
            # Ephemeral manager: an instance that has done a StandardSave
            # pins its handler registry to the Standard handler and then
            # rejects PyTreeRestore args (and vice versa) — a fresh
            # instance resolves the handler from the restore args.
            mgr = self._ocp.CheckpointManager(self.directory)
            # explicit restore_args: without them PyTreeRestore lays
            # arrays out with the sharding recorded at save time, not the
            # template's (evaluator mesh != trainer mesh is the normal
            # case)
            restore_args = self._ocp.checkpoint_utils.construct_restore_args(
                abstract
            )
            try:
                restored = mgr.restore(
                    int(step),
                    args=self._ocp.args.PyTreeRestore(
                        item=abstract,
                        restore_args=restore_args,
                        partial_restore=True,
                    ),
                )
            finally:
                mgr.close()
            return {k: restored[k] for k in templates}
        out = self._npy_restore(int(step), wrapped, subtrees=tuple(templates))
        return {k: out[k] for k in templates}

    def _npy_restore(self, step: int, tmpl_tree: Any,
                     subtrees: Optional[tuple] = None) -> Any:
        import jax
        import numpy as np

        d = os.path.join(self.directory, f"step_{step}")
        manifest_path = os.path.join(d, "manifest.json")
        if not os.path.exists(manifest_path):
            raise FileNotFoundError(f"no checkpoint at step {step} under {self.directory}")
        with open(manifest_path) as f:
            manifest = json.load(f)
        self._check_restore_world(manifest, step)
        records = manifest["leaves"]
        if subtrees is not None:
            # Partial restore: only the saved leaves under these top-level
            # keys (their leaf_{index}.npy files carry the full-tree index).
            prefixes = tuple(f"['{k}']" for k in subtrees)
            records = [r for r in records if r["path"].startswith(prefixes)]
        paths, treedef = jax.tree_util.tree_flatten_with_path(tmpl_tree)
        saved_paths = [leaf["path"] for leaf in records]
        tmpl_paths = [jax.tree_util.keystr(p) for p, _ in paths]
        if saved_paths != tmpl_paths:
            # Pairing saved leaf files with template leaves is by
            # flatten order; a structure drift (optimizer/model config
            # changed between save and restore) would silently load
            # weights into the wrong slots.
            missing = set(saved_paths) ^ set(tmpl_paths)
            raise ValueError(
                f"checkpoint tree at step {step} does not match restore "
                f"template (differing leaves: {sorted(missing)[:6] or 'order'})"
            )
        arrays = []
        for (path, tmpl_leaf), rec in zip(paths, records):
            arr = np.load(os.path.join(d, f"leaf_{rec['index']}.npy"))
            if "dtype" in rec and arr.dtype != np.dtype(rec["dtype"]):
                # Extension dtypes (bfloat16, fp8) round-trip through .npy
                # as raw void bytes ('V2'); the manifest carries the real
                # dtype — a same-itemsize view restores it losslessly.
                arr = arr.view(np.dtype(rec["dtype"]))
            if "shape" in rec:
                # Path equality alone misses same-structure config drift
                # (d_model or dtype changed between save and restore) —
                # fail loudly instead of device_put-ing wrong arrays.
                tmpl_shape = tuple(getattr(tmpl_leaf, "shape", np.shape(tmpl_leaf)))
                tmpl_dtype = np.dtype(
                    getattr(tmpl_leaf, "dtype", None) or np.asarray(tmpl_leaf).dtype
                )
                if tuple(rec["shape"]) != tmpl_shape or np.dtype(rec["dtype"]) != tmpl_dtype:
                    raise ValueError(
                        f"checkpoint leaf {rec['path']} at step {step} is "
                        f"{rec['dtype']}{tuple(rec['shape'])} but the restore "
                        f"template expects {tmpl_dtype}{tmpl_shape} — model/"
                        "optimizer config changed between save and restore"
                    )
            sharding = getattr(tmpl_leaf, "sharding", None)
            if sharding is not None:
                arrays.append(jax.device_put(arr, sharding))
            else:
                arrays.append(jax.numpy.asarray(arr))
        return jax.tree_util.tree_unflatten(treedef, arrays)

    def close(self) -> None:
        if self._ocp_mgr is not None:
            self._ocp_mgr.wait_until_finished()
            self._ocp_mgr.close()
            return
        self.wait_until_finished()  # fence the npy drain before exit


class WorkloadCheckpointer:
    """The one checkpoint wiring shared by operator-launchable workloads.

    Config keys (from the TPUJob workload dict): ``checkpoint_dir``,
    ``checkpoint_every`` (steps between saves, 0 = final only),
    ``checkpoint_keep``, ``checkpoint_async`` (default on),
    ``checkpoint_backend`` (auto|npy|orbax). Tracks the
    step count on the HOST (mirroring ``state.step``) so the hot loop
    never forces a device sync on non-saving steps, and saves are keyed
    without fetching the step scalar. Disabled (all methods no-ops) when
    ``checkpoint_dir`` is unset.

    With a :class:`~tf_operator_tpu.rendezvous.context.JobContext` passed
    as ``ctx``, the checkpointer also speaks the peer warm-restore
    protocol (rendezvous/statechannel.py): every committed step is pushed
    to this host's shard depot (``TPUJOB_PEER_DEPOT``), restore consults
    the controller-provided peer depots (``TPUJOB_RESTORE_PEERS``) before
    disk, and save-stall / restore-source spans land in the job trace.
    """

    def __init__(self, workload: Dict[str, Any], ctx=None) -> None:
        self.ctx = ctx
        self.manager: Optional[CheckpointManager] = None
        if workload.get("checkpoint_dir"):
            self.manager = CheckpointManager(
                workload["checkpoint_dir"],
                keep=int(workload.get("checkpoint_keep", 3)),
                backend=str(workload.get("checkpoint_backend", "auto")),
                async_save=bool(workload.get("checkpoint_async", True)),
                on_commit=self._push_to_depot,
                world_size=getattr(ctx, "num_processes", None),
                allow_world_resize=bool(workload.get("elastic")),
            )
        self.every = int(workload.get("checkpoint_every", 0))
        self._step = 0
        self.start_step = 0
        # Checkpoint-cadence directive (r16): last applied epoch + poll
        # throttle. The autopilot retunes `every` live through
        # status.checkpoint_cadence_directive; the chief applies it at a
        # step boundary via poll_cadence_directive().
        self._cadence_epoch = 0
        self._cadence_poll_s = float(workload.get("cadence_poll_s", 2.0))
        self._cadence_last_poll = 0.0
        # Per-accepted-save caller stall (seconds) — the overlap receipt.
        self.save_stalls: List[float] = []
        # "peer" | "disk" after a warm restore; "" cold / not restored.
        self.restore_source = ""
        # Losses of this run's first LOSS_TRACE_STEPS dispatches, kept as
        # device scalars (no sync in the step loop) — loss_trace() fetches
        # them after the loop for the worker's run report.
        self._loss_trace: List[Any] = []

    # -- peer warm-restore protocol (rendezvous/statechannel.py) ----------

    def _push_to_depot(self, step: int, step_dir: str) -> None:
        """on_commit hook: publish a COMMITTED step to this host's shard
        depot so it survives the gang teardown a restart implies. Runs on
        the drain thread; best-effort by contract."""
        if self.ctx is None or not getattr(self.ctx, "peer_depot", ""):
            return
        from tf_operator_tpu.rendezvous.statechannel import DepotClient

        DepotClient().push_step(
            self.ctx.peer_depot, self.ctx.namespace, self.ctx.job_name,
            step, step_dir,
        )

    def prefetch_from_peers(self) -> str:
        """Restore-source decision (docs/design.md §4.9): if a live peer
        depot holds a committed step at least as new as the store's,
        materialize it as a committed step dir under the checkpoint
        directory — the ordinary disk-restore path then loads it
        bit-identically. A tie deliberately goes to the PEER: at flagship
        scale ``checkpoint_dir`` is slow bulk storage, and skipping its
        read even for an already-known step is the protocol's entire
        payoff (when the step is already materialized locally the fetch
        is a no-op). Any peer failure (dead mid-transfer, integrity
        mismatch) excludes that peer and re-runs the source decision over
        the survivors — the NEXT live peer holding an eligible step is
        tried before disk, the fallback order the statechannel module
        promises. Returns the source the subsequent restore will read
        from."""
        if self.manager is None or self.ctx is None:
            return "disk"
        peers = list(getattr(self.ctx, "restore_peers", []) or [])
        if not peers:
            return "disk"
        from tf_operator_tpu.rendezvous.statechannel import (
            DepotClient,
            choose_restore_source,
        )

        disk_step = self.manager.latest_step() or 0
        client = DepotClient()
        remaining = list(peers)
        while remaining:
            source, url, step = choose_restore_source(
                remaining, self.ctx.namespace, self.ctx.job_name, disk_step,
                client=client,
            )
            if source != "peer":
                return "disk"
            fetched = client.fetch_step(
                url, self.ctx.namespace, self.ctx.job_name, step,
                self.manager.directory,
            )
            if fetched is not None:
                log.info("warm restore: pulled step %d from peer %s", step, url)
                return "peer"
            remaining = [u for u in remaining if u != url]
            log.warning(
                "peer restore of step %d from %s failed; %d peer(s) left "
                "before disk fallback (step %d)",
                step, url, len(remaining), disk_step,
            )
        return "disk"

    def restore_or_init(self, trainer, key):
        """Resume from the best warm source (peer depot, then latest disk
        checkpoint) or fresh-init; primes the host-side step mirror and
        records the restore-source span."""
        t0 = time.time()
        self.restore_source = self.prefetch_from_peers()
        state = trainer.restore_or_init(key, self.manager)
        self._step = self.start_step = int(state.step)
        if self.start_step:
            log.info(
                "resumed from checkpoint at step %d (source=%s)",
                self.start_step, self.restore_source,
            )
            if self.ctx is not None:
                self.ctx.record_restore(
                    self.restore_source, self.start_step, t0, time.time()
                )
        return state

    def resume_step(self) -> int:
        """Latest checkpointed step (0 if none) WITHOUT restoring — lets
        stream-data workloads skip already-consumed batches (DeviceLoader
        ``skip``) before entering run_loop."""
        if self.manager is not None:
            return self.manager.latest_step() or 0
        return 0

    def is_complete(self, steps: int) -> bool:
        """True when a previous run already trained past the step budget
        (the +1 accounts for the warmup step, which also trains). Peeks at
        the manifest only — call BEFORE restore_or_init so an
        already-complete job skips the full (possibly many-GB) restore."""
        if self.manager is not None:
            latest = self.manager.latest_step()
            if latest is not None:
                return latest >= steps + 1
        return self.start_step >= steps + 1

    def timed_steps(self, steps: int) -> int:
        """How many timed-loop iterations remain; the telemetry divisor.
        0 means throughput numbers would be meaningless — don't log them."""
        return max(0, steps - self.start_step)

    def advance(self, state, loss=None) -> None:
        """Call once per trainer.step; saves when a periodic save is due.

        Pass the step's loss so a diverged state is never checkpointed —
        saving NaN params would make them the latest checkpoint and poison
        every restart's resume into a permanent crash loop. The finiteness
        check fetches the loss to host, but only on saving steps, so the
        hot loop stays sync-free."""
        import math

        self._step += 1
        if self.manager is not None and self.every and self._step % self.every == 0:
            if loss is not None and not math.isfinite(float(loss)):
                raise AssertionError(
                    f"non-finite loss {float(loss)} at step {self._step}; "
                    "refusing to checkpoint a diverged state"
                )
            if self.manager.save(self._step, state):
                self._note_save_stall(self._step)

    LOSS_TRACE_STEPS = 32

    def _trace_loss(self, loss) -> None:
        if len(self._loss_trace) < self.LOSS_TRACE_STEPS:
            self._loss_trace.append(loss)

    def loss_trace(self) -> List[float]:
        """The first steps' losses as floats — call after run_loop, it
        syncs."""
        return [float(x) for x in self._loss_trace]

    def _note_save_stall(self, step: int) -> None:
        """Record how long the step loop was actually blocked by the save
        just accepted — with the async pipeline this is the staging copy,
        not the device→host fetch or the disk write. Span lands in the
        job trace (the overlap-window evidence `tpujob trace` shows)."""
        import time as _time

        stall = self.manager.last_save_stall_s
        self.save_stalls.append(stall)
        if self.ctx is not None:
            now = _time.time()
            self.ctx.record_save_stall(step, now - stall, now)

    def poll_cadence_directive(self, step: Optional[int] = None) -> bool:
        """Apply a pending checkpoint-cadence directive (r16) at a step
        boundary. The autopilot publishes {"epoch", "checkpoint_every"}
        into the job status; the chief calls this between steps, applies
        each epoch exactly once (updating ``self.every`` — ``advance``
        reads it every step, so the new interval takes effect
        immediately), and acks ``applied_epoch``/``applied_step``
        back. Throttled to one API read per ``cadence_poll_s`` seconds;
        best-effort by contract (an unreachable API changes nothing).
        Returns True when a new epoch was applied this call."""
        if self.ctx is None:
            return False
        if getattr(self.ctx, "process_id", 0) != 0:
            return False  # the chief owns cadence, as it owns the saves
        poll = getattr(self.ctx, "poll_checkpoint_cadence_directive", None)
        if poll is None:
            return False
        import time as _time

        now = _time.time()
        if now - self._cadence_last_poll < self._cadence_poll_s:
            return False
        self._cadence_last_poll = now
        directive = poll() or {}
        epoch = int(directive.get("epoch", 0))
        if epoch <= self._cadence_epoch:
            return False
        self._cadence_epoch = epoch
        every = int(directive.get("checkpoint_every", 0))
        if every > 0 and every != self.every:
            log.info(
                "checkpoint cadence directive epoch %d: every %d -> %d steps",
                epoch, self.every, every,
            )
            self.every = every
        applied_step = self._step if step is None else int(step)
        self.ctx.ack_checkpoint_cadence(epoch, applied_step)
        return True

    def final(self, state) -> None:
        """Final save — call AFTER any throughput timing is read, so the
        write never pollutes step-time/MFU telemetry. Fenced (wait=True):
        the process may exit right after, and an unfenced async write
        would tear the checkpoint."""
        if self.manager is not None:
            if self.manager.save(self._step, state, wait=True):
                self._note_save_stall(self._step)

    def run_loop(self, trainer, key, batch, steps: int, on_step=None):
        """The one warmup+timed train loop shared by workloads.

        restore-or-init → warmup step (compile boundary) → ``steps -
        start_step`` timed steps with periodic NaN-gated saves → finiteness
        guard → final save. Returns ``(state, loss, timed, step_s)`` where
        ``timed`` counts only the steps inside the timed region (warmup
        trains but is excluded) and ``step_s`` is None when no timed steps
        remained. Callers must check :meth:`is_complete` first.
        ``on_step(global_step)`` fires after every step — the
        fault-injection / progress-reporting seam.

        ``batch`` is either one fixed batch (re-trained every step: the
        benchmarking shape) or a batch *iterator* — e.g. a
        ``train.data.DeviceLoader`` — pulled once per step. All batches
        must share one shape/dtype structure (jit compiles once). On
        restart-based recovery an iterator starts over unless the caller
        fast-forwards it (``DeviceLoader(skip=resume_step())``) — without
        that, a resumed run re-trains the stream's leading batches."""
        import math
        import time

        import jax

        is_iter = hasattr(batch, "__next__")
        pull = (lambda: next(batch)) if is_iter else (lambda: batch)

        def run_step(state):
            state, m = trainer.step(state, pull())
            self._trace_loss(m["loss"])
            self.advance(state, loss=m["loss"])
            return state, m

        state = self.restore_or_init(trainer, key)
        remaining = self.timed_steps(steps)
        state, m = run_step(state)  # warmup: the compile boundary
        if on_step is not None:
            on_step(self._step)
        jax.block_until_ready(m["loss"])
        timed = remaining
        t0 = time.perf_counter()
        for _ in range(remaining):
            state, m = run_step(state)
            self.poll_cadence_directive()
            if on_step is not None:
                on_step(self._step)
        loss = float(m["loss"])
        step_s = (time.perf_counter() - t0) / timed if timed else None
        if not math.isfinite(loss):
            # deliberately NOT checkpointed: saving a diverged state would
            # poison every restart's resume
            raise AssertionError(f"non-finite loss {loss}")
        self.final(state)
        return state, loss, timed, step_s


def _stage_tree(tree: Any) -> Any:
    """Donation-safe staging snapshot of a state pytree.

    The trainer's step is jitted with ``donate_argnums`` over params /
    opt_state / extra — the moment the NEXT step runs, the buffers a save
    captured may be reused. Staging makes a device-side copy of every
    array leaf (an HBM→HBM copy, bounded by device memory bandwidth — the
    deliberate, small stall) and blocks until the copies materialize; the
    background drain then owns the copies outright and the step loop may
    donate the originals immediately."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def one(leaf):
        if isinstance(leaf, jax.Array):
            return jnp.copy(leaf)
        return np.array(leaf, copy=True)

    staged = jax.tree_util.tree_map(one, tree)
    jax.block_until_ready(staged)
    return staged


def _write_npy_chunked(path: str, arr, chunk_bytes: int) -> None:
    """np.save-compatible .npy writer that streams the array body in
    fixed-byte quanta instead of one write syscall — the disk half of the
    chunked pipeline (a multi-GB leaf never pins one giant dirty buffer,
    and the drain yields to the OS between quanta)."""
    import numpy as np

    arr = np.asarray(arr)
    if not arr.flags["C_CONTIGUOUS"]:
        # NOT ascontiguousarray unconditionally: it promotes 0-d arrays
        # to shape (1,), which would corrupt the header (scalars like
        # TrainState.step must round-trip 0-d).
        arr = np.ascontiguousarray(arr)
    with open(path, "wb") as f:
        np.lib.format.write_array_header_1_0(
            f, np.lib.format.header_data_from_array_1_0(arr)
        )
        if arr.ndim == 0:
            f.write(arr.tobytes())  # a scalar is one (tiny) quantum
            return
        try:
            mv = memoryview(arr).cast("B")
        except (TypeError, ValueError):
            # Extension dtypes (bfloat16, fp8) have no buffer protocol;
            # a uint8 view of the contiguous body streams the same bytes.
            mv = memoryview(arr.view(np.uint8)).cast("B")
        for off in range(0, len(mv), chunk_bytes):
            f.write(mv[off : off + chunk_bytes])


def _abstractify(tree: Any) -> Any:
    """Concrete/abstract array pytree -> ShapeDtypeStructs carrying
    shardings (what StandardRestore needs to lay out device arrays)."""
    import jax

    def one(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=getattr(leaf, "sharding", None)
        )

    return jax.tree_util.tree_map(one, tree)
