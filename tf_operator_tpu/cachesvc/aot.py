"""AOT-at-admission: overlap compilation with the scheduling wait.

The second leg of the r11 TTFS attack. The moment the fleet scheduler
decides a job's fate — admitted (gang about to be created) or parked
(QUEUED behind quota/capacity) — the reconciler hands the job to this
compiler. A worker thread registers a compile *intent* with the
compile-cache service (fleet-wide single-flight: any gang member that
races ahead gets 202/Retry-After instead of duplicating the compile),
compiles the workload's step function, and publishes the executable.
By the time the gang finishes placement + spawn + rendezvous and
reaches ``compile_cache.enable()``, the cache is warm — the compile
cost paid during a wait that was happening anyway.

Workload contract (``spec.workload`` JSON, all optional):

- ``{"aot": {"key": "<key material>", "compile_ms": 1500}}`` — modeled
  mode: the executable is a deterministic artifact derived from the key
  material, produced after a modeled ``compile_ms`` delay. The workload
  side retrieves it with ``compile_cache.cached_compile(key_material,
  fn)`` — same key derivation (sha256 of the material), so the
  admission-time publish is a remote hit at enable() time. This is the
  bench/CI mode: real intents, transport, and integrity machinery;
  modeled compile cost (no chips in CI — the r8 ``--disk-restore-delay``
  precedent).
- ``{"aot": {"topology": "v5e:2x4"}}`` — topology mode: spawn
  ``tools/hloprobe.py``'s AOT machinery in a subprocess with
  ``JAX_COMPILATION_CACHE_DIR`` pointed at a scratch dir, then publish
  every ``*-cache`` entry that landed, under jax's own keys. Requires
  the TPU compiler (libtpu); degrades to a logged skip without it —
  never a job failure.

Dedup: one kick per (job uid, key). A re-sync of a parked job does not
re-compile; a gang restart of the same job finds the entry already
published (the service is first-writer-wins).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, Optional

from tf_operator_tpu.cachesvc.client import CacheClient

log = logging.getLogger("tpujob.cachesvc.aot")

# Modeled-mode executables are this many bytes: big enough that a
# corrupted transfer cannot accidentally verify, small enough to be free.
_MODELED_PAYLOAD_BYTES = 4096


def modeled_payload(key_material: str, size: int = _MODELED_PAYLOAD_BYTES) -> bytes:
    """The deterministic modeled 'executable' for a key: both the
    admission-time compiler and the workload's local fallback produce
    byte-identical artifacts, so integrity verification is end-to-end
    real even though the compile itself is modeled."""
    seed = hashlib.sha256(key_material.encode()).digest()
    out = bytearray()
    block = seed
    while len(out) < size:
        out.extend(block)
        block = hashlib.sha256(block).digest()
    return bytes(out[:size])


def aot_spec_of(workload) -> Optional[Dict]:
    """Extract the ``aot`` section from a job's spec.workload (the dict
    itself, or its ENV_WORKLOAD JSON form); None when absent/unparseable
    (most jobs: nothing to pre-compile)."""
    if not workload:
        return None
    if isinstance(workload, str):
        try:
            spec = json.loads(workload)
        except ValueError:
            return None
    else:
        spec = workload
    aot = spec.get("aot") if isinstance(spec, dict) else None
    return aot if isinstance(aot, dict) and ("key" in aot or "topology" in aot) else None


class AOTCompiler:
    """Admission-time compiler pool. ``kick()`` is called from the
    reconciler's sync path and must be O(µs): it only enqueues; worker
    threads do the announce/compile/publish. Every failure is a logged
    degradation (the gang compiles at first step, exactly the pre-r11
    behavior), never an error surfaced to the job.
    """

    def __init__(
        self,
        cache_url: str,
        workers: int = 2,
        on_done: Optional[Callable[..., None]] = None,
    ) -> None:
        """``on_done(namespace, job_name, trace_id, key, mode, start, end,
        ok)`` — the reconciler wires this to its span recorder so the
        aot-compile span lands in the job timeline."""
        self.client = CacheClient(cache_url)
        self.on_done = on_done
        self._kicked: set = set()  # (job_uid, key) — one compile per pair
        self._lock = threading.Lock()
        self._queue: list = []
        self._wake = threading.Condition(self._lock)
        self._stopping = False
        self.stats = {"kicked": 0, "published": 0, "skipped": 0, "failed": 0}
        self._threads = [
            threading.Thread(target=self._worker, daemon=True, name=f"aot-{i}")
            for i in range(max(1, workers))
        ]
        for t in self._threads:
            t.start()

    # -- reconciler-facing -------------------------------------------------

    def kick(self, namespace: str, job_name: str, job_uid: str,
             workload) -> bool:
        """Queue an admission-time compile for the job's workload. Returns
        True when a new compile was scheduled (False: nothing declared, or
        already kicked for this job)."""
        aot = aot_spec_of(workload)
        if aot is None:
            return False
        key = self._cache_key(aot)
        with self._lock:
            if self._stopping or (job_uid, key) in self._kicked:
                return False
            self._kicked.add((job_uid, key))
            self.stats["kicked"] += 1
            self._queue.append((namespace, job_name, job_uid, aot))
            self._wake.notify()
        return True

    def stop(self) -> None:
        with self._lock:
            self._stopping = True
            self._wake.notify_all()

    # -- workers -----------------------------------------------------------

    @staticmethod
    def _cache_key(aot: Dict) -> str:
        if "key" in aot:
            return hashlib.sha256(str(aot["key"]).encode()).hexdigest()
        return f"topology:{aot.get('topology', '')}"

    def _worker(self) -> None:
        while True:
            with self._lock:
                while not self._queue and not self._stopping:
                    self._wake.wait()
                if self._stopping and not self._queue:
                    return
                namespace, job_name, job_uid, aot = self._queue.pop(0)
            start = time.time()
            mode = "modeled" if "key" in aot else "topology"
            ok = False
            key = self._cache_key(aot)
            try:
                if mode == "modeled":
                    ok = self._compile_modeled(aot)
                else:
                    ok = self._compile_topology(aot)
            except Exception:  # noqa: BLE001 — degradation, never job failure
                log.exception("aot compile for %s/%s failed", namespace, job_name)
            self.stats["published" if ok else "failed"] += 1
            if self.on_done is not None:
                try:
                    self.on_done(namespace, job_name, job_uid, key, mode,
                                 start, time.time(), ok)
                except Exception:  # noqa: BLE001
                    log.exception("aot on_done callback failed")

    def _compile_modeled(self, aot: Dict) -> bool:
        key_material = str(aot["key"])
        key = hashlib.sha256(key_material.encode()).hexdigest()
        # Repeat submission of an already-compiled workload: the entry is
        # there, the cache is warm — nothing to do (and no modeled cost
        # to pay). fetch(wait_s=0) is a cheap existence probe.
        if self.client.fetch(key) is not None:
            return True
        # Single-flight: the intent makes racing gang members wait the
        # few hundred ms for this publish instead of recompiling.
        self.client.announce(key)
        delay = max(0.0, float(aot.get("compile_ms", 0)) / 1000.0)
        if delay:
            time.sleep(delay)  # the modeled XLA compile cost
        return self.client.publish(key, modeled_payload(key_material))

    def _compile_topology(self, aot: Dict) -> bool:
        """Real AOT against a virtual TPU topology (no chips needed, but
        the TPU *compiler* — libtpu — must be importable). Runs hloprobe
        in a subprocess that writes the one persistent compilation cache
        every process of this checkout uses (``compile_cache.cache_dir()``
        — the environment's directory when it names one), then publishes
        every executable the run ADDED under jax's own cache keys."""
        from tf_operator_tpu.train import compile_cache

        topology = str(aot.get("topology", ""))
        self.client.announce(self._cache_key(aot))
        cache = compile_cache.cache_dir()
        os.makedirs(cache, exist_ok=True)
        before = set(os.listdir(cache))
        env = dict(os.environ)
        env[compile_cache.ENV_DIR] = cache
        env.setdefault("JAX_PLATFORMS", "cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.hloprobe",
             "--topology", topology],
            env=env, capture_output=True, timeout=float(
                aot.get("timeout_s", 600)),
            check=False,
        )
        if proc.returncode != 0:
            log.warning(
                "aot topology compile for %s failed (hloprobe rc=%d): %s",
                topology, proc.returncode,
                proc.stderr.decode(errors="replace")[-400:],
            )
            self.stats["skipped"] += 1
            return False
        published = 0
        for fname in sorted(set(os.listdir(cache)) - before):
            if not fname.endswith("-cache"):
                continue
            with open(os.path.join(cache, fname), "rb") as f:
                data = f.read()
            if self.client.publish(fname[: -len("-cache")], data):
                published += 1
        return published > 0
