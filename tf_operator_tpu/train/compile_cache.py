"""Persistent XLA compilation cache: submit→first-step latency control.

The north-star latency metric (BASELINE.json; SURVEY.md §7 hard part d) is
submit→first-step, and on TPU it is dominated by XLA compilation (~20-40 s
for the bench models) — a cost the reference never had to manage because it
ran TF's pre-compiled kernels. The TPU-native answer is JAX's persistent
compilation cache: executables are keyed by (HLO, compile options, backend)
and reloaded from disk, so

- a gang restart (the framework's recovery path — restart-based recovery,
  SURVEY.md §5) relaunches the training program at near-interactive speed,
- repeat submissions of the same workload skip straight to step 1.

r11 adds the fleet tier: when the controller stamps ``TPUJOB_COMPILE_CACHE``
(cachesvc/), the hardened get/put pair becomes read-through/write-back
against the shared service — a local miss fetches the sha256-verified
executable from the fleet before falling back to compilation, and every
local compile publishes asynchronously (off the step path). A dead or
unreachable service degrades to the PR 10 local-only path; the degradation
is recorded in ``stats()`` and surfaced as a span attribute by
``JobContext.mark_first_step``, never as a job failure.

``enable()`` is called by the rendezvous harness before user ``train_fn``
runs (every operator-launched process gets it). Safe to call multiple
times.

Where the cache lives is decided from OUTSIDE the program, in one place
(``cache_dir()``): ``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed
path inside the checkout (``<repo>/.cache/xla``, git-ignored). The path is
part of what makes a second run hit, so nothing in the tree passes its
own directory, and nothing lands under the user's home or a temp name.
"""

from __future__ import annotations

import hashlib
import logging
import os
import threading
from typing import Callable, Dict, Optional

log = logging.getLogger("tpujob.compile_cache")

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".cache", "xla",
)
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_DISABLE = "TPUJOB_NO_COMPILE_CACHE"
ENV_FORCE = "TPUJOB_FORCE_COMPILE_CACHE"
# Remote-tier wait budget for a key whose compile intent is live at the
# service (AOT-at-admission in flight): how long a worker polls before
# giving up and compiling locally.
ENV_REMOTE_WAIT = "TPUJOB_COMPILE_CACHE_WAIT_S"

_DIGEST_SUFFIX = "-sha256"
_LOCK_STALE_S = 60.0
_hardened = False

# Remote tier (cachesvc/): configured by enable() from the controller-
# stamped TPUJOB_COMPILE_CACHE env, or explicitly via configure_remote().
_remote = None
_remote_lock = threading.Lock()
_stats = {
    "local_hits": 0, "remote_hits": 0, "misses": 0,
    "local_puts": 0, "remote_puts": 0,
}


def cache_dir() -> str:
    """The one directory both cache tiers write: the environment's when
    it names one, else the fixed in-checkout path."""
    return os.environ.get(ENV_DIR) or DEFAULT_CACHE_DIR


def _digest_path(cache_path):
    return cache_path.with_name(cache_path.name + _DIGEST_SUFFIX)


def publish_pair(dir_path, key: str, val: bytes) -> bool:
    """Atomically publish the ``{key}-cache`` payload and its sha256
    sidecar as a UNIT under ``dir_path``.

    The r10 version wrote the sidecar with a bare ``write_bytes()`` at
    its final name BEFORE the payload landed — two processes racing the
    same key could interleave (A's sidecar overwritten by B's, then A's
    payload published: a mismatched pair every get() purges), and a
    reader could even observe a partially-written sidecar. Now both
    files are written to writer-unique temp names and published with
    ``os.replace`` — sidecar strictly first, so no instant ever shows a
    payload ahead of its matching digest — and the publish sequence is
    serialized by an O_EXCL lock file, so concurrent writers cannot
    interleave their replaces: the winner publishes a matched pair, the
    losers skip (the entry exists). A stale lock (holder died mid-
    publish) is broken after ``_LOCK_STALE_S``; the half-published state
    it can leave (sidecar without payload, or a mismatched pair) is
    exactly what get()'s verify-and-purge already self-heals.

    Returns True when this writer published (or the entry already
    existed); False when the publish was skipped (lock contention) or
    failed — callers treat False as "not cached", never as an error."""
    import pathlib

    dir_path = pathlib.Path(dir_path)
    cache_path = dir_path / f"{key}-cache"
    if cache_path.exists():
        return True
    lock = dir_path / f"{key}-cache.lock"
    try:
        fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
    except FileExistsError:
        # Another writer is publishing this key right now — unless it
        # died and left the lock behind: break stale locks once.
        try:
            import time as _time

            if _time.time() - lock.stat().st_mtime <= _LOCK_STALE_S:
                return False
            lock.unlink()
            fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except (OSError, FileExistsError):
            return False
    except OSError:
        return False
    try:
        if cache_path.exists():
            return True  # the previous lock holder finished first
        suffix = f".tmp{os.getpid()}-{threading.get_ident()}"
        digest_tmp = dir_path / f"{key}-cache{_DIGEST_SUFFIX}{suffix}"
        payload_tmp = dir_path / f"{key}-cache{suffix}"
        digest_tmp.write_bytes(hashlib.sha256(val).hexdigest().encode())
        payload_tmp.write_bytes(val)
        os.replace(digest_tmp, _digest_path(cache_path))  # digest first...
        os.replace(payload_tmp, cache_path)  # ...payload never ahead of it
        import time as _time

        (dir_path / f"{key}-atime").write_bytes(
            _time.time_ns().to_bytes(8, "little")
        )
        return True
    except OSError:
        return False
    finally:
        os.close(fd)
        try:
            lock.unlink()
        except OSError:
            pass


def configure_remote(url: Optional[str]) -> None:
    """Point the remote tier at a cachesvc URL (None disconnects it).
    ``enable()`` calls this from the controller-stamped env; tests and
    the AOT compiler call it directly."""
    global _remote
    from tf_operator_tpu.cachesvc.client import CacheClient

    with _remote_lock:
        _remote = CacheClient(url) if url else None


def remote_client():
    return _remote


def stats() -> Dict[str, object]:
    """Cache-tier counters for this process, plus the remote endpoint and
    whether it was ever observed dead — the payload of the workload's
    ``compile-cache`` span (JobContext.mark_first_step)."""
    out: Dict[str, object] = dict(_stats)
    client = _remote
    out["remote_url"] = client.url if client else ""
    out["remote_dead"] = bool(client.dead) if client else False
    return out


def _remote_jax_tier_active() -> bool:
    """The shared tier for JAX-PRODUCED executables. cpu-pinned processes
    are excluded UNCONDITIONALLY (not even ENV_FORCE overrides): an
    XLA:CPU executable is built for the compiling host's instruction set
    (see enable()), so one host's entry must never reach another through
    the fleet. force only re-enables the LOCAL cache for machinery
    tests."""
    return _remote is not None and not _cpu_only_platform()


def _remote_wait_s() -> float:
    try:
        return float(os.environ.get(ENV_REMOTE_WAIT, "") or 10.0)
    except ValueError:
        return 10.0


def _publish_async(key: str, val: bytes) -> None:
    """Write-back to the fleet tier off the step path: the put that
    follows a compile must not serialize a network round-trip into the
    step loop."""
    client = _remote
    if client is None:
        return

    def _push():
        if client.publish(key, val):
            _stats["remote_puts"] += 1

    threading.Thread(target=_push, daemon=True, name=f"cc-publish-{key[:12]}").start()


def _harden_cache_io() -> None:
    """Crash-safe + fleet-tiered jax file cache (r10 hardening, r11
    remote tier): jax's ``LRUCache.put`` writes entries with a bare
    ``write_bytes()`` and never overwrites an existing key. A process
    killed mid-write — the operator's preempt path SIGKILLs workers, so
    this is a *routine* event, not a freak one — leaves a truncated blob
    under the final name; every warm-restarted incarnation that hits that
    key then deserializes garbage inside XLA and dies with
    SIGSEGV/SIGABRT, which the restart taxonomy rightly calls permanent.

    The wraps:

    - ``put``: atomic sidecar+payload pair publish (``publish_pair``) —
      a kill at any instant leaves either no entry or a complete one,
      and concurrent writers can no longer interleave a mismatched
      pair — then an async write-back to the fleet tier.
    - ``get``: verify the sidecar before handing bytes to XLA; a
      mismatching or missing sidecar deletes the entry and reports a
      miss (recompile), so pre-existing poison self-heals instead of
      aborting the process. A verified local miss read-throughs the
      fleet tier (sha256-checked again in transfer) and lands the entry
      locally before returning it.

    Private-API patch (``jax._src.lru_cache.LRUCache``; signatures as
    of jax 0.9.0, the one installation this tree is written for)."""
    global _hardened
    if _hardened:
        return
    from jax._src.lru_cache import LRUCache

    orig_put, orig_get = LRUCache.put, LRUCache.get

    def safe_put(self, key: str, val: bytes) -> None:
        try:
            published = publish_pair(self.path, key, val)
            if published:
                _stats["local_puts"] += 1
                if _remote_jax_tier_active():
                    _publish_async(key, val)
            # The original put sees the entry already present and returns
            # without rewriting the payload; calling it keeps the
            # eviction-lock bookkeeping of eviction-enabled caches intact.
        except OSError:
            pass
        orig_put(self, key, val)

    def _remote_fill(self, key: str):
        """Local miss: read-through the fleet tier. The fetched bytes are
        landed locally via the same atomic pair publish, so the next
        process on this host hits disk, not the network."""
        if not _remote_jax_tier_active():
            _stats["misses"] += 1
            return None
        val = _remote.fetch(key, wait_s=_remote_wait_s())
        if val is None:
            _stats["misses"] += 1
            return None
        try:
            publish_pair(self.path, key, val)
        except OSError:
            pass
        _stats["remote_hits"] += 1
        log.info("compilation cache remote hit for %s (%d bytes)", key, len(val))
        return val

    def safe_get(self, key: str):
        val = orig_get(self, key)
        if val is None:
            return _remote_fill(self, key)
        cache_path = self.path / f"{key}-cache"
        dpath = _digest_path(cache_path)
        try:
            want = dpath.read_bytes().decode()
        except OSError:
            want = ""
        if want == hashlib.sha256(val).hexdigest():
            _stats["local_hits"] += 1
            return val
        # Unverifiable (legacy or torn write): purge and recompile.
        log.warning(
            "compilation cache entry %s failed integrity check; "
            "dropping it (will recompile)", key,
        )
        for p in (cache_path, dpath, self.path / f"{key}-atime"):
            try:
                p.unlink()
            except OSError:
                pass
        _stats["misses"] += 1
        return None

    LRUCache.put, LRUCache.get = safe_put, safe_get
    _hardened = True


def _cpu_only_platform() -> bool:
    """True when JAX is pinned to the CPU backend (JAX_PLATFORMS=cpu).
    Env-only check on purpose: enable() runs BEFORE
    jax.distributed.initialize in the harness, and asking jax for its
    backend would initialize it too early."""
    plats = (os.environ.get("JAX_PLATFORMS") or "").replace(" ", "").lower()
    return plats.strip(",") == "cpu"


def cached_compile(
    key_material: str,
    compile_fn: Callable[[], bytes],
    wait_s: Optional[float] = None,
) -> tuple:
    """Generic read-through/write-back compile against both cache tiers,
    for artifacts the jax LRUCache never sees (AOT-serialized executables
    published at admission time, the bench's modeled compiles).

    Key = sha256 of ``key_material`` (the caller's full config string —
    the analogue of jax's (HLO, compile options, backend) triple).
    Lookup order: local dir (sha-verified pair) → fleet tier (honoring a
    live compile intent with a bounded wait) → ``compile_fn()``, whose
    result is landed locally and published to the fleet asynchronously.

    Returns ``(data, source)`` with source in {"local", "remote",
    "compiled"}. Unlike the jax-executable tier this is platform-
    agnostic: payloads are caller-defined artifacts, not process-local
    jaxlib executables, so the cpu-pinned exclusion does not apply."""
    import pathlib

    key = hashlib.sha256(key_material.encode()).hexdigest()
    root = pathlib.Path(cache_dir())
    try:
        root.mkdir(parents=True, exist_ok=True)
    except OSError:
        root = None
    if root is not None:
        cache_path = root / f"{key}-cache"
        try:
            val = cache_path.read_bytes()
            want = _digest_path(cache_path).read_bytes().decode()
            if want == hashlib.sha256(val).hexdigest():
                _stats["local_hits"] += 1
                return val, "local"
        except OSError:
            pass
    from tf_operator_tpu.rendezvous.env import ENV_COMPILE_CACHE

    client = _remote
    if client is None and os.environ.get(ENV_COMPILE_CACHE):
        # Workloads that call cached_compile() directly (without the
        # enable() path initialize_distributed() runs) still get the
        # fleet tier the controller stamped into their env.
        configure_remote(os.environ[ENV_COMPILE_CACHE])
        client = _remote
    if client is not None:
        val = client.fetch(
            key, wait_s=_remote_wait_s() if wait_s is None else wait_s
        )
        if val is not None:
            _stats["remote_hits"] += 1
            if root is not None:
                publish_pair(root, key, val)
            return val, "remote"
    _stats["misses"] += 1
    val = compile_fn()
    if root is not None:
        try:
            publish_pair(root, key, val)
            _stats["local_puts"] += 1
        except OSError:
            pass
    _publish_async(key, val)
    return val, "compiled"


def enable(force: bool = False) -> str | None:
    """Turn on the persistent compilation cache at ``cache_dir()``;
    returns the directory in use, or None when disabled via
    TPUJOB_NO_COMPILE_CACHE=1 or because the process is pinned to the CPU
    backend.

    When the controller stamped a compile-cache service URL
    (TPUJOB_COMPILE_CACHE, cli/operator.py), the hardened cache I/O also
    becomes read-through/write-back against that fleet tier — except on
    cpu-pinned processes, where even force leaves the remote tier off
    (see below).

    CPU is excluded. The r10 reason — entries read back by another
    process crashing it — was re-checked on jax/jaxlib 0.9.0 and no
    longer reproduces (a second process took 5/5 hits on the tiny LM
    step and computed identical losses). What is true today: XLA:CPU
    reloads an entry through its AOT loader, which logs an error on
    every hit that the compile machine's features do not match the
    host's ("could lead to execution errors such as SIGILL") — the
    executable is specific to the CPU that compiled it, and a checkout's
    cache outlives the machine. The cache is a TPU submit-latency lever;
    on CPU (tests, local benches) compiles are cheap, so there is
    nothing to buy with that risk. ``force=True`` /
    TPUJOB_FORCE_COMPILE_CACHE=1 override for cache machinery tests —
    the override re-enables only the LOCAL tier; host-specific
    executables must never enter the shared one."""
    if os.environ.get(ENV_DISABLE, "") == "1":
        return None
    from tf_operator_tpu.rendezvous.env import ENV_COMPILE_CACHE

    if _remote is None and os.environ.get(ENV_COMPILE_CACHE, ""):
        configure_remote(os.environ[ENV_COMPILE_CACHE])
    if not force and os.environ.get(ENV_FORCE, "") != "1" and _cpu_only_platform():
        log.debug("persistent compilation cache disabled on cpu-only backend")
        return None
    path = cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as exc:
        log.warning("compilation cache dir %s unusable: %s", path, exc)
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    # Cache even small/fast-compiling programs: the latency metric counts
    # every compile on the submit path.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax initializes its cache object lazily at the first compile and
    # then never re-reads the config dir — if ANYTHING compiled before
    # enable() (an orbax restore, a warmup jit), the cache would stay
    # pinned to that moment's (usually disabled) state and this call
    # would silently do nothing (r6: observed as checkpoint-restore →
    # compile-cache test-order pollution, present since the seed).
    from jax._src import compilation_cache as _jcc  # private; jax 0.9.0

    _jcc.reset_cache()
    _harden_cache_io()
    return path
