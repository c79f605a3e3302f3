"""Persistent compilation cache tests (submit→first-step latency lever,
SURVEY.md §7 hard part d)."""

import os
import time

import tf_operator_tpu.train.compile_cache as cc


def _place(monkeypatch, path) -> str:
    """The only way to place the cache: the environment."""
    monkeypatch.setenv(cc.ENV_DIR, str(path))
    return str(path)


def test_enable_creates_and_configures_dir(tmp_path, monkeypatch):
    target = _place(monkeypatch, tmp_path / "xla-cache")
    got = cc.enable(force=True)
    assert got == target and os.path.isdir(target)
    import jax

    assert jax.config.jax_compilation_cache_dir == target


def test_env_set_means_that_directory_and_no_other(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set ⇒ both tiers (jax's executables via
    enable(), caller artifacts via cached_compile()) write there and
    nowhere else — in particular not the in-checkout default."""
    target = _place(monkeypatch, tmp_path / "from-env")
    default_before = (
        set(os.listdir(cc.DEFAULT_CACHE_DIR))
        if os.path.isdir(cc.DEFAULT_CACHE_DIR) else None
    )
    assert cc.cache_dir() == target
    assert cc.enable(force=True) == target
    data, source = cc.cached_compile("env-set-case", lambda: b"x", wait_s=0.0)
    assert (data, source) == (b"x", "compiled")
    assert any(n.endswith("-cache") for n in os.listdir(target))
    default_after = (
        set(os.listdir(cc.DEFAULT_CACHE_DIR))
        if os.path.isdir(cc.DEFAULT_CACHE_DIR) else None
    )
    assert default_after == default_before


def test_env_unset_means_the_fixed_in_checkout_path(monkeypatch):
    """Unset ⇒ one fixed path inside the checkout: not under home, not a
    temp name, nothing that changes from one process to the next (the
    path is part of what makes a second run hit)."""
    import inspect
    import tempfile

    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.cache_dir() == cc.DEFAULT_CACHE_DIR == os.path.join(
        repo, ".cache", "xla"
    )
    assert not cc.DEFAULT_CACHE_DIR.startswith(tempfile.gettempdir() + os.sep)
    assert os.path.expanduser("~") + os.sep + ".cache" not in cc.DEFAULT_CACHE_DIR
    # and no entry point takes a directory of its own
    assert "cache_dir" not in inspect.signature(cc.enable).parameters
    assert "cache_dir" not in inspect.signature(cc.cached_compile).parameters


def test_disable_env(monkeypatch, tmp_path):
    _place(monkeypatch, tmp_path / "x")
    monkeypatch.setenv(cc.ENV_DISABLE, "1")
    assert cc.enable(force=True) is None
    assert not (tmp_path / "x").exists()


def test_unwritable_dir_degrades_to_none(monkeypatch, tmp_path):
    blocker = tmp_path / "file"
    blocker.write_text("not a dir")
    _place(monkeypatch, blocker / "sub")
    assert cc.enable(force=True) is None


def test_cache_populates_on_compile(tmp_path, monkeypatch):
    """A jitted computation lands executables in the cache directory."""
    target = _place(monkeypatch, tmp_path / "xla-cache")
    assert cc.enable(force=True) == target
    import jax
    import jax.numpy as jnp

    # A distinctive shape to avoid any earlier in-memory hit being the
    # only artifact; the persistent cache writes on cache miss.
    x = jnp.arange(37.0)
    jax.jit(lambda v: (v * 3 + 1).sum())(x).block_until_ready()
    entries = os.listdir(target)
    assert entries, "compilation cache is empty after a jit compile"


# -- crash-safe cache I/O (r10) ----------------------------------------
#
# enable() wraps jax's LRUCache with atomic writes + sha256 sidecars:
# a worker SIGKILLed mid-write (the operator's preempt path) must not be
# able to leave a truncated executable that aborts every later warm
# restart in native deserialization code.


def _lru(tmp_path, monkeypatch):
    _place(monkeypatch, tmp_path / "xc")
    cc.enable(force=True)  # installs hardened put/get
    from jax._src.lru_cache import LRUCache

    return LRUCache(str(tmp_path / "lru"), max_size=-1)


def test_put_writes_payload_digest_and_atime(tmp_path, monkeypatch):
    cache = _lru(tmp_path, monkeypatch)
    cache.put("k1", b"executable-bytes")
    names = sorted(os.listdir(tmp_path / "lru"))
    assert names == ["k1-atime", "k1-cache", "k1-cache-sha256"]
    assert cache.get("k1") == b"executable-bytes"


def test_torn_write_is_a_miss_and_self_heals(tmp_path, monkeypatch):
    """A truncated payload under the final name (pre-fix poison, or a
    legacy jax write killed mid-flight) must read as a miss and be
    deleted — never handed to XLA."""
    cache = _lru(tmp_path, monkeypatch)
    cache.put("k2", b"full-payload")
    (tmp_path / "lru" / "k2-cache").write_bytes(b"full-pay")  # torn
    assert cache.get("k2") is None
    assert not (tmp_path / "lru" / "k2-cache").exists()
    # the key is writable again afterwards (put skips existing entries)
    cache.put("k2", b"recompiled")
    assert cache.get("k2") == b"recompiled"


def test_legacy_entry_without_digest_is_purged(tmp_path, monkeypatch):
    """Entries from before the hardening have no sidecar; they are
    unverifiable, so get() drops them once and recompilation repopulates
    with a digest."""
    cache = _lru(tmp_path, monkeypatch)
    (tmp_path / "lru" / "k3-cache").write_bytes(b"who knows")
    assert cache.get("k3") is None
    assert not (tmp_path / "lru" / "k3-cache").exists()


def test_harden_is_idempotent(tmp_path, monkeypatch):
    from jax._src.lru_cache import LRUCache

    _place(monkeypatch, tmp_path / "a")
    cc.enable(force=True)
    put1, get1 = LRUCache.put, LRUCache.get
    _place(monkeypatch, tmp_path / "b")
    cc.enable(force=True)
    assert LRUCache.put is put1 and LRUCache.get is get1


def test_concurrent_writers_never_publish_torn_pairs(tmp_path):
    """r11 safe_put race pin: many writers racing one key must commit the
    sidecar+payload as a unit. Before the fix, two writers staging to the
    SAME tmp names could interleave replace()s and publish writer A's
    payload under writer B's digest — a permanently unverifiable entry.
    A concurrent verifier must only ever observe (a) no entry, or (b) a
    payload that matches its sidecar AND equals one writer's value."""
    import hashlib
    import threading

    root = tmp_path / "cc"
    root.mkdir()
    values = [f"payload-from-writer-{i}".encode() * 8 for i in range(8)]
    digests = {hashlib.sha256(v).hexdigest(): v for v in values}
    stop = threading.Event()
    bad: list = []

    def verifier():
        payload_path = root / "k-cache"
        digest_path = root / "k-cache-sha256"
        while not stop.is_set():
            try:
                data = payload_path.read_bytes()
                want = digest_path.read_bytes().decode()
            except OSError:
                continue  # not published yet / mid-swap: a miss, fine
            got = hashlib.sha256(data).hexdigest()
            if got == want and want not in digests:
                bad.append(("foreign verified payload", data[:40]))

    def writer(val):
        for _ in range(50):
            cc.publish_pair(root, "k", val)

    v = threading.Thread(target=verifier)
    v.start()
    writers = [threading.Thread(target=writer, args=(val,)) for val in values]
    for t in writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    v.join()
    assert not bad
    # Quiesced: exactly one writer's value, verified by its own sidecar.
    data = (root / "k-cache").read_bytes()
    want = (root / "k-cache-sha256").read_bytes().decode()
    assert hashlib.sha256(data).hexdigest() == want
    assert data in values


def test_publish_pair_skips_existing_entry(tmp_path):
    cc.publish_pair(tmp_path, "k", b"first")
    cc.publish_pair(tmp_path, "k", b"second")
    assert (tmp_path / "k-cache").read_bytes() == b"first"


def test_publish_pair_breaks_stale_lock(tmp_path, monkeypatch):
    """A writer SIGKILLed between lock and publish must not wedge the key
    forever: the O_EXCL lock is age-broken."""
    lock = tmp_path / "k-cache.lock"
    lock.write_text("")
    old = time.time() - 2 * cc._LOCK_STALE_S
    os.utime(lock, (old, old))
    cc.publish_pair(tmp_path, "k", b"value")
    assert (tmp_path / "k-cache").read_bytes() == b"value"
    assert not lock.exists()


def test_cpu_only_platform_skips_cache(monkeypatch, tmp_path):
    """An XLA:CPU executable is specific to the CPU that compiled it (its
    AOT loader says so on every reload) and a CPU compile is cheap —
    enable() must refuse on a cpu-pinned process unless explicitly
    forced."""
    _place(monkeypatch, tmp_path / "x")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv(cc.ENV_FORCE, raising=False)
    assert cc.enable() is None
    monkeypatch.setenv(cc.ENV_FORCE, "1")
    assert cc.enable() is not None
