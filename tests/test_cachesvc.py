"""Fleet compile-cache service tests (r11 TTFS tentpole).

The failure modes pinned here are the acceptance bar's "zero
cache-integrity failures surfaced as job failures": a corrupted entry, a
full service, and a dead service must all degrade a workload to the
PR 10 local-compile path — observable in stats/span attributes, never an
exception on the job's step path.
"""

import hashlib
import os
import threading
import time

import pytest

import tf_operator_tpu.train.compile_cache as cc
from tf_operator_tpu.cachesvc import CacheClient, CompileCacheService
from tf_operator_tpu.cachesvc.aot import AOTCompiler, aot_spec_of, modeled_payload


@pytest.fixture()
def svc():
    service = CompileCacheService(max_bytes=1 << 20)
    yield service
    service.stop()


@pytest.fixture(autouse=True)
def _isolate_compile_cache(monkeypatch, tmp_path):
    """Each test gets a disconnected remote tier, zeroed counters and —
    placed the only way the cache can be placed, through the environment
    — a local tier under its own tmp_path."""
    monkeypatch.delenv("TPUJOB_COMPILE_CACHE", raising=False)
    monkeypatch.setenv(cc.ENV_DIR, str(tmp_path))
    cc.configure_remote(None)
    for k in cc._stats:
        cc._stats[k] = 0
    yield
    cc.configure_remote(None)


def test_publish_fetch_round_trip(svc):
    client = CacheClient(svc.url)
    payload = b"serialized-executable" * 64
    assert client.publish("jit_step-abc123", payload)
    assert client.fetch("jit_step-abc123") == payload
    snap = svc.snapshot()
    assert snap["puts"] == 1 and snap["hits"] == 1 and snap["entries"] == 1
    assert not client.dead


def test_duplicate_publish_is_first_writer_wins(svc):
    client = CacheClient(svc.url)
    assert client.publish("k", b"first")
    assert client.publish("k", b"second")  # 200/409 either way: not a death
    assert client.fetch("k") == b"first"
    assert not client.dead


def test_key_sanitization_rejects_path_shapes(svc):
    client = CacheClient(svc.url)
    for bad in ("../../etc/passwd", "a/b", "a.b", "", "x" * 201, "kéy"):
        assert not client.publish(bad, b"data")
        assert client.fetch(bad) is None
        assert bad not in svc._entries
    # nothing escaped the root
    assert all(p.endswith((".bin",)) or p.startswith(".")
               for p in os.listdir(svc.root))


def test_transfer_digest_mismatch_rejected(svc):
    import urllib.request

    req = urllib.request.Request(
        f"{svc.url}/cachesvc/v1/entry?key=k", data=b"payload", method="PUT",
        headers={"X-Entry-SHA256": "0" * 64},
    )
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(req, timeout=5)
    assert err.value.code == 409
    assert svc.snapshot()["put_rejects"] == 1
    assert svc.snapshot()["entries"] == 0


def test_corrupted_entry_purged_and_workload_falls_back(svc, tmp_path):
    """Disk rot under a committed entry: the service must drop it (404),
    and a workload hitting that miss compiles locally — the integrity
    failure never reaches the job as anything but latency."""
    client = CacheClient(svc.url)
    key_material = "ns/job-fingerprint"
    key = hashlib.sha256(key_material.encode()).hexdigest()
    assert client.publish(key, modeled_payload(key_material))
    # rot the committed file behind the index's back
    with open(os.path.join(svc.root, f"{key}.bin"), "wb") as f:
        f.write(b"rotten")
    cc.configure_remote(svc.url)
    calls = []

    def compile_fn():
        calls.append(1)
        return modeled_payload(key_material)

    data, source = cc.cached_compile(
        key_material, compile_fn, wait_s=0.0
    )
    assert source == "compiled" and calls == [1]
    assert data == modeled_payload(key_material)
    # The rotten entry was purged; the async write-back of the fresh
    # compile may have re-published it. Both states are fine — what must
    # never happen is the rotten bytes being served as a hit.
    refetched = CacheClient(svc.url).fetch(key, wait_s=0.0)
    assert refetched in (None, modeled_payload(key_material))


def test_eviction_under_byte_cap():
    service = CompileCacheService(max_bytes=250)
    try:
        client = CacheClient(service.url)
        assert client.publish("old", b"a" * 100)
        assert client.publish("mid", b"b" * 100)
        client.fetch("old")  # refresh: now "mid" is the oldest-touched
        assert client.publish("new", b"c" * 100)
        snap = service.snapshot()
        assert snap["evictions"] == 1
        assert snap["bytes"] <= 250
        assert client.fetch("mid") is None  # the oldest-touched victim
        assert client.fetch("old") == b"a" * 100
        assert client.fetch("new") == b"c" * 100
    finally:
        service.stop()


def test_oversized_entry_rejected_not_fatal():
    service = CompileCacheService(max_bytes=64)
    try:
        client = CacheClient(service.url)
        assert not client.publish("big", b"x" * 100)
        assert not client.dead  # a policy reject is not a transport death
        assert service.snapshot()["entries"] == 0
    finally:
        service.stop()


def test_dead_cachesvc_degrades_to_local_with_span_attr(tmp_path, monkeypatch):
    """A dead service is a latency event: cached_compile() compiles
    locally, stats record the degradation, and mark_first_step carries it
    as a span attribute — never an exception on the step path."""
    cc.configure_remote("http://127.0.0.1:9")  # nothing listens there
    data, source = cc.cached_compile(
        "some/config", lambda: b"compiled-bytes",
        wait_s=0.0,
    )
    assert (data, source) == (b"compiled-bytes", "compiled")
    stats = cc.stats()
    assert stats["remote_dead"] is True and stats["misses"] == 1

    from tf_operator_tpu.rendezvous.context import JobContext

    captured = {}

    def fake_record(self, op, start, end, attrs=None, name=None):
        captured.update(attrs or {})
        return True

    monkeypatch.setattr(JobContext, "record_span", fake_record)
    assert JobContext(job_name="j", trace_id="t").mark_first_step(0)
    assert captured["cache_degraded"] == "1"
    assert captured["warm"] == "0"  # a degraded miss is a cold start


def test_intent_single_flight(svc):
    """A worker that reaches its miss while an admission-time compile is
    in flight waits it out (202 + Retry-After) and gets the publish —
    instead of duplicating the compile."""
    client = CacheClient(svc.url)
    client.announce("k")
    got = {}

    def waiter():
        got["data"] = client.fetch("k", wait_s=5.0)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.3)  # the modeled admission-time compile
    assert client.publish("k", b"aot-built")
    t.join(timeout=10)
    assert got["data"] == b"aot-built"
    assert svc.snapshot()["waits"] >= 1


def test_intent_ttl_expires_to_miss():
    service = CompileCacheService(intent_ttl=0.05)
    try:
        client = CacheClient(service.url)
        client.announce("k")
        time.sleep(0.1)
        assert client.fetch("k", wait_s=0.0) is None  # 404, not an endless 202
    finally:
        service.stop()


def test_remote_fill_lands_locally(svc, tmp_path):
    """A remote hit is written through to the local tier: the next lookup
    on this host never touches the network."""
    client = CacheClient(svc.url)
    key_material = "cfg"
    key = hashlib.sha256(key_material.encode()).hexdigest()
    assert client.publish(key, b"remote-built")
    cc.configure_remote(svc.url)
    data, source = cc.cached_compile(
        key_material, lambda: b"never", wait_s=0.0
    )
    assert (data, source) == (b"remote-built", "remote")
    data2, source2 = cc.cached_compile(
        key_material, lambda: b"never", wait_s=0.0
    )
    assert (data2, source2) == (b"remote-built", "local")


def test_cached_compile_configures_remote_from_env(svc, tmp_path, monkeypatch):
    """Workloads that call cached_compile() without enable() still reach
    the controller-stamped fleet tier."""
    key_material = "env-cfg"
    key = hashlib.sha256(key_material.encode()).hexdigest()
    CacheClient(svc.url).publish(key, b"fleet-built")
    monkeypatch.setenv("TPUJOB_COMPILE_CACHE", svc.url)
    data, source = cc.cached_compile(
        key_material, lambda: b"never", wait_s=0.0
    )
    assert (data, source) == (b"fleet-built", "remote")


# -- AOT-at-admission ---------------------------------------------------


def test_aot_spec_of_accepts_dict_and_json():
    assert aot_spec_of({"aot": {"key": "k"}}) == {"key": "k"}
    assert aot_spec_of('{"aot": {"topology": "v5e:2x4"}}') == {
        "topology": "v5e:2x4"
    }
    assert aot_spec_of({"dim": 16}) is None
    assert aot_spec_of("not json") is None
    assert aot_spec_of({"aot": "nope"}) is None


def test_aot_kick_publishes_and_dedupes(svc):
    done = threading.Event()
    spans = []

    def on_done(namespace, job_name, trace_id, key, mode, start, end, ok):
        spans.append((namespace, job_name, mode, ok))
        done.set()

    aot = AOTCompiler(svc.url, workers=1, on_done=on_done)
    try:
        workload = {"aot": {"key": "cfg", "compile_ms": 0}}
        assert aot.kick("ns", "job", "uid1", workload) is True
        assert aot.kick("ns", "job", "uid1", workload) is False  # dedup
        assert done.wait(timeout=10)
        assert spans == [("ns", "job", "modeled", True)]
        key = hashlib.sha256(b"cfg").hexdigest()
        assert CacheClient(svc.url).fetch(key) == modeled_payload("cfg")
        assert aot.stats["kicked"] == 1 and aot.stats["published"] == 1
    finally:
        aot.stop()


def test_aot_kick_nothing_declared(svc):
    aot = AOTCompiler(svc.url, workers=1)
    try:
        assert aot.kick("ns", "job", "uid", {"dim": 16}) is False
        assert aot.stats["kicked"] == 0
    finally:
        aot.stop()
