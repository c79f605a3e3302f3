"""Workqueue tests: dedup, deferred re-add, backoff, shutdown."""

import threading
import time

from tf_operator_tpu.controller.workqueue import (
    ItemExponentialBackoff,
    RateLimitingQueue,
    TokenBucket,
)


def test_dedup_while_queued():
    q = RateLimitingQueue()
    q.add("a")
    q.add("a")
    assert len(q) == 1
    assert q.get(timeout=1) == "a"
    assert q.get(timeout=0.05) is None


def test_deferred_readd_while_processing():
    q = RateLimitingQueue()
    q.add("a")
    item = q.get(timeout=1)
    q.add("a")  # re-added while in flight: must not be handed out yet
    assert q.get(timeout=0.05) is None
    q.done(item)
    assert q.get(timeout=1) == "a"  # now it comes back


def test_exponential_backoff_growth_and_forget():
    b = ItemExponentialBackoff(base_delay=0.005, max_delay=1000.0)
    delays = [b.when("x") for _ in range(5)]
    assert delays == [0.005, 0.01, 0.02, 0.04, 0.08]
    b.forget("x")
    assert b.when("x") == 0.005
    # cap
    for _ in range(40):
        b.when("y")
    assert b.when("y") == 1000.0


def test_token_bucket_burst_then_throttle():
    tb = TokenBucket(qps=10.0, burst=3)
    assert [tb.when() for _ in range(3)] == [0.0, 0.0, 0.0]
    assert tb.when() > 0.0


def test_add_rate_limited_delivers_later():
    # 0.25 s against the first get's 5 ms: under six loaded workers a 20-ms
    # delay could run out before that get was reached (one failure, PR 43)
    q = RateLimitingQueue(base_delay=0.25)
    q.add_rate_limited("a")
    assert q.get(timeout=0.005) is None  # not yet
    assert q.get(timeout=1) == "a"


def test_shutdown_unblocks_getters():
    q = RateLimitingQueue()
    got = []

    def getter():
        got.append(q.get())

    t = threading.Thread(target=getter)
    t.start()
    time.sleep(0.05)
    q.shutdown()
    t.join(timeout=2)
    assert got == [None]
