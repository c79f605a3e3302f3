"""The generator: clipped quantiles, seed determinism, the same work for every seed
in an order the seed draws."""

import numpy as np

from benchmarks import traffic

CHAT = traffic.load_mix("chat-0.8knee")


def test_length_quantiles_are_clipped_and_centred():
    p = traffic.length_quantiles(400, CHAT["prompt_len"])
    assert p.min() == 32 and p.max() == 1024
    assert 240 <= np.median(p) <= 272  # median 256
    o = traffic.length_quantiles(400, CHAT["output_len"])
    assert o.min() >= 8 and o.max() <= 128 and 44 <= np.median(o) <= 52


def test_gap_quantiles_are_the_poisson_process_s():
    g = traffic.gap_quantiles(1000, 4.0)
    assert abs(g.mean() - 0.25) < 0.002  # mean gap 1 / rate
    assert abs(np.median(g) - np.log(2) / 4.0) < 0.002  # exponential, not even


def test_same_seed_same_requests_other_seed_same_work_in_another_order():
    mix = dict(CHAT, rate_per_s=6.0)
    a = traffic.requests(2**31 + 5, 32000, mix, 10.0)
    b = traffic.requests(2**31 + 5, 32000, mix, 10.0)
    c = traffic.requests(9, 32000, mix, 10.0)
    assert a == b and len(a) == 60
    lens = lambda rs: [len(r["prompt"]) for r in rs]  # noqa: E731
    budgets = lambda rs: [r["max_new"] for r in rs]  # noqa: E731
    gaps = lambda rs: np.diff([0.0] + [r["arrival"] for r in rs])  # noqa: E731
    for key in (lens, budgets):
        assert key(a) != key(c) and sorted(key(a)) == sorted(key(c))
    assert not np.allclose(gaps(a), gaps(c))  # the seed draws the clumps ...
    np.testing.assert_allclose(np.sort(gaps(a)), np.sort(gaps(c)), atol=1e-9)
    # ... and long prompts are not tied to long answers or to wide gaps
    assert abs(np.corrcoef(lens(a), budgets(a))[0, 1]) < 0.4
    assert 0 < a[0]["arrival"] and a[-1]["arrival"] < 10.5
    assert all(1 <= t < 32000 for r in a for t in r["prompt"])


def test_stratified_order_spreads_the_work_over_the_window():
    sat = traffic.load_mix("chat-1.5knee")
    assert sat["stratify_every"] == 6 and "stratify_every" not in CHAT
    lens = lambda rs: np.array([len(r["prompt"]) for r in rs])  # noqa: E731
    a, b = (traffic.requests(s, 32000, sat, 30.0) for s in (1, 2))
    assert list(lens(a)) != list(lens(b)) and sorted(lens(a)) == sorted(lens(b))
    bands = np.sort(lens(a)).reshape(6, 15)  # six bands of the 90 lengths
    for rs in (a, b):  # every run of six holds one length of each band
        for run in lens(rs).reshape(15, 6):
            assert sorted(np.searchsorted(bands[:, -1], run, side="left")) == list(range(6))
    halves = [lens(rs)[:45].sum() / lens(rs).sum() for rs in (a, b)]
    assert all(0.45 < h < 0.55 for h in halves)


def test_token_batches_walk_distinct_rows():
    mix = {"batch_size": 2, "seq_len": 16, "rows": 6}
    it = traffic.token_batches(2**31 + 1, 256, mix)
    first = [next(it)["tokens"] for _ in range(4)]
    again = traffic.token_batches(2**31 + 1, 256, mix)
    assert all(np.array_equal(x, next(again)["tokens"]) for x in first)
    rows = np.concatenate(first[:3])
    assert len({r.tobytes() for r in rows}) == 6  # all differ
    assert np.array_equal(first[3], first[0])  # the walk wraps
