"""benchmarks/span_reduce.py on made-up planes (stand-ins for ProfileData's,
as in benchmarks/tests/test_trace_reduce.py), on the trace recorded on the
chip before the program had spans, and the new per-layer readers through the
harness at ``tiny``."""

import gzip
import json
import os
import shutil
from types import SimpleNamespace as NS

import pytest

from benchmarks import run, span_reduce as sr, trace_reduce as tr
from conftest import benchmarks_conftest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(REPO, "benchmarks", "testdata", "tiny_train.xplane.pb.gz")
KERNEL = ('%paged_attention.{n} = f32[4,2,4,64]{{3,2,1,0}} custom-call(%a), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.{n} = f32[8]{{0}} fusion(), kind=kLoop"
US = 1e-6


def ev(name, start_us, dur_us, **attrs):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=list(attrs.items()))


def serving_planes(spans=True):
    """Two engine steps around an idle engine: a prefill chunk and a decode
    call, a sleep, a decode call. Times in microseconds."""
    ops = NS(name="XLA Ops", events=[
        ev(FUSION.format(n=1), 50, 50), ev(KERNEL.format(n=3), 110, 40),   # prefill run
        ev(FUSION.format(n=2), 195, 105), ev(KERNEL.format(n=7), 300, 70),  # decode run
        ev(KERNEL.format(n=7), 700, 60), ev(FUSION.format(n=2), 760, 90),  # decode run
    ])
    modules = NS(name="XLA Modules", events=[
        ev("jit_prefill_chunk(11)", 50, 100), ev("jit_decode_step(22)", 195, 175),
        ev("jit_decode_step(22)", 700, 150),
    ])
    host = [ev("bench.window", 0, 1000),
            ev("bench.engine_run", 5, 990),
            # opened in one step's callback and closed in the next one's: it
            # overlaps the program's spans without nesting, and is not theirs
            ev("bench.engine_step", 390, 490)]
    if spans:
        host += [
            ev("serve.admit", 10, 10),
            ev("serve.step", 20, 380, step=1, occupied=2, waiting=0,
               free_pages=60, kv_tokens=8, kv_reserved=32),
            ev("serve.prefill", 30, 30, rid=0, slot=0, start=0, n_valid=5, chunk=8, last=1),
            ev("serve.prefill_fetch", 60, 100, rid=0),
            ev("serve.decode_prep", 170, 20, active=3),
            ev("serve.decode", 190, 10, active=3, slots=4),
            ev("serve.decode_fetch", 200, 180),
            ev("serve.idle", 420, 180),
            ev("serve.step", 620, 280, step=2, occupied=1, waiting=0,
               free_pages=62, kv_tokens=24, kv_reserved=32),
            ev("serve.decode_prep", 630, 50, active=1),
            ev("serve.decode", 680, 20, active=1, slots=4),
            ev("serve.decode_fetch", 700, 170),
            ev("serve.counters", 950, 0, admitted=1, decode_steps=2, idle_sleeps=1),
        ]
    return [NS(name="/device:TPU:0", lines=[ops, modules]),
            NS(name="/host:CPU", lines=[NS(name="python3", events=host)])]


@pytest.fixture(scope="module")
def served():
    return sr.reduce_planes(serving_planes())


def test_every_idle_piece_is_filed_under_exactly_one_class(served):
    assert served.window_s == pytest.approx(1000 * US)
    assert served.busy_s == pytest.approx(415 * US)  # 50 + 40 + 175 + 150
    expected = {
        "in_program": 10,    # 100..110, between two ops of the prefill run
        "engine_empty": 180,  # serve.idle
        "device_side": 40,   # 150..160, 370..380, 850..870: inside a *_fetch
        # admit 10, prefill 20 (to the program's start), prep 20 + 50, decode
        # 5 + 20, and the steps' own time 10 + 10 + 20 + 10 + 30
        "host_bound": 205,
        "outside": 150,      # 0..10, 400..420, 600..620, 900..1000
    }
    assert set(served.idle) == set(sr.IDLE_CLASSES)
    for cls, us in expected.items():
        assert served.idle[cls] == pytest.approx(us * US), cls
    assert served.idle_s == pytest.approx(served.window_s - served.busy_s)
    # the same idle time trace_reduce reads from the same planes
    assert served.idle_s / served.window_s == pytest.approx(
        tr.reduce_planes(serving_planes()).idle_share)


def test_innermost_attribution_and_self_time(served):
    first, second = served.spans["serve.step"]
    assert [c.name for c in first.children] == [
        "serve.prefill", "serve.prefill_fetch", "serve.decode_prep",
        "serve.decode", "serve.decode_fetch"]
    assert first.self_s == pytest.approx(40 * US)   # 20..30, 160..170, 380..400
    assert second.self_s == pytest.approx(40 * US)  # 620..630, 870..900
    assert not served.spans["serve.idle"][0].children
    assert not any(k.startswith("bench.") for k in served.spans)
    segments = sr.innermost_segments(sr.build_tree(
        [sr.Span(s.name, s.start, s.end, {}) for v in served.spans.values() for s in v]))
    at = lambda t: [n for a, b, n in segments if a <= t * US < b]  # noqa: E731
    assert at(25) == ["serve.step"] and at(100) == ["serve.prefill_fetch"]
    assert at(500) == ["serve.idle"] and at(410) == []


@pytest.mark.parametrize("metric,expected", [
    # step 1: 380 - (100 + 180) = 100 us; step 2: 280 - 170 = 110 us
    ("engine_host_step_ms", 0.105),
    ("decode_slot_occupancy", 100.0 * (3 + 1) / (4 + 4)),
    ("prefill_padding_share", 100.0 * (1 - 5 / 8)),
    ("kv_reserved_unused_share", 100.0 * ((1 - 8 / 32) + (1 - 24 / 32)) / 2),
])
def test_span_metrics_on_made_up_planes(served, metric, expected):
    assert getattr(served, metric)() == pytest.approx(expected)


def test_device_seconds_by_program_and_kernel(served):
    assert served.program_runs == {"prefill_chunk": 1, "decode_step": 2}
    assert served.program_busy_s["prefill_chunk"] == pytest.approx(90 * US)
    assert served.program_busy_s["decode_step"] == pytest.approx(325 * US)
    assert served.device_program_share("prefill_chunk") == pytest.approx(100 * 90 / 415)
    assert served.kernel_s == {
        ("prefill_chunk", "paged_attention"): pytest.approx(40 * US),
        ("decode_step", "paged_attention"): pytest.approx(130 * US)}
    assert served.kernel_seconds("decode_step") == pytest.approx(130 * US)
    assert served.idle_share("host_bound") == pytest.approx(20.5)
    assert served.idle_share("engine_empty") == pytest.approx(18.0)
    assert served.counters() == {"admitted": 1, "decode_steps": 2, "idle_sleeps": 1}


def test_the_longest_fetch_is_reported_with_the_device_work_under_it(served):
    f = served.longest_fetch
    assert f["span"] == "serve.decode_fetch" and f["seconds"] == pytest.approx(180 * US)
    assert f["at_s"] == pytest.approx(200 * US) and f["programs"] == ["decode_step"]
    assert f["device_busy_s"] == pytest.approx(170 * US)  # 200..370
    assert f["top_ops"][0][1] == pytest.approx(100 * US)  # fusion.2, 200..300


def test_a_program_without_spans_reads_as_nothing_not_as_an_error():
    r = sr.reduce_planes(serving_planes(spans=False))
    assert r.spans == {} and r.longest_fetch is None and r.counters() == {}
    for metric in ("engine_host_step_ms", "decode_slot_occupancy",
                   "prefill_padding_share", "kv_reserved_unused_share",
                   "loader_wait_ms"):
        assert getattr(r, metric)() is None
    assert r.idle_share("host_bound") is None and r.idle_share("engine_empty") is None
    # what needs no span is still read: programs, kernels, idle inside programs
    assert r.idle["in_program"] == pytest.approx(10 * US)
    assert r.idle["outside"] == pytest.approx(575 * US)
    assert r.device_program_share("prefill_chunk") == pytest.approx(100 * 90 / 415)
    assert r.kernel_seconds("prefill_chunk") == pytest.approx(40 * US)


def test_training_threads_the_window_marks_the_consumer():
    ops = NS(name="XLA Ops", events=[ev(FUSION.format(n=1), 100, 300),
                                     ev(FUSION.format(n=1), 600, 300)])
    modules = NS(name="XLA Modules", events=[ev("jit__step_body(5)", 100, 300),
                                             ev("jit__step_body(5)", 600, 300)])
    stager = NS(name="device-loader", events=[
        ev("train.data_stage", 20, 40), ev("train.data_stage", 300, 40),
        ev("train.data_stage", 500, 60)])
    consumer = NS(name="python3", events=[
        ev("bench.window", 0, 1000),
        ev("train.data_wait", 10, 60, queued=0), ev("train.step", 80, 20, call=7),
        ev("train.data_wait", 410, 10, queued=1), ev("train.step", 430, 150, call=8),
        ev("train.data_wait", 910, 30, queued=2)])
    r = sr.reduce_planes([NS(name="/device:TPU:0", lines=[ops, modules]),
                          NS(name="/host:CPU", lines=[stager, consumer])])
    assert r.loader_wait_ms() == pytest.approx(0.030)
    assert len(r.spans["train.data_stage"]) == 3
    # idle 0..100, 400..600, 900..1000 is filed by the CONSUMER's spans:
    # data_wait 60 + 10 + 30, step 20 + 150; the stager's spans file nothing
    assert r.idle["host_bound"] == pytest.approx(270 * US)
    assert r.idle["outside"] == pytest.approx(130 * US)
    assert r.idle["device_side"] == r.idle["engine_empty"] == r.idle["in_program"] == 0.0


def test_spans_nest_by_containment():
    s = lambda n, a, b: sr.Span(n, a, b, {})  # noqa: E731
    roots = sr.build_tree([s("b", 2, 5), s("a", 0, 10), s("c", 3, 4), s("d", 5.5, 5.5),
                           s("e", 10, 12), s("f", 6, 9)])
    assert [r.name for r in roots] == ["a", "e"]
    a = roots[0]
    assert [c.name for c in a.children] == ["b", "d", "f"]
    assert [c.name for c in a.children[0].children] == ["c"]
    assert a.self_s == 10 - 3 - 0 - 3
    assert sr.intersect([(0, 10)], [(2, 3), (8, 12)]) == [(2, 3), (8, 10)]
    assert sr.program_of("jit_prefill_chunk(123)") == "prefill_chunk"
    assert sr.idle_class(None) == "outside" and sr.idle_class("train.step") == "host_bound"
    with pytest.raises(ValueError):
        sr.reduce_planes([NS(name="/host:CPU", lines=[])])


# ---- the trace recorded on the chip (PR 23), and the readers through the harness


@pytest.fixture
def recorded_home(tmp_path, monkeypatch):
    """A trace home that holds the chip's recorded trace as its newest file."""
    os.makedirs(tmp_path / "cell")
    with gzip.open(RECORDED, "rb") as src, \
            open(tmp_path / "cell" / "tiny.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(sr, "TRACE_HOME", str(tmp_path))
    return str(tmp_path)


def test_recorded_trace_of_a_program_without_spans(recorded_home):
    r = sr.reduce_file(sr.newest_xplane())
    assert r.spans == {} and r.loader_wait_ms() is None
    # trace_reduce's numbers, worked out by hand there: three step programs
    # inside the window, 124,254 ns busy of 7,044,989
    assert r.program_runs == {"_step_body": 3}
    assert r.window_s == pytest.approx(7_044_989e-9, rel=1e-9)
    assert r.busy_s == pytest.approx(124_254e-9, rel=1e-6)
    assert r.program_busy_s["_step_body"] == pytest.approx(r.busy_s)
    assert r.idle_s == pytest.approx(r.window_s - r.busy_s)
    assert r.idle["in_program"] == pytest.approx(
        (49_326 + 49_552 + 50_058 - 124_254) * 1e-9, rel=1e-3)


def _new_metrics():
    """The per-layer entries whose reader imports span_reduce."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    home = os.path.join(REPO, "benchmarks")
    return [(m["name"], cell) for m in bench["per_layer"]
            if "span_reduce" in open(run.reader_path(home, m["name"])).read()
            for cell in m["workloads"][:1]]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return benchmarks_conftest().make_tiny_root(
        tmp_path_factory.mktemp("bench") / "root")


def test_this_pr_added_fifteen_entries():
    # + the five `.longdoc` entries of PR 37: the same readers on the
    # Olmo-Hybrid cell, whose engine writes the same spans; + PR 38's
    # `engine_runs_ahead_share`, one entry a serve cell; + PR 45's
    # `chunk_carries_decode_share`, the same three; + the seven of PR 47's
    # eight `.reason` entries whose reader reads spans (the Jamba cell)
    assert len(_new_metrics()) == 15 + 5 + 3 + 3 + 7


@pytest.mark.parametrize("metric,counter,n,share", [
    # 100 x runs enqueued ahead / (chunks + decode steps) — PR 38
    ("engine_runs_ahead_share", "runs_enqueued_ahead", 2, 100.0 * 2 / 3),
    # 100 x chunks whose run carried a decode row / chunks — PR 45
    ("chunk_carries_decode_share", "chunks_carrying_decode", 1, 100.0),
])
def test_a_counter_share_reads_the_counter_or_nothing(served, metric, counter, n, share):
    """Three entries, one reader: a share of the program runs that the trace's
    ``serve.counters`` count; None where the counter is absent (the parent of
    the PR that brought it), where there is nothing to take a share of, and
    for an untraced run."""
    home = os.path.join(REPO, "benchmarks")
    paths = {run.reader_path(home, metric + cell) for cell in ("", ".sat", ".longdoc")}
    assert paths == {os.path.join(home, "metrics", metric + ".py")}
    reader = run._load_py(paths.pop(), metric + "_reader_under_test")
    assert reader.read(NS(trace=None)) is None
    assert counter not in served.counters()
    assert reader.read(NS(trace=NS(), spans=served)) is None
    planes = serving_planes()
    planes[1].lines[0].events[-1] = ev(
        "serve.counters", 950, 0, admitted=1, prefill_chunks=1, decode_steps=2,
        idle_sleeps=1, **{counter: n})
    counted = NS(trace=NS(), spans=sr.reduce_planes(planes))
    assert reader.read(counted) == pytest.approx(share)
    planes[1].lines[0].events[-1] = ev(
        "serve.counters", 950, 0, prefill_chunks=0, decode_steps=0, **{counter: 0})
    assert reader.read(NS(trace=NS(), spans=sr.reduce_planes(planes))) is None


@pytest.mark.parametrize("metric,cell", _new_metrics())
def test_new_entries_resolve_to_readers_that_return_none_without_a_trace(
        tiny_root, recorded_home, metric, cell):
    loaded = run.load_cell(tiny_root, cell)
    assert metric in {m["name"] for m in loaded.per_layer}
    path = run.reader_path(loaded.home, metric)
    assert os.path.exists(path)
    reader = run._load_py(path, "reader_under_test")
    said = []
    record = NS(samples={}, trace=None, sizes=loaded.sizes, mix=loaded.mix,
                config=loaded.config, peaks=None, chips=loaded.chips, say=said.append)
    assert reader.read(record) is None  # an untraced run
    assert not hasattr(record, "spans")  # ... reads no file
    # a traced run of a program that wrote no span (this PR's parent)
    record.trace = NS(idle_share=0.5)
    record.samples = {"traced": {"finished": [], "prefill_chunk": 16}}
    value = reader.read(record)
    assert value is None or metric.startswith("device_prefill_share")
    assert record.spans.spans == {} and any("idle_share_by_class" in s for s in said)
