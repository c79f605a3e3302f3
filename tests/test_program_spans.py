"""The program's own spans and counters (serve/engine.py, train/data.py,
train/trainer.py): written into the profiler's trace while a session is
open, free and without effect on results while none is."""

import glob
import os
import time

import pytest

SERVE_SPANS = (
    "serve.admit", "serve.step", "serve.prefill", "serve.prefill_fetch",
    "serve.decode_prep", "serve.decode", "serve.decode_fetch", "serve.idle",
    "serve.counters",
)
STEP_CHILDREN = SERVE_SPANS[2:7]


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from tf_operator_tpu.models.transformer import init_transformer, preset
    from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine

    cfg = preset("tiny")
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    # four slots, but a pool that never holds more than three of _requests()
    # at once: their queue waits for pages, never for a slot
    scfg = ServeConfig(page_size=8, pool_pages=6, max_slots=4, prefill_chunk=8)
    return ServeEngine(cfg, params, scfg)


def _requests():
    from tf_operator_tpu.serve.engine import Request

    # six at once (a full batch and a queue), then one after the engine has
    # drained (an idle sleep); prompts of one and of two chunks, one padded
    reqs = [Request(rid=i, prompt=[1 + i] * (5 + 3 * i), max_new=3 + i % 3)
            for i in range(6)]
    return reqs + [Request(rid=6, prompt=[9] * 8, max_new=2, arrival=1e9)]


def _run(engine, monkeypatch, on_event=None, requests=None):
    """With a clock that jumps past the last arrival when the idle engine
    sleeps: the run does not depend on the host's speed."""
    from types import SimpleNamespace

    from tf_operator_tpu.serve import engine as engine_module

    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    def sleep(_):
        t[0] = 2e9

    monkeypatch.setattr(engine_module, "time", SimpleNamespace(
        sleep=sleep, perf_counter=time.perf_counter))
    return engine.run(requests or _requests(), clock=clock, on_event=on_event)


def _host_spans(trace_dir, prefix):
    """{thread line index: [(name, start_ns, end_ns, attrs)]} of one trace."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            found = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                     for e in line.events if e.name.startswith(prefix)]
            if found:
                out[(plane.name, i)] = found
    return out


@pytest.fixture(scope="module")
def traced(tiny_engine, tmp_path_factory):
    """One engine run under a profiler session, and the same run without."""
    import jax

    mp = pytest.MonkeyPatch()
    try:
        plain = _run(tiny_engine, mp)
        trace_dir = tmp_path_factory.mktemp("serve_trace")
        with jax.profiler.trace(str(trace_dir)):
            res = _run(tiny_engine, mp)
    finally:
        mp.undo()
    (spans,) = _host_spans(trace_dir, "serve.").values()  # one serving thread
    return plain, res, spans


@pytest.mark.serve
@pytest.mark.parametrize("name", SERVE_SPANS)
def test_every_engine_span_is_in_the_trace(traced, name):
    _, _, spans = traced
    assert any(n == name for n, *_ in spans)


@pytest.mark.serve
def test_step_children_lie_inside_a_step_and_admit_and_idle_outside(traced):
    _, res, spans = traced
    steps = [(a, b) for n, a, b, _ in spans if n == "serve.step"]
    assert len(steps) == res.steps
    for n, a, b, _ in spans:
        if n != "serve.step":
            inside = any(sa <= a and b <= sb for sa, sb in steps)
            assert inside == (n in STEP_CHILDREN), n


@pytest.mark.serve
def test_counters_in_the_trace_equal_the_run_results(traced):
    from dataclasses import asdict

    _, res, spans = traced
    (written,) = [attrs for n, _, _, attrs in spans if n == "serve.counters"]
    assert written == dict(asdict(res.counters),
                           pool_peak_in_use=res.pool_peak_in_use,
                           pool_alloc_failures=res.pool_alloc_failures)
    # ... and the spans' attributes add up to the counters
    by = {}
    for n, _, _, attrs in spans:
        by.setdefault(n, []).append(attrs)
    c = res.counters
    assert len(by["serve.prefill"]) == c.prefill_chunks
    assert sum(a["n_valid"] for a in by["serve.prefill"]) == c.prefill_tokens \
        == sum(len(r.prompt) for r in res.requests)
    assert sum(a["chunk"] - a["n_valid"] for a in by["serve.prefill"]) == c.prefill_padded
    assert sum(a["kv_pages"] for a in by["serve.prefill"]) == c.prefill_kv_pages > 0
    assert sum(a["last"] for a in by["serve.prefill"]) == len(by["serve.prefill_fetch"]) \
        == len(res.requests) == c.admitted
    assert len(by["serve.decode"]) == c.decode_steps
    assert sum(a["active"] for a in by["serve.decode"]) == c.decode_slot_tokens
    assert c.prefill_chunks and c.prefill_padded and c.decode_steps
    assert c.decode_slot_tokens + c.admitted == res.generated_tokens
    assert len(by["serve.idle"]) == c.idle_sleeps == 1
    # every program run but the first of each busy stretch went to the device
    # before the host had read the run before it
    assert written["runs_enqueued_ahead"] == c.runs_enqueued_ahead \
        == c.prefill_chunks + c.decode_steps - 2
    assert [a["step"] for a in by["serve.step"]] == list(range(1, res.steps + 1))
    for a in by["serve.step"]:
        assert a["occupied"] <= 3 and a["kv_tokens"] <= a["kv_reserved"]
        assert a["kv_reserved"] <= 8 * res.pool_peak_in_use
    # behind each of the two busy stretches ONE step that enqueues nothing and
    # collects what is in flight, before the engine sleeps or returns; the
    # late request's chunk and its only decode run are a step each between
    # them (a sequence decodes from the step AFTER its last chunk's)
    assert [a["step"] for a in by["serve.step"] if not a["occupied"]] \
        == [res.steps - 3, res.steps]
    # a chunk beside decoding slots carried their rows: one run, counted as
    # the chunk and the decode step it is
    assert 0 < c.chunks_carrying_decode <= min(c.prefill_chunks, c.decode_steps)
    inside = [(a, b) for n, a, b, _ in spans if n == "serve.prefill"]
    assert sum(any(pa <= a and b <= pb for pa, pb in inside)
               for n, a, b, _ in spans if n == "serve.decode") == c.chunks_carrying_decode
    # four slots, a pool for three of these requests: the queue's head waited
    # for pages while a slot was free, never for a slot
    assert c.blocked_on_pool > 0 and c.blocked_on_slots == 0
    assert res.pool_alloc_failures == c.blocked_on_pool
    assert 0 < res.pool_peak_in_use <= 6


@pytest.mark.serve
def test_a_profiler_session_changes_no_served_token(traced):
    plain, res, _ = traced
    assert [r.tokens for r in plain.requests] == [r.tokens for r in res.requests]
    assert plain.counters == res.counters and plain.steps == res.steps


@pytest.mark.serve
def test_blocked_on_slots_counts_a_full_batch(tiny_engine):
    from tf_operator_tpu.serve.engine import Request

    reqs = [Request(rid=i, prompt=[3] * 4, max_new=2) for i in range(6)]
    res = tiny_engine.run(reqs)  # one page each: the pool is never short
    assert res.completed == 6 and res.counters.blocked_on_slots > 0
    assert res.counters.blocked_on_pool == res.pool_alloc_failures == 0
    assert res.pool_peak_in_use == 4


@pytest.mark.serve
def test_a_run_cut_from_on_event_leaves_no_span_open(tiny_engine, tmp_path,
                                                     monkeypatch):
    """The benchmark's above-capacity cell closes its window by raising from
    the step callback."""
    import jax

    class Cut(Exception):
        pass

    seen = {}

    def on_event(kind, payload):
        if kind == "step":
            seen.update(step=payload["step"], counters=payload["counters"])
            if payload["step"] == 4:
                raise Cut

    requests = _requests()
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(Cut):
            _run(tiny_engine, monkeypatch, on_event, requests)
        with jax.profiler.TraceAnnotation("serve.after"):
            pass
    (spans,) = _host_spans(tmp_path, "serve.").values()
    after = next(a for n, a, _, _ in spans if n == "serve.after")
    # every span closed before the exception left run(): each ends before
    # the marker written right after, and the fourth step holds its children
    for n, a, b, _ in spans:
        assert n == "serve.after" or b <= after, n
    assert sum(n == "serve.step" for n, *_ in spans) == 4
    from dataclasses import asdict

    (written,) = [attrs for n, _, _, attrs in spans if n == "serve.counters"]
    assert {k: written[k] for k in asdict(seen["counters"])} == asdict(seen["counters"])
    assert seen["step"] == 4 and written["decode_steps"] > 0
    # the cut leaves a step in flight: its tokens never reach the host, and a
    # request is finished only when its last token has — none reads finished
    # with a short list, and what the host holds is stamped token for token
    assert written["decode_slot_tokens"] + written["admitted"] \
        > sum(len(r.tokens) for r in requests) > 0
    for r in requests:
        assert len(r.tokens) == len(r.token_times) <= r.max_new
        assert (r.finished >= 0) == (len(r.tokens) == r.max_new)
    assert any(r.finished >= 0 for r in requests) \
        and any(r.tokens and r.finished < 0 for r in requests)


# ---- train/data.py and train/trainer.py ------------------------------------


def _slow_source(n, delay_s):
    import numpy as np

    for i in range(n):
        time.sleep(delay_s)
        yield {"x": np.full((2, 2), i, np.float32)}


def test_device_loader_counts_its_waits_with_a_slow_source():
    """A source that yields only 50 ms after each pull began: every pull
    finds nothing staged and waits that long."""
    import threading

    import jax
    import numpy as np

    from tf_operator_tpu.train.data import DeviceLoader

    go = threading.Semaphore(0)

    def source():
        for i in range(3):
            go.acquire()
            yield {"x": np.full((2, 2), i, np.float32)}

    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    with DeviceLoader(source(), sharding) as loader:
        assert (loader.batches, loader.wait_s, loader.empty_pulls) == (0, 0.0, 0)
        for i in range(3):
            threading.Timer(0.05, go.release).start()
            assert int(next(loader)["x"][0, 0]) == i
        assert loader.batches == 3 and loader.empty_pulls == 3
        assert 0.14 <= loader.wait_s < 30.0


def test_device_loader_does_not_wait_for_a_source_that_is_ahead():
    import jax

    from conftest import wait_for
    from tf_operator_tpu.train.data import DeviceLoader

    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    with DeviceLoader(_slow_source(6, 0.0), sharding, prefetch=2) as loader:
        pulled_s = 0.0
        for _ in range(3):  # a slow consumer: the stager fills the queue first
            assert wait_for(lambda: loader._q.qsize() == 2)
            t0 = time.perf_counter()
            next(loader)
            pulled_s += time.perf_counter() - t0
        assert loader.batches == 3 and loader.empty_pulls == 0
        assert 0.0 < loader.wait_s <= pulled_s


def test_loader_and_trainer_spans_are_in_the_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from tf_operator_tpu.parallel.mesh import build_mesh
    from tf_operator_tpu.train.data import DeviceLoader
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

    mesh = build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, batch, extra: jnp.mean((batch["x"] @ p["w"]) ** 2),
        init_fn=lambda k: {"w": jax.random.normal(k, (2, 2))},
        config=TrainerConfig(optimizer="sgd", learning_rate=0.1),
    )
    state = trainer.init(jax.random.PRNGKey(0))
    with DeviceLoader(_slow_source(6, 0.01), trainer.batch_sharding) as loader:
        state, m = trainer.step(state, next(loader))  # compiles, untraced
        with jax.profiler.trace(str(tmp_path)):
            for batch in loader:
                state, m = trainer.step(state, batch)
            jax.block_until_ready(m["loss"])
    by_thread = _host_spans(tmp_path, "train.")
    names = {n for spans in by_thread.values() for n, *_ in spans}
    assert names == {"train.data_wait", "train.data_stage", "train.step"}
    consumer = next(s for s in by_thread.values()
                    if any(n == "train.step" for n, *_ in s))
    # the host-side call count, not the device's state.step
    assert [a["call"] for n, _, _, a in consumer if n == "train.step"] == [2, 3, 4, 5, 6]
    waits = [a for n, _, _, a in consumer if n == "train.data_wait"]
    assert len(waits) == 6 and all("queued" in a for a in waits)  # the last finds the end
    stager = next(s for s in by_thread.values()
                  if any(n == "train.data_stage" for n, *_ in s))
    assert stager is not consumer  # the transfer is on the stager's thread


# ---- the serve workload: the operator's reading of the same counters --------


class _StubContext:
    """What workloads.serve.main asks of a JobContext, recorded."""

    process_id = 0
    job_name = "serve-spans"
    trace_id = "0123456789abcdef"

    def __init__(self, workload):
        self.workload = workload
        self.reports, self.spans = [], []

    def initialize_distributed(self):
        pass

    def mark_first_step(self, step):
        pass

    def report_eval_metrics(self, step, metrics):
        self.reports.append((step, dict(metrics)))
        return True

    def record_span(self, op, start, end, attrs=None, name=None):
        self.spans.append(op)


@pytest.mark.serve
def test_serve_workload_reports_the_counters_and_honours_profile_dir(tmp_path):
    from tf_operator_tpu.workloads import serve as workload

    ctx = _StubContext({
        "preset": "tiny", "requests": 5, "prompt_len": 6, "max_new_tokens": 4,
        "arrival_rate": 0.0, "kv_page_size": 8, "kv_pool_pages": 32,
        "max_slots": 2, "prefill_chunk": 8, "report_every": 2,
        "profile_dir": str(tmp_path),
    })
    workload.main(ctx)
    assert len(ctx.reports) > 1
    for _, metrics in ctx.reports:  # live and final: the same names
        assert metrics["engine_admitted"] <= 5 and "engine_decode_steps" in metrics
    last = ctx.reports[-1][1]
    assert last["engine_admitted"] == last["requests_completed"] == 5.0
    assert last["engine_blocked_on_slots"] > 0  # five requests, two slots
    assert last["engine_pool_peak_in_use"] > 0 and last["engine_pool_alloc_failures"] == 0
    assert last["engine_prefill_tokens"] + last["engine_prefill_padded"] \
        == 8 * last["engine_prefill_chunks"]
    (spans,) = _host_spans(tmp_path, "serve.").values()  # under <dir>/<process index>
    assert sum(n == "serve.step" for n, *_ in spans) == ctx.reports[-1][0]
    (written,) = [a for n, _, _, a in spans if n == "serve.counters"]
    assert written["admitted"] == 5 and written["pool_peak_in_use"] == last["engine_pool_peak_in_use"]


# ---- the LM workload: a step's routing counters beside its loss ---------------


class _LmStubContext(_StubContext):
    job_name = "lm-moe-counters"

    def build_mesh(self):
        import jax

        from tf_operator_tpu.parallel.mesh import build_mesh

        return build_mesh({"dp": 1}, devices=jax.devices()[:1])


def test_lm_workload_reports_the_routing_counters(caplog, tmp_path):
    """gmm-dispatched experts: the four ``moe_*`` counters of the last step
    are in the ``run report`` and on ``eval_metrics``; they left the step
    as ``TrainState.extra``, not through a sync of their own. Beside them
    ``step_sections``: the compiled step's instructions by section, and a
    capture (``profile_dir``) holds them as its ``train.program`` span."""
    import json
    import logging

    from tf_operator_tpu.workloads import lm as workload

    ctx = _LmStubContext({
        "preset": "tiny-moe", "moe_dispatch": "gmm", "moe_top_k": 2,
        "steps": 3, "batch_size": 2, "seq_len": 32})
    with caplog.at_level(logging.INFO, logger="tpujob.lm"):
        workload.main(ctx)
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("run report: ")]
    report = json.loads(line[len("run report: "):])
    counts = report["step_sections"]["instructions"]
    assert {"embed", "stack", "attn_proj", "attn_core", "router", "moe_dispatch",
            "moe_experts", "mlp", "head_ce", "optimizer", "none"} == set(counts)
    assert all(n > 0 for n in counts.values())
    assert 0 < report["step_sections"]["parse_s"] < 0.5
    moe = report["moe"]
    assert set(moe) == {"moe_routed_here", "moe_rows_computed",
                        "moe_held_load_max", "moe_held_load_mean",
                        "moe_rows_walked", "moe_rows_bound"}
    # 2 layers x 64 tokens x top-2, every expert held: nothing routed away
    assert moe["moe_routed_here"] == 2 * 64 * 2
    assert moe["moe_rows_computed"] % 256 == 0 and moe["moe_rows_computed"] >= 256
    # every expert held: the segment is the whole lossless bound, in both layers
    assert moe["moe_rows_walked"] == moe["moe_rows_bound"] == 2 * (1 + 4) * 256
    assert moe["moe_held_load_mean"] == 2 * 64 * 2 / 4
    assert ctx.reports[-1] == (3, moe)
    # a dense run has no such entry and reports nothing
    dense = _LmStubContext({"preset": "tiny", "steps": 2, "batch_size": 2,
                            "seq_len": 16, "profile_dir": str(tmp_path)})
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="tpujob.lm"):
        workload.main(dense)
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("run report: ")]
    report = json.loads(line[len("run report: "):])
    assert report["moe"] is None and not dense.reports
    assert "moe_dispatch" not in report["step_sections"]["instructions"]
    (spans,) = _host_spans(tmp_path, "train.program").values()
    ((_, _, _, attrs),) = spans  # once a capture
    assert attrs["program"] == "jit__step_body" and "optimizer.optimizer" in attrs


@pytest.mark.parametrize("remat, clones, warned", [
    ("save_mid", 2, True), ("save_mid", 0, False), ("full", 2, False)])
def test_lm_workload_reports_the_compilers_own_remats(
        caplog, monkeypatch, remat, clones, warned):
    """``step_remats`` (the compiled step's ``.remat`` clones) is in the
    ``run report`` beside ``step_kernels``, and a names policy that
    compiles with any is warned about: the step runs, slower than a set
    that fits (here the count is planted: the CPU's compiler makes none)."""
    import json
    import logging

    from tf_operator_tpu.train import trainer
    from tf_operator_tpu.workloads import lm as workload

    monkeypatch.setattr(trainer, "compiled_remats", lambda text: clones)
    ctx = _LmStubContext({"preset": "tiny", "remat": remat, "steps": 2,
                          "batch_size": 2, "seq_len": 16})
    with caplog.at_level(logging.INFO, logger="tpujob.lm"):
        workload.main(ctx)
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("run report: ")]
    report = json.loads(line[len("run report: "):])
    assert report["step_remats"] == clones and report["step_kernels"] == {}
    warnings = [r.getMessage() for r in caplog.records
                if r.levelno == logging.WARNING and "rematerialises" in r.getMessage()]
    assert bool(warnings) == warned, warnings
