"""Serve subsystem tests (r10): paged-KV engine correctness (completion,
leak-freedom, determinism, admission validation), the serve spec/CLI
surface, serving-class scheduling priority, and memplan's KV-pool
accounting. The decode-vs-full attention numerics oracle lives in
tests/test_flash_decode.py; the kernel itself in test_flash_attention."""

import pytest

import tools.memplan as memplan
from tf_operator_tpu.api.defaults import set_defaults
from tf_operator_tpu.api.types import (
    JOB_CLASS_SERVING,
    JOB_CLASS_TRAINING,
    ObjectMeta,
    ReplicaType,
)
from tf_operator_tpu.api.validation import ValidationError, validate_job
from tf_operator_tpu.cli.tpujob import _parse_override, build_parser
from tf_operator_tpu.runtime.scheduler import GangScheduler
from tf_operator_tpu.runtime.store import Store
from tf_operator_tpu.sched.fleet import SERVING_DEFAULT_PRIORITY, FleetScheduler
from tf_operator_tpu.sched.objects import PriorityClass
from tf_operator_tpu.serve.kvcache import (
    PagePool,
    PoolExhausted,
    SequencePages,
    pages_needed,
)
from tf_operator_tpu.serve.spec import build_serve_job

# ---- kv cache bookkeeping (pure python, no jax) ---------------------------


def test_pages_needed_rounds_up():
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2
    assert pages_needed(0, 8) == 1  # a live sequence always owns a page


def test_pool_alloc_free_roundtrip():
    pool = PagePool(4)
    start = pool.free_count
    pages = pool.alloc(3)
    assert len(set(pages)) == 3 and pool.free_count == start - 3
    pool.free(pages)
    assert pool.free_count == start


def test_pool_exhaustion_is_atomic():
    """A failed alloc must not leak a partial grab."""
    pool = PagePool(2)
    start = pool.free_count
    with pytest.raises(PoolExhausted):
        pool.alloc(start + 1)
    assert pool.free_count == start


def test_sequence_pages_grow_and_release():
    pool = PagePool(8)
    start = pool.free_count
    sp = SequencePages(page_size=4)
    sp.ensure(5, pool)  # 2 pages
    assert sp.capacity >= 5
    held = len(sp.pages)
    sp.ensure(3, pool)  # no shrink, no new alloc
    assert len(sp.pages) == held
    sp.release(pool)
    assert pool.free_count == start and not sp.pages


# ---- engine: completion, leaks, determinism -------------------------------


def _fake_clock(dt=0.001):
    """Deterministic clock: admission order can't depend on host speed."""
    t = [0.0]

    def clock():
        t[0] += dt
        return t[0]

    return clock


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from tf_operator_tpu.models.transformer import init_transformer, preset
    from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine

    cfg = preset("tiny")
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    scfg = ServeConfig(page_size=8, pool_pages=48, max_slots=3,
                       prefill_chunk=8)
    return ServeEngine(cfg, params, scfg)


def _requests(n=7, seed=3):
    from tf_operator_tpu.workloads.serve import synthesize_requests

    return synthesize_requests(
        {"requests": n, "seed": seed, "prompt_len": 6, "max_new_tokens": 6,
         "arrival_rate": 0.0},
        vocab=256,
    )


@pytest.mark.serve
def test_engine_completes_all_requests_without_leaks(tiny_engine):
    res = tiny_engine.run(_requests(), clock=_fake_clock())
    assert res.completed == len(res.requests)
    assert res.free_pages_start == res.free_pages_end  # zero page leaks
    assert res.generated_tokens == sum(len(r.tokens) for r in res.requests)
    for r in res.requests:
        assert 1 <= len(r.tokens) <= r.max_new
        assert 0 <= r.arrival <= r.admitted <= r.first_token <= r.finished


HLO_POOL = "f32[2,9,2,8,16]{4,3,2,1,0}"
HLO_LAYER = "f32[9,2,8,16]{3,2,1,0}"
HLO_CASES = {
    # what the engine's programs hold today: names, views, updates in place
    "in_place": (0, f"""
%fused_scatter (p0: f32[2,9,2,8,16], p1: s32[4,4], p2: f32[4,16]) -> f32[2,9,2,8,16] {{
  %p0 = {HLO_POOL} parameter(0)
  ROOT %scatter.0 = {HLO_POOL} scatter(%p0, %p1, %p2), to_apply=%add
}}

%body (arg: (s32[], f32[2,9,2,8,16])) -> (s32[], f32[2,9,2,8,16]) {{
  %gte.1 = {HLO_POOL} get-tuple-element(%arg), index=1
  %dus.1 = {HLO_POOL} dynamic-update-slice(%gte.1, %u, %i, %j, %k, %l, %m)
}}

ENTRY %main (kp: f32[2,9,2,8,16]) -> f32[2,9,2,8,16] {{
  %kp = {HLO_POOL} parameter(0)
  %fusion.2 = {HLO_POOL} fusion(%kp, %idx, %upd), kind=kCustom, calls=%fused_scatter
  %bitcast.1 = {HLO_POOL} bitcast(%flat)
  %write.1 = {HLO_POOL} custom-call(%fusion.2, %k), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{{{}}: (0, {{}})}}
  ROOT %out = {HLO_POOL} get-tuple-element(%while.1), index=1
}}
"""),
    # what they held before PR 25: a relayout of the pool in and out, and
    # a slice + relayout fusion of a whole layer in front of a kernel
    "relaid": (5, f"""
%fused_slice (p0: f32[2,9,2,8,16]) -> f32[9,2,8,16] {{
  %p0 = f32[2,9,2,8,16]{{4,2,3,1,0}} parameter(0)
  %slice.9 = f32[1,9,2,8,16]{{4,2,3,1,0}} slice(%p0), slice={{[1:2], [0:9], [0:2], [0:8], [0:16]}}
  ROOT %bitcast.9 = {HLO_LAYER} bitcast(%slice.9)
}}

ENTRY %main (kp: f32[2,9,2,8,16]) -> f32[2,9,2,8,16] {{
  %kp = {HLO_POOL} parameter(0)
  %copy.1 = f32[2,9,2,8,16]{{4,2,3,1,0}} copy(%kp)
  %slice.2 = f32[1,9,2,8,16]{{4,2,3,1,0:T(8,128)S(1)}} slice(%copy.1), slice={{[0:1], [0:9], [0:2], [0:8], [0:16]}}
  %copy_bitcast_fusion = {HLO_LAYER} fusion(%slice.2), kind=kLoop, calls=%fused_slice
  %slice_bitcast_fusion = {HLO_LAYER} fusion(%copy.1), kind=kLoop, calls=%fused_slice
  ROOT %copy.2 = {HLO_POOL} copy(%copy.1)
}}
"""),
    # a pool staged through fast memory is a copy too; its start is not a second one
    "staged": (1, f"""
ENTRY %main (kp: f32[2,9,2,8,16]) -> f32[2,9,2,8,16] {{
  %kp = {HLO_POOL} parameter(0)
  %copy-start.3 = ({HLO_POOL}, {HLO_POOL}, u32[]) copy-start(%kp)
  ROOT %copy-done.3 = f32[2,9,2,8,16]{{4,3,2,1,0:S(1)}} copy-done(%copy-start.3)
}}
"""),
}


@pytest.mark.serve
@pytest.mark.parametrize("case", sorted(HLO_CASES))
def test_pool_copies_counts_what_moves_a_pool_or_a_layer(case):
    """``compile()``'s ``<program>_pool_copies`` on hand-written HLO: the
    executed instructions whose result is a whole pool side or layer and
    that are not a name, a view or an update in place. Other shapes (a
    weight, the same dims in another order) never count."""
    from tf_operator_tpu.serve.engine import pool_copies

    want, text = HLO_CASES[case]
    assert pool_copies(text, (2, 9, 2, 8, 16)) == want
    assert pool_copies(text, (2, 9, 8, 2, 16)) == 0


@pytest.mark.serve
def test_engine_is_deterministic(tiny_engine):
    a = tiny_engine.run(_requests(), clock=_fake_clock())
    b = tiny_engine.run(_requests(), clock=_fake_clock())
    assert [r.tokens for r in a.requests] == [r.tokens for r in b.requests]


@pytest.mark.serve
@pytest.mark.parametrize("compiled", [False, True], ids=["jit", "aot"])
def test_engine_agrees_with_unpaged_greedy_decoding(tiny_engine, compiled):
    """The engine against the MODEL (not only attention against
    attention): paged, cached, chunk-prefilled decoding picks the tokens
    plain greedy decoding through transformer_forward picks — through
    the lazy jit path and through the AOT executables ``compile()``
    installs (what a warmed-up server runs)."""
    from tf_operator_tpu.serve.engine import greedy_reference_gaps

    if compiled:
        report = tiny_engine.compile()
        assert report["decode_tpu_custom_calls"] == 0  # CPU: no Mosaic
        assert report["decode_compile_s"] >= 0
        # neither program copies or slices a whole pool side or layer
        assert report["decode_pool_copies"] == 0
        assert report["prefill_pool_copies"] == 0
    res = tiny_engine.run(_requests(), clock=_fake_clock())
    for req in res.requests[:3]:
        n_exact, max_gap = greedy_reference_gaps(
            tiny_engine.cfg, tiny_engine.params, req.prompt, req.tokens
        )
        assert n_exact == len(req.tokens) and max_gap == 0.0


CHUNK_PROMPTS = (5, 19, 37, 40, 64)  # one chunk to eight; ragged and exact ends


def _chunk_engine(tiny_engine, chunk, page=8):
    from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine

    return ServeEngine(
        tiny_engine.cfg, tiny_engine.params,
        ServeConfig(page_size=page, pool_pages=48, max_slots=3,
                    prefill_chunk=chunk))


def _chunk_requests():
    import numpy as np

    from tf_operator_tpu.serve.engine import Request

    rng = np.random.RandomState(11)
    return [Request(rid=i, prompt=[int(t) for t in rng.randint(1, 256, n)],
                    max_new=5)
            for i, n in enumerate(CHUNK_PROMPTS)]


@pytest.mark.serve
@pytest.mark.parametrize("chunk", [8, 32])
def test_chunked_prefill_picks_the_unchunked_oracles_tokens(tiny_engine, chunk):
    """A chunk is ONE query tile over its sequence's pages: prompts of one
    chunk to eight, ending mid-chunk, mid-page and on both boundaries,
    prefilled 8 or 32 positions at a time, continue with the tokens plain
    un-paged, un-chunked greedy decoding picks."""
    from tf_operator_tpu.serve.engine import greedy_reference_gaps

    res = _chunk_engine(tiny_engine, chunk).run(
        _chunk_requests(), clock=_fake_clock())
    assert res.completed == len(CHUNK_PROMPTS)
    assert res.free_pages_start == res.free_pages_end
    for req in res.requests:
        n_exact, max_gap = greedy_reference_gaps(
            tiny_engine.cfg, tiny_engine.params, req.prompt, req.tokens)
        assert n_exact == len(req.tokens) and max_gap == 0.0, req.rid


@pytest.mark.serve
@pytest.mark.parametrize("chunk,page", [(8, 8), (32, 8), (8, 16)])
def test_prefill_kv_pages_is_its_arithmetic(tiny_engine, chunk, page):
    """``prefill_kv_pages``: the pages of K/V each chunk's attention walks
    — the sequence's prefix up to the chunk's last valid position, rounded
    up to pages — counted once a chunk."""
    res = _chunk_engine(tiny_engine, chunk, page).run(
        _chunk_requests(), clock=_fake_clock())
    ends = [min(start + chunk, n) for n in CHUNK_PROMPTS
            for start in range(0, n, chunk)]
    c = res.counters
    assert c.prefill_chunks == len(ends)
    assert c.prefill_tokens == sum(CHUNK_PROMPTS)
    assert c.prefill_kv_pages == sum(-(-end // page) for end in ends)
    # a request's last chunk walks its whole prompt
    assert c.prefill_kv_pages >= sum(-(-n // page) for n in CHUNK_PROMPTS)


# The loop enqueues step n+1 on counts before it reads step n's tokens: the
# cases the split into enqueue and collect can get wrong, each against plain
# un-paged, un-chunked greedy decoding. (prompt length, max_new) a request,
# all due at once; page 8, chunk 8.
LOOKAHEAD_CASES = {
    # one slot: the next tenant's first chunk is enqueued while the old
    # tenant's last token is still unfetched
    "slot_readmitted_in_the_next_step": dict(
        slots=1, pool=48, reqs=[(5, 3), (11, 4), (8, 2), (3, 5)]),
    # finished at the prefill's fetch: no decode run ever carries the slot
    "max_new_1": dict(slots=2, pool=48, reqs=[(5, 1), (19, 1), (8, 3), (16, 1)]),
    "prompts_of_exactly_k_chunks": dict(
        slots=3, pool=48, reqs=[(8, 4), (16, 4), (24, 4), (32, 2)]),
    # three slots, pages for one request: admission waits for the pages that
    # a COUNTED finish releases, never for a slot
    "pool_for_one_request": dict(slots=3, pool=3, reqs=[(12, 4), (9, 7), (16, 2)]),
    "hybrid": dict(slots=2, pool=48, hybrid=True,
                   reqs=[(7, 4), (17, 1), (16, 6), (33, 3)]),
}


@pytest.mark.serve
@pytest.mark.parametrize("case", sorted(LOOKAHEAD_CASES))
def test_lookahead_serves_the_unchunked_oracles_tokens(tiny_engine, case):
    import jax
    import numpy as np

    from tf_operator_tpu.serve.engine import (
        Request,
        ServeConfig,
        ServeEngine,
        greedy_reference_gaps,
    )

    spec = LOOKAHEAD_CASES[case]
    cfg, params, tol = tiny_engine.cfg, tiny_engine.params, 0.0
    if spec.get("hybrid"):  # linear layers beside the attending ones
        from test_olmo_hybrid import LOGIT_TOL as tol, TINY

        from tf_operator_tpu.models import transformer as tr

        cfg = tr.preset("olmo-hybrid-7b", **TINY)
        params = jax.jit(lambda k: tr.init_transformer(k, cfg))(jax.random.PRNGKey(5))
    engine = ServeEngine(cfg, params, ServeConfig(
        page_size=8, pool_pages=spec["pool"], max_slots=spec["slots"],
        prefill_chunk=8))
    rng = np.random.RandomState(17)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.randint(1, 256, n)],
                    max_new=m) for i, (n, m) in enumerate(spec["reqs"])]
    res = engine.run(reqs, clock=_fake_clock())
    assert res.completed == len(reqs)
    assert res.free_pages_start == res.free_pages_end  # pages all returned
    assert res.generated_tokens == sum(m for _, m in spec["reqs"])
    for req in reqs:
        assert req.finished >= 0 and len(req.tokens) == req.max_new
        assert req.arrival <= req.admitted <= req.first_token <= req.finished
        assert req.token_times == sorted(req.token_times)
        assert (req.token_times[0], req.token_times[-1]) == (req.first_token, req.finished)
        n_exact, max_gap = greedy_reference_gaps(cfg, engine.params, req.prompt, req.tokens)
        assert max_gap <= tol and (tol or n_exact == len(req.tokens)), req.rid
    # a busy engine never waits for a token before it enqueues the next run:
    # every run but the first went out ahead, the re-admissions included
    c = res.counters
    assert c.runs_enqueued_ahead == c.prefill_chunks + c.decode_steps - 1
    assert c.idle_sleeps == 0
    if case == "pool_for_one_request":
        assert c.blocked_on_pool > 0 and c.blocked_on_slots == 0
        assert res.pool_peak_in_use <= 3
    if case == "slot_readmitted_in_the_next_step":
        assert c.blocked_on_slots > 0 and c.blocked_on_pool == 0


# A run that carries a chunk also carries a decode row for every slot that
# decodes as the run goes out (one pass over the weights for both). What that
# can get wrong, each case against every request served ALONE: (prompt length
# in chunks c and rows, max_new, arrival on a clock that ticks once a reading).
MIXED_CASES = {
    # chunks of later arrivals ride with the decode rows of earlier ones
    "staggered_arrivals": [((2, 3), 12, 0), ((3, 1), 6, 6), ((0, 5), 5, 14)],
    # a last chunk of 3 valid rows beside decoding slots: padding rows and
    # decode rows both follow the chunk's valid rows in the program
    "short_last_chunk": [((1, 0), 10, 0), ((1, 3), 4, 4), ((2, 3), 3, 4)],
    # two slots prefill in one step while a third decodes: BOTH chunks' runs
    # carry its row (two tokens a step), and the slot whose prompt ends first
    # decodes beside the other's later chunks
    "two_slots_prefill_in_one_step": [((0, 4), 16, 0), ((2, 1), 3, 5), ((3, 0), 3, 5)],
    # nobody decodes beside these chunks (every decode row inactive in all
    # four runs); one request ends at its prefill, the other decodes alone
    "a_chunk_with_nobody_decoding": [((1, 2), 1, 0), ((2, 0), 3, 0)],
}


class _Spy:
    """Both programs of an engine wrapped for the length of a ``with``: every
    call is noted with what it carried — ("prefill", slot, start, n_valid,
    the chunk's tokens, active decode rows) or ("decode", active rows) — a
    step's end as ("step",), and the state the last call returned is kept."""

    def __init__(self, engine):
        self.engine, self.log, self.state = engine, [], None

    def __enter__(self):
        import numpy as np

        self.saved = self.engine._prefill, self.engine._decode

        def prefill(*args):
            row, start, tokens_c, n_valid, slot, table, lens, toks, active = args[3:]
            self.log.append(("prefill", int(slot), int(start), int(n_valid),
                             np.array(tokens_c), int(np.sum(active))))
            out = self.saved[0](*args)
            self.state = out[1]
            return out

        def decode(*args):
            self.log.append(("decode", int(np.sum(args[-1]))))
            out = self.saved[1](*args)
            self.state = out[1]
            return out

        self.engine._prefill, self.engine._decode = prefill, decode
        return self

    def __exit__(self, *exc):
        self.engine._prefill, self.engine._decode = self.saved

    def on_event(self, kind, payload):
        if kind == "step":
            self.log.append(("step",))

    def steps(self):
        """The program calls of each engine step, in order."""
        out, cur = [], []
        for entry in self.log:
            if entry[0] == "step":
                out.append(cur)
                cur = []
            else:
                cur.append(entry)
        return out

    def slot_of(self, req):
        """The batch slot ``req`` prefilled in: its first chunk's."""
        import numpy as np

        (slot,) = {e[1] for e in self.log if e[0] == "prefill" and e[2] == 0
                   and np.array_equal(e[4][: e[3]], req.prompt[: e[3]])}
        return slot


def check_mixed_case(engine, case):
    """Serve ``MIXED_CASES[case]`` through ``engine`` together, then each
    request alone: the same tokens, token for token; for a model with linear
    layers the same recurrent state and convolution tail in the sequence's
    slot; the counters are their arithmetic; the case's situation occurred."""
    import numpy as np

    from tf_operator_tpu.serve.engine import Request

    c = engine.scfg.prefill_chunk
    rng = np.random.RandomState(23)

    def make():
        return [Request(rid=i, prompt=[int(t) for t in rng.randint(1, 256, k * c + r)],
                        max_new=m, arrival=float(at))
                for i, ((k, r), m, at) in enumerate(MIXED_CASES[case])]

    state0 = rng.get_state()
    together = make()
    ticks = iter(range(10**6))
    with _Spy(engine) as spy:
        res = engine.run(together, clock=lambda: float(next(ticks)),
                         on_event=spy.on_event)
    assert res.completed == len(together)
    assert res.free_pages_start == res.free_pages_end  # zero page leaks
    ctr = res.counters
    calls = [e for e in spy.log if e[0] != "step"]
    chunks = [e for e in calls if e[0] == "prefill"]
    carrying = [e for e in chunks if e[5]]
    assert ctr.prefill_chunks == len(chunks)
    assert ctr.chunks_carrying_decode == len(carrying) <= ctr.prefill_chunks
    assert ctr.decode_steps == len(calls) - len(chunks) + len(carrying)
    assert ctr.runs_enqueued_ahead <= ctr.prefill_chunks + ctr.decode_steps
    # a request's first token is its last chunk's, every other a decode row's
    assert ctr.decode_slot_tokens == sum(r.max_new - 1 for r in together) \
        == sum(e[-1] for e in calls)
    n_lin = 0 if engine.store is None else engine.store.n_layers
    assert ctr.lin_slot_steps == n_lin * ctr.decode_slot_tokens
    steps = spy.steps()
    for step in steps:  # a chunk a prefilling slot; the decode step only
        kinds = [e[0] for e in step]  # in a step without one
        assert kinds in ([], ["decode"]) or set(kinds) == {"prefill"}, step
    if case == "staggered_arrivals":
        assert len(carrying) >= 3 and ctr.decode_steps > len(carrying)
    if case == "short_last_chunk":
        assert any(e[3] == 3 for e in carrying)
    if case == "two_slots_prefill_in_one_step":
        assert any(len(step) == 2 and step[0][5] == 1 and step[1][5] == 1
                   for step in steps)
        # the 2c + 1 prompt's last chunk is the FIRST run of its step; the
        # second run of that step already carries its first decode row
        assert any(len(step) == 2 and step[0][3] == 1 and step[1][5] == 2
                   for step in steps)
    if case == "a_chunk_with_nobody_decoding":
        assert [[e[0] for e in step] for step in steps[:3]] \
            == [["prefill"] * 2, ["prefill"] * 2, ["decode"]] and not carrying

    rng.set_state(state0)
    last_tenant = {spy.slot_of(r): r.rid for r in together}
    for req, alone in zip(together, make()):
        alone.arrival = 0.0
        with _Spy(engine) as spy_alone:
            engine.run([alone])
        assert req.tokens == alone.tokens and len(req.tokens) == req.max_new, req.rid
        slot = spy.slot_of(req)
        if engine.store is None or last_tenant[slot] != req.rid:
            continue
        # float32 sums in another order at most: rows of one product
        for got, want in zip(spy.state, spy_alone.state):
            np.testing.assert_allclose(np.asarray(got[:, slot]), np.asarray(want[:, 0]),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.serve
@pytest.mark.parametrize("case", sorted(MIXED_CASES))
def test_a_mixed_run_serves_each_request_as_if_alone(tiny_engine, case):
    check_mixed_case(_chunk_engine(tiny_engine, 8), case)


@pytest.mark.serve
def test_compile_builds_the_decode_step_and_the_run_that_carries_a_chunk(tiny_engine):
    """Two programs, as before the chunk's run took the decode rows: there is
    no chunk-only program and no third. (The hybrid model's pair, with the
    state store in place: tests/test_olmo_hybrid.py, tests/test_chip_compile.py.)"""
    engine = _chunk_engine(tiny_engine, 8)
    report = engine.compile()
    assert {k.rsplit("_compile_s", 1)[0] for k in report if k.endswith("_compile_s")} \
        == {"decode", "prefill"}
    assert sorted(k.split("_", 1)[1] for k in report if k.startswith("decode_")) \
        == sorted(k.split("_", 1)[1] for k in report if k.startswith("prefill_"))
    assert report["decode_pool_copies"] == 0 == report["prefill_pool_copies"]
    compiled = {k for k, v in vars(engine).items() if hasattr(v, "as_text")}
    assert compiled == {"_decode", "_prefill"}


class _ReadNoted:
    """A program's token array that notes when the host first reads it."""

    def __init__(self, arr, log, n):
        self.arr, self.log, self.n = arr, log, n

    def __array__(self, dtype=None, copy=None):
        import numpy as np

        self.log.append(("read", self.n))
        return np.asarray(self.arr, dtype)


def _recorded(engine, log):
    """Both programs of ``engine`` wrapped: every call is noted with its
    ordinal, and the token array it returns notes its host read."""
    def wrap(program):
        def call(*args):
            n = sum(kind == "call" for kind, _ in log)
            log.append(("call", n))
            pools, state, tok = program(
                *(a.arr if isinstance(a, _ReadNoted) else a for a in args))
            return pools, state, _ReadNoted(tok, log, n)
        return call

    engine._prefill, engine._decode = wrap(engine._prefill), wrap(engine._decode)


@pytest.mark.serve
def test_a_run_is_enqueued_before_the_run_before_it_is_read(tiny_engine):
    """ONE request of P chunks and N tokens alone: P + N - 1 program runs
    (the N - 1 decode runs behind the P chunks), every one but the first
    enqueued while the run before it is unread — ``runs_enqueued_ahead`` =
    P + N - 2 — and never two: the loop is one step deep, and a step of one
    sequence is ONE run (its first decode run is the step AFTER its last
    chunk's). Non-last chunks hand the host nothing to read."""
    from tf_operator_tpu.serve.engine import Request

    chunks, tokens = 3, 6
    engine = _chunk_engine(tiny_engine, 8)
    log = []
    _recorded(engine, log)
    req = Request(rid=0, prompt=[7] * (8 * chunks - 3), max_new=tokens)
    res = engine.run([req], clock=_fake_clock())
    assert len(req.tokens) == tokens and res.completed == 1
    runs = chunks + tokens - 1
    assert [n for kind, n in log if kind == "call"] == list(range(runs))
    # read: the last chunk's array (the first token), then every decode run's
    assert [n for kind, n in log if kind == "read"] == list(range(chunks - 1, runs))
    at = {entry: i for i, entry in enumerate(log)}
    for n in range(chunks - 1, runs - 1):
        assert at["call", n + 1] < at["read", n]
    # from the last chunk on, calls and reads alternate
    for n in range(chunks - 1, runs - 2):
        assert at["read", n] < at["call", n + 2]
    c = res.counters
    assert (c.prefill_chunks, c.decode_steps) == (chunks, tokens - 1)
    assert c.chunks_carrying_decode == 0  # alone: nobody decodes beside a chunk
    assert c.runs_enqueued_ahead == chunks + tokens - 2
    assert res.steps == chunks + tokens  # a run a step + the step that only collects


@pytest.mark.serve
def test_engine_rejects_impossible_requests(tiny_engine):
    from tf_operator_tpu.serve.engine import Request

    with pytest.raises(ValueError, match="empty prompt"):
        tiny_engine.run([Request(rid=0, prompt=[], max_new=1)])
    with pytest.raises(ValueError, match="exceeds max_seq"):
        tiny_engine.run([Request(rid=0, prompt=[1] * 100, max_new=100)])
    # fits max_seq but not the page pool: flagged before serving starts
    # (fresh engine with a 2-page pool; jit builds lazily, so this is cheap)
    from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine

    small = ServeEngine(
        tiny_engine.cfg, tiny_engine.params,
        ServeConfig(page_size=8, pool_pages=2, max_slots=1, prefill_chunk=8),
    )
    with pytest.raises(ValueError, match="never be admitted"):
        small.run([Request(rid=0, prompt=[1] * 30, max_new=8)])


# ---- spec validation / defaulting -----------------------------------------


def test_serve_spec_validates_clean():
    validate_job(build_serve_job("s1"))


@pytest.mark.parametrize("key,bad,msg", [
    ("kv_page_size", 0, "kv_page_size"),
    ("kv_page_size", "eight", "kv_page_size"),
    ("kv_pool_pages", 0, "kv_pool_pages"),
    ("max_slots", 0, "max_slots"),
])
def test_bad_kv_geometry_rejected_at_submit(key, bad, msg):
    job = build_serve_job("s1", workload={key: bad})
    with pytest.raises(ValidationError, match=msg):
        validate_job(job)


def test_unknown_job_class_rejected():
    job = build_serve_job("s1")
    job.spec.scheduling.job_class = "batchy"
    with pytest.raises(ValidationError, match="job_class"):
        validate_job(job)


def test_serve_entrypoint_defaults_job_class():
    job = build_serve_job("s1")
    job.spec.scheduling.job_class = ""  # submitter said nothing
    set_defaults(job)
    assert job.spec.scheduling.job_class == JOB_CLASS_SERVING
    # an explicit class is never overridden
    job2 = build_serve_job("s2")
    job2.spec.scheduling.job_class = JOB_CLASS_TRAINING
    set_defaults(job2)
    assert job2.spec.scheduling.job_class == JOB_CLASS_TRAINING


# ---- fleet priority -------------------------------------------------------


def _fleet():
    store = Store()
    store.create(PriorityClass(
        metadata=ObjectMeta(name="low", namespace="default"), value=1))
    return FleetScheduler(store, GangScheduler(store))


def test_serving_class_outranks_classless_training():
    fleet = _fleet()
    serve = build_serve_job("s1")
    train = build_serve_job("t1")
    train.spec.scheduling.job_class = JOB_CLASS_TRAINING
    assert fleet.priority_of(serve) == SERVING_DEFAULT_PRIORITY
    assert fleet.priority_of(train) == 0
    assert fleet.priority_of(serve) > fleet.priority_of(train)


def test_explicit_priority_class_beats_serving_default():
    fleet = _fleet()
    serve = build_serve_job("s1", priority="low")
    assert fleet.priority_of(serve) == 1  # named class wins, even downward


# ---- CLI ------------------------------------------------------------------


def test_parse_override_coerces_types():
    assert _parse_override("kv_page_size=8") == ("kv_page_size", 8)
    assert _parse_override("arrival_rate=2.5") == ("arrival_rate", 2.5)
    assert _parse_override("router_f32=false") == ("router_f32", False)
    assert _parse_override("preset=tiny") == ("preset", "tiny")
    with pytest.raises(ValueError):
        _parse_override("no-equals-sign")


def test_submit_workload_serve_builds_valid_job():
    args = build_parser().parse_args([
        "submit", "--workload", "serve", "--name", "edge",
        "--queue", "main", "--set", "kv_page_size=8",
        "--set", "requests=12",
    ])
    from tf_operator_tpu.cli.tpujob import _build_workload_job

    job = _build_workload_job(args)
    assert job.metadata.name == "edge"
    assert job.spec.scheduling.queue == "main"
    assert job.spec.scheduling.job_class == JOB_CLASS_SERVING
    assert job.spec.workload["kv_page_size"] == 8
    assert job.spec.workload["requests"] == 12
    worker = job.spec.replica_specs[ReplicaType.WORKER]
    assert worker.template.entrypoint.startswith(
        "tf_operator_tpu.workloads.serve"
    )
    # the canned job runs on the machine's own backend (on a TPU host,
    # the TPU) and asks for the chip it uses: no platform pin unless the
    # caller passes one
    assert "JAX_PLATFORMS" not in worker.template.env
    assert worker.template.chips_per_process == 1
    pinned = build_serve_job("cpu", env={"JAX_PLATFORMS": "cpu"})
    assert pinned.spec.replica_specs[ReplicaType.WORKER].template.env == {
        "JAX_PLATFORMS": "cpu"
    }
    validate_job(job)


# ---- memplan accounting ---------------------------------------------------


def test_memplan_serve_accounts_kv_pool():
    out = memplan.serve_plan("tiny", {"kv_page_size": 8, "kv_pool_pages": 32})
    assert out["mode"] == "serve"
    assert out["kv_pool_gb"] > 0
    assert out["total_gb"] >= out["params_gb"] + out["kv_pool_gb"]
    assert "warning" not in out


def test_memplan_refuses_unadmittable_pool():
    # tiny max_seq=128 @ page 8 needs 16 pages; a 4-page pool can never
    # admit a max-length sequence — memplan must refuse, not warn-and-pass
    import argparse

    out = memplan.serve_plan("tiny", {"kv_page_size": 8, "kv_pool_pages": 4})
    assert "warning" in out
    rc = memplan._finish_serve(out, argparse.Namespace(hbm_gb=None))
    assert rc == 1


def test_memplan_refuses_over_budget():
    import argparse

    out = memplan.serve_plan(
        "gpt-small", {"kv_page_size": 16, "kv_pool_pages": 4096}
    )
    rc = memplan._finish_serve(out, argparse.Namespace(hbm_gb=0.001))
    assert rc == 1


def test_memplan_detects_serve_workload_doc():
    assert memplan._is_serve_workload(
        {"spec": {"workload": {"kv_pool_pages": 64}}}
    )
    assert memplan._is_serve_workload(
        {"spec": {"scheduling": {"job_class": "serving"}}}
    )
    assert not memplan._is_serve_workload(
        {"spec": {"workload": {"preset": "tiny"}}}
    )
