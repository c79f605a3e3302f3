"""Gated-DeltaNet layers beside paged attention: the whole-sequence model
and the serve engine, each against ``benchmarks/reference_olmo_hybrid.py``
(plain jnp, float32 HIGHEST, the recurrence token by token; it shares no
code with the program) at a tiny size on the CPU, seeded random weights.
(The op alone: tests/test_gated_delta.py.)

The tolerance. Both sides are float32, the gap is rounding, and through the
MODEL rounding grows: the OLMo order renormalises every sublayer's output to unit scale, so
a relative 1e-7 at the embedding reads ~2.5e-5 at the logits of the 8-layer
toy (measured: the reference against itself with the embedding scaled by
1 + 1e-7); program and reference differ by <= 1.6e-4 on logits of ~0.7.
LOGIT_TOL = 2e-3 stands 10x above that and 50x below what a wrong mechanism
reads (a zero convolution tail at a chunk boundary, a state not carried or
not reset: >= 0.1 each, tried while this was written).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_olmo_hybrid as ref
from test_serve import MIXED_CASES, check_mixed_case
from tf_operator_tpu.models import transformer as tr
from tf_operator_tpu.serve.engine import Request, ServeConfig, ServeEngine

LOGIT_TOL = 2e-3

TINY = dict(vocab=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=4, d_ff=128,
            max_seq=192, lin_heads=4, lin_dk=8, lin_dv=16)
SIZES = dict(vocab=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=4, d_ff=128,
             rope_theta=None, norm_eps=1e-6, lin_heads=4, lin_dk=8, lin_dv=16,
             lin_conv=4, pattern=("linear", "linear", "linear", "full"))
SEED = 5


# ---- the whole-sequence model -----------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = tr.preset("olmo-hybrid-7b", **TINY)
    params = jax.jit(lambda k: tr.init_transformer(k, cfg))(jax.random.PRNGKey(SEED))
    return cfg, params, ref.init_weights(SEED, SIZES)


def test_preset_counts_the_published_parameters():
    cfg = tr.preset("olmo-hybrid-7b")
    lin = 3840 * 17280 + 3840 * 60 + 5760 * 3840 + 11520 * 4 + 60 + 192  # ISSUE 37
    mlp = 3 * 3840 * 11008
    full = 4 * 3840 ** 2 + 2 * 3840  # + the q/k norm gains
    assert cfg.n_params() == (24 * (lin + mlp + 2 * 3840) + 8 * (full + mlp + 2 * 3840)
                              + 2 * 100352 * 3840 + 3840)
    assert [cfg.kind_index(l) for l in range(8)] == [0, 1, 2, 0, 3, 4, 5, 1]
    assert cfg.n_of_kind(tr.LINEAR) == 24 and cfg.n_of_kind(tr.ATTN) == 8
    assert preset_roundtrip(cfg) == cfg


def preset_roundtrip(cfg):
    """Through a job's ``workload`` dict, as workloads/serve.py reaches it."""
    import json

    wl = json.loads(json.dumps({
        "preset": "tiny", "layer_pattern": list(cfg.layer_pattern),
        **{k: getattr(cfg, k) for k in (
            "vocab", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff",
            "max_seq", "lin_heads", "lin_dk", "lin_dv", "lin_conv",
            "lin_neg_eigval", "norm_order", "qk_norm", "tied_head")}}))
    got = tr.preset_from_workload(wl)
    return tr.replace(got, norm_eps=cfg.norm_eps, remat=cfg.remat)


def test_initialised_leaves_are_stacked_by_kind_and_counted(tiny):
    cfg, params, _ = tiny
    layers = params["layers"]
    assert layers["lin_wqkv"].shape == (6, 64, 4 * (8 + 8 + 16))
    assert layers["wq"].shape == (2, 64, 64) and layers["q_norm"].shape == (2, 64)
    assert layers["w_gate"].shape == (8, 64, 128) and params["head"].shape == (256, 64)
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == cfg.n_params()
    axes = tr.transformer_logical_axes(cfg)
    assert set(axes["layers"]) == set(layers)
    assert all(len(axes["layers"][k]) == layers[k].ndim for k in layers)


def test_whole_sequence_forward_equals_the_reference(tiny):
    cfg, params, w = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 100), 0, cfg.vocab)
    f32 = tr.replace(cfg, dtype=jnp.float32, remat=False)
    got = jax.jit(lambda p, t: tr.transformer_forward(p, t, f32))(params, tokens)
    reference = jax.jit(lambda w, t: ref.logits(w, t, SIZES))
    for row in range(2):
        want = reference(w, tokens[row])
        assert float(jnp.abs(got[row] - want).max()) < LOGIT_TOL


def test_the_bfloat16_state_control_moves_the_logits(tiny):
    """The control rounds the state for real: every entry of the state it
    leaves is a bfloat16 value, the unrounded scan's is not, and the logits
    move by far more than float32 rounding does (LOGIT_TOL; read here: 0.26
    on logits of ~0.7 — this toy renormalises every sublayer's output). (A
    bare float32 -> bfloat16 -> float32 cast is folded away on the TPU; a
    control that computes float32 reads an exact 0.)"""
    w = tiny[2]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (100,), 0, 256)
    f32 = jax.jit(lambda w, t: ref.logits(w, t, SIZES))(w, tokens)
    low = jax.jit(lambda w, t: ref.logits(w, t, SIZES, precision="state_bf16"))(w, tokens)
    assert float(jnp.abs(low - f32).max()) > 10 * LOGIT_TOL
    _, exact = ref.linear_state(w, SIZES, tokens.tolist())
    inputs, rounded = ref.linear_state(w, SIZES, tokens.tolist(), "state_bf16")
    assert bool((rounded == rounded.astype(jnp.bfloat16).astype(jnp.float32)).all())
    assert not bool((exact == exact.astype(jnp.bfloat16).astype(jnp.float32)).all())
    gap = float(jnp.linalg.norm(rounded - exact) / jnp.linalg.norm(exact))
    assert 1e-4 < gap < 3e-2
    assert inputs[0].shape == (100, 4, 8) and float(inputs[4].max()) > 1.0


def test_write_strength_without_negative_eigenvalues_stays_under_one(tiny):
    """``lin_neg_eigval`` False (the fla layer's other setting; the preset's
    and the default are True): beta = sigmoid(b) in [0, 1], nothing else of
    the gates moves."""
    cfg, params, _ = tiny
    lp = {k: v[0] for k, v in params["layers"].items() if k.startswith("lin_")}
    u = jax.random.normal(jax.random.PRNGKey(3), (5, cfg.lin_conv_channels))
    b = 4.0 * jax.random.normal(jax.random.PRNGKey(4), (5, cfg.lin_heads))
    both = [tr.lin_gates(u, b, b, lp, tr.replace(cfg, lin_neg_eigval=neg))
            for neg in (True, False)]
    assert float(both[0][4].max()) > 1.0 >= float(both[1][4].max())
    assert bool(jnp.allclose(both[0][4], 2 * both[1][4]))
    assert all(bool((x == y).all()) for x, y in zip(both[0][:4], both[1][:4]))
    assert tr.TransformerConfig().lin_neg_eigval and tr.TransformerConfig().lin_conv == 4


@pytest.mark.parametrize("field,value,what", [
    ("pp_microbatches", 2, "pipeline stages"),
    ("attn_impl", "ring", "attn_impl='ring'"),
    ("attn_impl", "ulysses", "attn_impl='ulysses'"),
    ("n_experts", 4, "dense gqa layers only"),
    ("causal", False, "bidirectional"),
    ("lin_dk", 0, "lin_heads, lin_dk, lin_dv > 0"),
])
def test_config_refuses_by_name_what_does_not_run_with_a_linear_layer(field, value, what):
    with pytest.raises(ValueError, match=what):
        tr.preset("olmo-hybrid-7b", **{**TINY, field: value})


# ---- the engine ---------------------------------------------------------------

CHUNK = 16


_ENGINES = {}


def _engine(tiny, chunk=CHUNK, slots=3):
    """One engine a shape for the whole file (a run starts from fresh pools
    and a fresh store; the two jitted programs are the engine's)."""
    cfg, params, _ = tiny
    if (chunk, slots) not in _ENGINES:
        _ENGINES[chunk, slots] = ServeEngine(cfg, params, ServeConfig(
            page_size=8, pool_pages=72, max_slots=slots, prefill_chunk=chunk))
    return _ENGINES[chunk, slots]


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


def _gap(w, req) -> float:
    """The served tokens against the reference's teacher-forced logits: how
    far the reference's best logit lies above the token the engine chose."""
    lg = ref.served_logits(w, SIZES, req.prompt, req.tokens, pad_to=TINY["max_seq"],
                           rows=32)
    return float(ref.gaps(lg, req.tokens).max())


def test_engine_serves_across_chunk_boundaries_as_the_reference(tiny):
    """Prompts of c-1, c, c+1 and 2c+1 tokens (a short chunk, a full one, a
    second chunk of one row, a third), then 9 decode steps each, more
    requests than slots: logits, not tokens."""
    w = tiny[2]
    c = CHUNK
    reqs = [Request(rid=i, prompt=_prompt(n, i), max_new=10)
            for i, n in enumerate((c - 1, c, c + 1, 2 * c + 1, 3))]
    res = _engine(tiny).run(reqs)
    assert res.completed == 5 and res.free_pages_end == res.free_pages_start
    for r in reqs:
        assert len(r.tokens) == 10 and _gap(w, r) < LOGIT_TOL
    ctr = res.counters
    assert ctr.state_resets == 5 and ctr.prefill_state_carries == 0 + 0 + 1 + 2 + 0
    assert ctr.lin_slot_steps == 6 * ctr.decode_slot_tokens


def test_an_evicted_slot_serves_its_next_request_as_if_alone(tiny):
    """One slot, two requests in turn: the second finds the first's state and
    convolution tail in its slot and must start from zeros."""
    second = lambda: Request(rid=1, prompt=_prompt(37, 11), max_new=8)  # noqa: E731
    alone = second()
    _engine(tiny, slots=1).run([alone])
    after = second()
    _engine(tiny, slots=1).run(
        [Request(rid=0, prompt=_prompt(50, 10), max_new=12), after])
    assert after.tokens == alone.tokens and _gap(tiny[2], after) < LOGIT_TOL


def test_two_sequences_decode_while_a_third_prefills_each_as_if_alone(tiny):
    """The third arrives (by the injected clock) once the first two decode;
    its chunks interleave with their decode steps, its slot is inactive in
    those steps, and nobody's state moves but its owner's."""
    def make():
        return [Request(rid=0, prompt=_prompt(20, 20), max_new=24),
                Request(rid=1, prompt=_prompt(9, 21), max_new=24),
                Request(rid=2, prompt=_prompt(4 * CHUNK + 3, 22), max_new=6,
                        arrival=5.0)]

    ticks = iter(range(10**6))
    together = make()
    res = _engine(tiny).run(together, clock=lambda: float(next(ticks)))
    first_chunk_at = together[2].admitted
    assert any(t > first_chunk_at for t in together[0].token_times[:-6])
    assert res.counters.prefill_state_carries == 1 + 4
    for i, req in enumerate(make()):
        req.arrival = 0.0
        _engine(tiny).run([req])
        assert together[i].tokens == req.tokens
        assert _gap(tiny[2], together[i]) < LOGIT_TOL


@pytest.mark.parametrize("case", sorted(MIXED_CASES))
def test_a_mixed_run_leaves_each_sequence_its_tokens_and_its_state(tiny, case):
    """The dense engine's mixed-run cases (tests/test_serve.py) with linear
    layers among the attending ones: a run that carries a chunk runs the
    chunked scan on the chunk's slot and the recurrent step on the decode
    slots — the tokens, the recurrent state and the convolution tail each
    sequence is left with are those of the sequence served alone."""
    check_mixed_case(_engine(tiny), case)


@pytest.mark.parametrize("chunk", [64, 128, 256])
def test_the_chunk_size_is_a_schedule_not_a_result(tiny, chunk):
    """One 150-token prompt served in prefill chunks of 64, 128 and 256 (3,
    2 and 1 calls; 1 to 4 scan chunks a call): the same logits within the
    stated tolerance, judged on the reference's."""
    req = Request(rid=0, prompt=_prompt(150, 30), max_new=8)
    _engine(tiny, chunk=chunk, slots=1).run([req])
    assert _gap(tiny[2], req) < LOGIT_TOL


def test_dense_preset_programs_hold_no_state_and_no_new_kernel():
    """A model without a linear layer keeps today's two programs: no state
    argument, the pool over all its layers, no state counters."""
    cfg = tr.preset("tiny")
    engine = ServeEngine(cfg, tr.init_transformer(jax.random.PRNGKey(0), cfg),
                         ServeConfig(page_size=8, pool_pages=24, max_slots=3,
                                     prefill_chunk=8))
    assert engine.store is None and engine._pool_shape()[0] == cfg.n_layers
    report = engine.compile()
    assert "decode_state_copies" not in report
    assert report["decode_kernels"] == {} == report["prefill_kernels"]
    assert report["decode_pool_copies"] == 0 == report["prefill_pool_copies"]
    res = engine.run([Request(rid=0, prompt=[1, 2, 3], max_new=4)])
    ctr = res.counters
    assert (ctr.state_resets, ctr.prefill_state_carries, ctr.lin_slot_steps) == (0, 0, 0)


def test_hybrid_engine_counts_both_kinds_of_state(tiny):
    from tf_operator_tpu.serve.kvcache import StateStore, pool_bytes

    cfg = tiny[0]
    engine = _engine(tiny)
    store = engine.store
    assert engine._pool_shape() == (2, 73, 4, 8, 16)
    assert store.state_shape == (6, 4, 4, 8, 16) and store.conv_shape == (6, 4, 3, 128)
    assert store.trash_slot == 3
    pages_only = pool_bytes(2, 72, 8, 4, 16)
    assert pages_only == 2 * 4 * 2 * 73 * 8 * 4 * 16
    assert pool_bytes(2, 72, 8, 4, 16, state=store) - pages_only == store.bytes \
        == 4 * 4 * 6 * (4 * 8 * 16 + 3 * 128)
    assert StateStore.for_model(tr.preset("tiny"), 3) is None
    # the store's in-place-ness is the TPU compiler's to show
    # (tests/test_chip_compile.py); here: the counters exist for this model
    report = engine.compile()
    assert report["decode_state_copies"] == 0 and "prefill_state_copies" in report
    # two programs for this model too: the decode step, the run that carries
    # a chunk (no chunk-only program, no third)
    assert {k.rsplit("_compile_s", 1)[0] for k in report if k.endswith("_compile_s")} \
        == {"decode", "prefill"}
    with pytest.raises(ValueError, match="no sliding window"):
        ServeEngine(tr.replace(cfg, layer_pattern=("linear", (16, True))),
                    tiny[1], ServeConfig())
