"""Mamba-1 layers beside multi-query paged attention: the whole-sequence
model and the serve engine, each against ``benchmarks/reference_jamba.py``
(plain jnp, float32 HIGHEST, the recurrence token by token; it shares no code
with the program) at a tiny size on the CPU, seeded random weights. (The op
alone: tests/test_selective_scan.py.)

The tiny model: two periods of [mamba, mamba, attention, mamba], ONE key/value
head under 4 query heads, a state of 4 numbers a channel. Both sides are
float32 and the model is pre-norm, so the gap is rounding: program and
reference differ by <= 3e-6 on logits of ~0.7; LOGIT_TOL stands well above
that and far below what a wrong mechanism reads (a zero convolution tail at a
chunk boundary, a state not carried or not reset: >= 0.03 each).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_jamba as ref
from test_serve import MIXED_CASES, check_mixed_case
from tf_operator_tpu.models import transformer as tr
from tf_operator_tpu.serve.engine import Request, ServeConfig, ServeEngine
from tf_operator_tpu.serve.kvcache import StateStore

LOGIT_TOL = 2e-4
PATTERN = ("mamba", "mamba", (0, False), "mamba")
TINY = dict(vocab=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=1, d_ff=128,
            max_seq=192, layer_pattern=PATTERN, mamba_d_state=4, mamba_dt_rank=8,
            dtype=jnp.float32, remat=False)
SIZES = dict(vocab=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=1, d_ff=128,
             rope_theta=None, norm_eps=1e-6, mamba_d_state=4, mamba_d_conv=4,
             mamba_expand=2, mamba_dt_rank=8, pattern=("mamba", "mamba", "attn", "mamba"))
SEED = 5


@pytest.fixture(scope="module")
def tiny():
    cfg = tr.preset("ai21-jamba2-3b", **TINY)
    params = jax.jit(lambda k: tr.init_transformer(k, cfg))(jax.random.PRNGKey(SEED))
    return cfg, params, ref.init_weights(SEED, SIZES)


# ---- the configuration -------------------------------------------------------


def test_preset_counts_the_published_parameters():
    cfg = tr.preset("ai21-jamba2-3b")
    assert cfg.n_params() == 3_029_337_472
    assert [l for l in range(28) if tr.kind_of(cfg.pattern[l % 14]) == tr.ATTN] == [7, 21]
    assert cfg.n_of_kind(tr.MAMBA) == 26 and cfg.n_of_kind(tr.ATTN) == 2
    assert [cfg.kind_index(l) for l in (0, 6, 7, 8, 20, 21, 27)] == [0, 6, 0, 7, 19, 1, 25]
    assert cfg.recurrent_kind == tr.MAMBA and cfg.mamba_inner == 5120
    assert tr.preset("olmo-hybrid-7b").recurrent_kind == tr.LINEAR
    assert tr.preset("tiny").recurrent_kind is None


def test_initialised_leaves_are_stacked_by_kind(tiny):
    cfg, params, _ = tiny
    layers = params["layers"]
    kinds = tr.stacked_by(cfg)
    mamba = sorted(n for n, k in kinds.items() if k == tr.MAMBA)
    assert len(mamba) == 12 and all(n.startswith("mamba_") for n in mamba)
    assert all(layers[n].shape[0] == 6 for n in mamba)
    assert all(layers[n].shape[0] == 2 for n in ("wq", "wk", "wv", "wo"))
    assert layers["wk"].shape == (2, 64, 16) and layers["w_gate"].shape[0] == 8
    np.testing.assert_allclose(layers["mamba_A_log"][3, 17], np.log(np.arange(1, 5)), rtol=1e-6)
    assert float(jnp.abs(layers["mamba_conv_bias"]).max()) == 0.0
    assert float(layers["mamba_D"].min()) == 1.0
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) \
        == cfg.n_params()
    # a workload's JSON form of the pattern comes back as the config's
    assert tr.preset_from_workload(dict(
        preset="ai21-jamba2-3b", n_layers=4,
        layer_pattern=["mamba", "mamba", [0, False], "mamba"])).pattern == PATTERN


@pytest.mark.parametrize("field, value, what", [
    ("pp_microbatches", 2, "pipeline stages"),
    ("attn_impl", "ring", "attn_impl='ring'"),
    ("attn_impl", "ulysses", "attn_impl='ulysses'"),
    ("n_experts", 4, "experts"),
    ("attn_kind", "latent", "latent attention"),
    ("mtp_depth", 1, "a prediction module"),
    ("causal", False, "a bidirectional model"),
    ("layer_pattern", ("mamba", "linear", (0, False), "mamba"), "a 'linear' layer in the same model"),
])
def test_config_refuses_by_name_what_does_not_run_with_a_mamba_layer(field, value, what):
    extra = dict(lin_heads=2, lin_dk=8, lin_dv=8) if field == "layer_pattern" else {}
    with pytest.raises(ValueError, match="a 'mamba' layer does not run with " + what):
        tr.preset("ai21-jamba2-3b", **{**TINY, **extra, field: value})
    with pytest.raises(ValueError, match="a 'mamba' layer needs mamba_d_state"):
        tr.preset("ai21-jamba2-3b", **{**TINY, "mamba_dt_rank": 0})


def test_engine_refuses_by_name_beside_the_old_limits(tiny):
    cfg, params, _ = tiny
    windowed = tr.preset("ai21-jamba2-3b", **{**TINY, "layer_pattern": (
        "mamba", "mamba", (16, False), "mamba")})
    with pytest.raises(ValueError, match="window layer runs in training only"):
        ServeEngine(windowed, params, ServeConfig())
    store = StateStore.for_model(cfg, 3)
    assert (store.n_layers, store.heads, store.d_k, store.d_v) == (6, 1, 4, 128)
    assert store.conv_shape == (6, 4, 3, 128) and store.trash_slot == 3
    assert store.slot_bytes == 4 * 6 * (4 * 128 + 3 * 128)


# ---- the whole-sequence model -------------------------------------------------


def test_whole_sequence_forward_equals_the_reference(tiny):
    cfg, params, w = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, (2, 50)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: tr.transformer_forward(p, t, cfg))(params, tokens)
    for row in range(2):
        want = ref.logits(w, tokens[row], SIZES)
        assert float(jnp.abs(got[row] - want).max()) < LOGIT_TOL


def test_the_bfloat16_state_control_moves_the_logits_and_the_state(tiny):
    w = tiny[2]
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, 256, 96), jnp.int32)
    exact = ref.logits(w, tokens, SIZES)
    rounded = ref.logits(w, tokens, SIZES, "state_bf16")
    assert float(jnp.abs(exact - rounded).max()) > 20 * LOGIT_TOL
    _, state = ref.mamba_state(w, SIZES, list(map(int, tokens)))
    _, low = ref.mamba_state(w, SIZES, list(map(int, tokens)), "state_bf16")
    assert float(jnp.linalg.norm(low - state) / jnp.linalg.norm(state)) > 1e-3


# ---- the serve engine ---------------------------------------------------------

CHUNK = 16
_ENGINES = {}


def _engine(tiny, chunk=CHUNK, slots=3):
    cfg, params, _ = tiny
    if (chunk, slots) not in _ENGINES:
        _ENGINES[chunk, slots] = ServeEngine(cfg, params, ServeConfig(
            page_size=8, pool_pages=72, max_slots=slots, prefill_chunk=chunk))
    return _ENGINES[chunk, slots]


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


def _gap(w, req) -> float:
    lg = ref.served_logits(w, SIZES, req.prompt, req.tokens, pad_to=TINY["max_seq"],
                           rows=32)
    return float(ref.gaps(lg, req.tokens).max())


@pytest.mark.parametrize("chunk", [16, 40])
def test_engine_serves_across_chunk_boundaries_as_the_reference(tiny, chunk):
    """Prompts of c-1, c, c+1 and 2c+1 tokens (a short chunk, a full one, a
    second chunk of one row, a third) and one of 3, then 9 decode steps each
    through store and pages, more requests than slots (slots join, leave and
    are taken again): logits, not tokens."""
    w, c = tiny[2], chunk
    reqs = [Request(rid=i, prompt=_prompt(n, i), max_new=10)
            for i, n in enumerate((c - 1, c, c + 1, 2 * c + 1, 3))]
    res = _engine(tiny, chunk).run(reqs)
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


@pytest.mark.parametrize("case", sorted(MIXED_CASES))
def test_a_mixed_run_leaves_each_sequence_its_tokens_and_its_state(tiny, case):
    """The dense engine's mixed-run cases (tests/test_serve.py) with Mamba
    layers among the attending ones: the tokens, the recurrent state and the
    convolution tail each sequence is left with are those of the sequence
    served alone."""
    check_mixed_case(_engine(tiny), case)


def test_programs_update_the_store_in_place_and_count_their_paged_calls(tiny):
    engine = _engine(tiny)
    report = engine.compile()
    assert engine._pool_shape() == (2, 73, 1, 8, 16)
    for program in ("decode", "prefill"):
        assert report[f"{program}_state_copies"] == 0 == report[f"{program}_pool_copies"]
    # off the TPU every paged call is the gather form: one an attending layer
    # over the decode rows, one more over the chunk
    assert (report["decode_paged_reference_calls"],
            report["prefill_paged_reference_calls"]) == (2, 4)
    del _ENGINES[CHUNK, 3]  # it serves from compiled programs from here on


def test_the_serve_workload_runs_the_preset_from_a_job_spec():
    """The normal path, no side script: a job's ``workload`` dict ->
    ``workloads/serve.py`` -> ``preset_from_workload`` -> ``ServeEngine``; the
    three state counters count the Mamba layers on ``eval_metrics``."""
    from test_program_spans import _StubContext
    from tf_operator_tpu.workloads import serve as workload

    ctx = _StubContext({
        "preset": "ai21-jamba2-3b", "vocab": 256, "d_model": 64, "n_layers": 8,
        "n_heads": 4, "n_kv_heads": 1, "d_ff": 128, "max_seq": 64,
        "layer_pattern": ["mamba", "mamba", [0, False], "mamba"],
        "mamba_d_state": 4, "mamba_dt_rank": 8,
        "requests": 4, "prompt_len": 20, "max_new_tokens": 5, "arrival_rate": 0.0,
        "kv_page_size": 8, "kv_pool_pages": 32, "max_slots": 2, "prefill_chunk": 8,
        "report_every": 2,
    })
    workload.main(ctx)
    last = ctx.reports[-1][1]
    assert last["engine_admitted"] == last["requests_completed"] == 4.0
    assert last["engine_state_resets"] == 4.0
    assert last["engine_lin_slot_steps"] == 6 * last["engine_decode_slot_tokens"] > 0
