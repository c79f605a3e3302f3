"""The engine's two programs serve the tokens they served before PR 48, value
for value, on the CPU: one mixed run a model kind — a dense decoder, Gated
DeltaNet layers beside attention, Mamba layers beside multi-query attention,
each at its file's tiny size — whose chunks carry decode rows and whose
drained tail is decode-only steps, so every token passes one of the two
programs. ``RECORDED`` was printed by this file's ``served_tokens`` on the
parent commit (323665a): a change to how a program multiplies (PR 48 holds
each head-split product whole, ``serve/engine.py`` ``whole``) that moved a
value would move a token here long before a tolerance on logits saw it. A
change that MEANS to move values (another precision, another summation
order) records its own tokens, and says so.
"""

import jax
import pytest

from tf_operator_tpu.models import transformer as tr
from tf_operator_tpu.serve.engine import Request, ServeConfig, ServeEngine

CHUNK = 16
RECORDED = {
    "dense": [
        [33, 71, 219, 33, 71, 219, 130, 211, 33, 191],
        [219, 235, 219, 171, 67, 235, 62, 145, 63, 14],
        [27, 58, 58, 58, 14, 154, 154, 154, 154, 154],
        [105, 145, 183, 99, 119, 159, 21, 166, 80, 131],
        [155, 167, 155, 233, 233, 39, 249, 39, 110, 24],
    ],
    "hybrid": [
        [120, 127, 89, 59, 214, 156, 7, 129, 70, 158],
        [65, 235, 12, 59, 91, 158, 94, 211, 101, 65],
        [145, 243, 175, 226, 76, 119, 105, 169, 95, 204],
        [120, 182, 6, 223, 105, 13, 165, 0, 9, 78],
        [0, 48, 173, 201, 142, 31, 52, 105, 87, 38],
    ],
    "mamba": [
        [219, 59, 206, 232, 80, 222, 201, 44, 91, 225],
        [104, 190, 82, 80, 85, 140, 200, 54, 227, 187],
        [151, 237, 250, 103, 228, 197, 187, 246, 33, 71],
        [170, 247, 107, 34, 237, 26, 91, 24, 49, 104],
        [222, 153, 149, 111, 241, 135, 0, 149, 76, 235],
    ],
}


def _model(kind):
    if kind == "dense":
        return tr.preset("tiny"), 0
    if kind == "hybrid":
        from test_olmo_hybrid import SEED, TINY

        return tr.preset("olmo-hybrid-7b", **TINY), SEED
    from test_jamba import SEED, TINY

    return tr.preset("ai21-jamba2-3b", **TINY), SEED


def served_tokens(kind):
    """Prompts of c-1, c, c+1, 2c+1 and 3 tokens, 10 tokens each, through 3
    slots: five requests' tokens in request order."""
    from test_olmo_hybrid import _prompt

    cfg, seed = _model(kind)
    params = jax.jit(lambda k: tr.init_transformer(k, cfg))(jax.random.PRNGKey(seed))
    engine = ServeEngine(cfg, params, ServeConfig(
        page_size=8, pool_pages=72, max_slots=3, prefill_chunk=CHUNK))
    c = CHUNK
    reqs = [Request(rid=i, prompt=_prompt(n, i), max_new=10)
            for i, n in enumerate((c - 1, c, c + 1, 2 * c + 1, 3))]
    res = engine.run(reqs)
    assert res.completed == 5
    # both programs ran: chunks that carried decode rows, and decode-only steps
    ctr = res.counters
    assert 0 < ctr.chunks_carrying_decode < ctr.decode_steps
    return [r.tokens for r in reqs]


@pytest.mark.serve
@pytest.mark.parametrize("kind", sorted(RECORDED))
def test_both_programs_serve_the_parents_tokens(kind):
    assert served_tokens(kind) == RECORDED[kind]
