"""The plain reference against the program's own forward pass at ``tiny``:
it imports nothing of the program, so this is where the two are held together."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference
from tf_operator_tpu.models.transformer import (
    init_transformer,
    lm_loss,
    preset,
    transformer_forward,
)

SIZES = dict(vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
             d_ff=128, rope_theta=10000.0, norm_eps=1e-5)
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny", dtype=jnp.float32, remat=False, attn_impl="dense")


def test_seeded_weights_are_the_programs(cfg):
    ours = reference.init_weights(SEED, SIZES)
    theirs = init_transformer(jax.random.PRNGKey(SEED), cfg)
    flat_o = jax.tree_util.tree_flatten_with_path(ours)[0]
    flat_t = dict(jax.tree_util.tree_flatten_with_path(theirs)[0])
    assert len(flat_o) == len(flat_t) == 11
    for path, leaf in flat_o:
        # the same draws; a jitted and an eager scale may differ in the last bit
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(flat_t[path]), rtol=1e-6)


def test_logits_and_loss_match_transformer_forward(cfg):
    w = reference.init_weights(SEED, SIZES)
    tokens = np.random.default_rng(0).integers(0, 256, (3, 48), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = transformer_forward(w, jnp.asarray(tokens), cfg)
        want_loss = lm_loss(w, jnp.asarray(tokens), cfg)
    for row, ref in zip(tokens, want):
        got = reference.logits(w, jnp.asarray(row), SIZES)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
    loss, _ = reference.loss_and_grad(w, tokens, SIZES)
    assert abs(loss - float(want_loss)) < 1e-5


def test_blocked_attention_is_the_whole_attention():
    w = reference.init_weights(5, SIZES)
    row = jnp.arange(64, dtype=jnp.int32) % 256
    whole = reference.logits(w, row, SIZES, q_block=64)
    blocked = reference.logits(w, row, SIZES, q_block=16)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(blocked), atol=1e-5)


def test_gradient_and_adamw_follow_optax(cfg):
    import optax

    opt = dict(learning_rate=3e-4, beta1=0.9, beta2=0.95, eps=1e-8,
               weight_decay=0.0, grad_clip=1.0)
    batches = [np.random.default_rng(i).integers(0, 256, (2, 32), dtype=np.int32)
               for i in range(2)]
    out = reference.train_reference(SEED, SIZES, opt, batches)

    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.0))
    p0 = init_transformer(jax.random.PRNGKey(SEED), cfg)
    p, st, losses, g1 = p0, tx.init(p0), [], None
    with jax.default_matmul_precision("highest"):
        for b in batches:
            loss, g = jax.value_and_grad(lm_loss)(p, jnp.asarray(b), cfg)
            losses.append(float(loss))
            up, st = tx.update(g, st, p)
            if g1 is None:
                g1 = reference.leaf_norms(st[1][0].mu)
            p = optax.apply_updates(p, up)
    change = reference.leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0))
    np.testing.assert_allclose(out["losses"], losses, atol=2e-5)
    for k, v in out["grad1_norms"].items():
        assert v == pytest.approx(g1[k] / 0.1, rel=2e-3), k
    for k, v in out["change_norms"].items():
        assert v == pytest.approx(change[k], rel=2e-3), k


def test_served_gaps_zero_for_greedy_tokens_and_positive_for_wrong_ones():
    w = reference.init_weights(3, SIZES)
    prompt = list(range(1, 20))
    seq = list(prompt)
    for _ in range(6):  # plain greedy decoding, one full pass a token
        seq.append(int(jnp.argmax(reference.logits(w, jnp.asarray(seq), SIZES)[-1])))
    tokens = seq[len(prompt):]
    ref = reference.served_logits(w, SIZES, prompt, tokens, pad_to=64, rows=16)
    gaps = reference.gaps(ref, tokens)
    assert gaps.shape == (6,) and float(gaps.max()) == 0.0
    wrong = [(t + 1) % 256 for t in tokens]
    assert float(reference.gaps(ref, wrong).min()) > 0.0
    for precision in ("bfloat16", "float8"):
        low = reference.served_logits(w, SIZES, prompt, tokens, 64, 16, precision)
        assert low.shape == ref.shape and low.dtype == np.float32
        assert 0.0 < float(np.abs(low - ref).max()) < 0.5  # rounded, not wrong
        assert float(reference.gaps(ref, low.argmax(-1)).min()) >= 0.0
