"""The Gated-DeltaNet op (``ops/gated_delta.py``) against the token-by-token
recurrence of ``benchmarks/reference_olmo_hybrid.py`` (``delta_rule_scan``:
plain jnp under ``lax.scan``, float32 HIGHEST; no code shared): the chunked
form and the recurrent step, each as the ``jnp`` form and as the Pallas
kernel under the interpreter, on the CPU.

The tolerance: both sides are float32 and the op is compared alone, so the
gap is rounding (<= 2e-6 read); OP_TOL = 2e-5 is 10x that and 1e3x below
what a dropped term reads.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmarks import reference_olmo_hybrid as ref
from tf_operator_tpu.ops import gated_delta as gd

OP_TOL = 2e-5
scan = jax.jit(ref.delta_rule_scan)


# ---- the op against the token-by-token oracle ------------------------------


def _operands(t, H, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = ref.l2_norm(jax.random.normal(ks[0], (t, H, dk))) * dk ** -0.5
    k = ref.l2_norm(jax.random.normal(ks[1], (t, H, dk)))
    v = jax.random.normal(ks[2], (t, H, dv))
    alpha_log = -jnp.exp(jax.random.normal(ks[3], (t, H)) - 1.0)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (t, H)))  # up to 2: negative eigenvalues
    state0 = 0.5 * jax.random.normal(ks[5], (H, dk, dv))
    return q, k, v, alpha_log, beta, state0


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("t,H,dk,dv", [(100, 3, 16, 24), (128, 2, 96, 192)],
                         ids=["ragged", "published-head"])
def test_chunk_form_equals_the_token_by_token_recurrence(t, H, dk, dv, interpret):
    """A non-zero state0, a length that is no whole number of chunks, write
    strengths up to 2; then the same with the last rows flagged invalid: they
    write nothing and the state is the one after the last valid row."""
    q, k, v, al, b, s0 = _operands(t, H, dk, dv)
    o_ref, s_ref = scan(q, k, v, jnp.exp(al), b, s0)
    o, s1 = gd.gated_delta_chunk(q, k, v, al, b, s0, interpret=interpret)
    assert float(jnp.abs(o - o_ref).max()) < OP_TOL
    assert float(jnp.abs(s1 - s_ref).max()) < OP_TOL
    n = t - 7
    o_ref, s_ref = scan(q[:n], k[:n], v[:n], jnp.exp(al[:n]), b[:n], s0)
    o, s1 = gd.gated_delta_chunk(q, k, v, al, b, s0, valid=jnp.arange(t) < n,
                                 interpret=interpret)
    assert float(jnp.abs(o[:n] - o_ref).max()) < OP_TOL
    assert float(jnp.abs(s1 - s_ref).max()) < OP_TOL


def test_chunk_form_survives_a_decay_that_underflows():
    """alpha of e^-30 a token: e^{g_i - g_j} underflows to 0 inside a chunk
    and nothing overflows (the exponent is taken of the difference)."""
    q, k, v, al, b, s0 = _operands(128, 2, 16, 24, seed=3)
    al = jnp.full_like(al, -30.0)
    o_ref, s_ref = scan(q, k, v, jnp.exp(al), b, s0)
    for interpret in (None, True):
        o, s1 = gd.gated_delta_chunk(q, k, v, al, b, s0, interpret=interpret)
        assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s1).all())
        assert float(jnp.abs(o - o_ref).max()) < OP_TOL


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "kernel"])
def test_step_form_updates_named_slots_of_the_store_in_place(interpret):
    """One token of each of 5 sequences whose states lie in slots 4..0 of
    layer 1 of a store; row 2 is invalid and steered to the trash slot: its
    own slot keeps its state, no other layer is touched."""
    s, H, dk, dv = 5, 3, 16, 24
    q, k, v, al, b, _ = _operands(s, H, dk, dv, seed=1)
    states = jax.random.normal(jax.random.PRNGKey(9), (s, H, dk, dv))
    o_ref, s_ref = jax.vmap(lambda *a: scan(*(x[None] for x in a[:-1]), a[-1]))(
        q, k, v, jnp.exp(al), b, states)  # each row a sequence of one token
    o_ref = o_ref[:, 0]
    o, s1 = gd.gated_delta_step(q, k, v, al, b, states, interpret=interpret)
    assert float(jnp.abs(o - o_ref).max()) < OP_TOL
    assert float(jnp.abs(s1 - s_ref).max()) < OP_TOL
    slots = jnp.arange(s)[::-1]
    store = jnp.ones((2, s + 2, H, dk, dv)).at[1, slots].set(states)
    valid = jnp.arange(s) != 2
    o, store1 = gd.gated_delta_step(
        q, k, v, al, b, store, valid=valid, layer=1,
        slots=jnp.where(valid, slots, s + 1), interpret=interpret)
    err = jnp.abs(store1[1, slots] - s_ref).max(axis=(1, 2, 3))
    assert float(err[valid].max()) < OP_TOL
    assert float(jnp.abs(o - o_ref)[valid].max()) < OP_TOL
    assert bool((store1[1, slots[2]] == states[2]).all())
    assert bool((store1[0] == 1.0).all()) and bool((store1[1, s] == 1.0).all())


def test_step_refuses_a_store_without_its_layer_and_slots():
    q, k, v, al, b, _ = _operands(2, 3, 16, 24)
    with pytest.raises(ValueError, match="layer= and slots="):
        gd.gated_delta_step(q, k, v, al, b, jnp.zeros((2, 3, 3, 16, 24)))
