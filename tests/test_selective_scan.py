"""Mamba-1's selective scan (ops/selective_scan.py): the form for a chunk of
one sequence and the one-token step, each as the Pallas kernel under the
interpreter and as plain ``jnp``, against ``benchmarks/reference_jamba.py``'s
token-by-token scan (it shares no code with the program)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_jamba as ref
from tf_operator_tpu.ops.selective_scan import (
    selective_scan_chunk,
    selective_scan_step,
)

TOL = dict(rtol=2e-5, atol=2e-6)


def rows(t, I, N, seed=0):
    """(u, delta, B, C, A, D, state0 [N, I]) of one sequence."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (t, I)),
            jax.nn.softplus(jax.random.normal(ks[1], (t, I)) - 2.0),
            jax.random.normal(ks[2], (t, N)), jax.random.normal(ks[3], (t, N)),
            -jnp.exp(jax.random.normal(ks[4], (I, N))), jax.random.normal(ks[5], (I,)),
            jax.random.normal(ks[6], (N, I)))


# d_state 4 is the tiny model's (the jnp form: the kernel wants whole sublanes)
@pytest.mark.parametrize("t, I, N, interpret", [
    (37, 1024, 8, True), (272, 512, 16, True),  # 2 channel blocks; 2 position blocks
    (37, 256, 8, False), (21, 128, 4, False)])
def test_chunks_one_call_and_steps_equal_the_reference_scan(t, I, N, interpret):
    """k chunks ≡ one call ≡ the step token by token ≡ the reference."""
    u, d, B, C, A, D, s0 = rows(t, I, N)
    y_ref, s_ref = ref.selective_scan(u, d, B, C, A, D, state0=s0.T)
    y, s1 = selective_scan_chunk(u, d, B, C, A, D, s0, interpret=interpret)
    np.testing.assert_allclose(y, y_ref, **TOL)
    np.testing.assert_allclose(s1.T, s_ref, **TOL)
    S, ys = s0, []
    c = -(-t // 3)  # three chunks, the last one short
    for a in range(0, t, c):
        cut = slice(a, min(a + c, t))
        yc, S = selective_scan_chunk(u[cut], d[cut], B[cut], C[cut], A, D, S,
                                     interpret=interpret)
        ys.append(yc)
    np.testing.assert_allclose(jnp.concatenate(ys), y_ref, **TOL)
    np.testing.assert_allclose(S.T, s_ref, **TOL)
    step = jax.jit(lambda S, *row: selective_scan_step(*row, A, D, S, interpret=interpret))
    S, ys = s0[None], []
    for i in range(6):
        yi, S = step(S, u[i:i + 1], d[i:i + 1], B[i:i + 1], C[i:i + 1])
        ys.append(yi[0])
    np.testing.assert_allclose(jnp.stack(ys), y_ref[:6], **TOL)


@pytest.mark.parametrize("interpret", [True, False])
def test_invalid_rows_leave_the_state_untouched(interpret):
    """A short chunk's padding: the state after the last VALID row, whatever
    the padding rows hold."""
    u, d, B, C, A, D, s0 = rows(40, 128, 8, seed=1)
    valid = jnp.arange(40) < 29
    _, want = selective_scan_chunk(u[:29], d[:29], B[:29], C[:29], A, D, s0,
                                   interpret=interpret)
    y, got = selective_scan_chunk(u, d, B, C, A, D, s0, valid=valid, interpret=interpret)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    _, none = selective_scan_chunk(u, d, B, C, A, D, s0, valid=jnp.zeros(40, bool),
                                   interpret=interpret)
    np.testing.assert_array_equal(none, s0)


@pytest.mark.parametrize("interpret", [True, False])
def test_step_updates_named_slots_of_the_store_in_place(interpret):
    """The engine's form: the whole store, a layer, a slot a row; inactive
    rows steered to the trash slot (6) change no slot anybody reads."""
    u, d, B, C, A, D, _ = rows(5, 256, 8, seed=2)
    store = jax.random.normal(jax.random.PRNGKey(3), (3, 7, 1, 8, 256))
    slots = jnp.array([4, 6, 1, 6, 0])
    valid = jnp.array([True, False, True, False, True])
    y, out = selective_scan_step(u, d, B, C, A, D, store, valid=valid, layer=1,
                                 slots=slots, interpret=interpret)
    for row in (0, 2, 4):
        y_ref, s_ref = ref.selective_scan(
            u[row:row + 1], d[row:row + 1], B[row:row + 1], C[row:row + 1], A, D,
            state0=store[1, slots[row], 0].T)
        np.testing.assert_allclose(y[row], y_ref[0], **TOL)
        np.testing.assert_allclose(out[1, slots[row], 0].T, s_ref, **TOL)
    untouched = np.ones((3, 7), bool)
    untouched[1, [4, 1, 0]] = False
    untouched[1, 6] = False  # the trash slot: written by whoever came last
    np.testing.assert_array_equal(np.asarray(out)[untouched], np.asarray(store)[untouched])
    np.testing.assert_array_equal(out[1, 6], store[1, 6])  # Δ = 0 writes the same state


def test_step_refuses_a_store_without_its_layer_and_slots():
    u, d, B, C, A, D, _ = rows(2, 128, 8)
    store = jnp.zeros((2, 3, 1, 8, 128))
    with pytest.raises(ValueError, match="layer= and slots="):
        selective_scan_step(u, d, B, C, A, D, store)
    with pytest.raises(ValueError, match="layer= and slots="):
        selective_scan_step(u, d, B, C, A, D, store[0, :2, 0], layer=0,
                            slots=jnp.arange(2))


def test_a_strong_decay_underflows_and_nothing_overflows():
    u, d, B, C, A, D, s0 = rows(24, 128, 8, seed=4)
    y, s1 = selective_scan_chunk(u, 50.0 * d + 30.0, B, C, 40.0 * A, D, s0, interpret=True)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(s1).all())
