"""Ring attention over the cp axis: the ring section of
tests/test_parallel.py as a file of its own, so that ``--dist loadfile``
gives it a worker (the file was one worker's 1,000 s, the length of the
whole tier-1 run once tests/test_moe_transformer.py was split; PR 31).

The ring and its oracle each run as ONE compiled call, forward value and
gradients together (``conftest.jit_out_and_grads``), as the trainer's step
does: eagerly a cp=8 ring cost 5.9 s a forward and 34 s a gradient against
1.6 s for the jitted ``value_and_grad`` (PR 32)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import jit_out_and_grads
from tf_operator_tpu.parallel import build_mesh
from tf_operator_tpu.parallel.ring_attention import reference_attention, ring_attention


# ---- ring attention ------------------------------------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = build_mesh({"cp": 8})
    b, t, h, d = 2, 64, 4, 16
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, axis_name="cp", causal=causal))(q, k, v)
    ref = jax.jit(lambda q, k, v: reference_attention(
        q, k, v, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gqa_matches_repeat_oracle(causal):
    """GQA-native ring (r3): k/v keep n_kv heads through the whole ring —
    each ppermute hop moves blocks g-times smaller (the llama2-70b
    64q/8kv shape cuts ring ICI traffic 8x). Must equal the repeat-based
    formulation exactly, forward and grads."""
    mesh = build_mesh({"cp": 8})
    b, t, h, h_kv, d = 2, 64, 4, 2, 16
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h_kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h_kv, d), jnp.float32)
    g = h // h_kv
    out, got = jit_out_and_grads(
        lambda q, k, v: ring_attention(q, k, v, mesh, axis_name="cp",
                                       causal=causal),
        q, k, v, argnums=(0, 1, 2))
    # the repeat sits INSIDE the oracle, so its transpose already folds
    # dk/dv back to [b, t, h_kv, d]
    ref, want = jit_out_and_grads(
        lambda q, k, v: reference_attention(
            q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2),
            causal=causal),
        q, k, v, argnums=(0, 1, 2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
    for name, a, w in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name}",
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_impl_parity(causal):
    """The flash-backed body (r3 default: per-hop flash_attention_lse +
    exact lse merge) must agree with the blockwise einsum body — forward
    and grads, including GQA — since both are exact decompositions of the
    same softmax."""
    mesh = build_mesh({"cp": 8})
    b, t, h, h_kv, d = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h_kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h_kv, d), jnp.float32)

    def run(**kw):
        return jit_out_and_grads(
            lambda q, k, v: ring_attention(q, k, v, mesh, axis_name="cp",
                                           causal=causal, **kw),
            q, k, v, argnums=(0, 1, 2))

    out, got = run(impl="flash")  # the default body
    ref, want = run(impl="einsum")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    for name, a, w in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_kernel_interpret(causal):
    """Force the per-hop Pallas kernel (interpreter) inside the ring —
    the TPU path's kernel logic: per-hop lse from the kernel, merged
    across hops, gradients through the custom VJP incl. the lse
    cotangent."""
    mesh = build_mesh({"dp": 2, "cp": 4})
    b, t, h, d = 1, 128, 2, 16  # t_local=32: tiles cleanly in interpret
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d), jnp.float32) for kk in ks)
    def run(interpret):
        return jit_out_and_grads(
            lambda q, k, v: ring_attention(q, k, v, mesh, axis_name="cp",
                                           causal=causal, interpret=interpret),
            q, k, v, argnums=(0, 1, 2))

    out, got = run(True)
    _, want = run(False)
    ref = jax.jit(lambda q, k, v: reference_attention(
        q, k, v, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    for name, a, w in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=2e-3, atol=2e-4, err_msg=f"d{name}")


def test_ring_attention_with_batch_sharding():
    mesh = build_mesh({"dp": 2, "cp": 4})
    b, t, h, d = 4, 32, 2, 8
    key = jax.random.PRNGKey(1)
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, axis_name="cp", causal=True, batch_axes=("dp",)))(q, k, v)
    ref = jax.jit(lambda q, k, v: reference_attention(
        q, k, v, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
