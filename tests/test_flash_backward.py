"""The fused flash backward kernel (PR 43) in the Pallas interpreter on the
CPU: dq, dk and dv against the dense oracle's autodiff and against the two
kernels it replaced. A file of its own beside tests/test_flash_attention.py:
a case is mostly compile time (≈ 2.5 s), and the gate's workers take files."""

import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("tf_operator_tpu.ops.flash_attention")

# ONE kernel over (k block, q block) pairs builds s, the mask, p, dp and ds
# once and feeds dq, dk and dv; dq's accumulator lives in HBM tiles the kernel
# reads and writes back itself. Every case against the oracle's autodiff AND
# against the two kernels it replaced (tests/flash_bwd_two_kernel.py), whose
# f32 sums it repeats in their order: bit for bit.

FUSED_BWD_CASES = {
    # what differs from one row of t = 256 in 64-blocks, 2 heads of 32, f32,
    # causal (two KV heads everywhere: a write-back is still in flight when
    # the grid moves on to the next head, and with b = 2 to the next row)
    "full": dict(causal=False),
    "causal": dict(b=2),
    "window-one-block": dict(window=64),
    "window-2.5-blocks": dict(window=160),
    "window-inside-a-block": dict(window=40),
    "g4": dict(h=8),
    "g7": dict(h=14, block_q=32),
    "g4-full": dict(h=8, causal=False),
    "g7-window-2.5-blocks": dict(h=14, window=160),
    # the revisit cases: a dq tile comes round again one inner walk later
    "walks-of-1": dict(t=64, b=2),
    "walks-of-2": dict(t=128),
    "walks-of-3": dict(t=192),
    "q-walk-of-1-k-walk-of-4": dict(block_q=256),
    "q-walk-of-2-k-walk-of-1": dict(t=128, block_k=128),
    "q-walk-of-8-k-walk-of-2": dict(block_q=32, block_k=128),
    "walks-of-2-full": dict(t=128, causal=False, b=2),
    "walks-of-1-g4": dict(t=64, h=8),
    # a q block's first live pair writes its tile without reading it: under
    # a window that pair is not k block 0 (q block 3 starts at k block 2)
    "first-live-k-block-is-2": dict(window=64, block_q=64, block_k=64),
    "first-live-k-block-by-q-block-g4": dict(window=96, block_q=32, h=8),
    "bf16": dict(dtype=jnp.bfloat16),
    "bf16-g4-window": dict(dtype=jnp.bfloat16, h=8, window=160),
    "lse-cotangent": dict(dlse=True),
    "lse-cotangent-g4-window": dict(dlse=True, h=8, window=64),
    "lse-cotangent-full": dict(dlse=True, causal=False),
}


def fused_bwd_case(name, differs, d=32, dv=32):
    """A case's operands and cotangents (seeded by its name) beside how the
    kernels are to be called."""
    case = dict(b=1, t=256, block_q=64, block_k=64, h=2, h_kv=2, causal=True,
                window=0, dtype=jnp.float32, dlse=False)
    case.update(differs)
    ks = jax.random.split(jax.random.PRNGKey(sum(map(ord, name))), 5)
    b, t, dt = case["b"], case["t"], case["dtype"]
    case.update(
        q=jax.random.normal(ks[0], (b, t, case["h"], d), dt),
        k=jax.random.normal(ks[1], (b, t, case["h_kv"], d), dt),
        v=jax.random.normal(ks[2], (b, t, case["h_kv"], dv), dt),
        do=jax.random.normal(ks[3], (b, t, case["h"], dv), dt),
        dlse=(jax.random.normal(ks[4], (b, t, case["h"]), jnp.float32)
              if case["dlse"] else None))
    return case


def check_fused_bwd_against_autodiff(case):
    """dq, dk, dv of the public entry (the lse entry, so an lse cotangent
    can ride along) against the dense oracle's autodiff."""
    how = dict(causal=case["causal"], window=case["window"])

    def loss(attend, q, k, v):
        o, lse = attend(q, k, v)
        out = jnp.sum(o.astype(jnp.float32) * case["do"].astype(jnp.float32))
        return out if case["dlse"] is None else out + jnp.sum(lse * case["dlse"])

    kernel = lambda q, k, v: fa.flash_attention_lse(  # noqa: E731
        q, k, v, block_q=case["block_q"], block_k=case["block_k"],
        interpret=True, **how)
    oracle = lambda q, k, v: fa.reference_attention_lse(q, k, v, **how)  # noqa: E731
    args = (case["q"], case["k"], case["v"])
    got = jax.grad(functools.partial(loss, kernel), (0, 1, 2))(*args)
    want = jax.grad(functools.partial(loss, oracle), (0, 1, 2))(*args)
    tol = 2e-4 if case["dtype"] == jnp.float32 else 6e-2
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32), atol=tol,
            rtol=tol, err_msg=f"d{name}")


def check_fused_bwd_against_two_kernels(case):
    """``_bwd`` on the forward's residuals against the two kernels it
    replaced on the same residuals: equal, bit for bit."""
    from tests.flash_bwd_two_kernel import two_kernel_bwd

    _, bq, bk = fa._dispatch(case["q"], case["k"], case["v"], case["block_q"],
                             case["block_k"], True, None)
    _, res = fa._fwd(case["q"], case["k"], case["v"], case["causal"], bq, bk,
                     True, case["window"])
    got = fa._bwd(case["causal"], bq, bk, True, res, case["do"],
                  dlse=case["dlse"], window=case["window"])
    want = two_kernel_bwd(case["causal"], bq, bk, res, case["do"],
                          dlse=case["dlse"], window=case["window"])
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert bool(jnp.all(g == w)), (
            f"d{name}: {float(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)).max())}")
        assert bool(jnp.any(g != 0))


@pytest.mark.parametrize("name", sorted(FUSED_BWD_CASES))
def test_fused_backward_matches_the_oracles_autodiff(name):
    check_fused_bwd_against_autodiff(fused_bwd_case(name, FUSED_BWD_CASES[name]))


@pytest.mark.parametrize("name", sorted(FUSED_BWD_CASES))
def test_fused_backward_equals_the_two_kernels_it_replaced(name):
    check_fused_bwd_against_two_kernels(fused_bwd_case(name, FUSED_BWD_CASES[name]))
