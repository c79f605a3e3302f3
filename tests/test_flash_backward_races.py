"""The fused flash backward's own DMAs (dq's read-modify-write of its HBM
tiles, PR 43) under the TPU interpreter: copies run LATE, when they are waited
for, uninitialised memory reads NaN and every access is checked against the
copies in flight. The plain interpreter of tests/test_flash_backward.py copies
at ``start()`` and cannot see a tile read before its write-back has landed;
this one reads the stale tile and says where. A case is a geometry: which q
blocks two live pairs running name is decided by the blocks, the window and
the mask, not by dtype or cotangent."""

import importlib

import pytest

import jax.numpy as jnp
from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter
from jax.experimental.pallas import tpu as pltpu

from tests.flash_bwd_two_kernel import two_kernel_bwd
from tests.test_flash_backward import fused_bwd_case

fa = importlib.import_module("tf_operator_tpu.ops.flash_attention")

RACE_CASES = {
    # what differs from one row of t = 256 in 64-blocks, 2 heads of 32, f32,
    # causal. Same tile on two live pairs running: the last live pair of a k
    # row and the first of the next (REVIEW.md, PR 43) …
    "causal-square-blocks": dict(),
    "causal-square-blocks-walks-of-2": dict(t=128, b=2),
    "causal-block-q-over-block-k": dict(block_q=128, block_k=64),
    "window-of-a-q-block": dict(window=64),
    "window-of-a-q-block-and-one": dict(window=65),
    "window-under-a-q-block-that-divides-block-k": dict(
        block_q=32, block_k=64, window=33),
    "window-inside-a-block-g4": dict(window=40, h=8),
    # … or every pair of an inner walk of one block
    "walks-of-1": dict(t=64, b=2),
    "q-walk-of-1-k-walk-of-4": dict(block_q=256),
    "q-walk-of-1-k-walk-of-4-full": dict(block_q=256, causal=False),
    # a tile comes round a whole inner walk later: nothing to wait for
    "full": dict(causal=False),
    "walks-of-2-full": dict(t=128, causal=False, b=2),
    "block-q-under-block-k": dict(block_q=32, block_k=128),
    "window-2.5-blocks-g7": dict(h=14, window=160),
    "first-live-k-block-by-q-block-g4": dict(window=96, block_q=32, h=8),
}


@pytest.mark.parametrize("name", sorted(RACE_CASES))
def test_no_dq_tile_is_read_before_its_write_back_lands(name):
    case = fused_bwd_case(name, RACE_CASES[name])
    _, bq, bk = fa._dispatch(case["q"], case["k"], case["v"], case["block_q"],
                             case["block_k"], True, None)
    _, res = fa._fwd(case["q"], case["k"], case["v"], case["causal"], bq, bk,
                     True, case["window"])
    late = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                 detect_races=True, uninitialized_memory="nan")
    got = fa._bwd(case["causal"], bq, bk, late, res, case["do"],
                  window=case["window"])
    assert not tpu_interpreter.races.races_found
    want = two_kernel_bwd(case["causal"], bq, bk, res, case["do"],
                          window=case["window"])
    for which, g, w in zip("qkv", got, want):
        assert bool(jnp.all(g == w)), f"d{which}"
