"""A q tile too large for the paged kernel's VMEM goes as tiles of fewer
positions in turn (``_rows_per_tile`` / ``_row_tiles``): multi-query attention
at 20 query heads over ONE key/value head makes a 256-token chunk 5,120 rows.
The kernel under the interpreter against the gather reference."""

import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("tf_operator_tpu.ops.flash_attention")


def _kernels(call, *args) -> int:
    """Pallas calls in the traced ``call``."""
    return str(jax.make_jaxpr(call)(*args)).count("pallas_call")


def test_rows_per_tile_at_the_cells_shapes():
    """The Mistral and hybrid shapes keep the one tile they had; group 20
    at 256 positions is two tiles of 128; a group that no cut serves is 0."""
    assert fa._rows_per_tile(128, 4, 8, 128, 64, 4) == 128      # -serve1 chunk, hb 4
    assert fa._kv_heads_per_step(8, 128 * 4, 128, 64, 4) == 4
    assert fa._rows_per_tile(256, 1, 30, 128, 64, 4) == 256     # hybrid chunk
    assert fa._rows_per_tile(1, 20, 1, 128, 64, 4) == 1         # a decode row
    assert fa._tile_vmem_bytes(256 * 20, 128) > fa._TILE_VMEM_BUDGET
    assert fa._rows_per_tile(256, 20, 1, 128, 64, 4) == 128
    assert fa._rows_per_tile(509, 20, 1, 128, 64, 4) == 0  # a prime: whole or one position, 20 rows


@pytest.mark.parametrize("g, r, budget", [
    (20, 256, None),      # the Jamba chunk: 5,120 rows, over the 12 MiB budget
    (5, 64, 1 << 19),     # a group that is no multiple of 8, under a small budget
])
def test_a_tile_over_the_budget_is_cut_and_equals_the_gather_form(monkeypatch, g, r, budget):
    if budget:
        monkeypatch.setattr(fa, "_TILE_VMEM_BUDGET", budget)
    d, ps = 128, 64
    rows = fa._rows_per_tile(r, g, 1, d, ps, 4)
    assert 0 < rows < r and not fa._kv_heads_per_step(1, r * g, d, ps, 4)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, r, g, d))
    kp, vp = (jax.random.normal(k, (2, 12, 1, ps, d)) for k in ks[1:])
    table = jnp.array([[3, 7, 1, 9, 0, 5]], jnp.int32)
    # the chunk starts mid-page and ends 37 rows short: its last tile is part padding
    start = jnp.array([70], jnp.int32)
    lens = start + r - 37
    call = partial(fa.flash_attention_decode, interpret=True, layer=1, q_start=start)
    assert _kernels(call, q, kp, vp, table, lens) == 1  # ONE call, its tiles a grid axis
    got = call(q, kp, vp, table, lens)
    want = fa.paged_decode_reference(q, kp, vp, table, lens, 1, start)
    np.testing.assert_allclose(got[:, :r - 37], want[:, :r - 37], rtol=2e-5, atol=2e-6)
    # a chunk wholly inside its first tile: the second tile walks no page
    short = start + rows - 5
    got = fa.flash_attention_decode(q, kp, vp, table, short, interpret=True, layer=1,
                                    q_start=start)
    want = fa.paged_decode_reference(q, kp, vp, table, short, 1, start)
    np.testing.assert_allclose(got[:, :rows - 5], want[:, :rows - 5], rtol=2e-5, atol=2e-6)


def test_decode_rows_at_group_twenty_hold_the_kernels_own_padding():
    """One row a slot x 20 heads: a 20-row tile (no multiple of 8)."""
    g, d, ps = 20, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (5, g, d))
    kp, vp = (jax.random.normal(k, (2, 12, 1, ps, d)) for k in ks[1:])
    table = jnp.array([[3, 7, 1, 9, 0, 5]] * 5, jnp.int32)
    lens = jnp.array([1, 64, 65, 0, 200], jnp.int32)
    got = fa.flash_attention_decode(q, kp, vp, table, lens, interpret=True, layer=0)
    want = fa.paged_decode_reference(q, kp, vp, table, lens, 0)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-6)


def test_a_call_no_cut_serves_takes_the_gather_form():
    """3 positions x 20 heads is no multiple of 8 rows: no kernel in the
    traced call, which is what ``ServeEngine.compile`` counts."""
    q = jnp.zeros((1, 3, 20, 128))
    pool = jnp.zeros((4, 1, 64, 128))
    assert _kernels(partial(fa.flash_attention_decode, interpret=True), q, pool, pool,
                    jnp.zeros((1, 2), jnp.int32), jnp.array([3], jnp.int32)) == 0
