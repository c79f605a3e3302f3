"""Ring attention: exact attention over a context-parallel (cp) mesh axis.

Long-context support (SURVEY.md §5: absent from the reference — sequence
length was invisible to the operator; here it is a first-class library
capability). The sequence dimension of Q/K/V is sharded over the ``cp``
axis; each device computes flash-style blockwise attention of its local Q
block against the K/V block it currently holds, then rotates K/V around the
ring with ``ppermute`` — after cp_size block-steps every Q block has
attended to every K/V block, with online-softmax accumulators keeping the
result exact. K/V traffic totals cp_size-1 neighbor hops per layer (the
last block needs no onward rotation), the ring-attention recipe (Liu et
al.) mapped onto XLA collectives that ride ICI neighbor links.

Shapes follow [batch, seq, heads, head_dim]. Self-attention only: q and k/v
must share one global sequence length (the causal mask is defined by global
positions within that single sequence).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tf_operator_tpu.ops.flash_attention import NEG_INF, flash_attention_lse
from tf_operator_tpu.parallel.collectives import axis_index, axis_size, ring_shift


def reference_attention(q, k, v, causal: bool = False):
    """Dense softmax attention, the correctness oracle."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Per-device body (runs inside shard_map). q: [b, t_local, h, d];
    k/v: [b, t_local, h_kv, d] with h % h_kv == 0 — GQA-native (r3): the
    score/value einsums carry a (kv_head, group) split of the query heads
    instead of materializing repeated K/V, so the ring rotates the SMALL
    [b, t_local, h_kv, d] blocks — ICI traffic per hop drops by the group
    factor (8x for the llama2-70b 64q/8kv shape), exactly where ring
    attention's cost lives. h_kv == h is the classic path (group 1).
    Returns the local output block [b, t_local, h, d]."""
    n = axis_size(axis_name)
    my_idx = axis_index(axis_name)
    b, t_local, h, d = q.shape
    h_kv = k.shape[2]
    g = h // h_kv
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

    # [b, t, h, d] -> [b, t, h_kv, g, d]: group dim explicit for the
    # grouped contractions (h label below is the KV head dim).
    qf = q.astype(jnp.float32).reshape(b, t_local, h_kv, g, d)

    def attend_block(o, m, l, k_blk, v_blk, step):
        """Fold one K/V block into the online-softmax accumulators."""
        # The block currently held arrived from device (my_idx - step) mod n.
        src = (my_idx - step) % n
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_blk.astype(jnp.float32)) * scale
        if causal:
            q_pos = my_idx * t_local + jnp.arange(t_local)
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1)  # [b,h_kv,g,q]
        m_new = jnp.maximum(m, m_blk)
        # -inf accumulators need explicit guards: exp(-inf - -inf) is nan.
        alpha = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - m_new))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isneginf(m_new)[..., None], 0.0, p)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32)
        )
        return o_new, m_new, l_new

    def scan_body(carry, step):
        o, m, l, k_blk, v_blk = carry
        o, m, l = attend_block(o, m, l, k_blk, v_blk, step)
        # Rotate K/V onward for the next step (the final block, handled
        # outside the scan, needs no rotation).
        k_next = ring_shift(k_blk, axis_name)
        v_next = ring_shift(v_blk, axis_name)
        return (o, m, l, k_next, v_next), None

    o0 = jnp.zeros((b, h_kv, g, t_local, d), jnp.float32)
    m0 = jnp.full((b, h_kv, g, t_local), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h_kv, g, t_local), jnp.float32)
    (o, m, l, k_last, v_last), _ = jax.lax.scan(
        scan_body, (o0, m0, l0, k, v), jnp.arange(n - 1)
    )
    o, m, l = attend_block(o, m, l, k_last, v_last, n - 1)
    # Rows that attended to nothing keep l=0 (cannot happen for causal self-
    # attention with t_local >= 1, but guard the division anyway).
    o = o / jnp.where(l == 0.0, 1.0, l)[..., None]
    return jnp.einsum("bhgqd->bqhgd", o).reshape(b, t_local, h, d).astype(q.dtype)


def _merge_partials(o, m, d_acc, o_j, lse_j):
    """Fold one normalized partial attention (o_j, lse_j) into the
    running lse-weighted merge. Carry: o = Σ o_i·exp(lse_i − m) (f32),
    d_acc = Σ exp(lse_i − m), m = max lse so far. The standard exact
    softmax decomposition: each block's normalized output re-weighted by
    its share of the global mass. A fully-masked hop folds in with
    weight 0 — masked means lse <= NEG_INF/2, covering BOTH the empty
    carry's true -inf and the kernels' finite NEG_INF sentinel (-1e30;
    r3 advisor: an isneginf-only guard gave a fully-masked partial
    weight 1 against an empty carry, surviving as its uniform-softmax
    artifact)."""
    m_new = jnp.maximum(m, lse_j)
    # exp(-inf - -inf) would be nan: a masked running max (nothing folded
    # yet) or a masked hop must contribute factor 0, not nan.
    alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
    beta = jnp.where(lse_j <= NEG_INF / 2, 0.0, jnp.exp(lse_j - m_new))
    o_new = o * alpha[..., None] + o_j.astype(jnp.float32) * beta[..., None]
    return o_new, m_new, d_acc * alpha + beta


def _ring_attention_local_flash(q, k, v, axis_name: str, causal: bool,
                                interpret: bool):
    """Per-device body, flash-backed (r3): each hop's local attention runs
    through ``flash_attention_lse`` — the Pallas kernel when shapes tile
    (O(t_local·d) HBM per hop), the dense lse fallback otherwise — and
    hops merge EXACTLY via their logsumexp (_merge_partials). Versus the
    einsum body this never materializes the [t_local, t_local] score
    tensor on the kernel path, which is what caps per-device chunk sizes
    at long context (at t_local=8k, b=1, h=12 the per-hop score tensor
    alone is 3 GiB f32 — the kernel path needs none of it). Gradients are
    exact: flash_attention_lse's VJP includes the lse path, and autodiff
    composes it through the merge + scan + ppermute.

    Hop schedule: the diagonal hop (local K/V, causal mask iff causal)
    runs first, outside the scan; the scan then rotates K/V and folds
    each arriving block — under causal masking a block from a LATER
    device contributes nothing and is skipped via lax.cond (its flash
    call never runs; ICI rotation still proceeds)."""
    n = axis_size(axis_name)
    my_idx = axis_index(axis_name)
    b, t_local, h, d = q.shape

    attend = partial(flash_attention_lse, interpret=interpret)

    # Hop 0: the device's own K/V block — the only hop that can need a
    # causal mask (q and k positions share the same global block).
    o0, lse0 = attend(q, k, v, causal=causal)
    o_acc = jnp.zeros((b, t_local, h, d), jnp.float32)
    m0 = jnp.full((b, t_local, h), -jnp.inf, jnp.float32)
    o_acc, m_acc, d_acc = _merge_partials(
        o_acc, m0, jnp.zeros((b, t_local, h), jnp.float32), o0, lse0)

    def scan_body(carry, step):
        o_m_d, k_blk, v_blk = carry
        k_blk = ring_shift(k_blk, axis_name)
        v_blk = ring_shift(v_blk, axis_name)
        src = (my_idx - step) % n  # device whose block just arrived

        def live(_):
            return attend(q, k_blk, v_blk, causal=False)

        def skip(_):
            return (jnp.zeros((b, t_local, h, d), q.dtype),
                    jnp.full((b, t_local, h), -jnp.inf, jnp.float32))

        if causal:
            # src > my_idx ⇒ every key position is in the future of every
            # local query position ⇒ the hop is fully masked.
            o_j, lse_j = jax.lax.cond(src < my_idx, live, skip, None)
        else:
            o_j, lse_j = live(None)
        return ((_merge_partials(*o_m_d, o_j, lse_j), k_blk, v_blk), None)

    ((o_acc, m_acc, d_acc), _, _), _ = jax.lax.scan(
        scan_body, ((o_acc, m_acc, d_acc), k, v), jnp.arange(1, n))
    o = o_acc / jnp.where(d_acc == 0.0, 1.0, d_acc)[..., None]
    return o.astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    mesh,
    axis_name: str = "cp",
    causal: bool = False,
    batch_axes: Optional[tuple] = None,
    impl: Optional[str] = None,
    interpret: bool = False,
):
    """Exact self-attention with sequence sharded over ``axis_name``.

    q/k/v: global arrays [batch, seq, heads, head_dim] sharing one seq
    length divisible by the cp axis size. ``batch_axes``: mesh axes the
    batch dim is sharded over (kept sharded through the computation).

    ``impl``: "flash" (default — per-hop local attention through
    flash_attention_lse, Pallas kernel on TPU when shapes tile, dense
    lse fallback otherwise) or "einsum" (the blockwise online-softmax
    oracle body, materializes per-hop scores). ``interpret`` forces the
    flash path's kernels through the Pallas interpreter (CPU tests).
    """
    from tf_operator_tpu.parallel.collectives import shard_map

    cp = mesh.shape[axis_name]
    if q.shape[1] != k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"ring attention is self-attention: q/k/v seq lengths must match, "
            f"got {q.shape[1]}/{k.shape[1]}/{v.shape[1]}"
        )
    if k.shape[2] != v.shape[2]:
        raise ValueError(f"k/v head mismatch: {k.shape[2]} vs {v.shape[2]}")
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"q heads {q.shape[2]} not a multiple of kv heads {k.shape[2]} "
            "(GQA group must divide evenly)"
        )
    if q.shape[1] % cp:
        raise ValueError(f"seq length {q.shape[1]} must divide by {axis_name}={cp}")
    if impl not in (None, "flash", "einsum"):
        raise ValueError(f"unknown ring attention impl {impl!r}")
    if impl == "einsum":
        body = partial(_ring_attention_local, axis_name=axis_name, causal=causal)
    else:
        body = partial(_ring_attention_local_flash, axis_name=axis_name,
                       causal=causal, interpret=interpret)
    spec = P(batch_axes, axis_name, None, None)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
