"""Ulysses-style sequence parallelism: all-to-all head/sequence re-shard.

The second long-context recipe (SURVEY.md §2.3 SP/CP row lists ring,
blockwise, and Ulysses — the reference has none). Where ring attention
keeps heads whole and ROTATES K/V sequence blocks around the cp ring
(cp-1 neighbor hops per layer), Ulysses (DeepSpeed) re-shards ONCE per
attention: an all-to-all turns [seq-sharded, all heads] into
[full seq, head-sharded], each device runs ordinary attention on its
head slice over the FULL sequence, and a second all-to-all restores the
sequence sharding. Two all-to-alls total, each moving t·h·d/cp per
device — cheaper than the ring when cp is large and heads divide evenly,
and the inner attention is just the single-device kernel, so the Pallas
flash path applies untouched (`attn_fn=`).

Trade-off vs ring (why both exist): Ulysses needs n_heads % cp == 0 and
materializes the full-sequence K/V per device (HBM: t·h·d/cp per tensor
— fine until t·d/cp outgrows a head shard); ring keeps per-device memory
at t/cp blocks and has no head-divisibility constraint, at the cost of
cp-1 sequential ppermute steps. The transformer exposes both:
``attn_impl="ring" | "ulysses"``.

GQA (r3): with n_kv % cp == 0, K/V all-to-all on their OWN head dim —
each device then holds h/cp query heads and n_kv/cp kv heads, and
``attn_fn`` MUST accept GQA-shaped inputs (the flash kernel and the
grouped dense reference both do). n_kv % cp != 0 (r4): K/V are
ALL-GATHERED over cp on the sequence dim instead — (cp-1)/cp · t·n_kv·d
moved per device vs the r3 silent repeat's (cp-1)/cp · t·h·d/cp through
the all-to-all, i.e. cp/g the traffic (less whenever cp < g) and no
[t, h, d] repeated tensor is ever materialized. Each shard then takes
exactly the kv heads its contiguous query-head block maps to
(j -> j//g), so the local attention is equal-headed and any MHA
``attn_fn`` works. Per-device K/V HBM is t·(n_kv + h/cp)·d — same
order as the n_kv % cp == 0 path when g >= cp.

Layout contract matches ring_attention: global [batch, seq, heads,
head_dim], sequence sharded over ``axis_name`` on entry and exit.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tf_operator_tpu.parallel.collectives import axis_size
# the GQA-native dense oracle (grouped einsum) — parallel/ring_attention's
# reference is MHA-only and would reject mismatched local head counts
from tf_operator_tpu.ops.flash_attention import reference_attention


def _ulysses_local(q, k, v, axis_name: str, causal: bool,
                   attn_fn: Optional[Callable], gather_kv: bool = False):
    """Per-device body. q/k/v: [b, t_local, h, d] (sequence-sharded).

    all_to_all over the heads dim: [b, t_local, h, d] -> concat over the
    cp group's t blocks with h/cp local heads -> [b, t_global, h_local, d].

    ``gather_kv`` (the n_kv % cp != 0 path): K/V skip the head split —
    they are all-gathered whole over the sequence dim, then each shard
    TAKES the kv head serving each of its h/cp contiguous query heads
    (global query head i·h/cp + j -> kv head (i·h/cp + j)//g), handing
    attn_fn an equal-headed local problem. Exact: same softmax, the
    take only materializes the repeat lazily and only for this shard's
    query block.
    """
    n = axis_size(axis_name)

    def seq_to_heads(x):
        # split heads into n groups, hand group i to shard i, receiving
        # every shard's sequence block for OUR head group
        x = jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )
        return x  # [b, t_global, h/n, d]

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )  # [b, t_local, h, d]

    qg = seq_to_heads(q)
    if gather_kv:
        h, h_kv = q.shape[2], k.shape[2]
        g, h_loc = h // h_kv, h // n
        kg = jax.lax.all_gather(k, axis_name, axis=1, tiled=True)
        vg = jax.lax.all_gather(v, axis_name, axis=1, tiled=True)
        i = jax.lax.axis_index(axis_name)
        head_map = (i * h_loc + jnp.arange(h_loc)) // g
        kg = jnp.take(kg, head_map, axis=2)
        vg = jnp.take(vg, head_map, axis=2)
    else:
        kg, vg = seq_to_heads(k), seq_to_heads(v)
    if attn_fn is None:
        out = reference_attention(qg, kg, vg, causal=causal)
    else:
        out = attn_fn(qg, kg, vg)
    return heads_to_seq(out.astype(q.dtype))


def ulysses_attention(
    q,
    k,
    v,
    mesh,
    axis_name: str = "cp",
    causal: bool = False,
    batch_axes: Optional[tuple] = None,
    attn_fn: Optional[Callable] = None,
):
    """Exact self-attention with sequence sharded over ``axis_name`` via
    head/sequence all-to-all re-sharding (DeepSpeed-Ulysses recipe).

    q/k/v: global [batch, seq, heads, head_dim] (k/v may carry
    n_kv < heads GQA heads); seq % cp == 0 and heads % cp == 0 required.
    ``attn_fn(q, k, v)`` runs the per-device full-sequence attention and
    must handle GQA-shaped k/v when n_kv % cp == 0 (its local inputs are
    then h/cp query vs n_kv/cp kv heads — the flash kernel and the
    grouped dense default both do; an MHA-only attn_fn is safe only for
    equal-head models)."""
    from tf_operator_tpu.parallel.collectives import shard_map

    cp = mesh.shape[axis_name]
    b, t, h, d = q.shape
    h_kv = k.shape[2]
    if t % cp:
        raise ValueError(f"seq length {t} must divide by {axis_name}={cp}")
    if h % cp:
        raise ValueError(
            f"ulysses needs heads % cp == 0 (got {h} heads, cp={cp}) — "
            "use attn_impl='ring' for head counts the cp axis cannot split"
        )
    if k.shape[2] != v.shape[2]:
        raise ValueError(f"k/v head mismatch: {k.shape[2]} vs {v.shape[2]}")
    if h % h_kv:
        raise ValueError(
            f"q heads {h} not a multiple of kv heads {h_kv}"
        )
    # GQA (r3): when the kv heads divide cp, K/V all-to-all on their OWN
    # (smaller) head dim — each shard gets n_kv/cp kv heads + full seq,
    # moving group-times less data per all-to-all, and the local
    # attention runs GQA-native (contiguous head blocks keep query head
    # j -> kv head j//group aligned per shard since h/cp = g * n_kv/cp).
    # Indivisible kv counts (r4): all-gather the small K/V whole and map
    # heads per shard inside the body — no silent repeat (the r3
    # fallback restored exactly the K/V traffic GQA removes).
    gather_kv = bool(h_kv != h and h_kv % cp)
    spec = P(batch_axes, axis_name, None, None)
    fn = shard_map(
        partial(_ulysses_local, axis_name=axis_name, causal=causal,
                attn_fn=attn_fn, gather_kv=gather_kv),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
