"""Fused (blockwise) softmax cross-entropy over a tied vocab projection.

The naive LM loss materializes f32 logits ``[batch*seq, vocab]`` in HBM
(BERT-base at b=32/s=512: 2.0 GB), then log_softmax re-reads and re-writes
them, the gather reads them again, and autodiff stores log-probs as a
residual for the backward — on a bandwidth-bound chip those passes cost
more than the head matmul itself. This op never materializes the logits:
the hidden states are processed in row (token) blocks, each block computes
its ``[rows, vocab]`` logits tile on the MXU with f32 accumulation,
reduces it to a log-sum-exp and the target logit immediately, and the
backward pass recomputes the tile (flash-attention-style) to form
``softmax - onehot`` on the fly. Residuals are just the per-token LSE —
O(batch*seq) instead of O(batch*seq*vocab). On a mesh that only splits the
batch the op partitions itself: each chip walks its own rows (``mesh``).

The reference operator has no numerics at all (SURVEY.md §2 — it
configures TensorFlow's runtime); this is part of the TPU data-plane layer
that replaces what TF shipped pre-compiled. Same-math unfused path =
``models.transformer.lm_loss`` with ``fused_xent=False``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tf_operator_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def data_parallel_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes the loss's rows are split over, when splitting rows is
    ALL the mesh does: the data axes (``dp``, ``fsdp``) of size > 1 of a
    mesh whose every other axis has size 1. () for no mesh, one chip, or a
    mesh that also shards anything else (``tp`` shards the vocab, ``cp``
    the sequence, ``pp`` / ``ep`` the layers / experts)."""
    if mesh is None or getattr(mesh, "devices", None) is None:
        return ()
    sizes = dict(mesh.shape)
    if any(n > 1 for a, n in sizes.items() if a not in (AXIS_DATA, AXIS_FSDP)):
        return ()
    return tuple(a for a in (AXIS_DATA, AXIS_FSDP) if sizes.get(a, 1) > 1)


def fused_cross_entropy(
    x: jax.Array,
    embed: jax.Array,
    targets: jax.Array,
    weights: Optional[jax.Array] = None,
    *,
    row_block: int = 1024,
    mesh=None,
) -> jax.Array:
    """Mean softmax cross-entropy of ``x @ embed.T`` against ``targets``.

    Args:
      x: [..., d] hidden states (bf16 or f32). Differentiated.
      embed: [vocab, d] tied projection table (f32 params). Differentiated.
      targets: [...] int32 class ids. Not differentiated.
      weights: optional [...] per-token weights (e.g. an MLM mask); the loss
        is ``sum(w * xent) / max(sum(w), 1)`` — with weights omitted this
        is the plain mean, matching the unfused path exactly.
      row_block: tokens per block; each block's logit tile is
        ``[row_block, vocab]`` f32 and lives only inside the block.
      mesh: the mesh the operands live on. Where all it does is split the
        batch (``data_parallel_axes``) and the leading dim of ``x`` divides
        over it, each chip walks the rows it already holds, under
        ``shard_map``: the head's ``d`` slices (``fsdp``) are cast to
        ``x.dtype`` and gathered ONCE a pass, the head's gradient leaves as
        the gather's transpose (one reduce-scatter of the f32 accumulator),
        and two scalars are summed across chips. Left to propagation the
        d-sharded head makes every chip multiply ALL rows by its slice and
        all-reduce the ``[row_block, vocab]`` f32 partial logits, once a
        block a pass (PERF.md §6, PR 31). Any other mesh, or none: one walk
        over all rows, partitioned (if at all) by propagation.

    Returns: scalar f32 loss.
    """
    if targets.size == 0:
        raise ValueError(
            "fused_cross_entropy needs at least one row (n=0; causal lm_loss "
            "with seq_len=1 produces an empty target set)"
        )
    if weights is None:
        weights = jnp.ones(targets.shape, jnp.float32)
    weights = weights.astype(jnp.float32)

    axes = data_parallel_axes(mesh)
    shards = math.prod(mesh.shape[a] for a in axes)
    with jax.named_scope("fused_xent"):
        if shards > 1 and x.shape[0] % shards == 0:
            from jax.sharding import PartitionSpec as P

            from tf_operator_tpu.parallel.collectives import shard_map

            # the head as parallel/sharding.py stores it: d over fsdp
            gather = AXIS_FSDP if (AXIS_FSDP in axes and x.shape[-1]
                                   % mesh.shape[AXIS_FSDP] == 0) else None

            def local(x, embed, targets, weights):
                return jax.lax.psum(
                    _xent_sums(x, embed, targets, weights, row_block, gather), axes)

            loss_sum, w_sum = shard_map(
                local, mesh=mesh,
                in_specs=(P(axes), P(None, gather), P(axes), P(axes)),
                out_specs=(P(), P()),
            )(x, embed, targets, weights)
        else:
            loss_sum, w_sum = _xent_sums(x, embed, targets, weights, row_block)
        return loss_sum / jnp.maximum(w_sum, 1.0)


def _xent_sums(x, embed, targets, weights, row_block, gather_axis=None):
    """(sum of weights * xent, sum of weights) over the rows handed in:
    x [..., d] flattened, padded to whole blocks and walked block by block."""
    d = x.shape[-1]
    x, targets, weights = x.reshape(-1, d), targets.reshape(-1), weights.reshape(-1)
    n = x.shape[0]
    r = min(row_block, _round_up(n, 8))
    n_pad = _round_up(n, r)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        targets = jnp.pad(targets, (0, n_pad - n))
        weights = jnp.pad(weights, (0, n_pad - n))  # pad rows weigh zero
    # dx matches the padded primal; autodiff of the pad and the reshape
    # slices the pad rows back off for the caller.
    loss_sum = _weighted_xent_sum(x, embed, targets, weights, r, gather_axis)
    return loss_sum, jnp.sum(weights)


# targets and weights are explicit, non-differentiated operands (not closed
# over): a closure's tracers leak when the call sits under shard_map + grad.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _weighted_xent_sum(x, embed, targets, weights, r, gather_axis):
    """sum(weights * xent) over rows x [n, d] walked in blocks of ``r`` (n a
    multiple of r); embed [vocab, d], or its [vocab, d/S] slice along
    ``gather_axis`` inside a shard_map."""
    return _xent_fwd(x, embed, targets, weights, r, gather_axis)[0]


def _blocked(r, *arrays):
    return tuple(a.reshape(a.shape[0] // r, r, *a.shape[1:]) for a in arrays)


def _xent_fwd(x, embed, targets, weights, r, gather_axis):
    et = embed.astype(x.dtype)  # one cast, BEFORE any gather; reused by every block
    if gather_axis is not None:
        et = jax.lax.all_gather(et, gather_axis, axis=1, tiled=True)
    cols = jnp.arange(et.shape[0], dtype=targets.dtype)

    def block(loss_sum, inp):
        x_c, t_c, w_c = inp
        logits = jnp.dot(x_c, et.T, preferred_element_type=jnp.float32)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        # target logit via a fused compare+select reduction over the tile
        # (a take_along_axis gather here costs a real gather op per block)
        onehot = t_c[:, None] == cols[None, :]
        tgt = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        return loss_sum + jnp.sum(w_c * (lse - tgt)), lse

    loss_sum, lse = jax.lax.scan(
        block, jnp.float32(0.0), _blocked(r, x, targets, weights))
    # the gathered head is kept for the backward (it follows at once): one
    # gather a step, not two; ungathered, the cast is redone from the params
    return loss_sum, (x, embed, None if gather_axis is None else et,
                      targets, weights, lse)


def _xent_bwd(r, gather_axis, res, g):
    x, embed, et, targets, weights, lse = res
    if et is None:
        et = embed.astype(x.dtype)
    cols = jnp.arange(et.shape[0], dtype=targets.dtype)

    def block(d_embed, inp):
        x_c, t_c, c_c, lse_c = inp
        logits = jnp.dot(x_c, et.T, preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lse_c[:, None])  # softmax, recomputed
        # minus onehot(target), as fused select (not a scatter)
        p = jnp.where(t_c[:, None] == cols[None, :], p - 1.0, p)
        pc = (p * c_c[:, None]).astype(x.dtype)
        dx_c = jnp.dot(pc, et, preferred_element_type=jnp.float32)
        d_embed = d_embed + jnp.dot(pc.T, x_c, preferred_element_type=jnp.float32)
        return d_embed, dx_c

    d_embed, dx = jax.lax.scan(
        block, jnp.zeros(et.shape, jnp.float32),
        _blocked(r, x, targets, g * weights) + (lse,))
    if gather_axis is not None:
        # the gather's transpose, on the f32 accumulator: each chip keeps
        # the sum of its own d slice
        d_embed = jax.lax.psum_scatter(
            d_embed, gather_axis, scatter_dimension=1, tiled=True)
    return dx.reshape(x.shape).astype(x.dtype), d_embed.astype(embed.dtype), None, None


_weighted_xent_sum.defvjp(_xent_fwd, _xent_bwd)
