"""Collective helpers for shard_map bodies.

Thin, named wrappers over the XLA collectives (psum / all_gather /
reduce_scatter / ppermute) — the framework NEVER reimplements collectives
(SURVEY.md §2.3: the reference delegated them to TF's runtime; we delegate
to XLA, which maps them onto ICI rings).
"""

from __future__ import annotations

import functools

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with VMA checking off — the shard bodies reduce
    their own stats with explicit psum/pmean, which the checker cannot
    see through custom_vjp boundaries. Every shard_map in the
    parallelism layer routes through here so that policy is stated
    once."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def all_reduce_mean(x, axis_name: str):
    """Gradient-style mean all-reduce."""
    return jax.lax.pmean(x, axis_name)


def all_reduce_sum(x, axis_name: str):
    return jax.lax.psum(x, axis_name)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` (FSDP param gather)."""
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter_sum(x, axis_name: str, axis: int = 0, tiled: bool = True):
    """Sum-reduce then scatter along ``axis`` (FSDP grad reduce)."""
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=tiled)


# Megatron's f/g conjugate operator pair for tensor parallelism inside a
# manual (shard_map) region. Plain lax.psum is WRONG for this pattern
# under direct jax.vjp: JAX's psum transpose is psum again (the pmap-era
# convention), which inflates every cotangent behind the reduction by the
# axis size — and the factors compound per layer. The pair pins the
# correct transposes: activations enter the tp region through tp_enter
# (identity fwd / psum bwd: each shard's partial input-cotangent sums to
# the true one) and partial row-parallel products leave through tp_exit
# (psum fwd / identity bwd: the output cotangent is replicated and flows
# to every shard untouched).


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_region_enter(x, axis_name: str):
    """Megatron f: identity forward; backward psums the (shard-partial)
    input cotangent over the tp axis."""
    return x


def _tp_enter_fwd(x, axis_name):
    return x, None


def _tp_enter_bwd(axis_name, _, g):
    return (jax.lax.psum(g, axis_name),)


tp_region_enter.defvjp(_tp_enter_fwd, _tp_enter_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_region_exit(x, axis_name: str):
    """Megatron g: psum forward (combine row-parallel partials); backward
    passes the replicated output cotangent through unchanged."""
    return jax.lax.psum(x, axis_name)


def _tp_exit_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _tp_exit_bwd(axis_name, _, g):
    return (g,)


tp_region_exit.defvjp(_tp_exit_fwd, _tp_exit_bwd)


def ring_shift(x, axis_name: str, shift: int = 1):
    """Rotate shards around the ring (ring attention / pipeline transfers)."""
    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str):
    return jax.lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return jax.lax.psum(1, axis_name)
