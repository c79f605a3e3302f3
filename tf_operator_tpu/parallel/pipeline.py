"""Pipeline parallelism: microbatch schedules over the ``pp`` axis.

Each pipeline stage lives on one slice of the ``pp`` mesh axis and holds its
own layer parameters; activations flow stage-to-stage with ``ppermute`` over
neighbor ICI links. Two schedules:

- ``"gpipe"``: the classic fill-drain loop; the backward pass is whatever
  JAX autodiff derives from the forward scan. With S stages and M
  microbatches, T = M + S - 1 ticks per phase; bubble (S-1)/(M+S-1).
  Autodiff saves per-TICK residuals — T slots, garbage fill/drain ticks
  included.
- ``"1f1b"`` (r3): an explicit custom-VJP schedule with the 1F1B memory
  discipline — the forward saves ONLY each stage's M microbatch inputs,
  and the backward is a hand-scheduled reverse pipeline that recomputes
  each stage-microbatch forward via jax.vjp at its saved input (the
  standard 1F1B recompute recipe). Per-stage activation memory drops from
  M+S-1 tick-saves to M input-saves, and the backward never replays the
  fill/drain garbage ticks' residuals. Because JAX's grad boundary sits
  at the loss (all output cotangents arrive at once), the fwd and bwd
  phases cannot physically interleave — the schedule realizes 1F1B's
  memory/recompute structure, with the same 2(M+S-1)-tick timeline as
  GPipe at equal M. The practical bubble win is therefore what 1F1B's
  always was: at a FIXED activation budget the schedule affords a larger
  M — e.g. at pp=4 with an 8-slot budget, GPipe fits M=5 (bubble
  (S-1)/(M+S-1) = 37.5%) while 1F1B fits M=8 (27%); see
  ``bubble_fraction``.

- ``"1f1b"`` with ``n_chunks=v > 1`` (r3): the INTERLEAVED virtual-stage
  schedule. The model splits into J = S·v chunks; device d holds chunks
  d, d+S, …, d+(v-1)S, so a microbatch laps the ring v times. Schedule:
  microbatches run in rounds of S; round r's chunk-j execution of its
  m-th member lands at tick r·S·v + m + j on device j mod S. Two
  properties make this a single uniform scan: (a) within a round each
  device's executions occupy DISTINCT ticks (m < S and the device's
  chunks are S apart), and (b) consecutive rounds offset by S·v slot
  into each device's busy window back-to-back — so every activation
  produced at tick t is consumed at tick t+1 one neighbor over
  (chunk j → j+1 is device j%S → (j+1)%S, cyclic: the wrap S-1 → 0 is
  the same ppermute hop), no buffering, no stalls beyond fill/drain.
  Timeline: M·v + S - 1 ticks for M·v chunk-executions per device ⇒
  bubble (S-1)/(M·v + S - 1) — v times smaller than GPipe/plain-1F1B at
  equal M (27% → 16% at pp=4, M=4, v=2 → v=4). Costs: v·M saved stage
  inputs per device (vs M) and v× the ppermute volume — the standard
  interleaved trade. v=1 reduces exactly to the plain 1F1B schedule.

The reference has no pipeline support at all (SURVEY.md §2.3); this is new
TPU-native surface.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tf_operator_tpu.parallel.collectives import axis_index, axis_size, ring_shift


def _pipeline_local(stage_params, x_micro, fn: Callable, axis_name: str,
                    aux_size: int):
    """Per-device body (inside shard_map).

    stage_params: this stage's params (leading dim of size 1 stripped).
    x_micro: [n_micro, mb, ...] — full microbatched input, replicated.
    Returns [n_micro, mb, ...] outputs (valid on the last stage; psum'ed so
    every stage returns the same array).

    fn ALWAYS returns (out, aux[aux_size] f32) — plain stage bodies are
    wrapped by _with_aux at the call sites (a zero dummy row). aux rows
    are summable side losses (MoE router lb/z): each stage accumulates
    its VALID ticks' aux and returns the LOCAL sum (no collective — the
    caller stacks per-shard rows through the shard_map output and reduces
    outside it, where autodiff needs no collective-transpose reasoning).
    Returns (y, aux_local)."""
    n_stages = axis_size(axis_name)
    stage = axis_index(axis_name)
    n_micro = x_micro.shape[0]
    mb_shape = x_micro.shape[1:]

    total_ticks = n_micro + n_stages - 1

    def tick(carry, t):
        prev_out, y_acc, aux_acc = carry
        # Receive activation from the previous stage (stage 0 receives
        # garbage from the last stage and ignores it).
        recv = ring_shift(prev_out, axis_name)
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        first_in = jax.lax.dynamic_index_in_dim(x_micro, mb_idx, keepdims=False)
        x_in = jnp.where(stage == 0, first_in, recv)
        out, aux = fn(stage_params, x_in)
        live = (t - stage >= 0) & (t - stage < n_micro)
        aux_acc = aux_acc + jnp.where(live, aux, jnp.zeros_like(aux))
        # Last stage writes its result for microbatch t-(S-1) when valid.
        out_idx = t - (n_stages - 1)
        valid = (stage == n_stages - 1) & (out_idx >= 0) & (out_idx < n_micro)
        write_idx = jnp.clip(out_idx, 0, n_micro - 1)
        prev_slot = jax.lax.dynamic_index_in_dim(y_acc, write_idx, keepdims=False)
        new_slot = jnp.where(valid, out, prev_slot)
        y_acc = jax.lax.dynamic_update_index_in_dim(y_acc, new_slot, write_idx, 0)
        return (out, y_acc, aux_acc), None

    out0 = jnp.zeros(mb_shape, x_micro.dtype)
    y0 = jnp.zeros((n_micro,) + mb_shape, x_micro.dtype)
    aux0 = jnp.zeros((aux_size,), jnp.float32)
    (_, y, aux_acc), _ = jax.lax.scan(
        tick, (out0, y0, aux0), jnp.arange(total_ticks)
    )
    # Broadcast the last stage's result to every stage (replicated output).
    y = jax.lax.psum(
        jnp.where(stage == n_stages - 1, y, jnp.zeros_like(y)), axis_name
    )
    return y, aux_acc


def bubble_fraction(n_stages: int, n_micro: int, n_chunks: int = 1) -> float:
    """Idle fraction of the fill-drain timeline: (S-1)/(M·v + S-1).
    v = 1: both schedules share it at equal M — plain 1F1B's lever is
    affording a larger M at fixed activation memory. v > 1 (interleaved):
    the same S-1 fill/drain ticks amortize over v times the per-device
    work (module docstring)."""
    return (n_stages - 1) / (n_micro * n_chunks + n_stages - 1)


def _fwd_coords(t, stage, n_stages, n_micro, n_chunks):
    """Decode the interleaved forward schedule: at tick t, the device at
    ``stage`` executes chunk i (its i-th virtual stage, global
    j = stage + i·S) of microbatch m_total — or nothing (valid False).
    Derivation (module docstring): exec tick of round r's m-th member at
    virtual stage j is r·S·v + m + j, so with u = t - stage:
    u = (r·v + i)·S + m."""
    u = t - stage
    q = u // n_stages
    m = u % n_stages
    r = q // n_chunks
    i = q % n_chunks
    m_total = r * n_stages + m
    valid = (u >= 0) & (u < n_micro * n_chunks)
    return valid, jnp.clip(i, 0, n_chunks - 1), jnp.clip(m_total, 0, n_micro - 1)


def _bwd_coords(t, stage, n_stages, n_micro, n_chunks):
    """The backward schedule is the forward's mirror (stage → S-1-stage,
    chunk → v-1-chunk, round → R-1-round, member → S-1-member): cotangents
    enter at the last virtual stage on device S-1 and hop backwards one
    neighbor per tick, with the same contiguous busy windows."""
    ub = t - (n_stages - 1 - stage)
    valid = (ub >= 0) & (ub < n_micro * n_chunks)
    if n_chunks == 1:
        # plain mirror over microbatches — no round structure, so any M
        # (the interleaved decode below needs M % S == 0, enforced by
        # pipeline_apply for v > 1)
        m_total = n_micro - 1 - ub
        return valid, jnp.zeros_like(ub), jnp.clip(m_total, 0, n_micro - 1)
    qb = ub // n_stages
    mb = ub % n_stages
    rb = qb // n_chunks
    ib = qb % n_chunks
    n_rounds = n_micro // n_stages
    i = n_chunks - 1 - ib
    m_total = (n_rounds - 1 - rb) * n_stages + (n_stages - 1 - mb)
    return valid, jnp.clip(i, 0, n_chunks - 1), jnp.clip(m_total, 0, n_micro - 1)


def _fwd_save_ticks(stage_params, x_micro, fn: Callable, axis_name: str,
                    aux_size: int, n_chunks: int = 1):
    """_pipeline_local plus residual capture: returns (y, aux, x_saved)
    where x_saved[i·M + m] is THIS device's chunk-i input for microbatch
    m — the only activation the 1F1B backward needs (it recomputes the
    rest). stage_params carry a leading chunk dim [v, ...] (v = n_chunks;
    1 = plain 1F1B). Same fn contract as _pipeline_local: ALWAYS
    (out, aux) — wrap plain bodies with _with_aux."""
    n_stages = axis_size(axis_name)
    stage = axis_index(axis_name)
    n_micro = x_micro.shape[0]
    mb_shape = x_micro.shape[1:]
    total_ticks = n_micro * n_chunks + n_stages - 1

    def tick(carry, t):
        prev_out, y_acc, aux_acc, x_saved = carry
        recv = ring_shift(prev_out, axis_name)
        valid, ci, m_total = _fwd_coords(t, stage, n_stages, n_micro, n_chunks)
        first_in = jax.lax.dynamic_index_in_dim(x_micro, m_total, keepdims=False)
        # Fresh microbatches enter only at the FIRST virtual stage (device
        # 0, chunk 0); every other execution consumes its neighbor's hop.
        x_in = jnp.where((stage == 0) & (ci == 0), first_in, recv)
        slot = ci * n_micro + m_total
        prev_save = jax.lax.dynamic_index_in_dim(x_saved, slot, keepdims=False)
        x_saved = jax.lax.dynamic_update_index_in_dim(
            x_saved, jnp.where(valid, x_in, prev_save), slot, 0
        )
        params_i = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, ci, keepdims=False),
            stage_params,
        )
        out, aux = fn(params_i, x_in)
        aux_acc = aux_acc + jnp.where(valid, aux, jnp.zeros_like(aux))
        # The LAST virtual stage (device S-1, chunk v-1) emits results.
        ovalid = valid & (stage == n_stages - 1) & (ci == n_chunks - 1)
        prev_slot = jax.lax.dynamic_index_in_dim(y_acc, m_total, keepdims=False)
        y_acc = jax.lax.dynamic_update_index_in_dim(
            y_acc, jnp.where(ovalid, out, prev_slot), m_total, 0
        )
        return (out, y_acc, aux_acc, x_saved), None

    out0 = jnp.zeros(mb_shape, x_micro.dtype)
    y0 = jnp.zeros((n_micro,) + mb_shape, x_micro.dtype)
    aux0 = jnp.zeros((aux_size,), jnp.float32)
    s0 = jnp.zeros((n_chunks * n_micro,) + mb_shape, x_micro.dtype)
    (_, y, aux_acc, x_saved), _ = jax.lax.scan(
        tick, (out0, y0, aux0, s0), jnp.arange(total_ticks)
    )
    y = jax.lax.psum(
        jnp.where(stage == n_stages - 1, y, jnp.zeros_like(y)), axis_name
    )
    return y, aux_acc, x_saved


def _bwd_ticks(stage_params, x_saved, gy, fn: Callable, axis_name: str, g_aux,
               n_chunks: int = 1):
    """The reverse pipeline: cotangents enter at the LAST virtual stage
    (device S-1, chunk v-1) and ppermute backwards one neighbor per tick
    (_bwd_coords — the forward schedule's mirror); each tick recomputes
    its (chunk, microbatch) forward from the saved input via jax.vjp
    (1F1B recompute) and accumulates that chunk's param grads. Inputs:
    stage_params [v, ...] per-chunk, x_saved [v·M, mb...] as
    _fwd_save_ticks wrote it. Returns (dparams [v, ...], dx) with dx
    valid on stage 0 (psum-broadcast like the forward's y).

    tp-within-stage note: ``fn`` must handle its own tp cotangent algebra
    via the Megatron f/g conjugate pair (collectives.tp_region_enter/
    tp_region_exit, as models/transformer._layer does) — with those in
    place every shard's vjp already yields the full replicated input
    cotangent, so no stage-level reduction is needed here (and a naive
    psum of dx would double-count the residual path)."""
    n_stages = axis_size(axis_name)
    stage = axis_index(axis_name)
    n_micro = x_saved.shape[0] // n_chunks
    mb_shape = x_saved.shape[1:]
    total_ticks = n_micro * n_chunks + n_stages - 1

    dp0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), stage_params
    )

    def tick(carry, t):
        prev_dx, dp_acc, dx_acc = carry
        recv = ring_shift(prev_dx, axis_name, shift=-1)  # from stage s+1
        valid, ci, m_total = _bwd_coords(t, stage, n_stages, n_micro, n_chunks)
        g_in = jnp.where(
            (stage == n_stages - 1) & (ci == n_chunks - 1),
            jax.lax.dynamic_index_in_dim(gy, m_total, keepdims=False),
            recv,
        )
        slot = ci * n_micro + m_total
        x_in = jax.lax.dynamic_index_in_dim(x_saved, slot, keepdims=False)
        params_i = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, ci, keepdims=False),
            stage_params,
        )
        _, vjp_fn = jax.vjp(fn, params_i, x_in)
        # every valid tick's aux entered the sum with weight 1, so its
        # cotangent is g_aux itself; invalid ticks' pollution of dparams
        # is masked below and their dx never reaches a valid consumer
        # (the reverse schedule masks by the same validity)
        dp, dx = vjp_fn((g_in, g_aux))
        dp_acc = jax.tree_util.tree_map(
            lambda acc, new: jax.lax.dynamic_update_index_in_dim(
                acc,
                jax.lax.dynamic_index_in_dim(acc, ci, keepdims=False)
                + jnp.where(valid, new.astype(jnp.float32),
                            jnp.zeros_like(new, jnp.float32)),
                ci, 0,
            ),
            dp_acc,
            dp,
        )
        w_valid = valid & (stage == 0) & (ci == 0)
        prev_slot = jax.lax.dynamic_index_in_dim(dx_acc, m_total, keepdims=False)
        dx_acc = jax.lax.dynamic_update_index_in_dim(
            dx_acc, jnp.where(w_valid, dx, prev_slot), m_total, 0
        )
        return (dx, dp_acc, dx_acc), None

    dx0 = jnp.zeros(mb_shape, x_saved.dtype)
    dxa0 = jnp.zeros((n_micro,) + mb_shape, x_saved.dtype)
    (_, dparams, dx), _ = jax.lax.scan(
        tick, (dx0, dp0, dxa0), jnp.arange(total_ticks)
    )
    dx = jax.lax.psum(
        jnp.where(stage == 0, dx, jnp.zeros_like(dx)), axis_name
    )
    dparams = jax.tree_util.tree_map(
        lambda g, p: g.astype(p.dtype), dparams, stage_params
    )
    return dparams, dx


def _shard_specs(stage_params, x, mesh, n_microbatches, axis_name, batch_axes,
                 param_specs):
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError(f"batch {batch} not divisible by {n_microbatches} microbatches")
    mb = batch // n_microbatches
    data_axes = tuple(
        a for a in batch_axes
        if a in getattr(mesh, "axis_names", ()) and mesh.shape[a] > 1
    )
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    if mb % n_data:
        raise ValueError(
            f"microbatch size {mb} (batch {batch} / {n_microbatches} "
            f"microbatches) not divisible by data shards {n_data}"
        )
    # STRIDED microbatch layout (r5, VERDICT r4 #3): microbatch i takes
    # rows [i::n_micro], i.e. x_micro[i, j] = x[j*n_micro + i], built as
    # reshape(mb, n_micro)+swapaxes. A microbatch-MAJOR split
    # (x.reshape(n_micro, mb)) can never be computed locally under a
    # batch-dim sharding — microbatch 0 = rows [0, mb) spans several
    # shards' contiguous blocks, so GSPMD falls back to "involuntary full
    # rematerialization" (replicate then re-slice) on every entry to and
    # exit from the pipeline's shard_map. With the strided split, target
    # device g's rows {j*n_micro + i : j in g's mb-block} ARE g's
    # contiguous batch block: the reshape is layout-local. Which rows
    # form a microbatch is internal to the pipeline (the inverse
    # permutation at the exit restores batch order exactly), so the math
    # is unchanged up to microbatch membership — the same freedom any
    # pipeline implementation exercises. The with_sharding_constraint
    # anchors x's batch dim to the data axes so the propagated layout
    # matches the local-reshape contract.
    if data_axes and getattr(mesh, "devices", None) is not None:
        from jax.sharding import NamedSharding

        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(data_axes, *(None,) * (x.ndim - 1)))
        )
    x_micro = jnp.swapaxes(
        x.reshape((mb, n_microbatches) + x.shape[1:]), 0, 1
    )
    x_spec = P(None, data_axes or None)  # [n_micro, mb(sharded over dp), ...]
    if param_specs is None:
        param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), stage_params)
    return x_micro, x_spec, param_specs, data_axes


def pipeline_apply(
    stage_params,
    x,
    fn: Callable,
    mesh,
    n_microbatches: int,
    axis_name: str = "pp",
    batch_axes: tuple = ("dp", "fsdp"),
    schedule: str = "gpipe",
    param_specs=None,
    aux_size: int = 0,
    n_chunks: int = 1,
):
    """Run ``fn(stage_params, x_mb)`` as a pipeline over ``axis_name``.

    stage_params: pytree whose leaves have leading dim == pp size ×
    ``n_chunks`` (one slice per VIRTUAL stage, in model order — chunk j
    runs on device j mod pp). x: [batch, ...] input. fn must map a
    microbatch through ONE virtual stage, preserving shape (classic
    equal-width pipeline). Returns [batch, ...] outputs.

    ``n_chunks``: virtual stages per device (the interleaved 1F1B
    schedule, module docstring) — requires schedule="1f1b" and
    n_microbatches % pp == 0; bubble shrinks to
    (pp-1)/(n_micro·v + pp-1).

    ``aux_size`` > 0: fn instead returns (x_mb_out, aux[aux_size] f32) —
    summable side losses (MoE router lb/z). pipeline_apply then returns
    (y, aux_total) where aux_total sums every (stage, microbatch)
    contribution (psum over pp, mean over the data axes) — the caller
    normalizes by layers x microbatches. Differentiable under both
    schedules (the 1F1B backward feeds each tick's vjp the aux cotangent
    directly).

    ``schedule``: "gpipe" (autodiff backward) or "1f1b" (explicit
    custom-VJP backward with stage-input-only residuals + recompute — the
    1F1B memory discipline; see module docstring).

    ``param_specs``: optional pytree of PartitionSpecs for stage_params
    (leading dim must map to ``axis_name``); default shards ONLY the stage
    dim and replicates the rest. Passing specs with a tensor axis (e.g.
    P("pp", None, "tp")) enables tp-within-stage — ``fn`` then runs on
    tp-local weight shards and must psum its row-parallel outputs over the
    tp axis itself (models/transformer._layer does when given tp_axis).

    Composes with data parallelism: the microbatch dim shards over any
    ``batch_axes`` present in the mesh (each dp group runs its own
    pipeline over its batch slice — activations ppermute within the group,
    nothing crosses dp), while stage params shard over ``axis_name`` (+ tp
    when param_specs say so) and replicate over the data axes.
    """
    from tf_operator_tpu.parallel.collectives import shard_map

    batch = x.shape[0]
    if n_chunks > 1:
        if schedule != "1f1b":
            raise ValueError("n_chunks > 1 (interleaved) requires schedule='1f1b'")
        if n_microbatches % mesh.shape[axis_name]:
            raise ValueError(
                f"interleaved schedule needs n_microbatches "
                f"({n_microbatches}) divisible by {axis_name}="
                f"{mesh.shape[axis_name]} (round structure)"
            )
    x_micro, x_spec, param_specs, data_axes = _shard_specs(
        stage_params, x, mesh, n_microbatches, axis_name, batch_axes, param_specs
    )

    if schedule == "1f1b":
        res = _apply_1f1b(
            stage_params, x_micro, fn, mesh, axis_name, x_spec, param_specs,
            data_axes, aux_size, n_chunks,
        )
    elif schedule == "gpipe":
        def body(params, xm):
            # strip the per-stage leading dim of 1
            local = jax.tree_util.tree_map(lambda a: a[0], params)
            y, aux = _pipeline_local(
                local, xm, _with_aux(fn, aux_size), axis_name, max(aux_size, 1)
            )
            return y, aux[None]  # [1, k] row per (stage, data-shard)

        aux_spec = P((axis_name,) + data_axes, None)
        res = shard_map(
            body,
            mesh=mesh,
            in_specs=(param_specs, x_spec),
            out_specs=(x_spec, aux_spec),
        )(stage_params, x_micro)
    else:
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    out, aux_rows = res
    # invert the strided microbatch split: [n_micro, mb, ...] -> [batch]
    # with out[j*n_micro + i] = out_micro[i, j] (see _shard_specs)
    out = jnp.swapaxes(out, 0, 1).reshape((batch,) + out.shape[2:])
    if aux_size:
        return out, _reduce_aux_rows(aux_rows, mesh, axis_name, data_axes, aux_size)
    return out


def _with_aux(fn, aux_size: int):
    """Uniform stage-body contract: fn always returns (out, aux_row). A
    non-aux fn gets a zero dummy row so one code path serves both cases
    (the [1]-vector costs nothing and its cotangent is discarded)."""
    if aux_size:
        return fn
    return lambda p, x: (fn(p, x), jnp.zeros((1,), jnp.float32))


def _reduce_aux_rows(aux_rows, mesh, axis_name, data_axes, aux_size):
    """[S * n_data, k] stacked per-shard aux sums -> [k]: SUM over stages
    (each stage holds distinct layers), MEAN over data shards (each routes
    its own batch slice). Plain jnp outside the shard_map — autodiff
    differentiates it natively, so the cotangent rows arriving back at
    each shard already carry the right scaling."""
    n_data = 1
    for ax in data_axes:
        n_data *= mesh.shape[ax]
    rows = aux_rows.reshape(mesh.shape[axis_name], n_data, aux_size)
    return rows.sum(axis=0).mean(axis=0)


def _apply_1f1b(stage_params, x_micro, fn, mesh, axis_name, x_spec, param_specs,
                data_axes, aux_size: int = 0, n_chunks: int = 1):
    """custom-VJP wrapper: forward ticks save stage inputs; backward runs
    the explicit reverse pipeline (_bwd_ticks). One body serves the aux
    and non-aux cases (_with_aux dummy row): the primal output is always
    (y, aux_rows[S*n_data, k]); the caller reduces the rows outside the
    shard_map (sum over stages, mean over data shards), so aux cotangent
    rows arrive back per shard already correctly scaled and feed straight
    into every valid tick's vjp (a discarded dummy row's cotangent is
    zeros).

    Interleaved (n_chunks = v > 1): the caller's [S·v, ...] virtual-stage
    params reshape to [v, S, ...] OUTSIDE the custom_vjp (chunk j = i·S+d
    lands at [i, d] — device d's i-th chunk; autodiff transposes the
    reshape on the way back), specs shift to P(None, axis_name, …), and
    the local tick bodies see chunk-major [v, ...] params. v = 1 keeps
    the [S, ...] layout where the local [1, ...] block IS chunk-major."""
    from tf_operator_tpu.parallel.collectives import shard_map

    fn2 = _with_aux(fn, aux_size)
    k = max(aux_size, 1)
    n_stages = mesh.shape[axis_name]
    # saved stage inputs live stage-major: [S, v*M, mb, ...]
    saved_spec = P(axis_name, *x_spec)
    aux_spec = P((axis_name,) + data_axes, None)

    is_spec = lambda s: isinstance(s, P)
    if n_chunks > 1:
        pspecs = jax.tree_util.tree_map(
            lambda s: P(None, *s), param_specs, is_leaf=is_spec)
        prepare = lambda p: jax.tree_util.tree_map(
            lambda a: a.reshape((n_chunks, n_stages) + a.shape[1:]), p)
        to_local = lambda p: jax.tree_util.tree_map(lambda a: a[:, 0], p)
        from_local = lambda d: jax.tree_util.tree_map(lambda a: a[:, None], d)
    else:
        pspecs = param_specs
        prepare = lambda p: p
        to_local = lambda p: p      # local [1, ...] block is chunk-major
        from_local = lambda d: d

    @jax.custom_vjp
    def run(params, xm):
        out, _ = run_fwd(params, xm)
        return out

    def run_fwd(params, xm):
        def body(p, x):
            y, aux, x_saved = _fwd_save_ticks(
                to_local(p), x, fn2, axis_name, k, n_chunks)
            return y, aux[None], x_saved[None]

        y, aux_rows, x_saved = shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, x_spec),
            out_specs=(x_spec, aux_spec, saved_spec),
        )(params, xm)
        return (y, aux_rows), (params, x_saved)

    def run_bwd(residuals, g):
        params, x_saved = residuals
        gy, gaux_rows = g

        def _spec_axes(s):
            names = set()
            for part in s:
                if part is None:
                    continue
                for a in (part if isinstance(part, (tuple, list)) else (part,)):
                    if a:
                        names.add(a)
            return names

        # Per-leaf data-axis reduction (r4): a param leaf's grad is summed
        # over exactly the data axes the leaf REPLICATES over. An axis
        # the leaf's spec SHARDS (ep on expert-weight leaves under
        # ep-in-stage MoE) must NOT be psum'd — each ep shard's slice is
        # a different parameter block, and summing across it scrambles
        # the expert gradients (caught by the pp x ep oracle).
        # CSV strings because tuples are pytree nodes, not leaves.
        reduce_axes = jax.tree_util.tree_map(
            lambda s: ",".join(ax for ax in data_axes
                               if ax not in _spec_axes(s)),
            pspecs, is_leaf=is_spec,
        )

        def body(p, saved, gy_in, gaux_row):
            dparams, dx = _bwd_ticks(
                to_local(p),
                jax.tree_util.tree_map(lambda a: a[0], saved),
                gy_in, fn2, axis_name,
                gaux_row[0].astype(jnp.float32),
                n_chunks,
            )
            # params replicate over (most of) the data axes, so each data
            # shard holds PARTIAL grads from its batch slice — sum them
            # (the psum autodiff's transpose machinery would have
            # inserted), leaf by leaf per reduce_axes above.
            def reduce_leaf(a, axes_csv):
                for ax in (axes_csv.split(",") if axes_csv else ()):
                    a = jax.lax.psum(a, ax)
                return a

            # reduce_axes shares dparams' tree STRUCTURE (to_local only
            # reshapes leaves), so it zips directly
            dparams = jax.tree_util.tree_map(reduce_leaf, dparams, reduce_axes)
            return from_local(dparams), dx

        dparams, dx = shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, saved_spec, x_spec, aux_spec),
            out_specs=(pspecs, x_spec),
        )(params, x_saved, gy, gaux_rows)
        return dparams, dx

    run.defvjp(run_fwd, run_bwd)
    return run(prepare(stage_params), x_micro)
