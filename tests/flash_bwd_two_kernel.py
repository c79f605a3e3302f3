"""The flash backward as it was before PR 43, kept as a TEST ORACLE: two
``pallas_call``s, one over (q block, k block) for dq and one over (k block,
q block) for dk/dv, each rebuilding ``s``, the mask, ``p``, ``dp`` and ``ds``
for itself. The fused kernel of ``ops/flash_attention._bwd`` sums the same
f32 products in the same order — k blocks for a q row, q blocks for a k row —
so in the interpreter its three gradients are these, bit for bit."""

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

fa = importlib.import_module("tf_operator_tpu.ops.flash_attention")
LANES = fa.LSE_LANES


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _pair(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kb, *, causal,
          block_q, block_k, scale, g, window):
    """One pair's operands in f32 and its ``p`` and ``ds``."""
    rows = g * block_q
    q = q_ref[0].reshape(rows, -1).astype(jnp.float32) * scale
    do = do_ref[0].reshape(rows, -1).astype(jnp.float32)
    k = k_ref[0, 0, :, :].astype(jnp.float32)
    v = v_ref[0, 0, :, :].astype(jnp.float32)
    s = _dot(q, k, ((1,), (1,)))
    if causal:
        s = fa._causal_mask(s, qi, kb, block_q, block_k, window)
    p = jnp.exp(s - lse_ref[0].reshape(rows, LANES)[:, :1])
    dp = _dot(do, v, ((1,), (1,)))
    return q, k, do, p, p * (dp - delta_ref[0].reshape(rows, LANES)[:, :1])


def _dq_pass(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
             *, g, block_q, scale, **how):
    qi, kb = pl.program_id(2), pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:, :] = jnp.zeros_like(dq_scr)

    @pl.when(fa._block_live(qi, kb, block_q, how["block_k"], how["causal"],
                            how["window"]))
    def _step():
        _, k, _, _, ds = _pair(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                               qi, kb, g=g, block_q=block_q, scale=scale, **how)
        dq_scr[:, :] = dq_scr[:, :] + _dot(ds, k, ((1,), (0,)))

    @pl.when(kb == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:, :] * scale).reshape(g, block_q, -1).astype(dq_ref.dtype)


def _dkv_pass(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
              dk_scr, dv_scr, *, block_q, **how):
    ki, qb = pl.program_id(2), pl.program_id(3)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:, :] = jnp.zeros_like(dk_scr)
        dv_scr[:, :] = jnp.zeros_like(dv_scr)

    @pl.when(fa._block_live(qb, ki, block_q, how["block_k"], how["causal"],
                            how["window"]))
    def _step():
        q, _, do, p, ds = _pair(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                                qb, ki, block_q=block_q, **how)
        dv_scr[:, :] = dv_scr[:, :] + _dot(p, do, ((0,), (0,)))
        dk_scr[:, :] = dk_scr[:, :] + _dot(ds, q, ((0,), (0,)))

    @pl.when(qb == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0, :, :] = dk_scr[:, :].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:, :].astype(dv_ref.dtype)


def two_kernel_bwd(causal, block_q, block_k, residuals, do, dlse=None, window=0):
    """``fa._bwd``'s contract (kernel-layout residuals of ``fa._fwd``, the
    cotangents in model layout; dq, dk, dv in model layout) through the two
    kernels, in the interpreter."""
    qt, kt, vt, o, lse_c = residuals
    b, h, t, d = qt.shape
    h_kv, dv = kt.shape[1], vt.shape[3]
    grp = h // h_kv
    do = do.transpose(0, 2, 1, 3)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32).transpose(0, 2, 1)
    lanes = lambda a: jnp.broadcast_to(a[..., None], (b, h, t, LANES))  # noqa: E731
    operands = (qt, kt, vt, do, lanes(lse_c), lanes(delta))
    how = dict(causal=causal, block_q=block_q, block_k=block_k, scale=d**-0.5,
               g=grp, window=window)

    q_spec, kv_spec = fa._gqa_specs(grp, block_q, block_k, q_grid_dim=2)
    dq = pl.pallas_call(
        functools.partial(_dq_pass, **how),
        grid=(b, h_kv, t // block_q, t // block_k),
        in_specs=[q_spec(d), kv_spec(d), kv_spec(dv), q_spec(dv),
                  q_spec(LANES), q_spec(LANES)],
        out_specs=q_spec(d),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), qt.dtype),
        scratch_shapes=[pltpu.VMEM((grp * block_q, d), jnp.float32)],
        interpret=True,
    )(*operands)
    q_spec, kv_spec = fa._gqa_specs(grp, block_q, block_k, q_grid_dim=3)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_pass, **how),
        grid=(b, h_kv, t // block_k, t // block_q),
        in_specs=[q_spec(d), kv_spec(d), kv_spec(dv), q_spec(dv),
                  q_spec(LANES), q_spec(LANES)],
        out_specs=[kv_spec(d), kv_spec(dv)],
        out_shape=[jax.ShapeDtypeStruct((b, h_kv, t, d), kt.dtype),
                   jax.ShapeDtypeStruct((b, h_kv, t, dv), vt.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        interpret=True,
    )(*operands)
    return tuple(x.transpose(0, 2, 1, 3) for x in (dq, dk, dv_))
