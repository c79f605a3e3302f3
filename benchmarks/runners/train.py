"""Runner for training mixes: the dense LM step through ``Trainer``.

Builds the trainer as ``tf_operator_tpu/workloads/lm.py:57-80`` does
(``preset_from_workload``, ``lm_loss``, ``transformer_logical_axes``,
``TrainerConfig(optimizer="adamw", ...)``), feeds it through the
prefetching ``DeviceLoader`` from the benchmark's own seeded rows, and
hands the SAME compiled step and state that set-up drove through its
first steps to the measured window.
"""

from __future__ import annotations

import gc
import math
import time
from statistics import median
from typing import Any, Dict, List

import numpy as np


def _find_mu(opt_state):
    """AdamW's first moment inside the optimizer state, wherever the chain
    put it."""
    import jax

    for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda n: hasattr(n, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise RuntimeError("no adam state (mu) in the optimizer state")


def _change_norms(params, before_host) -> Dict[str, float]:
    """Per-leaf norm of params - before, one leaf on the device at a time."""
    import jax
    import jax.numpy as jnp

    from benchmarks import reference

    diff = jax.jit(lambda p, q: jnp.sqrt(jnp.sum(jnp.square(p - q))))
    now = jax.tree_util.tree_leaves(params)
    before = jax.tree_util.tree_leaves(before_host)
    return {k: float(diff(p, jax.device_put(q, p.sharding)))
            for k, p, q in zip(reference.leaf_names(params), now, before)}


def setup(ctx):
    return TrainJob(ctx)


class TrainJob:
    def __init__(self, ctx):
        import jax

        from benchmarks import reference, traffic
        from tf_operator_tpu.models.transformer import (
            init_transformer,
            lm_loss,
            preset_from_workload,
            transformer_logical_axes,
        )
        from tf_operator_tpu.parallel.mesh import build_mesh
        from tf_operator_tpu.train.data import DeviceLoader
        from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

        self.ctx = ctx
        mix, opt = ctx.mix, ctx.config["optimizer"]
        cfg = preset_from_workload(ctx.config["workload"])
        for k in ("vocab", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff"):
            if getattr(cfg, k) != ctx.sizes[k]:
                raise SystemExit(
                    f"config {k}: the program builds {getattr(cfg, k)}, the "
                    f"file states {ctx.sizes[k]}")
        self.batch, self.seq = int(mix["batch_size"]), int(mix["seq_len"])
        mesh = build_mesh(dict(ctx.config["mesh_axes"]), devices=ctx.devices)
        self.trainer = Trainer(
            mesh,
            loss_fn=lambda p, tokens, extra: lm_loss(p, tokens, cfg, mesh=mesh),
            init_fn=lambda k: init_transformer(k, cfg),
            logical_axes=transformer_logical_axes(cfg),
            # threefry init: the stream the reference's own draw repeats
            config=TrainerConfig(
                optimizer=opt["name"], learning_rate=opt["learning_rate"],
                weight_decay=opt["weight_decay"], beta1=opt["beta1"],
                beta2=opt["beta2"], grad_clip=opt["grad_clip"],
                fast_init_rng=False,
            ),
        )
        t0 = time.perf_counter()
        self.trainer.compile_step(
            jax.ShapeDtypeStruct((self.batch, self.seq), "int32"))
        ctx.say(f"note step_compile_s: {time.perf_counter() - t0!r}")
        self.state = self.trainer.init(jax.random.PRNGKey(ctx.seed))
        p0 = jax.device_get(self.state.params)  # for the change after followed steps

        self.followed = int(mix.get("followed_steps", 2))
        self.batches_fed: List[np.ndarray] = []
        self.loader = DeviceLoader(
            self._feed(traffic.token_batches(ctx.seed, cfg.vocab, mix)),
            self.trainer.batch_sharding,
        )
        self.data_wait_s: List[float] = []
        self.step_s: List[float] = []
        self.losses: List[float] = []

        # the first steps, through the window's own call and feed
        warm = max(int(mix.get("warmup_steps", 3)), self.followed)
        self.program: Dict[str, Any] = {"losses": []}
        for n in range(1, warm + 1):
            self._step()
            if n == 1:
                b1 = opt["beta1"]
                self.program["grad1_norms"] = {
                    k: v / (1.0 - b1) for k, v in
                    reference.leaf_norms(_find_mu(self.state.opt_state)).items()}
            if n == self.followed:
                self.program["losses"] = list(self.losses)
                self.program["change_norms"] = _change_norms(self.state.params, p0)
        del p0
        self.data_wait_s.clear(), self.step_s.clear(), self.losses.clear()

    def _feed(self, source):
        for batch in source:
            if len(self.batches_fed) < self.followed:
                self.batches_fed.append(batch["tokens"])
            yield batch

    def _step(self) -> None:
        """One optimizer step: wait for the loader, dispatch, wait for the
        device. The window and the followed first steps both come here."""
        import jax
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("bench.data_next"):
            batch = next(self.loader)["tokens"]
        t1 = time.perf_counter()
        with TraceAnnotation("bench.step_dispatch"):
            self.state, m = self.trainer.step(self.state, batch)
        with TraceAnnotation("bench.step_wait"):
            loss = float(jax.block_until_ready(m["loss"]))
        self.data_wait_s.append(t1 - t0)
        self.step_s.append(time.perf_counter() - t0)
        self.losses.append(loss)

    def window(self, seconds: float) -> Dict[str, Any]:
        """Steps until ``seconds`` have passed; the window closes when the
        step that crosses the mark completes, so the rate is over all the
        work and all the time."""
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._step()
        elapsed = time.perf_counter() - t0
        losses = list(self.losses)
        return {
            "elapsed_s": elapsed, "steps": len(losses), "losses": losses,
            "step_s": list(self.step_s), "data_wait_s": list(self.data_wait_s),
            "tokens_per_step": self.batch * self.seq,
            "attempted": len(losses),
            "failed": sum(1 for x in losses if not math.isfinite(x)),
            "notes": {
                "step_s_median": median(self.step_s),
                "loss_first_last": (losses[0], losses[-1]),
            },
        }

    def traced_window(self) -> Dict[str, Any]:
        n0 = len(self.step_s)
        t0 = time.perf_counter()
        for _ in range(int(self.ctx.mix.get("trace_steps", 4))):
            self._step()
        return {"elapsed_s": time.perf_counter() - t0,
                "steps": len(self.step_s) - n0, "step_s": self.step_s[n0:]}

    def end_to_end(self, samples) -> Dict[str, float]:
        return {
            "train_tokens_per_s":
                samples["steps"] * samples["tokens_per_step"]
                / samples["elapsed_s"] / self.ctx.chips,
        }

    def release(self) -> None:
        self.loader.close()
        self.state = self.trainer = self.loader = None
        gc.collect()

    def check(self, samples, control=None):
        """The first ``followed`` steps against the reference's own, from its
        own weights: each loss, the first gradient as the optimizer got it
        and the parameters' change, both by the worst leaf; and every loss of
        the window finite. ``control`` adds the same numbers for the
        reference computed in that precision (or each of several) and put in
        the program's place (names prefixed ``control.<precision>:``)."""
        from benchmarks import reference

        ctx, limits = self.ctx, self.ctx.config["limits"]
        args = (ctx.seed, ctx.sizes, ctx.config["optimizer"], self.batches_fed)
        t0 = time.perf_counter()
        ref = reference.train_reference(*args, sharding=reference_sharding(ctx))
        ctx.say(f"note reference_s: {time.perf_counter() - t0!r}")
        out = compare(ctx.Check, self.program, ref, limits) + [
            ctx.Check("window_losses_not_finite", float(samples["failed"]), 0.0)]
        for c in [control] if isinstance(control, str) else list(control or []):
            low = reference.train_reference(
                *args, precision=c, sharding=reference_sharding(ctx))
            out += compare(ctx.Check, low, ref, limits, prefix=f"control.{c}:")
        return out


def reference_sharding(ctx):
    """Where the reference keeps its weights: on the cell's one chip, or
    split over its chips along each matrix's last axis (plain GSPMD — the
    reference has no collectives of its own)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    if len(ctx.devices) == 1:
        return lambda shape: SingleDeviceSharding(ctx.devices[0])
    mesh = Mesh(np.asarray(ctx.devices), ("x",))
    n = len(ctx.devices)

    def place(shape):
        if len(shape) >= 2 and shape[-1] % n == 0:
            return NamedSharding(mesh, P(*([None] * (len(shape) - 1)), "x"))
        return NamedSharding(mesh, P())

    return place


def worst_leaf_gap(program: Dict[str, float], ref: Dict[str, float]) -> float:
    """Largest |program's norm - reference's norm| over the leaves, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    floor = median(ref.values())
    return max(abs(program[k] - ref[k]) / max(ref[k], floor) for k in ref)


def compare(Check, program, ref, limits, prefix=""):
    out = [
        Check(f"{prefix}loss_step{i + 1}_abs_gap", abs(p - r),
              limits[f"loss_step{i + 1}_abs_gap"]["limit"])
        for i, (p, r) in enumerate(zip(program["losses"], ref["losses"]))
    ]
    out.append(Check(
        prefix + "grad1_norm_worst_leaf_gap",
        worst_leaf_gap(program["grad1_norms"], ref["grad1_norms"]),
        limits["grad1_norm_worst_leaf_gap"]["limit"]))
    out.append(Check(
        prefix + "param_change_norm_worst_leaf_gap",
        worst_leaf_gap(program["change_norms"], ref["change_norms"]),
        limits["param_change_norm_worst_leaf_gap"]["limit"]))
    return out
