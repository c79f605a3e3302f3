"""Sharded trainer: init/step compiled once over the job's mesh.

Usage shape:

    trainer = Trainer(mesh, loss_fn=..., init_fn=..., logical_axes=...,
                      config=TrainerConfig(...))
    state = trainer.init(jax.random.PRNGKey(0))
    state, metrics = trainer.step(state, batch)   # jitted, donated

Sharding: param placement comes from the model's logical axes through
parallel.sharding.ShardingRules (DP/FSDP/TP by table edit); optimizer state
inherits the param shardings; batches shard over ("dp","fsdp").
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import optax

from tf_operator_tpu.parallel.collectives import (
    collectives_summary,
    compiled_collectives,
    compiled_kernels,
    compiled_remats,
    compiled_sections,
)
from tf_operator_tpu.parallel.sharding import DEFAULT_RULES, ShardingRules, replicated


@dataclass
class TrainerConfig:
    optimizer: str = "adamw"  # "adamw" | "sgd"
    learning_rate: float = 3e-4
    weight_decay: float = 0.0
    momentum: float = 0.9
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: Optional[float] = 1.0
    warmup_steps: int = 0
    lr_schedule: str = "constant"  # "constant" | "cosine"
    total_steps: int = 10000
    # Microbatch gradient accumulation: >1 splits each step's batch into
    # grad_accum equal microbatches, runs fwd+bwd per microbatch in a
    # lax.scan, and applies ONE optimizer update with the mean gradient —
    # the lever for configs whose global batch exceeds per-chip activation
    # memory (trade steps-in-flight for batch; peak activation memory drops
    # ~grad_accum-fold while the optimizer sees the same global batch).
    # Mean-of-microbatch-means == full-batch mean for equal-size
    # microbatches, so the loss trajectory is identical up to float
    # reassociation (oracle-pinned in tests/test_trainer_accum.py).
    grad_accum: int = 1
    # Re-seed init()'s key onto the 'rbg' PRNG (r4 submit-latency lever):
    # threefry RNG subgraphs dominate the init EXECUTABLE — the unrolled
    # ResNet-50 init measured roughly half the cold compile with rbg
    # (an earlier installation's reading; not re-measured). Same
    # distributions, different stream — and rbg streams vary with
    # BACKEND, COMPILER VERSION, and MESH/PARTITION LAYOUT (XLA
    # RngBitGenerator documents no stability across any of these), so
    # same-seed init is no longer bit-identical across dp=4 vs dp=8
    # meshes the way threefry was. Default False (r5, ADVICE r4):
    # library callers keep deterministic threefry init for seed-matched
    # ablations; the submit-latency paths (the lm/resnet workloads) opt
    # in explicitly. Restores/resumes never re-init, so
    # recovery semantics are unchanged either way.
    fast_init_rng: bool = False


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any  # int32 scalar array
    extra: Any = None  # model state (e.g. BN stats), optional


def _make_tx(cfg: TrainerConfig) -> optax.GradientTransformation:
    if cfg.lr_schedule == "cosine":
        sched = optax.warmup_cosine_decay_schedule(
            0.0, cfg.learning_rate, max(cfg.warmup_steps, 1), cfg.total_steps
        )
    elif cfg.warmup_steps:
        sched = optax.linear_schedule(0.0, cfg.learning_rate, cfg.warmup_steps)
    else:
        sched = cfg.learning_rate
    if cfg.optimizer == "adamw":
        tx = optax.adamw(sched, b1=cfg.beta1, b2=cfg.beta2, weight_decay=cfg.weight_decay)
    elif cfg.optimizer == "sgd":
        tx = optax.sgd(sched, momentum=cfg.momentum)
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    if cfg.grad_clip:
        tx = optax.chain(optax.clip_by_global_norm(cfg.grad_clip), tx)
    return tx


class Trainer:
    """Builds sharded, jitted init and train-step functions.

    loss_fn(params, batch, extra) -> loss  OR  (loss, new_extra).
    init_fn(key) -> params  OR  (params, extra).
    logical_axes: pytree matching params with logical axis tuples (or None
    to replicate everything).
    """

    def __init__(
        self,
        mesh,
        loss_fn: Callable,
        init_fn: Callable,
        logical_axes: Any = None,
        rules: ShardingRules = DEFAULT_RULES,
        config: Optional[TrainerConfig] = None,
    ) -> None:
        self.mesh = mesh
        self.config = config if config is not None else TrainerConfig()
        self.tx = _make_tx(self.config)
        self.loss_fn = loss_fn
        self.init_fn = init_fn
        self.rules = rules
        self.logical_axes = logical_axes
        self._repl = replicated(mesh)

        # Resolve param shardings by tracing init_fn's output structure
        # (traced once; _opt_shardings reuses it).
        shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        self._has_extra = isinstance(shapes, tuple)
        self._params_shape = shapes[0] if self._has_extra else shapes
        self._extra_shape = shapes[1] if self._has_extra else None
        self._opt_shape_cache = None
        self._opt_shardings_cache = None
        if logical_axes is None:
            self.param_shardings = jax.tree_util.tree_map(
                lambda _: self._repl, self._params_shape
            )
        else:
            self.param_shardings = jax.tree_util.tree_map(
                lambda axes: self.rules.sharding(mesh, list(axes)),
                logical_axes,
                is_leaf=lambda x: isinstance(x, tuple),
            )
        self.batch_sharding = self.rules.sharding(mesh, ["batch"])

        self._init_jit = None
        self._step_jit = None
        self._step_compiled = None
        # what the compiled step's collectives are (compile_step): by kind,
        # count and operand bytes a step, the largest operand inside a
        # ``while`` body, and the largest instruction; None until compiled
        self.step_collectives: Optional[Dict[str, Any]] = None
        # and its Pallas kernels: custom-call instructions by kernel name
        # (a remat tier that keeps flash_o/flash_lse holds ``flash_fwd``
        # once a layer kind, one that replays it twice); None until compiled
        self.step_kernels: Optional[Dict[str, int]] = None
        # and the instructions the COMPILER rebuilt for want of memory
        # (``.remat`` clones): 0 where the remat policy's saved set fits
        self.step_remats: Optional[int] = None
        # and what part of the model and of the step each instruction is
        # (``compiled_sections``: {"<section>.<phase>": [instruction names]}
        # from the ``sec_*`` scopes), with the seconds the parse took; a
        # profiler session gets it once as the ``train.program`` span
        self.step_sections: Optional[Dict[str, List[str]]] = None
        self.step_sections_parse_s: Optional[float] = None
        self._step_program = ""  # the compiled step's module name
        self._program_in_trace = False
        self._precompile_error = None
        self._compiled_hits = 0
        self._compiled_rejections = 0
        self._step_calls = 0

    # ---- init -----------------------------------------------------------

    @staticmethod
    def _fast_init_key(key):
        """Derive an 'rbg'-impl key from the caller's key (threefry or
        typed): distinct seeds stay distinct, and the init executable
        sheds its threefry subgraphs (see TrainerConfig.fast_init_rng)."""
        import numpy as np

        try:
            data = jax.random.key_data(key)
        except Exception:  # already a raw uint32 key array
            data = key
        arr = np.asarray(data).ravel().astype(np.uint64)
        seed = 0
        for word in arr:
            seed = (seed * 1000003 + int(word)) % (1 << 63)
        return jax.random.key(seed, impl="rbg")

    def init(self, key) -> TrainState:
        if self.config.fast_init_rng:
            key = self._fast_init_key(key)
        if self._init_jit is None:
            opt_shardings = self._opt_shardings()
            extra_out = self._repl if self._has_extra else None

            def go(key):
                out = self.init_fn(key)
                params, extra = out if self._has_extra else (out, None)
                return params, self.tx.init(params), jnp.zeros((), jnp.int32), extra

            self._init_jit = jax.jit(
                go,
                out_shardings=(
                    self.param_shardings,
                    opt_shardings,
                    self._repl,
                    extra_out,
                ),
            )
        params, opt_state, step, extra = self._init_jit(key)
        return TrainState(params, opt_state, step, extra)

    def _opt_shape(self):
        if self._opt_shape_cache is None:
            self._opt_shape_cache = jax.eval_shape(self.tx.init, self._params_shape)
        return self._opt_shape_cache

    def _opt_shardings(self):
        """Optimizer slots inherit their param's sharding, matched by tree
        PATH (optimizer moment trees embed the param tree, e.g.
        mu.layers.wq mirrors params.layers.wq). Shape-based matching would
        collide for same-shape params with transposed shardings (wq vs wo
        when n_heads*head_dim == d_model). Scalars and unmatched leaves
        replicate."""
        if self._opt_shardings_cache is not None:
            return self._opt_shardings_cache
        opt_shape = self._opt_shape()
        param_leaves = jax.tree_util.tree_flatten_with_path(self._params_shape)[0]
        sharding_leaves = jax.tree_util.tree_flatten(self.param_shardings)[0]
        path_map = {}
        for (path, leaf), sharding in zip(param_leaves, sharding_leaves):
            path_map[tuple(str(p) for p in path)] = (leaf.shape, sharding)

        def pick(opt_path, leaf):
            key = tuple(str(p) for p in opt_path)
            # Longest path suffix that names a param with the same shape.
            for k in range(len(key), 0, -1):
                hit = path_map.get(key[-k:])
                if hit is not None:
                    shape, sharding = hit
                    if shape == leaf.shape:
                        return sharding
                    break
            return self._repl

        self._opt_shardings_cache = jax.tree_util.tree_map_with_path(pick, opt_shape)
        return self._opt_shardings_cache

    def state_template(self) -> "TrainState":
        """Abstract TrainState (ShapeDtypeStructs carrying shardings) —
        the restore target for CheckpointManager.restore without paying
        an init compile."""
        opt_shape = self._opt_shape()
        opt_shardings = self._opt_shardings()

        def tag(shape_tree, sharding_tree):
            return jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                shape_tree,
                sharding_tree,
            )

        extra = (
            jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=self._repl),
                self._extra_shape,
            )
            if self._extra_shape is not None
            else None
        )
        return TrainState(
            params=tag(self._params_shape, self.param_shardings),
            opt_state=tag(opt_shape, opt_shardings),
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=self._repl),
            extra=extra,
        )

    def reshard_state(self, state: "TrainState") -> "TrainState":
        """Re-lay an existing TrainState onto THIS trainer's shardings —
        the elastic resize seam (r12). After a gang shrink/re-grow the
        surviving members build a Trainer over the NEW mesh and pass the
        old state through here at the next step boundary; every leaf is
        device_put onto the new state_template's sharding (params by rule,
        optimizer slots by param path, step/extra replicated). The same
        sharding machinery that lays out a restore lays out the resize —
        there is no separate elastic layout path to drift."""
        tmpl = self.state_template()

        def relay(leaf, spec):
            return jax.device_put(leaf, spec.sharding)

        return jax.tree_util.tree_map(relay, state, tmpl)

    def restore_or_init(self, key, ckpt=None) -> "TrainState":
        """Resume from ``ckpt``'s latest checkpoint if one exists, else
        fresh init — the restart-based recovery contract (SURVEY.md §5):
        the controller's gang restart relaunches the workload, which lands
        here and picks up at the saved step."""
        if ckpt is not None and ckpt.latest_step() is not None:
            return ckpt.restore(self.state_template())
        return self.init(key)

    # ---- step -----------------------------------------------------------

    def compile_step(self, batch):
        """AOT-compile the train-step program for ``batch`` (concrete
        arrays or ShapeDtypeStructs; host arrays assume
        ``batch_sharding``) and keep it: the next ``step()`` calls run
        this executable instead of paying a lazy jit. Returns the
        ``jax.stages.Compiled`` — its ``as_text()`` is the program the
        device runs (what the chip smoke counts ``tpu_custom_call`` in)
        and timing this call is the step's compile time, cleanly apart
        from its first execution. A compile failure raises. The program's
        collectives are counted as it is kept (``step_collectives``: the
        compile-time receipt of how the step was partitioned), and so are
        its Pallas kernels by name (``step_kernels``: the receipt of what
        the remat policy replays), the compiler's own rematerialisations
        (``step_remats``: what the policy saves beyond what the chip holds)
        and every instruction's section and phase (``step_sections``: what
        a trace of this program needs to name its ops; ``step()`` writes it
        into each profiler session)."""
        from jax.sharding import NamedSharding

        tmpl = self.state_template()

        def spec(a):
            # honor a leaf's sharding only when it's a mesh sharding (a
            # staged batch or an explicit ShapeDtypeStruct); a host array
            # or an unstaged jnp array carries a single-device sharding
            # that would contradict the state template's mesh
            sh = getattr(a, "sharding", None)
            if not isinstance(sh, NamedSharding) or sh.mesh != self.mesh:
                sh = self.batch_sharding
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

        batch_spec = jax.tree_util.tree_map(spec, batch)
        if self._step_jit is None:
            self._step_jit = self._build_step()
        self._step_compiled = self._step_jit.lower(
            tmpl.params, tmpl.opt_state, tmpl.step, tmpl.extra, batch_spec,
        ).compile()
        text = self._step_compiled.as_text()
        self.step_collectives = collectives_summary(compiled_collectives(text))
        self.step_kernels = compiled_kernels(text)
        self.step_remats = compiled_remats(text)
        t0 = time.perf_counter()
        self.step_sections = compiled_sections(text)
        self.step_sections_parse_s = time.perf_counter() - t0
        # "HloModule jit__step_body, ...": the name ``XLA Modules`` prints
        self._step_program = text.split(None, 2)[1].rstrip(",")
        self._program_in_trace = False
        return self._step_compiled

    def precompile_step_async(self, batch):
        """``compile_step`` on a BACKGROUND thread — the submit-latency
        overlap (VERDICT r3 #4): after trace time the step program's
        compile is independent of the init program's execution, but the
        lazy jit path serializes them. Call this before ``init()``, then
        ``join()`` the returned thread. The Python trace briefly contends
        for the GIL; the XLA compile genuinely overlaps. A compile
        failure is NOT swallowed: it is kept and raised by the next
        ``step()`` — the lazy jit would compile the same program and
        fail the same way, only later and with the cause one step
        removed."""
        import threading

        def go():
            try:
                self.compile_step(batch)
            except Exception as exc:  # noqa: BLE001 — re-raised by step()
                self._precompile_error = exc

        t = threading.Thread(target=go, name="step-precompile", daemon=True)
        t.start()
        return t

    def step(self, state: TrainState, batch) -> tuple:
        """One optimizer step (returns at enqueue). Under a profiler
        session the dispatch is a ``train.step`` span whose ``call`` is
        this trainer's host-side count of calls — never ``state.step``,
        which lives on the device and would synchronise to read. The first
        call of each session also writes the zero-length ``train.program``
        span: the compiled step's module name and, one attribute a
        ``<section>.<phase>``, its instructions' names (``step_sections``),
        so that the trace says by itself what part of the model each of its
        ops is. With no session open a call pays the one ``is_enabled()``
        read and writes nothing."""
        self._step_calls += 1
        if not jax.profiler.TraceAnnotation.is_enabled():
            self._program_in_trace = False
        elif not self._program_in_trace and self.step_sections is not None:
            self._program_in_trace = True
            # names hold no ',', '=' or '#' (TraceMe's own separators)
            with jax.profiler.TraceAnnotation(
                    "train.program", program=self._step_program,
                    **{k: " ".join(v) for k, v in self.step_sections.items()}):
                pass
        with jax.profiler.TraceAnnotation("train.step", call=self._step_calls):
            return self._dispatch_step(state, batch)

    def _dispatch_step(self, state: TrainState, batch) -> tuple:
        if self._precompile_error is not None:
            exc, self._precompile_error = self._precompile_error, None
            raise RuntimeError("train-step precompile failed") from exc
        if self._step_compiled is not None:
            try:
                params, opt_state, step, extra, loss = self._step_compiled(
                    state.params, state.opt_state, state.step, state.extra,
                    batch,
                )
                self._compiled_hits += 1
                self._compiled_rejections = 0
                return (TrainState(params, opt_state, step, extra),
                        {"loss": loss})
            except (TypeError, ValueError) as exc:
                # Argument/aval mismatch — raised by pre-execution
                # checking, so no buffer was donated. Route only THIS
                # call to the jit path and KEEP the executable: one
                # odd-shaped batch (e.g. a final partial batch) must not
                # force a cold recompile of the common shape. But an
                # executable that NEVER matched (the precompile guessed
                # the wrong batch spec) is dropped after 3 straight
                # rejections — otherwise every step of a long run pays
                # the failed call + a warning. Runtime errors propagate —
                # retrying after a mid-execution failure could touch
                # already-donated buffers.
                import logging

                self._compiled_rejections += 1
                if self._compiled_rejections == 1:
                    logging.getLogger(__name__).warning(
                        "precompiled step rejected args (%s); jit path "
                        "for this call", exc,
                    )
                if self._compiled_hits == 0 and self._compiled_rejections >= 3:
                    logging.getLogger(__name__).warning(
                        "precompiled step never matched a real batch; "
                        "dropping it (submit overlap not realized)",
                    )
                    self._step_compiled = None
        if self._step_jit is None:
            self._step_jit = self._build_step()
        params, opt_state, step, extra, loss = self._step_jit(
            state.params, state.opt_state, state.step, state.extra, batch
        )
        return TrainState(params, opt_state, step, extra), {"loss": loss}

    def _step_body(self, params, opt_state, step, extra, batch):
        if self.config.grad_accum > 1:
            loss, new_extra, grads = self._accum_grads(params, extra, batch)
        else:
            def wrapped(p):
                out = self.loss_fn(p, batch, extra)
                if isinstance(out, tuple):
                    return out
                return out, extra

            (loss, new_extra), grads = jax.value_and_grad(wrapped, has_aux=True)(params)
        with jax.named_scope("sec_optimizer"):  # clip, AdamW, the update
            updates, opt_state = self.tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, step + 1, new_extra, loss

    def _accum_grads(self, params, extra, batch):
        """Microbatched fwd+bwd: split the batch's leading dim into
        ``grad_accum`` equal microbatches and scan, summing grads in f32
        param-shaped accumulators; one mean at the end. Model ``extra``
        (e.g. BN stats) threads sequentially through the microbatches —
        the same semantics as training the microbatches as small steps.

        The [b,...] -> [accum, b/accum, ...] reshape keeps the microbatch
        dim under the batch sharding (constraint below) so each device
        keeps an equal slice of every microbatch — XLA lowers it to a
        layout change (worst case one input-sized reshard, amortized over
        grad_accum fwd+bwd passes)."""
        accum = self.config.grad_accum
        micro_shard = self.rules.sharding(self.mesh, [None, "batch"])

        def split(x):
            b = x.shape[0]
            if b % accum:
                raise ValueError(
                    f"batch dim {b} not divisible by grad_accum={accum}"
                )
            mb = x.reshape((accum, b // accum) + x.shape[1:])
            return jax.lax.with_sharding_constraint(mb, micro_shard)

        micro = jax.tree_util.tree_map(split, batch)

        def wrapped(p, mb, ex):
            out = self.loss_fn(p, mb, ex)
            if isinstance(out, tuple):
                return out
            return out, ex

        grad_fn = jax.value_and_grad(wrapped, has_aux=True)

        def body(carry, mb):
            gsum, loss_sum, ex = carry
            (loss, ex), g = grad_fn(params, mb, ex)
            # accumulate in f32 regardless of param dtype: with bf16 params
            # and accum>=8, summing in bf16 (~8 mantissa bits) absorbs
            # small microbatch contributions and breaks the oracle
            gsum = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), gsum, g
            )
            return (gsum, loss_sum + loss, ex), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (gsum, loss_sum, new_extra), _ = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32), extra), micro
        )
        inv = 1.0 / accum
        grads = jax.tree_util.tree_map(
            lambda g, p: (g * inv).astype(p.dtype), gsum, params
        )
        return loss_sum * inv, new_extra, grads

    def _build_step(self):
        # Donation contract for checkpointing: params/opt_state/extra are
        # donated, so the moment the next step dispatches, buffers any
        # in-flight save captured may be reused by XLA. The async save
        # pipeline (checkpoint._stage_tree) therefore snapshots the state
        # with a blocking device-side copy BEFORE returning control to the
        # step loop — that copy is the save stall; everything after it
        # (device->host fetch, chunked writes, commit) overlaps training.
        return jax.jit(self._step_body, donate_argnums=(0, 1, 3))
