"""Transformer language-model training workload (operator-launchable).

Covers the BASELINE.json BERT-base and Llama-2 configs: joins the gang,
builds the declared mesh (dp/fsdp/tp/cp), trains a transformer preset with
the sharded Trainer on synthetic tokens, logs tokens/sec and MFU.

workload config keys: preset (any models.transformer.PRESETS name:
"tiny"|"tiny-moe"|"gpt-small"|"moe-small"|"bert-base"|"llama2-7b"|
"llama2-13b"|"llama2-70b"), steps, batch_size, seq_len, lr,
attn ("dense"|"ring"|"flash"), profile_dir (capture an XLA trace),
checkpoint_dir, checkpoint_every (steps between saves; restart-based
recovery resumes from the latest checkpoint), grad_accum (microbatch
gradient accumulation — same global batch in 1/N-size activation
footprint; tools.memplan accounts for it), data ("fixed" resident
batch | "stream" synthetic through the prefetching DeviceLoader |
"memmap" + corpus=<path>: a REAL tokenized corpus in the
train.data.write_token_corpus memmap format, window-sharded per
process), plus any
TransformerConfig field as an override (e.g. n_layers, n_experts,
capacity_factor — MoE presets route through parallel.moe over the ep
mesh axis).
"""

from __future__ import annotations

import json
import logging
import time

from tf_operator_tpu.rendezvous.context import JobContext, RetryableFailure
from tf_operator_tpu.train.profile import profile_ctx

log = logging.getLogger("tpujob.lm")


def main(ctx: JobContext) -> None:
    ctx.initialize_distributed()

    import jax

    from tf_operator_tpu.models.transformer import (
        init_transformer,
        lm_loss,
        lm_loss_with_counters,
        moe_counter_names,
        preset_from_workload,
        remat_save_names,
        transformer_logical_axes,
        zero_moe_counters,
    )
    from tf_operator_tpu.train.metrics import (
        fmt_mfu,
        mfu,
        run_report,
        transformer_train_flops,
        transformer_train_flops_exact,
    )
    from tf_operator_tpu.parallel.collectives import sections_summary
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

    wl = ctx.workload
    steps = max(2, int(wl.get("steps", 10)))
    batch = int(wl.get("batch_size", 8))
    seq = int(wl.get("seq_len", 512))
    cfg = preset_from_workload(wl)
    mesh = ctx.build_mesh()

    # gmm-dispatched experts: the step's routing counters leave it beside
    # the loss as the state's ``extra`` — device scalars, read once below —
    # and a bias-balanced router's bias rides the same tree as STATE: the
    # step reads it, returns the next, and the checkpoint holds it (a
    # resumed job routes as the killed one did)
    counted = bool(moe_counter_names(cfg, mesh))

    def loss_fn(params, tokens, extra):
        if counted:
            return lm_loss_with_counters(params, tokens, cfg, mesh=mesh,
                                         extra=extra)
        return lm_loss(params, tokens, cfg, mesh=mesh)

    def init_fn(k):
        params = init_transformer(k, cfg)
        return (params, zero_moe_counters(cfg, mesh)) if counted else params

    trainer = Trainer(
        mesh,
        loss_fn=loss_fn,
        init_fn=init_fn,
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(
            optimizer="adamw", learning_rate=float(wl.get("lr", 3e-4)),
            grad_accum=int(wl.get("grad_accum", 1)),
            # submit-latency path: rbg init sheds the threefry subgraphs
            # (opt-in since r5 — library default stays deterministic)
            fast_init_rng=bool(wl.get("fast_init_rng", True)),
        ),
    )
    from tf_operator_tpu.train.checkpoint import WorkloadCheckpointer

    # ctx wires the warm-restore seam in: peer prefetch before disk
    # (TPUJOB_RESTORE_PEERS), committed-step pushes to this host's depot
    # (TPUJOB_PEER_DEPOT), and save-stall / restore spans on the timeline.
    ckpt = WorkloadCheckpointer(wl, ctx=ctx)
    if ckpt.is_complete(steps):
        log.info("already complete (budget %d); nothing to do", steps)
        return
    loader = None
    data_mode = wl.get("data", "fixed")
    if data_mode == "memmap":
        # REAL tokenized corpus: workload.corpus points at a memmap token
        # stream (train.data.write_token_corpus format); each process reads
        # a disjoint window shard through the prefetching DeviceLoader.
        from tf_operator_tpu.train.data import DeviceLoader, TokenMemmapDataset

        n_proc = jax.process_count()
        if batch % n_proc:
            raise ValueError(f"batch_size {batch} % {n_proc} processes != 0")
        # holdout_windows (r5): reserve the corpus tail for the Evaluator
        # BEFORE rank-sharding — the trainer never sees those windows, so
        # eval CE measures generalization on this corpus, not memorization.
        ds = TokenMemmapDataset(
            wl["corpus"], batch // n_proc, seq,
            holdout=int(wl.get("holdout_windows", 0)),
        )
        loader = DeviceLoader(
            ds, trainer.batch_sharding, skip=ckpt.resume_step()
        )
        tokens = (b["tokens"] for b in loader)
    elif data_mode == "stream":
        from tf_operator_tpu.train.data import SyntheticTokens, local_loader

        # batch_size is GLOBAL; local_loader splits it across processes
        # with rank-distinct data and prefetches onto the mesh. skip= keeps
        # a resumed incarnation from replaying batches steps 0..k consumed.
        loader = local_loader(
            SyntheticTokens, batch, trainer.batch_sharding,
            seq_len=seq, vocab=cfg.vocab, skip=ckpt.resume_step(),
        )
        tokens = (b["tokens"] for b in loader)
    else:
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab),
            trainer.batch_sharding,
        )

    # Fault injection (workload keys fail_at_step + fail_marker): die
    # RETRYABLY once at the given global step — the restart-based-recovery
    # e2e: the gang restarts and the next incarnation must resume from the
    # latest checkpoint, not step 0. The marker file makes it once-only.
    fail_at = int(wl.get("fail_at_step", 0))
    marker = wl.get("fail_marker")
    first_step_marked = []

    def on_step(step: int) -> None:
        if not first_step_marked:
            # TTFS boundary (obs/): the first completed training step of
            # this run — includes rendezvous, restore and compile time.
            first_step_marked.append(step)
            ctx.mark_first_step(step)
        if fail_at and marker and step >= fail_at:
            import os

            if not os.path.exists(marker):
                open(marker, "w").close()
                log.warning("fault injection: requesting retry at step %d", step)
                if ckpt.manager is not None:
                    # a requested retry must not race its own async save: an
                    # interpreter that shuts down under an in-flight commit
                    # can hang, and a hung worker is never restarted
                    ckpt.manager.wait_until_finished()
                # routed by the harness to the user-retryable exit code
                raise RetryableFailure(f"fault injection at step {step}")

    # The step program is compiled ahead of the loop: its compile time is
    # read apart from the first step's run time, a compile the device's
    # compiler refuses fails HERE with its own error, and the compiled
    # text says which kernels the device will really run.
    t_compile = time.perf_counter()
    trainer.compile_step(jax.ShapeDtypeStruct((batch, seq), "int32"))
    compile_s = time.perf_counter() - t_compile
    if trainer.step_remats and remat_save_names(cfg.remat):
        # the step fits only because XLA rebuilds values it could not hold:
        # it runs, and a names policy that saves less may run faster
        # (PERF.md §6, PR 33: gqa-2048 at b = 6, 13 % between two sets)
        log.warning(
            "remat=%r at batch %d does not fit with every saved value held: "
            "the compiler rematerialises %d instructions on its own; compare "
            "a smaller save set (\"save:...\") or batch",
            cfg.remat, batch, trainer.step_remats)

    try:
        with profile_ctx(wl.get("profile_dir")):
            state, loss, timed, step_s = ckpt.run_loop(
                trainer, jax.random.PRNGKey(0), tokens, steps, on_step=on_step,
            )
    finally:
        if loader is not None:
            loader.close()
    if cfg.n_experts:
        # Router health check on the trained params: collapsed routing
        # (entropy << ln(E)) or heavy dropping is a silent quality bug —
        # surface it in the training log where operators look first.
        import math

        from tf_operator_tpu.models.transformer import lm_loss_and_metrics

        probe = tokens if not hasattr(tokens, "__next__") else jax.device_put(
            jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0, cfg.vocab),
            trainer.batch_sharding,
        )
        _, m = jax.jit(
            lambda p, tok, bias: lm_loss_and_metrics(
                p, tok, cfg, mesh=mesh, router_bias=bias)
        )(state.params, probe, (state.extra or {}).get("router_bias"))
        if "moe_expert_entropy" in m:
            log.info(
                "moe router: expert_entropy=%.3f (uniform=%.3f) "
                "drop_frac=%.3f lb_loss=%.3f z_loss=%.4f",
                float(m["moe_expert_entropy"]), math.log(cfg.n_experts),
                float(m["moe_drop_frac"]), float(m["moe_lb_loss"]),
                float(m["moe_z_loss"]),
            )
        else:
            # pipeline + MoE: per-layer router telemetry doesn't ride the
            # pp aux channel — only the scalar losses do (transformer
            # docstring); a missing key must not fail the job (caught by
            # the pp x ep gang e2e, r4)
            log.info(
                "moe router (pp — scalar losses only): lb_loss=%.3f "
                "z_loss=%.4f",
                float(m["moe_lb_loss"]), float(m["moe_z_loss"]),
            )
    moe_counters = (
        {k: float(v) for k, v in state.extra.items() if k != "router_bias"}
        if counted else None)
    if moe_counters:
        # the last step's routing, where dashboards read live job numbers
        ctx.report_eval_metrics(steps, moe_counters)
    log.info("run report: %s", json.dumps(run_report(
        workload="lm", preset=wl.get("preset", "tiny"), batch_size=batch,
        seq_len=seq, n_layers=cfg.n_layers, attn=cfg.attn_impl,
        step_compile_s=round(compile_s, 3),
        # the compiled step's Pallas kernels by name (instructions: what a
        # remat tier replays shows as a second ``flash_fwd``), and their sum
        step_kernels=trainer.step_kernels,
        step_tpu_custom_calls=sum(trainer.step_kernels.values()),
        # the compiler's own ``.remat`` clones: > 0 under a names policy is
        # a saved set that does not fit (warned above)
        step_remats=trainer.step_remats,
        # how the compiled step was partitioned: its collectives by kind
        step_collectives=trainer.step_collectives,
        # what a trace of it needs to name its ops: instructions by section
        # (``none``: no ``sec_*`` scope reached them) and the parse's seconds
        step_sections={
            "instructions": sections_summary(trainer.step_sections),
            "parse_s": round(trainer.step_sections_parse_s, 4)},
        step_s=step_s, losses=ckpt.loss_trace(),
        # how well the prefetch hid the input pipeline (None: no loader)
        loader=None if loader is None else {
            "batches": loader.batches, "wait_s": round(loader.wait_s, 4),
            "empty_pulls": loader.empty_pulls},
        # the last step's device scalars (None: no gmm-dispatched experts):
        # the routing counters summed over the expert layers and, where the
        # model has them, loss_main / loss_mtp and the router bias's two
        moe=moe_counters,
    )))
    if step_s is not None:
        n_chips = mesh.devices.size
        # active params: for top-1 MoE only one expert's FLOPs count per
        # token; mfu_attn adds the 12·L·t·d attention term (the honest
        # number at long context), mfu_6nd is the scaling-law-comparable one.
        flops_6nd = transformer_train_flops(cfg.n_active_params(), batch * seq)
        flops_exact = transformer_train_flops_exact(
            cfg.n_active_params(), batch * seq, cfg.n_layers, cfg.d_model, seq
        )
        log.info(
            "lm done: preset=%s loss=%.4f step=%.2fms tok/s=%.0f mfu_attn=%s "
            "mfu_6nd=%s (%d chips)",
            wl.get("preset", "tiny"), loss, step_s * 1e3, batch * seq / step_s,
            fmt_mfu(mfu(flops_exact, step_s, n_chips)),
            fmt_mfu(mfu(flops_6nd, step_s, n_chips)), n_chips,
        )
    else:
        log.info("lm done: preset=%s loss=%.4f (no timed steps remained)",
                 wl.get("preset", "tiny"), loss)
