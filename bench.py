"""Headline benchmark: ResNet-50 training throughput on the attached TPU.

Prints ONE JSON line:
  {"metric": "resnet50_images_per_sec_per_chip", "value": N,
   "unit": "images/sec/chip", "vs_baseline": MFU/0.50, ...}

The reference publishes no numbers (BASELINE.md); the driver-supplied north
star is ResNet-50 at >=50% MFU, so ``vs_baseline`` is achieved-MFU / 0.50 —
1.0 means the target is met.

Extra diagnostic fields beyond the required four are included (mfu,
step_time, batch, device) for the record; consumers key on the first four.

``BENCH_MODEL=bert`` (or any transformer preset name) benches the LM
training path instead — flash-attention transformer, tokens/sec/chip,
same single-JSON-line contract.

MFU basis (changed r3): LM rows report ``mfu_attn`` (6ND + the 12·L·t·d
attention matmul term — the honest number at long context) and
``mfu_6nd`` (parameter-only, comparable to scaling-law tables). ``mfu``/``vs_baseline`` follow mfu_attn from r3 on —
comparing them against pre-r3 archives across an accounting boundary
over-reads the gain by the attention fraction (~6% at t=512, ~2x at
t=8192 on gpt-small); use mfu_6nd for those diffs.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def require_tpu():
    """This file measures the chip. Without one it fails — it does not
    shrink to sizes a CPU can finish and print numbers under the names
    of device metrics."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"bench.py needs a TPU; jax found {dev.platform!r} "
            f"({getattr(dev, 'device_kind', '?')}). Run it on the machine "
            "with the chip (one process per chip); CPU checks of the "
            "training path are the tests and `chip_smoke.py --preset tiny`."
        )
    return dev


def run_timed_steps(trainer, state, pull, steps: int, stream: bool,
                    step_hint_s: float = 0.0):
    """The one timed-region protocol both benches share: optional device
    loop (BENCH_DEVICE_LOOP=K: K steps per compiled call, 0 disables; the
    K-step program compiles OUTSIDE the timed region), profiler capture
    outside the timing, one host fetch at the end. Returns
    (state, metrics, steps_run, step_s).

    The device loop exists to amortize per-step dispatch. Both readings
    behind the 100 ms auto-disable (a win at 10 ms steps, a 6.3% loss at
    0.6 s steps from the K-step scan's carry copies) were taken on an
    earlier installation whose dispatch cost ~5 ms a call; on the locally
    attached chip a dispatch measures tens of µs (PR 21, CHANGES.md), so
    the win side of that trade is not expected to survive — whether
    ``multi_step`` stays at all is ROADMAP C2. Unless BENCH_DEVICE_LOOP
    is set explicitly, the loop stays off above 100 ms steps, where the
    scan only costs."""
    import time

    from tf_operator_tpu.train.profile import profile_ctx

    k_env = os.environ.get("BENCH_DEVICE_LOOP")
    k = min(int(k_env if k_env is not None else "10"), steps)
    if k_env is None and step_hint_s > 0.1:
        k = 0
    device_loop = k > 1 and not stream
    full, rem = divmod(steps, k) if device_loop else (0, steps)
    if device_loop:
        # compile the K-step program OUTSIDE the timed region (the
        # single-step program is already warm from the caller's warmup)
        state, metrics = trainer.multi_step(state, pull(), k)
        _ = float(metrics["loss"])
    with profile_ctx(os.environ.get("BENCH_PROFILE")):
        t0 = time.perf_counter()
        for _ in range(full):
            state, metrics = trainer.multi_step(state, pull(), k)
        for _ in range(rem):  # BENCH_STEPS is honored exactly
            state, metrics = trainer.step(state, pull())
        _ = float(metrics["loss"])
        step_s = (time.perf_counter() - t0) / steps
    return state, metrics, steps, step_s


def start_precompile(trainer, batch_spec):
    """Kick off the background step compile (r4 submit overlap) — called
    BEFORE batch staging so the step program's trace+compile overlaps
    the batch upload AND the init phase. BENCH_OVERLAP=0 restores the
    serial path for A/B."""
    if os.environ.get("BENCH_OVERLAP", "1") != "1":
        return None
    if os.environ.get("BENCH_FUSED_SUBMIT", "0") == "1":
        return None
    return trainer.precompile_step_async(batch_spec)


def run_first_step(trainer, pull, breakdown, t_submit, pre=None):
    """Submit-phase protocol shared by both benches: the split
    init-then-step path by default (two programs, phase-timed, with the
    step program compiling on ``pre``'s background thread — r3 measured
    the two phases strictly serialized at 5.0 s + 9.9 s), or the fused
    single-program path under BENCH_FUSED_SUBMIT=1 (Trainer.init_and_step
    — one executable; no net win at its last measurement, ROADMAP C1).
    Returns (state, metrics). float() on the loss is the sync that ends
    each timed phase."""
    import jax

    if os.environ.get("BENCH_FUSED_SUBMIT", "0") == "1":
        state, metrics = trainer.init_and_step(jax.random.PRNGKey(0), pull())
        _ = float(metrics["loss"])
        breakdown["fused_init_first_step_s"] = round(
            time.perf_counter() - t_submit - breakdown["stage_batch_dispatch_s"], 2
        )
    else:
        t0 = time.perf_counter()
        state = trainer.init(jax.random.PRNGKey(0))
        breakdown["init_dispatch_s"] = round(time.perf_counter() - t0, 2)
        if pre is not None:
            t0 = time.perf_counter()
            pre.join()
            breakdown["step_compile_join_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        state, metrics = trainer.step(state, pull())
        _ = float(metrics["loss"])
        breakdown["first_step_s"] = round(time.perf_counter() - t0, 2)
    return state, metrics


def bench_lm(model: str) -> None:
    """Transformer pretraining throughput (BASELINE.json BERT/Llama configs)."""
    from tf_operator_tpu.train.compile_cache import enable as enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax

    from tf_operator_tpu.models.transformer import (
        init_transformer,
        lm_loss,
        preset,
        transformer_logical_axes,
    )
    from tf_operator_tpu.parallel import build_mesh
    from tf_operator_tpu.train.metrics import (
        mfu,
        transformer_train_flops,
        transformer_train_flops_exact,
    )
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

    dev = require_tpu()
    n_chips = jax.device_count()
    name = {"bert": "bert-base", "gpt": "gpt-small"}.get(model, model)

    batch = int(os.environ.get("BENCH_BATCH", "32"))
    seq = int(os.environ.get("BENCH_SEQ", "512"))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    attn = os.environ.get("BENCH_ATTN", "flash")
    # Remat matters: full remat frees enough HBM for 2x the batch (b=32
    # w/ remat: 36.5% MFU vs b=16 w/o: 34.0% on v5e — no-remat b=32 OOMs
    # even with the fused loss, 19.2G/15.75G). "dots" (selective
    # checkpointing) measured *worse* than full remat here (35.8%) — the
    # +3.6G of saved dot outputs cost more in scheduling than the saved
    # recompute, so full remat stays the default. BENCH_REMAT=1|0|full|none|dots.
    remat_env = os.environ.get("BENCH_REMAT", "1")
    remat = {"1": True, "0": False, "full": True, "none": False}.get(
        remat_env, remat_env
    )

    # BENCH_ACCUM=K: gradient accumulation over K microbatches — the
    # north-star d>=2048 configs need it to fit adamw state + activations
    # in one chip's HBM (tools/memplan sizes the combination).
    accum = int(os.environ.get("BENCH_ACCUM", "1"))

    overrides = {}
    # BENCH_CF: MoE capacity factor (expert rows = cf·k·T; FLOP padding
    # scales with it, as does drop_frac — see BASELINE.md MoE rows).
    if os.environ.get("BENCH_CF"):
        overrides["capacity_factor"] = float(os.environ["BENCH_CF"])
    # BENCH_MOE_DISPATCH=ragged: padding-free grouped-matmul experts (r5).
    if os.environ.get("BENCH_MOE_DISPATCH"):
        overrides["moe_dispatch"] = os.environ["BENCH_MOE_DISPATCH"]
    cfg = preset(name, max_seq=seq, attn_impl=attn, remat=remat, **overrides)
    mesh = build_mesh({"dp": n_chips})

    def loss_fn(params, tokens, extra):
        del extra
        return lm_loss(params, tokens, cfg, mesh=mesh)

    trainer = Trainer(
        mesh,
        loss_fn=loss_fn,
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-4,
                             grad_accum=accum, fast_init_rng=True),
    )
    # BENCH_DATA=stream: feed every step a fresh host batch through the
    # prefetching DeviceLoader instead of one resident device batch —
    # stream ≈ fixed is the proof the input pipeline stays off the step's
    # critical path.
    stream = os.environ.get("BENCH_DATA", "fixed") == "stream"
    loader = None
    if stream:
        # Built BEFORE t_submit: synthetic-data generation must not skew
        # the submit→first-step comparison against fixed mode.
        from tf_operator_tpu.train.data import DeviceLoader, SyntheticTokens

        loader = DeviceLoader(
            SyntheticTokens(batch, n=4 * batch, seq_len=seq, vocab=cfg.vocab),
            trainer.batch_sharding,
        )

        def pull():
            return next(loader)["tokens"]

    t_submit = time.perf_counter()
    breakdown = {}
    pre = start_precompile(
        trainer, jax.ShapeDtypeStruct((batch, seq), "int32")
    )
    if not stream:
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab),
            trainer.batch_sharding,
        )

        def pull():
            return tokens

    breakdown["stage_batch_dispatch_s"] = round(time.perf_counter() - t_submit, 2)
    try:
        state, metrics = run_first_step(trainer, pull, breakdown, t_submit, pre)
        first_step_s = time.perf_counter() - t_submit
        # 5 warmup steps, one sync: the loop-disable hint is a mean over
        # enough steps that one slow first dispatch cannot flip the
        # headline protocol run-to-run.
        t_warm = time.perf_counter()
        for _ in range(5):
            state, metrics = trainer.step(state, pull())
        _ = float(metrics["loss"])
        warm_step_s = (time.perf_counter() - t_warm) / 5

        state, metrics, steps, step_s = run_timed_steps(
            trainer, state, pull, steps, stream, step_hint_s=warm_step_s
        )
    finally:
        if loader is not None:
            loader.close()

    params = cfg.n_params()
    tokens_per_step = batch * seq
    # active params: for top-1 MoE only one expert's FLOPs count per token.
    # Two MFU readings (VERDICT r2 #3): mfu_6nd is the parameter-only rule
    # (comparable to scaling-law tables); mfu_attn adds the attention
    # matmul term (12·L·t·d per token) and is the honest number at long
    # context — the headline mfu/vs_baseline use it.
    flops_6nd = transformer_train_flops(cfg.n_active_params(), tokens_per_step)
    flops_exact = transformer_train_flops_exact(
        cfg.n_active_params(), tokens_per_step, cfg.n_layers, cfg.d_model, seq
    )
    achieved_6nd = mfu(flops_6nd, step_s, n_chips)
    achieved = mfu(flops_exact, step_s, n_chips)
    print(
        json.dumps(
            {
                "metric": f"{name}_tokens_per_sec_per_chip",
                "value": round(tokens_per_step / step_s / n_chips, 1),
                "unit": "tokens/sec/chip",
                "vs_baseline": round(achieved / 0.50, 4),
                "mfu": round(achieved, 4),
                "mfu_attn": round(achieved, 4),
                "mfu_6nd": round(achieved_6nd, 4),
                "step_time_s": round(step_s, 5),
                "batch": batch,
                "seq_len": seq,
                "grad_accum": accum,
                "attn": attn,
                "n_params": params,
                "n_chips": n_chips,
                "device": getattr(dev, "device_kind", dev.platform),
                "submit_to_first_step_s": round(first_step_s, 2),
                "submit_breakdown": breakdown,
                "compile_cache": bool(cache_dir),
                "loss": round(float(metrics["loss"]), 4),
            }
        )
    )


def bench_resnet_bn_ab() -> None:
    """Same-INVOCATION A/B of the BN stats-gradient modes (VERDICT r3
    #3): var and exact trainers built side by side, timed regions
    interleaved var/exact/var/exact on the same chip minutes apart — the
    receipt chip-day variance cannot fake. One JSON line with both."""
    from tf_operator_tpu.train.compile_cache import enable as enable_compile_cache

    enable_compile_cache()

    import dataclasses

    import jax
    import jax.numpy as jnp

    from tf_operator_tpu.models.resnet import ResNetConfig, init_resnet, resnet_forward
    from tf_operator_tpu.train.metrics import mfu, resnet_train_flops
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig
    from tf_operator_tpu.parallel import build_mesh

    dev = require_tpu()
    n_chips = jax.device_count()
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    image_size = int(os.environ.get("BENCH_IMAGE", "224"))
    steps = int(os.environ.get("BENCH_STEPS", "15"))
    mesh = build_mesh({"dp": n_chips})

    def make_trainer(mode):
        cfg = dataclasses.replace(
            ResNetConfig.resnet50(), bn_stats_stop_gradient=mode
        )

        def loss_fn(params, batch_data, st):
            images, labels = batch_data
            logits, new_state = resnet_forward(params, st, images, cfg, train=True)
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
            return loss, new_state

        return Trainer(
            mesh,
            loss_fn=loss_fn,
            init_fn=lambda k: init_resnet(k, cfg),
            config=TrainerConfig(optimizer="sgd", learning_rate=0.1,
                                 grad_clip=None, fast_init_rng=True),
        ), cfg

    arms = {}
    images = labels = None
    for mode in ("var", False):
        name = "var" if mode == "var" else "exact"
        trainer, cfg = make_trainer(mode)
        if images is None:
            images = jax.device_put(
                jax.random.normal(
                    jax.random.PRNGKey(1), (batch, image_size, image_size, 3)
                ),
                trainer.batch_sharding,
            )
            labels = jax.device_put(
                jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000),
                trainer.batch_sharding,
            )
        state = trainer.init(jax.random.PRNGKey(0))
        for _ in range(3):  # compile + warm
            state, m = trainer.step(state, (images, labels))
        _ = float(m["loss"])
        arms[name] = {"trainer": trainer, "state": state, "cfg": cfg,
                      "times": []}
    # interleave: var, exact, var, exact — same chip, minutes apart
    for _ in range(2):
        for name in ("var", "exact"):
            a = arms[name]
            t0 = time.perf_counter()
            st = a["state"]
            for _ in range(steps):
                st, m = a["trainer"].step(st, (images, labels))
            _ = float(m["loss"])
            a["state"] = st
            a["times"].append((time.perf_counter() - t0) / steps)
    fwd_flops = arms["var"]["cfg"].flops_per_image(image_size)
    train_flops = resnet_train_flops(fwd_flops, batch)
    out = {
        "metric": "resnet50_bn_ab_step_time_s",
        "value": round(min(arms["var"]["times"]), 5),
        "unit": "s/step (var mode, best of interleaved runs)",
        "vs_baseline": round(
            min(arms["exact"]["times"]) / min(arms["var"]["times"]), 4),
        "interleave_order": "var,exact,var,exact",
        "n_chips": n_chips,
        "batch": batch,
        "device": getattr(dev, "device_kind", dev.platform),
    }
    for name in ("var", "exact"):
        ts = arms[name]["times"]
        out[f"{name}_step_time_s"] = [round(t, 5) for t in ts]
        out[f"{name}_mfu"] = round(mfu(train_flops, min(ts), n_chips), 4)
    print(json.dumps(out))


def bench_submit_ab() -> None:
    """Same-SESSION submit→first-step repeats (r5, VERDICT r4 #5): the
    claim needs the spread, pinned minutes apart on the same chip, not a
    single draw. This parent never touches jax — each draw is a child
    bench process that has the chip to itself (fresh interpreter each —
    submit latency includes imports and trace); prints ONE JSON line
    with every draw + min/median/max. BENCH_MODEL picks the config
    (resnet50 default)."""
    import statistics

    n = int(os.environ.get("BENCH_SUBMIT_AB", "4"))
    draws, breakdowns = [], []
    for _ in range(n):
        row = _child_row({"BENCH_STEPS": "1", "BENCH_SUBMIT_AB": "0"})
        draws.append(row["submit_to_first_step_s"])
        breakdowns.append(row.get("submit_breakdown", {}))
    print(json.dumps({
        "metric": "submit_to_first_step_s_ab",
        "value": round(statistics.median(draws), 2),
        "unit": "s (median of same-session draws)",
        "vs_baseline": round(8.0 / statistics.median(draws), 4),
        "model": os.environ.get("BENCH_MODEL", "resnet50"),
        "draws": draws,
        "min": min(draws),
        "max": max(draws),
        "breakdowns": breakdowns,
    }))


def main() -> None:
    if os.environ.get("BENCH_SUBMIT_AB", "0") not in ("0", ""):
        bench_submit_ab()
        return
    if os.environ.get("BENCH_BN_AB", "0") == "1":
        bench_resnet_bn_ab()
        return
    model = os.environ.get("BENCH_MODEL", "resnet50").lower()
    if model in ("resnet50", "resnet") and os.environ.get(
        "BENCH_NORTHSTAR", "1"
    ) != "0":
        # two rows, two processes, a JAX-free parent (this one)
        bench_headline_and_northstar()
        return
    if model not in ("resnet50", "resnet"):
        from tf_operator_tpu.models.transformer import PRESETS

        known = {"bert", "gpt", *PRESETS}
        if model not in known:
            sys.exit(
                f"unknown BENCH_MODEL {model!r}; choose resnet50 or one of: "
                + ", ".join(sorted(known))
            )
        bench_lm(model)
        return
    from tf_operator_tpu.train.compile_cache import enable as enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from tf_operator_tpu.models.resnet import ResNetConfig, init_resnet, resnet_forward
    from tf_operator_tpu.train.metrics import mfu, resnet_train_flops
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig
    from tf_operator_tpu.parallel import build_mesh

    dev = require_tpu()
    n_chips = jax.device_count()

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    image_size = int(os.environ.get("BENCH_IMAGE", "224"))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    warmup = 5  # see bench_lm

    cfg = ResNetConfig.resnet50()
    # BN-stats levers (BASELINE.md "BN decomposition"). Default is the
    # config default — "var" since r3 (stop the variance gradient only:
    # ~+5 MFU pts (37.4% vs 31-32% exact), accuracy-validated on real data).
    # "exact"/"1" restores exact BN, "0" stops both stats gradients
    # (diverges at lr 0.1 on synthetic — measurement only). BENCH_FUSED_1X1=1
    # routes 1x1 convs through the Pallas fused matmul+stats kernel
    # (measured SLOWER than XLA convs — the documented negative result).
    import dataclasses

    sg_env = os.environ.get("BENCH_BN_STATS_GRAD", "var")
    if sg_env == "0":
        cfg = dataclasses.replace(cfg, bn_stats_stop_gradient=True)
    elif sg_env in ("1", "exact"):
        cfg = dataclasses.replace(cfg, bn_stats_stop_gradient=False)
    elif sg_env == "var":
        cfg = dataclasses.replace(cfg, bn_stats_stop_gradient="var")
    else:
        # a typo'd value silently landing on the (faster) var default
        # would corrupt an intended exact-BN measurement by +5 MFU pts
        sys.exit(f"unknown BENCH_BN_STATS_GRAD={sg_env!r}; use exact|1|0|var")
    if os.environ.get("BENCH_FUSED_1X1", "0") == "1":
        cfg = dataclasses.replace(cfg, fused_1x1=True)
    mesh = build_mesh({"dp": n_chips})

    def init_fn(key):
        return init_resnet(key, cfg)

    def loss_fn(params, batch_data, state):
        images, labels = batch_data
        logits, new_state = resnet_forward(params, state, images, cfg, train=True)
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
        return loss, new_state

    trainer = Trainer(
        mesh,
        loss_fn=loss_fn,
        init_fn=init_fn,
        config=TrainerConfig(optimizer="sgd", learning_rate=0.1, grad_clip=None,
                             fast_init_rng=True),
    )
    # BENCH_DATA=stream: fresh host batches through the prefetching
    # DeviceLoader (77 MB/step at b=128/224²) — stream ≈ fixed proves the
    # input pipeline overlaps the step instead of serializing on it.
    stream = os.environ.get("BENCH_DATA", "fixed") == "stream"
    loader = None
    if stream:
        # Built BEFORE t_submit (data generation isn't submit latency).
        from tf_operator_tpu.train.data import DeviceLoader, SyntheticImages

        loader = DeviceLoader(
            SyntheticImages(
                batch, n=4 * batch, image_size=image_size,
                num_classes=cfg.num_classes,
            ),
            trainer.batch_sharding,
        )

        def pull():
            b = next(loader)
            return b["image"], b["label"]

    t_submit = time.perf_counter()
    breakdown = {}
    pre = start_precompile(
        trainer,
        (
            jax.ShapeDtypeStruct((batch, image_size, image_size, 3), "float32"),
            jax.ShapeDtypeStruct((batch,), "int32"),
        ),
    )

    if not stream:
        # Staged FIRST: device_put dispatches the (77 MB at b=128) upload
        # asynchronously so it streams while the fused program traces.
        images = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(1), (batch, image_size, image_size, 3)),
            trainer.batch_sharding,
        )
        labels = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, cfg.num_classes),
            trainer.batch_sharding,
        )

        def pull():
            return images, labels

    breakdown["stage_batch_dispatch_s"] = round(time.perf_counter() - t_submit, 2)
    try:
        state, metrics = run_first_step(trainer, pull, breakdown, t_submit, pre)
        first_step_s = time.perf_counter() - t_submit
        t_warm = time.perf_counter()
        for _ in range(warmup):
            state, metrics = trainer.step(state, pull())
        _ = float(metrics["loss"])
        warm_step_s = (time.perf_counter() - t_warm) / warmup

        # Timed region: steps dispatched back-to-back (donation chains them
        # on device), ONE sync at the end — a sync per step would drain
        # the device queue each time and measure latency, not throughput.
        state, metrics, steps, step_s = run_timed_steps(
            trainer, state, pull, steps, stream, step_hint_s=warm_step_s
        )
    finally:
        if loader is not None:
            loader.close()
    images_per_sec = batch / step_s
    images_per_sec_per_chip = images_per_sec / n_chips
    fwd_flops = cfg.flops_per_image(image_size)
    train_flops = resnet_train_flops(fwd_flops, batch)
    achieved_mfu = mfu(train_flops, step_s, n_chips)

    # Measured v5e ceilings (BASELINE.md "roofline decomposition", measured
    # via tools/roofline --mode conv + the frozen-stats ablation): the
    # conv-only (BN-free) network fwd+bwd sustains 45.3% of peak; the full
    # step with BN statistics FROZEN (everything XLA can fuse, stats
    # barrier removed) reaches 39.4%. vs_ceiling judges the exact-BN step
    # against the latter — the achievable-step ceiling.
    ceiling = float(os.environ.get("BENCH_CEILING", "0.394"))

    out = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(images_per_sec_per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(achieved_mfu / 0.50, 4),
        "mfu": round(achieved_mfu, 4),
        "step_time_s": round(step_s, 5),
        "batch": batch,
        "image_size": image_size,
        "n_chips": n_chips,
        "device": getattr(dev, "device_kind", dev.platform),
        "submit_to_first_step_s": round(first_step_s, 2),
        "submit_breakdown": breakdown,
        "compile_cache": bool(cache_dir),
        "loss": round(float(metrics["loss"]), 4),
    }
    if ceiling:
        out["ceiling_mfu"] = ceiling
        out["vs_ceiling"] = round(achieved_mfu / ceiling, 4)
    print(json.dumps(out))


def _child_row(env_overrides: dict) -> dict:
    """One bench row from a CHILD process that has the chip to itself.
    The caller never touches jax: a process that has initialised a jax
    backend holds the chip, and a child that needs it then fails or
    hangs. A child's failure is the run's failure."""
    import subprocess

    env = dict(os.environ, BENCH_NORTHSTAR="0", **env_overrides)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, stdout=subprocess.PIPE, text=True, timeout=1100,
    )
    if proc.returncode != 0 or not proc.stdout.strip():
        sys.exit(
            f"bench child ({env.get('BENCH_MODEL', 'resnet50')}) "
            f"failed rc={proc.returncode}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_headline_and_northstar() -> None:
    """The default run: the ResNet-50 headline row, then the north-star
    LM row (gqa-2048: d_model=2048 GQA, the regime the 50%-MFU target
    presumes), each in its own child, one after the other; the LM row
    rides in the headline's ``northstar_lm`` field as before."""
    out = _child_row({})
    # Pin every measurement-affecting knob: the row must be THE canonical
    # north-star config even when the run was invoked with
    # stream/profile/remat overrides meant for the ResNet headline.
    env = {
        "BENCH_MODEL": "gqa-2048",
        "BENCH_BATCH": "6",
        "BENCH_SEQ": "2048",
        "BENCH_STEPS": "20",
        "BENCH_ATTN": "flash",
        "BENCH_REMAT": "save_mid",
        "BENCH_DATA": "fixed",
        "BENCH_ACCUM": "1",
        "BENCH_PROFILE": "",  # two processes tracing one dir collide
        "BENCH_DEVICE_LOOP": "0",
    }
    row = _child_row(env)
    row.pop("submit_breakdown", None)
    out["northstar_lm"] = row
    print(json.dumps(out))


if __name__ == "__main__":
    main()
