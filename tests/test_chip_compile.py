"""The main path's kernels, compiled at real widths for a TPU v5e that is
DESCRIBED, not attached (`jax.experimental.topologies`): what the chip's
compiler refuses — a block that does not tile, more VMEM than a kernel may
scope — it refuses here, at no chip time. Interpret-mode tests cannot see
either; both had passed every one of them.

The only file that describes a topology. The describing call loads the
TPU's library, which one process at a time may hold, so it lives in a
module-scoped fixture (never at import, in a skipif, in parametrize or in
conftest.py) and every compile runs in the test's own process, with the
persistent compilation cache off around it (an entry written for a
described chip cannot be read back without one). A compile that passes is
not a chip run: no time, no result comes from here.
"""

import contextlib
import importlib

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

fa = importlib.import_module("tf_operator_tpu.ops.flash_attention")
gm = importlib.import_module("tf_operator_tpu.ops.grouped_matmul")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no compiler here: skip, named
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _cache_off():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture
def no_cache():
    with _cache_off():
        yield


def _kernel_names_in(text: str) -> list:
    """The HLO instruction name of every Mosaic kernel in a compiled
    program's text, instance suffix cut, sorted (``compiled_kernels``, what
    ``Trainer.step_kernels`` keeps, one entry an instruction)."""
    from tf_operator_tpu.parallel.collectives import compiled_kernels

    return [name for name, n in compiled_kernels(text).items()
            for _ in range(n)]


def test_compiled_kernels_cut_instance_suffix_and_transform_wrappers():
    """By the instruction's own name only: a kernel's name also appears as
    an operand of the ops that read its result, and other custom calls
    have other targets."""
    from tf_operator_tpu.parallel.collectives import compiled_kernels

    call = 'custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    text = "\n".join([
        f"  %flash_fwd.3 = (bf16[2,8]{{1,0}}, f32[2]{{0}}) custom-call(%a), {call}",
        f"  %jvp_flash_fwd_ = bf16[2]{{0}} custom-call(%a, %flash_fwd.3), {call}",
        f"  ROOT %transpose_jvp_flash_bwd_dqkv__.1 = bf16[2]{{0}} custom-call(%b), {call}",
        f"  %gmm_dw_scaled.7 = f32[4]{{0}} custom-call(%c), {call}",
        '  %s = f32[] custom-call(%flash_fwd.3), custom_call_target="Sharding"',
    ])
    assert compiled_kernels(text) == {
        "flash_bwd_dqkv": 1, "flash_fwd": 2, "gmm_dw_scaled": 1}
    assert compiled_kernels("ENTRY %main { ROOT %x = f32[] add(%a, %b) }") == {}
    # a clone the compiler rebuilds for want of memory is one more run of
    # that kernel, under the kernel's name
    for clone in ("flash_fwd.3.remat", "flash_fwd.3.remat2", "flash_fwd.remat.1"):
        again = f"{text}\n  %{clone} = bf16[2]{{0}} custom-call(%a), {call}"
        assert compiled_kernels(again)["flash_fwd"] == 3, clone


@pytest.mark.parametrize("line, counted", [
    ("  %fusion.382.remat2 = bf16[6,2048,8192]{2,1,0} fusion(%a), kind=kLoop", 1),
    ("  %fusion.1678.remat = bf16[135168,2048]{1,0} fusion(%a), kind=kLoop", 1),
    ("  ROOT %copy.218.remat.1.remat2 = bf16[6,2048,16,128]{3,2,1,0} copy(%a)", 1),
    # a read of a rebuilt tuple is no work
    ("  %gte.remat.5 = bf16[6,2048]{1,0} get-tuple-element(%fusion.377.remat3), index=1", 0),
    # jax's own remat2 region, and a reader of a clone: not clones
    ("  %remat2.161 = bf16[6,2048,2048]{2,1,0} fusion(%a), kind=kOutput", 0),
    ("  %fusion.400 = bf16[6,2048]{1,0} fusion(%fusion.382.remat2), kind=kLoop", 0),
])
def test_compiled_remats_count_the_compilers_own_clones(line, counted):
    """``compiled_remats`` (``Trainer.step_remats``): the instructions XLA's
    scheduler cloned to fit the chip, by their DEFINITIONS' names."""
    from tf_operator_tpu.parallel.collectives import compiled_remats

    text = f"ENTRY %main {{\n  %a = f32[] parameter(0)\n{line}\n}}"
    assert compiled_remats(text) == counted


def _kernel_names(fn, *args) -> list:
    """Compile for the described chip; its Mosaic kernels by name."""
    return _kernel_names_in(jax.jit(fn).lower(*args).compile().as_text())


def _kernels(fn, *args) -> int:
    """Compile for the described chip; count the Mosaic kernels in it."""
    return len(_kernel_names(fn, *args))


# ---- flash attention: fwd and fwd+bwd -------------------------------------

FLASH_SHAPES = {
    # b, t, h, h_kv, d (, window) — bf16 as in training
    "gqa-2048": (6, 2048, 16, 4, 128),
    "hd64-t2048": (4, 2048, 12, 12, 64),
    # smallthinker-21ba3b: 7 query heads a KV head (448-row q tiles of
    # 64-row blocks), 8,192-token rows, a window layer and a global one
    "group7-8k-window4096": (1, 8192, 28, 4, 128, 4096),
    "group7-8k-global": (1, 8192, 28, 4, 128),
}


@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_flash_attention_compiles_for_v5e(one_chip, no_cache, shape, bwd):
    b, t, h, h_kv, d, *window = FLASH_SHAPES[shape]

    def spec(heads):
        return jax.ShapeDtypeStruct((b, t, heads, d), jnp.bfloat16,
                                    sharding=one_chip)

    def fwd(q, k, v):
        # This process's own backend is the CPU, so flash_attention()'s
        # dispatch would hand the compiler the reference: take the block
        # sizes the dispatch picks, then enter the kernel path itself.
        use, bq, bk = fa._dispatch(q, k, v, None, None, True, None)
        assert use
        return fa._flash_lse(q, k, v, True, bq, bk, False, *window)[0]

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if bwd else fwd
    names = _kernel_names(fn, spec(h), spec(h_kv), spec(h_kv))
    # each kernel under its own name, whatever jvp/transpose/remat wrapper
    # the call sits in (``transpose_jvp_flash_bwd_dqkv__`` still carries
    # it): the forward, and ONE backward kernel for dq, dk and dv
    assert names == ["flash_bwd_dqkv", "flash_fwd"][0 if bwd else 1:]


@pytest.mark.parametrize("policy, fwd_runs", [
    ("nothing_saveable", 2), ("flash_o+flash_lse", 1)])
def test_flash_kernels_keep_their_names_under_remat_in_a_scan(
        one_chip, no_cache, policy, fwd_runs):
    """As the train step holds them: a rematerialised layer inside
    ``lax.scan``. With nothing saved the forward runs twice (once
    replayed), both under ``flash_fwd``; before the kernels had names these
    read ``closed_call``, ``rematted_computation`` and ``checkpoint``. A
    policy that names the forward's two outputs keeps them for the backward
    kernel, and the replay is gone (what every ``*_mid`` tier does)."""
    b, t, h, h_kv, d = 2, 1024, 8, 2, 128
    policies = jax.checkpoint_policies
    policy = (policies.nothing_saveable if policy == "nothing_saveable" else
              policies.save_only_these_names("flash_o", "flash_lse"))

    def spec(heads):
        return jax.ShapeDtypeStruct((b, t, heads, d), jnp.bfloat16,
                                    sharding=one_chip)

    def layer(q, k, v):
        use, bq, bk = fa._dispatch(q, k, v, None, None, True, None)
        assert use
        return fa._flash_lse(q, k, v, True, bq, bk, False)[0]

    def loss(q, k, v):
        f = jax.checkpoint(layer, policy=policy)
        out, _ = jax.lax.scan(lambda c, _: (c + f(c, k, v), None), q, None,
                              length=2)
        return jnp.sum(out.astype(jnp.float32))

    names = _kernel_names(jax.grad(loss, argnums=(0, 1, 2)),
                          spec(h), spec(h_kv), spec(h_kv))
    assert names == ["flash_bwd_dqkv"] + ["flash_fwd"] * fwd_runs


def test_flash_kernels_compile_at_latent_widths_for_v5e(one_chip, no_cache):
    """Latent attention's shapes at the JoyAI cell's sizes: q and k 192 wide
    (not a lane multiple: Mosaic pads it), v and o 128, 32 heads, two
    8,192-token rows, the forward and the backward kernel under their names."""
    def spec(width):
        return jax.ShapeDtypeStruct((2, 8192, 32, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        use, bq, bk = fa._dispatch(q, k, v, None, None, True, None)
        assert use and (bq, bk) == (512, 1024)
        out = fa._flash_lse(q, k, v, True, bq, bk, False)[0]
        assert out.shape == (2, 8192, 32, 128)
        return jnp.sum(out.astype(jnp.float32))

    names = _kernel_names(jax.grad(loss, argnums=(0, 1, 2)),
                          spec(192), spec(192), spec(128))
    assert names == ["flash_bwd_dqkv", "flash_fwd"]


# the two share cells' whole steps: config file, traffic mix, what the parent
# of PR 36 compiled to (the compiler's own `.remat` clones), the row counts of
# its lossless bound, nb·B and T·k, and `temp`: the step's temporaries in bytes
# as the last PR that moved them left them (the test gives 2 % of room).
# SmallThinker's rose 7,319,812,096 -> 9,300,730,880 with PR 49: the walk's
# bodies hold less, but the compiler now sinks every layer's `wq` / `wo` weight
# gradients down to the gradient norm and keeps their inputs alive till then
# (PERF.md, PR 49). JoyAI's: 9,864,569,856 before it, 9,864,021,504 since.
SHARE_STEPS = {
    "joyai": dict(
        config="joyai-llm-flash-ep16share-train1.json",
        mix="pretrain-8k-ep16share.json", flash=3,
        temp=9_864_569_856, parent_remats=3,
        bound_rows=("135168,2048", "131072,2048")),
    "smallthinker": dict(
        config="smallthinker-21ba3b-ep4share-train1.json",
        mix="pretrain-8k-ep4share.json", flash=4,
        temp=9_300_730_880, parent_remats=0,
        bound_rows=("200704,2560", "196608,2560")),
}


@pytest.mark.slow  # 100 - 150 s each alone and 350+ CPU-seconds of compiler threads: tier-1's time limit has no room for them
@pytest.mark.parametrize("cell", sorted(SHARE_STEPS))
def test_share_cell_step_fits_v5e_and_holds_nothing_bound_sized(topo, no_cache, cell):
    """A share cell's WHOLE training step — the config file's workload
    through ``Trainer``, the traffic mix's rows a step — compiled for the
    described chip. JoyAI: 10.9 GB of state beside two 8,192-token rows is
    what the compiler accepted (it refused three), so a change that breaks
    the fit fails here. The flash kernels run under their names once a layer
    kind — ``save_mid`` keeps the forward's ``flash_o`` / ``flash_lse``, so no
    ``flash_fwd`` is replayed (PR 33). The expert layer walks segments
    (``parallel.moe._expert_walk``, PR 36): every ``gmm_*`` kernel is there by
    name, NO instruction has the lossless bound's row count in its shape, the
    compiler rebuilds nothing of its own accord where the parent's JoyAI
    step held three ``.remat`` clones, and the temporaries are within 2 % of
    the recorded ones (``SHARE_STEPS``). The combine and ``dx`` are
    ``moe_combine`` (PR 49): no scatter has the float32 [T, d] carry as its
    result."""
    import json
    import os
    import re
    from unittest import mock

    from tf_operator_tpu.models import transformer as tr
    from tf_operator_tpu.parallel.mesh import build_mesh
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

    spec = SHARE_STEPS[cell]
    home = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(home, "configs", spec["config"])) as f:
        config = json.load(f)
    with open(os.path.join(home, "traffic", spec["mix"])) as f:
        mix = json.load(f)
    cfg = tr.preset_from_workload(config["workload"])
    opt = config["optimizer"]
    mesh = build_mesh(dict(config["mesh_axes"]), devices=list(topo.devices)[:1])
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, t, extra: tr.lm_loss_with_counters(
            p, t, cfg, mesh=mesh, extra=extra),
        init_fn=lambda k: (tr.init_transformer(k, cfg), tr.zero_moe_counters(cfg)),
        logical_axes=tr.transformer_logical_axes(cfg),
        config=TrainerConfig(
            optimizer=opt["name"], learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"], beta1=opt["beta1"],
            beta2=opt["beta2"], grad_clip=opt["grad_clip"], fast_init_rng=False))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = trainer.compile_step(jax.ShapeDtypeStruct(
            (int(mix["batch_size"]), int(mix["seq_len"])), "int32"))
    text = compiled.as_text()
    kernels = trainer.step_kernels
    assert {k: n for k, n in kernels.items() if k.startswith("flash_")} == {
        "flash_fwd": spec["flash"], "flash_bwd_dqkv": spec["flash"]}
    assert {"gmm_fwd", "gmm_fwd_scaled", "gmm_dx", "gmm_dw",
            "gmm_dw_scaled"} <= set(kernels), kernels
    # the walk's combine and dx are the kernel, not a scatter onto [T, d]
    assert "moe_combine" in kernels, kernels
    tokens = int(mix["batch_size"]) * int(mix["seq_len"])
    assert not _f32_scatters(text, tokens, cfg.d_model)
    for rows in spec["bound_rows"]:
        assert not re.findall(r"\[" + rows + r"\]", text), rows
    assert trainer.step_remats <= spec["parent_remats"]
    # weights + two moments of every parameter, in and out in place
    held = compiled.memory_analysis()
    assert held.argument_size_in_bytes > 12 * cfg.n_params()
    assert held.alias_size_in_bytes > 12 * cfg.n_params()
    assert held.temp_size_in_bytes <= 1.02 * spec["temp"], held.temp_size_in_bytes


def _compiled_lm_trainer(cfg, mesh_axes, devices, batch_shape):
    """A ``Trainer`` over the described devices and its dense LM step
    compiled for them (the process's own backend is the CPU, so the kernels'
    dispatch is steered to "tpu" around the compile)."""
    from unittest import mock

    from tf_operator_tpu.models import transformer as tr
    from tf_operator_tpu.parallel.mesh import build_mesh
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

    mesh = build_mesh(mesh_axes, devices=devices)
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, t, extra: tr.lm_loss(p, t, cfg, mesh=mesh),
        init_fn=lambda k: tr.init_transformer(k, cfg),
        logical_axes=tr.transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3))
    assert trainer.step_kernels is None and trainer.step_collectives is None
    assert trainer.step_remats is None
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = trainer.compile_step(
            jax.ShapeDtypeStruct(batch_shape, "int32"))
    return trainer, compiled


def test_fsdp4_step_walks_the_cross_entropy_per_chip_on_v5e(topo, no_cache):
    """A small fsdp = 4 training step compiled for the described 2x2: the
    TPU's partitioner shows what the CPU's does (tests/
    test_fused_cross_entropy.py) — no collective on a [rows, vocab] tile,
    nothing of the CE's in a loop, the head gathered once in the compute dtype and its gradient leaving as ONE f32 reduce-scatter.
    Left to propagation the d-sharded head cost two all-reduces of the f32
    logits tile a block (PERF.md §6, PR 31). The cell's own size is compiled
    by hand (§6), not here."""
    from tf_operator_tpu.models import transformer as tr
    from tf_operator_tpu.parallel.collectives import compiled_collectives

    cfg = tr.preset("tiny", d_model=512, vocab=4096, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=1024, max_seq=2048, attn_impl="flash")
    trainer, compiled = _compiled_lm_trainer(
        cfg, {"fsdp": 4}, list(topo.devices), (4, 2048))
    ops = compiled_collectives(compiled.as_text())
    assert not [op for op in ops
                if any(s.endswith(f",{cfg.vocab}]") for s in op["shapes"])]
    head = f"[{cfg.vocab},{cfg.d_model}]"
    ce = sorted((op["kind"], op["shapes"][0], op["runs"]) for op in ops
                if "fused_xent" in op["op_name"] and op["bytes"] > 64)
    assert ce == [("all-gather", "bf16" + head, 1),
                  ("reduce-scatter", "f32" + head, 1)], ce
    summary = trainer.step_collectives
    # the layers' gradient reductions stay in the scan's loop (this backend's
    # ``all-reduce-scatter`` fusions; at these widths the small leaves are
    # plain all-reduces): none of them is near a logits tile, 16.8 MB here
    assert summary["all-reduce"]["in_loop_max_bytes"] < 2 ** 21, summary
    assert summary["reduce-scatter"]["count"] > 1, summary
    assert summary["largest"] == "reduce-scatter f32" + head, summary


# what each remat mode leaves of the flash forward in a compiled step: the
# tiers that keep its (o, lse) hold the kernel once, every other mode that
# rematerialises replays it — the programs they were before the two names
STEP_FLASH_FWD = {
    "save_mid": 1, "save_mlp_mid": 1, "save:flash_o,flash_lse": 1,
    "save:resid_mid": 2, "save_qkv": 2, "full": 2, "dots": 2, "none": 1,
}


@pytest.mark.parametrize("remat", sorted(STEP_FLASH_FWD))
def test_step_kernels_say_what_a_remat_tier_replays_on_v5e(topo, no_cache, remat):
    """``Trainer.step_kernels`` of a small one-chip step compiled for the
    described chip: the Pallas kernels by name, as instructions (the layer
    scan's body holds each once whatever its trips)."""
    from tf_operator_tpu.models import transformer as tr

    cfg = tr.preset("tiny", d_model=256, vocab=512, n_layers=2, n_heads=2,
                    n_kv_heads=1, d_ff=512, max_seq=512, attn_impl="flash",
                    remat=remat)
    trainer, _ = _compiled_lm_trainer(
        cfg, {"fsdp": 1}, list(topo.devices)[:1], (2, 512))
    assert trainer.step_kernels == {
        "flash_bwd_dqkv": 1, "flash_fwd": STEP_FLASH_FWD[remat]}
    assert trainer.step_remats == 0  # a step this small fits under any tier


# ---- paged decode: the kernel the serve engine cannot run without ---------


@pytest.mark.parametrize("rows", [1, 128], ids=["decode", "chunk128"])
@pytest.mark.parametrize("page", [16, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_attention_decode_compiles_for_v5e(one_chip, no_cache, dtype,
                                                 page, rows):
    """One row a sequence over a batch of slots (a decode step), and one
    sequence's chunk of 128 positions as ONE q tile of 128 x 4 rows."""
    h, h_kv, d, n_pages, p = 16, 4, 128, 81, 16  # gqa-2048 widths
    s_n = 8 if rows == 1 else 1

    def a(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = a((n_pages, h_kv, page, d), dtype)
    names = _kernel_names(
        lambda q, k, v, pt, sl, qs: fa._paged_call(
            q, k[None], v[None], 0, pt, sl, qs, False),
        a((s_n, rows, h, d), dtype), pool, pool, a((s_n, p), jnp.int32),
        a((s_n,), jnp.int32), a((s_n,), jnp.int32),
    )
    assert names == ["paged_attention"]


# ---- the serve engine's two programs: the KV pool stays where it lies ------

SERVE1 = dict(page_size=64, pool_pages=320, max_slots=16, prefill_chunk=128)


@pytest.fixture(scope="module")
def serve1_engine(one_chip):
    """``ServeEngine`` at the benchmark's -serve1 shapes (Mistral-7B widths,
    pool ``[L, 321, 8, 64, 128]``, 20 pages a sequence) with 2 layers, from
    abstract parameters, both programs compiled for the described chip by
    the engine's own ``compile()``. This process's backend is the CPU, so
    the kernel dispatch is steered to "tpu" here, in the test."""
    from unittest import mock

    from tf_operator_tpu.models.transformer import init_transformer, preset
    from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine

    cfg = preset("llama2-7b", n_kv_heads=8, d_ff=14336, n_layers=2,
                 max_seq=1280)
    shapes = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    engine = ServeEngine(cfg, params, ServeConfig(**SERVE1))
    with _cache_off(), mock.patch.object(jax, "default_backend",
                                         lambda: "tpu"):
        report = engine.compile()
    return engine, report


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_engine_programs_keep_the_pool_in_place_on_v5e(serve1_engine, program):
    """The pool enters, is written, is read and leaves each program in ONE
    layout, in place. Before PR 25 the write's scatter forced a layout of
    its own on the pool: four whole-pool ``copy`` in each program (2 GB
    moved each) and a slice + relayout of a whole layer in front of every
    kernel call."""
    import re

    from tf_operator_tpu.serve.engine import pool_copies

    engine, report = serve1_engine
    text = getattr(engine, f"_{program}").as_text()
    pool = engine._pool_shape()
    assert pool == (2, 321, 8, 64, 128)
    dims = ",".join(map(str, pool))
    layer = ",".join(map(str, pool[1:]))

    def results(shape):  # opcodes of executed-or-fused results of that shape
        return re.findall(
            r"= \w+\[" + shape + r"\]\S* ([\w\-]+)\(", text)

    # 1. nothing pool-shaped but names, views and updates in place
    moved = [op for op in results(dims) if op in (
        "copy", "copy-done", "transpose", "slice", "dynamic-slice")]
    assert not moved, moved
    # 2. nothing layer-shaped at all: no slice, no relayout feeding the kernel
    assert not results("1," + layer) and not results(layer)
    # ...which is what the engine's own counter says, here and in compile()
    assert pool_copies(text, pool) == 0
    assert report[f"{program}_pool_copies"] == 0
    # 3. the pools still alias parameter -> result; one kernel a layer in
    # the decode step, two in the run that carries a chunk (the chunk's
    # tile, then the decode rows': there is no chunk-only program)
    assert "input_output_alias" in text
    kernels = _kernel_names_in(text)
    calls = {"decode": 2, "prefill": 4}[program]
    assert kernels.count("paged_attention") == calls, kernels
    assert report[f"{program}_tpu_custom_calls"] >= calls
    # 4. a grid step is one page of as many KV heads as VMEM holds beside
    # their q tiles (``_kv_heads_per_step``): all 8 for a decode step's
    # 4-row tiles, 4 for a chunk's 512-row tiles (its 128 positions are
    # ONE tile a head over their sequence's pages). So a layer's kernel
    # walks 16 slots x 1 group x 20 page slots, or 1 x 2 x 20 (at the
    # cell's 12 layers 3,840 steps a decode run and 480 a chunk, where one
    # head a step made 30,720 and 1,920); the run that carries a chunk
    # walks both, whether or not a decode row is active
    per_layer = {"decode": 16 * 20, "prefill": 2 * 20 + 16 * 20}[program]
    assert report[f"{program}_attn_grid_steps"] == 2 * per_layer


@pytest.mark.parametrize("page", [8, 16])
def test_engine_compile_counts_no_pool_copies_at_tiny(page):
    """The same counter through ``compile()`` on this process's own backend
    (the CPU: gather reference, no kernel), as every engine reports it."""
    from tf_operator_tpu.models.transformer import init_transformer, preset
    from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine

    cfg = preset("tiny")
    engine = ServeEngine(
        cfg, init_transformer(jax.random.PRNGKey(0), cfg),
        ServeConfig(page_size=page, pool_pages=24, max_slots=3,
                    prefill_chunk=8))
    report = engine.compile()
    assert report["decode_pool_copies"] == 0
    assert report["prefill_pool_copies"] == 0
    # no kernel in the program, none counted
    assert report["decode_attn_grid_steps"] == 0
    assert report["prefill_attn_grid_steps"] == 0


# ---- the hybrid engine: two kinds of sequence state, both where they lie ----

HYBRID1 = dict(page_size=64, pool_pages=1024, max_slots=16, prefill_chunk=256)


@pytest.fixture(scope="module")
def hybrid_engine(one_chip):
    """``ServeEngine`` for preset ``olmo-hybrid-7b`` at the benchmark's
    -serve1 shapes (published widths, the whole vocabulary, pool ``[full
    layers, 1025, 30, 64, 128]``, state store ``[linear layers, 17, 30, 96,
    192]``, 64 page slots a sequence, 256-token chunks) with ONE period (3
    linear layers + 1 full) of the cell's two, from abstract parameters,
    compiled for the described chip by the engine's own ``compile()``."""
    from unittest import mock

    from tf_operator_tpu.models.transformer import init_transformer, preset
    from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine

    cfg = preset("olmo-hybrid-7b", n_layers=4, max_seq=4096)
    shapes = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    engine = ServeEngine(cfg, params, ServeConfig(**HYBRID1))
    with _cache_off(), mock.patch.object(jax, "default_backend",
                                         lambda: "tpu"):
        report = engine.compile()
    return engine, report


@pytest.mark.parametrize("program,kernels", [
    ("decode", {"gdn_step": 3, "paged_attention": 1}),
    ("prefill", {"gdn_chunk_fwd": 3, "gdn_step": 3, "paged_attention": 2})])
def test_hybrid_engine_programs_keep_both_stores_in_place_on_v5e(
        hybrid_engine, program, kernels):
    """A linear layer's kernel a layer beside the paged kernel of the full
    one — both kinds' in the run that carries a chunk, whose rows are the
    chunk's AND the decode step's — and neither the page pool nor the
    recurrent-state store is copied, sliced by layer or relaid between
    parameter and result: the decode rows update the store through the
    kernel's aliased operand, a chunk reads and writes its one slot."""
    from tf_operator_tpu.serve.engine import pool_copies

    engine, report = hybrid_engine
    assert engine._pool_shape() == (1, 1025, 30, 64, 128)
    assert engine.store.state_shape == (3, 17, 30, 96, 192)
    assert engine.store.conv_shape == (3, 17, 3, 11520)
    text = getattr(engine, f"_{program}").as_text()
    assert report[f"{program}_kernels"] == kernels
    assert report[f"{program}_tpu_custom_calls"] == sum(kernels.values())
    assert report[f"{program}_pool_copies"] == 0
    assert report[f"{program}_state_copies"] == 0
    assert pool_copies(text, engine.store.state_shape) == 0
    assert "input_output_alias" in text
    # grid steps: the full layer walks 64 page slots for 16 slots x 1 group of
    # 30 KV heads (a one-row tile) or 1 x 3 groups of 10 (a 256-row tile); a
    # linear layer steps 16 slots x 2 groups of 15 heads, or 30 heads x 4
    # chunks of 64 positions; the run that carries a chunk steps both
    decode, chunk = 16 * 64 + 3 * 16 * 2, 3 * 64 + 3 * 30 * 4
    assert report[f"{program}_attn_grid_steps"] == {
        "decode": decode, "prefill": chunk + decode}[program]


@pytest.mark.parametrize("fixture", ["serve1_engine", "hybrid_engine"])
def test_engine_programs_hand_the_token_array_on_v5e(request, fixture):
    """The loop runs a program ahead (PR 38): the token every slot decodes
    from is an operand of BOTH programs and the last of their three results —
    ``int32[max_slots]``, never donated, because the host reads the very array
    the next run takes — and a chunk names its slot whatever the model; the
    pools (and the state) are still the donated operands, still in place. The
    run that carries a chunk takes the chunk's operands, then the decode
    step's own four. ``compile()`` builds these two programs and no third."""
    engine, report = request.getfixturevalue(fixture)
    s_n = engine.scfg.max_slots
    decode = [(s_n, engine.max_pages_per_seq), (s_n,), (s_n,), (s_n,)]
    small = {"decode": decode,
             "prefill": [(engine.max_pages_per_seq,), (),
                         (engine.scfg.prefill_chunk,), (), ()] + decode}
    assert {k[: -len("_compile_s")] for k in report if k.endswith("_compile_s")} \
        == set(small)
    for program, shapes in small.items():
        compiled = getattr(engine, f"_{program}")
        args, _ = compiled.args_info
        assert [(a.shape, a.donated) for a in args[3:]] == [(s, False) for s in shapes]
        assert all(a.donated for a in jax.tree_util.tree_leaves(args[1:3]))
        tok = compiled.out_info[2]
        assert (tok.shape, str(tok.dtype)) == ((s_n,), "int32")
        assert report[f"{program}_pool_copies"] == 0
        assert report.get(f"{program}_state_copies", 0) == 0


# ---- every product reads its weight where it lies ---------------------------

# a weight's slice written out in bfloat16, transposed, then relaid in front of
# the product that reads it: what both programs held for wq, wk and wv (and the
# linear mixer's lin_wz) before PR 48 — and the same product reading the
# stacked parameter itself, as it does since
PLANTED_COPIED = """
%fused_slice (param_0: f32[2,64,32]) -> bf16[32,64] {
  %param_0 = f32[2,64,32]{2,1,0:T(8,128)} parameter(0)
  %slice.1 = bf16[1,64,32]{2,1,0:T(8,128)(2,1)} slice(%param_0), slice={[1:2], [0:64], [0:32]}
  ROOT %bitcast.1 = bf16[32,64]{0,1:T(8,128)(2,1)S(1)} bitcast(%slice.1)
}

%fused_product (param_0.1: bf16[32,64], param_1: f32[16,64]) -> f32[16,4,8] {
  %param_0.1 = bf16[32,64]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1 = f32[16,64]{1,0:T(8,128)} parameter(1)
  ROOT %convolution.1 = f32[16,4,8]{2,1,0:T(8,128)} convolution(%param_1, %param_0.1), dim_labels=bf_io->bf
}

ENTRY %main (wq: f32[2,64,32], h: f32[16,64]) -> f32[16,4,8] {
  %wq = f32[2,64,32]{2,1,0:T(8,128)} parameter(0)
  %h = f32[16,64]{1,0:T(8,128)} parameter(1)
  %slice_bitcast_fusion = bf16[32,64]{0,1:T(8,128)(2,1)S(1)} fusion(%wq), kind=kLoop, calls=%fused_slice
  %copy.1 = bf16[32,64]{1,0:T(8,128)(2,1)S(1)} copy(%slice_bitcast_fusion)
  ROOT %fusion.1 = f32[16,4,8]{2,1,0:T(8,128)} fusion(%copy.1, %h), kind=kOutput, calls=%fused_product
}
"""
PLANTED_IN_PLACE = """
%fused_slice (param_0: f32[2,64,32]) -> bf16[64,32] {
  %param_0 = f32[2,64,32]{2,1,0:T(8,128)} parameter(0)
  %slice.1 = bf16[1,64,32]{2,1,0:T(8,128)(2,1)} slice(%param_0), slice={[1:2], [0:64], [0:32]}
  ROOT %bitcast.1 = bf16[64,32]{1,0:T(8,128)(2,1)} bitcast(%slice.1)
}

%fused_product (param_0.1: f32[2,64,32], param_1: f32[16,64]) -> f32[16,32] {
  %param_0.1 = f32[2,64,32]{2,1,0:T(8,128)} parameter(0)
  %param_1 = f32[16,64]{1,0:T(8,128)} parameter(1)
  %fusion.2 = bf16[64,32]{1,0:T(8,128)(2,1)} fusion(%param_0.1), kind=kLoop, calls=%fused_slice
  ROOT %convolution.1 = f32[16,32]{1,0:T(8,128)} convolution(%param_1, %fusion.2), dim_labels=bf_io->bf
}

ENTRY %main (wq: f32[2,64,32], h: f32[16,64]) -> f32[16,32] {
  %wq = f32[2,64,32]{2,1,0:T(8,128)} parameter(0)
  %h = f32[16,64]{1,0:T(8,128)} parameter(1)
  %copy-start = (f32[1,64,32]{2,1,0:T(8,128)S(1)}, f32[1,64,32]{2,1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%wq), cross_program_prefetch_index=0
  %copy-done = f32[1,64,32]{2,1,0:T(8,128)S(1)} copy-done(%copy-start)
  ROOT %fusion.1 = f32[16,32]{1,0:T(8,128)} fusion(%wq, %h), kind=kOutput, calls=%fused_product
}
"""


@pytest.mark.parametrize("text,want", [(PLANTED_COPIED, 2), (PLANTED_IN_PLACE, 0)],
                         ids=["copied", "in_place"])
def test_weight_copies_counts_a_weight_written_out_before_its_product(text, want):
    """``compile()``'s ``<program>_weight_copies`` on planted text, no compiler:
    the conversion fusion and the relayout ``copy`` count, one each; a slice
    fused INTO its product, the product itself and the compiler's prefetch of
    the parameter into faster memory do not, nor does any result of other
    dimensions (the activations, a weight that is not in the list)."""
    from tf_operator_tpu.serve.engine import weight_copies

    assert weight_copies(text, [(64, 32)]) == want
    assert weight_copies(text, [(64, 64), (16, 64)]) == 0


def _multiplied_in_place(text: str, leaf: str) -> bool:
    """Whether the stacked parameter ``params["layers"][leaf]`` ITSELF — or a
    view of it (a ``bitcast``: the one layer of a stack of one), or the
    compiler's own prefetch of it or of a layer's slice into faster memory,
    float32 in the parameter's layout still — is an operand of an executed
    fusion whose computation holds a convolution."""
    import re

    names = set(re.findall(rf"(%params__layers____{leaf}__[.\d]*) = ", text))
    views = re.findall(
        r"(%[\w.\-]+) = .*? (?:bitcast|(?:copy|slice)-(?:start|done))\((%[\w.\-]+)[,)]",
        text)
    for _ in range(3):  # parameter -> view -> <op>-start -> <op>-done
        names |= {name for name, operand in views if operand in names}
    for m in re.finditer(r" fusion\(([^)]*)\).*?calls=%([\w.\-]+)", text):
        if names & set(m.group(1).split(", ")):
            body = text.split(f"\n%{m.group(2)} (", 1)[1].split("\n}", 1)[0]
            if " convolution(" in body:
                return True
    return False


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("fixture,leaves", [
    ("serve1_engine", ("wq", "wk", "wv")),
    ("hybrid_engine", ("lin_wz", "wv")),
    ("jamba_engine", ("wq", "wk", "wv")),
])
def test_engine_products_read_their_weight_in_place_on_v5e(
        request, fixture, leaves, program):
    """A product whose result is split by heads at once (q, k, v; the linear
    mixer's gate z) reads its weight as ``wo`` and the MLP always have: the
    stacked f32 parameter is an operand of the fusion that multiplies it.
    Before PR 48 the compiler folded the head reshape into the product and
    fed it a transposed bfloat16 copy of the weight's slice, relaid by a
    ``copy``: 72 weight-sized results a program at the -serve1 cell's 12
    layers, 16 at the hybrid cell's 8, 1.2 GB written and 1.8 GB read a run
    that nothing needed."""
    engine, report = request.getfixturevalue(fixture)
    assert report[f"{program}_weight_copies"] == 0
    text = getattr(engine, f"_{program}").as_text()
    for leaf in leaves + ("wo", "w_up"):
        assert _multiplied_in_place(text, leaf), leaf


def test_kv_heads_per_step_at_thirty_heads():
    """30 KV heads (divisors 1, 2, 3, 5, 6, 10, 15, 30; no power of two above
    2): every head of a one-row decode tile in one step, 10 a step for a
    256-row chunk tile (15 would overrun the 12 MiB budget), and a tile no
    divisor serves goes to the reference."""
    assert fa._kv_heads_per_step(30, 1, 128, 64, 4) == 30
    assert fa._kv_heads_per_step(30, 256, 128, 64, 4) == 10
    assert fa._tile_vmem_bytes(15 * 256, 128) + 4 * 15 * 64 * 128 * 4 > fa._TILE_VMEM_BUDGET
    assert fa._kv_heads_per_step(30, 256, 128, 64, 4, at_most=4) == 3
    assert fa._kv_heads_per_step(30, 1 << 14, 128, 64, 4) == 0


@pytest.mark.parametrize("rows", [1, 256], ids=["decode", "chunk256"])
def test_paged_kernel_compiles_at_thirty_kv_heads_for_v5e(one_chip, no_cache, rows):
    """One key a query head (g = 1): a decode tile is ONE row a head."""
    h, d, page, n_pages, p = 30, 128, 64, 1025, 64
    s_n = 16 if rows == 1 else 1

    def a(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = a((2, n_pages, h, page, d), jnp.float32)
    names = _kernel_names(
        lambda q, k, v, pt, sl, qs: fa._paged_call(q, k, v, 1, pt, sl, qs, False),
        a((s_n, rows, h, d), jnp.float32), pool, pool, a((s_n, p), jnp.int32),
        a((s_n,), jnp.int32), a((s_n,), jnp.int32),
    )
    assert names == ["paged_attention"]


@pytest.mark.parametrize("form", ["chunk", "step"])
def test_gated_delta_kernels_compile_for_v5e(one_chip, no_cache, form):
    """The two linear-attention kernels alone at the published head sizes:
    30 heads, keys 96 and values 192 wide (neither a multiple of 128)."""
    from unittest import mock

    gd = importlib.import_module("tf_operator_tpu.ops.gated_delta")
    H, dk, dv = 30, 96, 192

    def a(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        if form == "chunk":
            names = _kernel_names(
                gd.gated_delta_chunk, a((256, H, dk)), a((256, H, dk)),
                a((256, H, dv)), a((256, H)), a((256, H)), a((H, dk, dv)))
        else:
            names = _kernel_names(
                lambda q, k, v, al, b, st, sl: gd.gated_delta_step(
                    q, k, v, al, b, st, layer=4, slots=sl),
                a((16, H, dk)), a((16, H, dk)), a((16, H, dv)), a((16, H)),
                a((16, H)), a((6, 17, H, dk, dv)), a((16,), jnp.int32))
    assert names == ["gdn_chunk_fwd" if form == "chunk" else "gdn_step"]


# ---- the Jamba engine: Mamba-1 layers beside multi-query attention ----------

JAMBA1 = dict(page_size=64, pool_pages=2560, max_slots=64, prefill_chunk=256)


@pytest.mark.parametrize("form", ["chunk", "step"])
def test_selective_scan_kernels_compile_for_v5e(one_chip, no_cache, form):
    """The two Mamba-1 kernels alone at the published sizes: 5,120 channels,
    a state of 16 a channel, a 256-token chunk, 64 slots of a 26-layer store."""
    from unittest import mock

    ss = importlib.import_module("tf_operator_tpu.ops.selective_scan")
    I, N = 5120, 16

    def a(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        if form == "chunk":
            names = _kernel_names(
                ss.selective_scan_chunk, a((256, I)), a((256, I)), a((256, N)),
                a((256, N)), a((I, N)), a((I,)), a((N, I)))
        else:
            names = _kernel_names(
                lambda u, d, B, C, A, D, st, sl: ss.selective_scan_step(
                    u, d, B, C, A, D, st, layer=4, slots=sl),
                a((64, I)), a((64, I)), a((64, N)), a((64, N)), a((I, N)), a((I,)),
                a((26, 65, 1, N, I)), a((64,), jnp.int32))
    assert names == ["ssm_chunk_fwd" if form == "chunk" else "ssm_step"]


@pytest.fixture(scope="module")
def jamba_engine(one_chip):
    """``ServeEngine`` for preset ``ai21-jamba2-3b`` at the benchmark's
    -serve1 shapes (published widths, the whole vocabulary, pool ``[2 -> 1,
    2561, 1, 64, 128]``, state store ``[13, 65, 1, 16, 5120]``, 40 page slots
    a sequence, 256-token chunks, 64 slots) with ONE 14-layer period of the
    cell's two, from abstract parameters, compiled for the described chip by
    the engine's own ``compile()``."""
    from unittest import mock

    from tf_operator_tpu.models.transformer import init_transformer, preset
    from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine

    cfg = preset("ai21-jamba2-3b", n_layers=14, max_seq=2560)
    shapes = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    engine = ServeEngine(cfg, params, ServeConfig(**JAMBA1))
    with _cache_off(), mock.patch.object(jax, "default_backend",
                                         lambda: "tpu"):
        report = engine.compile()
    return engine, report


@pytest.mark.parametrize("program,kernels", [
    ("decode", {"ssm_step": 13, "paged_attention": 1}),
    ("prefill", {"ssm_chunk_fwd": 13, "ssm_step": 13, "paged_attention": 2})])
def test_jamba_engine_programs_run_both_kernels_and_keep_both_stores_in_place_on_v5e(
        jamba_engine, program, kernels):
    """Both recurrent kernels by name a Mamba layer, the attention layer
    THROUGH the paged kernel — at 20 query heads over one key/value head a
    256-token chunk is 5,120 rows and goes as two tiles of 128 — and neither
    the page pool nor the state store copied, sliced by layer or relaid."""
    engine, report = jamba_engine
    assert engine._pool_shape() == (1, 2561, 1, 64, 128)
    assert engine.store.state_shape == (13, 65, 1, 16, 5120)
    assert engine.store.conv_shape == (13, 65, 3, 5120)
    assert report[f"{program}_kernels"] == kernels
    assert report[f"{program}_tpu_custom_calls"] == sum(kernels.values())
    assert report[f"{program}_paged_reference_calls"] == 0
    assert report[f"{program}_pool_copies"] == 0
    assert report[f"{program}_state_copies"] == 0
    assert "input_output_alias" in getattr(engine, f"_{program}").as_text()
    # grid steps: the attention layer walks 40 page slots for 64 slots (a
    # 20-row tile each) or for 2 tiles of 128 positions; a Mamba layer steps
    # 64 slots, or 10 channel blocks of 512 over one block of 256 positions
    decode, chunk = 64 * 40 + 13 * 64, 2 * 40 + 13 * 10
    assert report[f"{program}_attn_grid_steps"] == {
        "decode": decode, "prefill": chunk + decode}[program]


# ---- grouped matmul: fwd, dx, dw, with and without the fused row scale ----

GMM_WIDTHS = {
    # E, k, n (the up/gate projection; "down" swaps k and n)
    "moe-small-up": (8, 768, 3072),
    "moe-small-down": (8, 3072, 768),
    # the widths whose dx (21 MB) and dw (28 MB) tiles overran the 16 MB
    # scoped-VMEM default before _plan_cols counted every resident tile
    "mixtral-8x7b-up": (8, 4096, 14336),
    "mixtral-8x7b-down": (8, 14336, 4096),
    # one chip's 16 held experts of 64, narrow (768) ReGLU experts
    "smallthinker-share-up": (16, 2560, 768),
    "smallthinker-share-down": (16, 768, 2560),
}
GMM_NAMES = {"fwd": "gmm_fwd", "dx": "gmm_dx", "dw": "gmm_dw"}


@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "row_scale"])
@pytest.mark.parametrize("part", ["fwd", "dx", "dw"])
@pytest.mark.parametrize("widths", sorted(GMM_WIDTHS))
def test_gmm_compiles_for_v5e(one_chip, no_cache, widths, part, scaled):
    E, k, n = GMM_WIDTHS[widths]
    rows, br = 4096, 256

    def a(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x, w = a((rows, k), jnp.bfloat16), a((E, k, n), jnp.bfloat16)
    be, sc = a((rows // br,), jnp.int32), a((rows,), jnp.float32)

    def y(x, w, be, sc):
        return gm.gmm(x, w, be, row_scale=sc if scaled else None,
                      block_rows=br)

    def total(x, w, be, sc):
        return jnp.sum(y(x, w, be, sc).astype(jnp.float32))

    fn = {
        "fwd": y,
        "dx": jax.grad(total, argnums=0),
        "dw": jax.grad(total, argnums=1),
    }[part]
    names = _kernel_names(fn, x, w, be, sc)
    # every product under its own name in a trace (dx of a scaled forward
    # is the unscaled transposed product: no suffix)
    want = GMM_NAMES[part] + ("_scaled" if scaled and part != "dx" else "")
    assert any(want in n for n in names), names


def test_gmm_refuses_what_it_cannot_tile_by_name():
    """No contraction tiling: a [block_rows, k] row tile that cannot fit
    even the raised VMEM limit is refused by gmm's own ValueError, not by
    a Mosaic allocation failure at compile time."""
    x = jax.ShapeDtypeStruct((512, 131072), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((2, 131072, 256), jnp.bfloat16)
    be = jax.ShapeDtypeStruct((2,), jnp.int32)
    with pytest.raises(ValueError, match="resident in VMEM"):
        jax.eval_shape(lambda x, w, be: gm.gmm(x, w, be, interpret=True),
                       x, w, be)


# ---- the expert walk's combine: moe_combine, and no scatter beside it -----

COMBINE_SHAPES = {
    # one segment of a share cell's walk: its rows, d, the step's tokens
    "smallthinker-share": (53248, 2560, 32768),
    "joyai-share": (12288, 2048, 16384),
}


def _f32_scatters(text: str, tokens: int, d: int) -> list:
    """Every ``scatter`` of a compiled program's text, fused or not, whose
    result is the float32 [tokens, d] carry."""
    import re

    return re.findall(rf"= f32\[{tokens},{d}\]\S* scatter\(", text)


def _combine_args(one_chip, shape):
    n_rows, d, tokens = COMBINE_SHAPES[shape]

    def a(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    return (a((tokens, d), jnp.float32), a((n_rows, d), jnp.bfloat16),
            a((n_rows,), jnp.int32), a((n_rows,), jnp.bool_))


@pytest.mark.parametrize("part", ["fwd", "two-operands", "vjp"])
@pytest.mark.parametrize("shape", sorted(COMBINE_SHAPES))
def test_moe_combine_compiles_for_v5e_and_leaves_no_scatter(
        one_chip, no_cache, shape, part):
    """``combine_rows`` at both share cells' widths — the forward's one
    bfloat16 operand, the walk's backward form (``dx_g``, ``dx_u`` as two)
    and the op's own ``vjp`` (what the whole-layer case differentiates):
    the kernel is there under the name the trace and ``step_kernels`` read,
    and nothing scatters onto the float32 [T, d] carry."""
    acc, rows, tok, valid = _combine_args(one_chip, shape)
    n_rows, d, tokens = COMBINE_SHAPES[shape]

    def combine(acc, rows, tok, valid):
        both = (rows, rows) if part == "two-operands" else rows
        return gm.combine_rows(acc, both, tok, valid, groups=16, block_rows=256)

    def with_vjp(acc, rows, tok, valid):
        out, back = jax.vjp(lambda a, r: combine(a, r, tok, valid), acc, rows)
        return out, back(out)

    fn = with_vjp if part == "vjp" else combine
    text = jax.jit(fn, donate_argnums=0).lower(
        acc, rows, tok, valid).compile().as_text()
    assert "moe_combine" in _kernel_names_in(text)
    assert not _f32_scatters(text, tokens, d)


def test_f32_scatters_finds_the_combine_the_walk_ran_before(one_chip, no_cache):
    """The reading the cases above and the share cells' steps rely on, on
    the form PR 49 replaced."""
    acc, rows, tok, valid = _combine_args(one_chip, "joyai-share")

    def scatter(acc, rows, tok, valid):
        return acc.at[tok].add(
            jnp.where(valid[:, None], rows, 0).astype(jnp.float32))

    text = jax.jit(scatter, donate_argnums=0).lower(
        acc, rows, tok, valid).compile().as_text()
    assert len(_f32_scatters(text, 16384, 2048)) == 1
    assert "moe_combine" not in _kernel_names_in(text)
