"""The train step names its own sections: ``sec_*`` scopes in the model,
``parallel.collectives.compiled_sections`` over the compiled step,
``Trainer``'s ``train.program`` span, and ``benchmarks/step_sections.py``
with its nine readers on made-up planes (``tests/test_span_reduce.py``'s
style)."""

import contextlib
import glob
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks import run, span_reduce as sr, step_sections as ss, trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1e-6

DENSE = dict(preset="tiny", n_layers=2, attn="flash", fused_xent=True)
# the two share cells' shapes at toy widths (tests/test_smallthinker_cell.py,
# tests/test_joyai_cell.py): gmm-dispatched experts behind a pre-attention
# router over a layer pattern; latent attention, a dense lead, a shared expert
# and the prediction module under a names remat policy
WORKLOADS = {
    "dense-full": dict(DENSE, remat="full"),
    "dense-none": dict(DENSE, remat="none"),
    "gmm-moe": dict(
        preset="smallthinker-21ba3b", vocab=256, d_model=64, n_heads=4,
        n_kv_heads=2, d_head=32, d_ff=32, n_experts=8, experts_held=2,
        max_seq=64, n_layers=4, attn="flash", remat="save_mid", fused_xent=True,
        layer_pattern=[[0, False], [16, True], [16, True], [16, True]]),
    "latent-mtp": dict(
        preset="joyai-llm-flash", vocab=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4, d_ff=32, d_ff_dense=96, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, n_experts=8, moe_top_k=2,
        experts_held=2, max_seq=64, attn="flash", remat="save_mid",
        fused_xent=True),
}
EVERY = {"optimizer.optimizer", "embed.fwd", "embed.bwd", "head_ce.fwd",
         "head_ce.bwd", "attn_proj.fwd", "attn_proj.bwd", "attn_core.fwd",
         "attn_core.bwd", "mlp.fwd", "mlp.bwd", "stack.fwd", "stack.bwd"}
# no "moe_experts.replay": the expert walk's residuals are its inputs, its
# backward computes gate and up again itself (``bwd``), and the forward walk
# that jax replays under ``save_mid`` is dead code (PR 36)
MOE = {"router.fwd", "moe_dispatch.fwd", "moe_dispatch.bwd", "moe_dispatch.replay",
       "moe_experts.fwd", "moe_experts.bwd"}
EXPECTED = {
    "dense-full": EVERY | {"mlp.replay", "attn_proj.replay", "attn_core.replay"},
    "dense-none": EVERY,
    "gmm-moe": EVERY | MOE | {"attn_proj.replay"},
    "latent-mtp": EVERY | MOE | {"attn_proj.replay", "mlp.replay"},
}
# by COUNT, on the CPU backend: layout copies and broadcasts of constants
# carry no metadata there (the chip's share is by seconds, PERF.md §5)
NONE_SHARE_LIMIT = 0.30


def _trainer(name):
    import jax

    from tf_operator_tpu.models import transformer as tf
    from tf_operator_tpu.parallel.mesh import build_mesh
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

    cfg = tf.preset_from_workload(dict(WORKLOADS[name]))
    mesh = build_mesh({"fsdp": 1}, devices=jax.devices()[:1])
    counted = bool(tf.moe_counter_names(cfg, mesh))
    trainer = Trainer(
        mesh,
        loss_fn=(lambda p, t, extra: tf.lm_loss_with_counters(
            p, t, cfg, mesh=mesh, extra=extra)) if counted
        else (lambda p, t, extra: tf.lm_loss(p, t, cfg, mesh=mesh)),
        init_fn=(lambda k: (tf.init_transformer(k, cfg), tf.zero_moe_counters(cfg)))
        if counted else (lambda k: tf.init_transformer(k, cfg)),
        logical_axes=tf.transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3))
    assert trainer.step_sections is None and trainer.step_sections_parse_s is None
    trainer.text = trainer.compile_step(
        jax.ShapeDtypeStruct((2, 64), "int32")).as_text()
    return trainer


def _two_losses(trainer):
    import jax
    import numpy as np

    state = trainer.init(jax.random.PRNGKey(3))
    tokens = np.random.default_rng(5).integers(0, 256, (2, 2, 64), dtype=np.int32)
    out = []
    for batch in tokens:
        state, m = trainer.step(state, batch)
        out.append(np.asarray(m["loss"]).tobytes())
    return out


@pytest.fixture(scope="module", params=sorted(WORKLOADS))
def compiled(request):
    return request.param, _trainer(request.param)


def test_every_expected_section_and_phase_is_in_the_compiled_step(compiled):
    name, trainer = compiled
    sections = trainer.step_sections
    missing = EXPECTED[name] - set(sections)
    assert not missing, (missing, sorted(sections))
    if name == "dense-none":  # nothing rematerialised: nothing replayed
        assert not [k for k in sections if k.endswith(".replay")], sorted(sections)
    from tf_operator_tpu.parallel.collectives import SECTION_PHASES

    for key in sections:
        section, phase = key.rsplit(".", 1)
        assert phase in SECTION_PHASES and (phase == "optimizer") == (section == "optimizer")
    assert 0 < trainer.step_sections_parse_s < 0.5


def test_no_instruction_is_in_two_keys_and_few_are_unnamed(compiled):
    from tf_operator_tpu.parallel.collectives import sections_summary

    _, trainer = compiled
    names = [n for v in trainer.step_sections.values() for n in v]
    assert len(names) == len(set(names))
    counts = sections_summary(trainer.step_sections)
    assert sum(counts.values()) == len(names)
    assert counts.get("none", 0) / len(names) < NONE_SHARE_LIMIT, counts


def test_the_expert_walks_backward_is_filed_as_backward_and_named(compiled):
    """The expert walk brings its own backward (``parallel.moe._expert_walk``:
    a loop inside the layer's): its ops — the segment's gathers and
    scatter-adds, the kernels and what lies between them — file under
    ``moe_dispatch.bwd`` / ``moe_experts.bwd``, nothing of either walk is
    left without a section, and no expert op is replayed."""
    import re

    from tf_operator_tpu.parallel.collectives import _computations

    name, trainer = compiled
    if name != "gmm-moe":  # latent-mtp's toy share is one segment: no loop
        pytest.skip("no expert walk")
    key_of = {n: k for k, v in trainer.step_sections.items() for n in v}
    comps, _ = _computations(trainer.text)
    walk = re.compile(r'op_name="[^"]*/while/body/sec_moe_(dispatch|experts)/')
    # not the entry: a constant hoisted out of a walk keeps the walk's path
    bodies = {c: lines for c, lines in comps.items()
              if not c.startswith("main")
              and any(walk.search(line) for line in lines)}
    assert bodies
    keys, backward = set(), set()
    for lines in bodies.values():
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
            if not m or m.group(1) not in key_of:
                continue
            # the CPU's copy insertion: the [T, d] carry on its way into the
            # interpreted ``moe_combine``'s own loop, with no metadata at all
            if " copy(" in line and "metadata=" not in line:
                continue
            key = key_of[m.group(1)]
            keys.add(key)
            w = walk.search(line)
            if w and "transpose(" in line:
                backward.add((w.group(1), key))
    assert not [k for k in keys if k.startswith("none.")], sorted(keys)
    assert backward == {("dispatch", "moe_dispatch.bwd"),
                        ("experts", "moe_experts.bwd")}, backward
    assert "moe_experts.replay" not in trainer.step_sections


def test_the_scopes_change_metadata_only(compiled, monkeypatch):
    """The first two losses, bit for bit, of the same step traced with
    ``jax.named_scope`` a null context: no ``sec_*`` reaches the compiled
    text then, and nothing else differs."""
    import jax

    name, trainer = compiled
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _trainer(name)
    assert {k.split(".")[0] for k in bare.step_sections} == {"none"}
    assert _two_losses(bare) == _two_losses(trainer)


PATH = "jit(_step_body)/{}/while/body/closed_call/{}dot_general"


@pytest.mark.parametrize("line,key", [
    ('%fusion.3 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.1, metadata={{op_name="'
     + PATH.format("jvp(sec_stack)", "sec_mlp/") + '"}}', "mlp.fwd"),
    ('%fusion.4 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.1, metadata={{op_name="'
     + PATH.format("transpose(jvp(sec_stack))", "checkpoint/sec_moe_dispatch/sec_router/")
     + '"}}', "router.bwd"),  # the innermost scope
    ('%fusion.5 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.1, metadata={{op_name="'
     + PATH.format("transpose(jvp(sec_stack))", "checkpoint/rematted_computation/sec_mlp/")
     + '"}}', "mlp.replay"),
    # the compiler's own clone of a FORWARD instruction is a replay too
    ('%fusion.382.remat2 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.1, metadata={{op_name="'
     + PATH.format("jvp(sec_stack)", "sec_moe_dispatch/") + '"}}', "moe_dispatch.replay"),
    ('%copy.218.remat = f32[8]{{0}} copy(%a)', "none.replay"),
    ('%fusion.6 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.1, metadata={{op_name='
     '"jit(_step_body)/transpose(jvp(sec_embed))/scatter-add"}}', "embed.bwd"),
    ('%fusion.7 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.1, metadata={{op_name='
     '"jit(_step_body)/sec_optimizer/mul"}}', "optimizer.optimizer"),
    ('%fusion.8 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.1, metadata={{op_name='
     '"jit(_step_body)/sec_attn_core/jit(tril)/select_n"}}', "attn_core.other"),
    ('%all-gather-start.2 = (f32[8]{{0}}, f32[32]{{0}}) all-gather-start(%a), metadata={{op_name='
     '"jit(_step_body)/jvp(sec_head_ce)/fused_xent/all_gather"}}', "head_ce.fwd"),
    ('%copy-start.9 = (f32[8]{{0}}, f32[8]{{0}}, u32[]) copy-start(%a)', "none.other"),
    # a fusion the compiler left bare takes its root's path
    ('%fusion.9 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.2', "attn_proj.fwd"),
])
def test_compiled_sections_files_an_instruction_by_its_op_name(line, key):
    from tf_operator_tpu.parallel.collectives import compiled_sections

    fused = {
        "%fc.1": ['  ROOT %inner.1 = f32[8]{0} add(%p, %p), metadata={op_name='
                  '"jit(_step_body)/sec_mlp/add"}'],
        "%fc.2": ['  %inner.2 = f32[8]{0} add(%p, %p), metadata={op_name='
                  '"jit(_step_body)/jvp(sec_attn_proj)/add"}',
                  "  ROOT %inner.3 = f32[8]{0} bitcast(%inner.2)"]}
    text = "\n".join(
        ["HloModule jit__step_body, is_scheduled=true", ""]
        + [ln for comp, body in fused.items() if comp + "," in line + ","
           for ln in [comp + " (p: f32[8]) -> f32[8] {"] + body + ["}", ""]]
        + ["%body (t: (f32[8])) -> (f32[8]) {",
           "  %gte = f32[8]{0} get-tuple-element(%t), index=0",
           "  " + line.format(),
           "  ROOT %out = (f32[8]{0}) tuple(%gte)",
           "}", "",
           "ENTRY %main (a: f32[8]) -> f32[8] {",
           "  %a = f32[8]{0} parameter(0)",
           "  %while.1 = (f32[8]{0}) while(%a), condition=%cond, body=%body",
           "  ROOT %r = f32[8]{0} get-tuple-element(%while.1), index=0",
           "}", ""])
    name = line.split(" = ")[0].lstrip("%")
    # a fusion's body and the containers are not instructions of their own
    assert compiled_sections(text) == {key: [name]}


def test_an_instruction_without_a_scope_is_filed_with_its_nearest_reader():
    """The compiler's own prefetches carry no metadata: the pair works for
    the fusion that reads it, through a bitcast; its own ``.remat`` mark
    still says replay; what nothing here reads stays ``none``. A loop's
    operand (a cast hoisted out of the scan) is read by what reads THAT
    element of the carry inside the body, not by what reads the loop."""
    from tf_operator_tpu.parallel.collectives import compiled_sections

    meta = ', metadata={op_name="jit(_step_body)/transpose(jvp(sec_stack))/while/body/%s"}'
    text = "\n".join([
        "HloModule jit__step_body, is_scheduled=true", "",
        "%fc.1 (p: f32[8]) -> f32[8] {",
        "  ROOT %inner.1 = f32[8]{0} add(%p, %p)",
        "}", "",
        "%body (t: (f32[8], f32[8])) -> (f32[8], f32[8]) {",
        "  %t = (f32[8]{0}, f32[8]{0}) parameter(0)",
        "  %w = f32[8]{0} get-tuple-element(%t), index=0",
        "  %v = f32[8]{0} get-tuple-element(%t), index=1",
        "  %copy-start.4 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%w)",
        "  %copy-done.4 = f32[8]{0} copy-done(%copy-start.4)",
        "  %bitcast.2 = f32[8]{0} bitcast(%copy-done.4)",
        "  %fusion.5 = f32[8]{0} fusion(%bitcast.2), kind=kLoop, calls=%fc.1"
        + meta % "closed_call/checkpoint/sec_mlp/dot_general",
        "  %copy.9.remat = f32[8]{0} copy(%v)",
        "  %fusion.6 = f32[8]{0} fusion(%copy.9.remat), kind=kLoop, calls=%fc.1"
        + meta % "closed_call/checkpoint/sec_attn_proj/mul",
        "  %slice-start.7 = (f32[8]{0}, f32[4]{0}, u32[]) slice-start(%w), slice={[0:4]}",
        "  %copy.8 = f32[8]{0} copy(%w)" + meta % "dynamic_slice",
        "  ROOT %out = (f32[8]{0}, f32[8]{0}) tuple(%fusion.5, %copy.8)",
        "}", "",
        "ENTRY %main (a: f32[8]) -> f32[8] {",
        "  %a = f32[8]{0} parameter(0)",
        "  %convert.1 = f32[8]{0} convert(%a)",
        "  %broadcast.2 = f32[8]{0} broadcast(%a), dimensions={0}",
        "  %tuple.3 = (f32[8]{0}, f32[8]{0}) tuple(%convert.1, %broadcast.2)",
        "  %while.4 = (f32[8]{0}, f32[8]{0}) while(%tuple.3), condition=%cond, body=%body",
        "  %gte.5 = f32[8]{0} get-tuple-element(%while.4), index=0",
        "  ROOT %fusion.10 = f32[8]{0} fusion(%gte.5), kind=kLoop, calls=%fc.1, "
        'metadata={op_name="jit(_step_body)/sec_optimizer/mul"}',
        "}", ""])
    assert compiled_sections(text) == {
        "attn_proj.bwd": ["fusion.6", "broadcast.2"], "attn_proj.replay": ["copy.9.remat"],
        "mlp.bwd": ["copy-start.4", "copy-done.4", "fusion.5", "convert.1"],
        "none.other": ["slice-start.7"], "optimizer.optimizer": ["fusion.10"],
        "stack.bwd": ["copy.8"]}


# ---- the span ---------------------------------------------------------------


def _program_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True)
    return [dict(e.stats) for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == ss.SPAN]


def test_train_program_is_written_once_a_session_and_never_outside(tmp_path, monkeypatch):
    import jax

    real = jax.profiler.TraceAnnotation
    made, reads = [], []

    class Spy(real):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

        @staticmethod
        def is_enabled():
            reads.append(1)
            return real.is_enabled()

    trainer = _trainer("dense-none")
    state = trainer.init(jax.random.PRNGKey(0))
    tokens = jax.numpy.zeros((2, 64), "int32")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)

    def steps(n):
        nonlocal state
        for _ in range(n):
            state, m = trainer.step(state, tokens)
        jax.block_until_ready(m["loss"])

    steps(3)  # no session: one read of is_enabled() a call, nothing written
    assert made == ["train.step"] * 3 and len(reads) == 3
    want = dict({k: " ".join(v) for k, v in trainer.step_sections.items()},
                program="jit__step_body")
    for session in ("first", "second"):
        del made[:]
        with jax.profiler.trace(str(tmp_path / session)):
            steps(3)
        assert made == [ss.SPAN] + ["train.step"] * 3
        (written,) = _program_spans(tmp_path / session)
        assert written == want  # through the xplane and ProfileData, unharmed
        del made[:]
        steps(2)
        assert made == ["train.step"] * 2


# ---- the reader on made-up planes ----------------------------------------------

OP = "%{name} = f32[8]{{0}} fusion(), kind=kLoop"
KERNEL = ('%{name} = f32[4]{{0}} custom-call(%a), custom_call_target="tpu_custom_call"')
ATTRS = {
    "program": "jit__step_body",
    "mlp.fwd": "fusion.1 fusion.2", "mlp.bwd": "fusion.3", "mlp.replay": "fusion.4 fusion.5.remat",
    "attn_core.fwd": "flash_fwd.6", "attn_proj.replay": "fusion.7",
    "embed.bwd": "fusion.8", "head_ce.fwd": "fusion.9",
    "router.fwd": "fusion.10", "moe_dispatch.replay": "fusion.11.remat",
    "optimizer.optimizer": "fusion.12", "stack.bwd": "fusion.13",
    "none.other": "copy-start.1",
}
# two steps; microseconds of each op a step
OPS_US = {"fusion.1": 30, "fusion.2": 10, "fusion.3": 50, "fusion.4": 20,
          "fusion.5.remat": 6, "flash_fwd.6": 40, "fusion.7": 8, "fusion.8": 12,
          "fusion.9": 25, "fusion.10": 5, "fusion.11.remat": 9, "fusion.12": 35,
          "fusion.13": 4, "copy-start.1": 2, "fusion.99": 3}  # .99: no map names it


def training_planes(span=True, devices=1):
    planes = []
    for d in range(devices):
        events, t = [], 100
        for _ in range(2):
            start = t
            for name, us in OPS_US.items():
                text = (KERNEL if name.startswith("flash") else OP).format(name=name)
                events.append(NS(name=text, start_ns=t * 1e3, duration_ns=us * 1e3, stats=[]))
                t += us
            events.append(NS(name="%while.3 = (f32[8]{0}) while(%t), body=%b",
                             start_ns=start * 1e3, duration_ns=(t - start) * 1e3, stats=[]))
            t += 41
        modules = NS(name="XLA Modules", events=[
            NS(name="jit__step_body(7)", start_ns=100e3, duration_ns=259e3, stats=[]),
            NS(name="jit__step_body(7)", start_ns=400e3, duration_ns=259e3, stats=[])])
        planes.append(NS(name=f"/device:TPU:{d}",
                         lines=[NS(name="XLA Ops", events=events), modules]))
    host = [NS(name="bench.window", start_ns=0, duration_ns=1000e3, stats=[]),
            NS(name="train.step", start_ns=20e3, duration_ns=30e3, stats=[("call", 4)]),
            NS(name="train.step", start_ns=380e3, duration_ns=30e3, stats=[("call", 5)])]
    if span:
        host.insert(1, NS(name=ss.SPAN, start_ns=10e3, duration_ns=0,
                          stats=list(ATTRS.items())))
    return planes + [NS(name="/host:CPU", lines=[NS(name="python3", events=host)])]


def record(planes, chips=1):
    said = []
    return NS(trace=tr.reduce_planes(planes, chips), spans=sr.reduce_planes(planes, chips),
              samples={"step_s": [0.30, 0.31, 0.29],
                       "traced": {"steps": 2, "step_s": [0.30, 0.32]}},
              chips=chips, say=said.append, said=said)


READERS = {  # microseconds a step, from OPS_US and ATTRS
    "step_fwd_ms": 30 + 10 + 40 + 25 + 5,
    "step_bwd_ms": 50 + 12 + 4,
    "step_replay_ms": 20 + 6 + 8 + 9,
    "step_optimizer_ms": 35,
    "step_attn_proj_ms": 8,
    "step_mlp_ms": 30 + 10 + 50 + 20 + 6,
    "step_head_ce_ms": 12 + 25,
    "step_moe_dispatch_ms": 5 + 9,
}


def _read(name, rec):
    home = os.path.join(REPO, "benchmarks")
    return run._load_py(run.reader_path(home, name), "metric_" + name).read(rec)


@pytest.mark.parametrize("chips", [1, 2])
def test_the_table_adds_up_to_every_op_second_and_the_readers_read_it(chips):
    rec = record(training_planes(devices=chips), chips)
    for name, us in READERS.items():
        assert _read(name, rec) == pytest.approx(us * 1e-3), name
    total = sum(OPS_US.values())
    assert _read("step_unattributed_share", rec) == pytest.approx(100.0 * (2 + 3) / total)
    table = ss.for_run(rec)
    assert table is rec.step_sections and table.steps == 2
    assert table.total_ms == pytest.approx(total * 1e-3)   # the containers are not ops
    assert table.total_ms == pytest.approx(
        1e3 * sum(d.ops[k] for d in rec.trace.devices for k in d.ops) / 2 / chips)
    assert table.busy_ms == pytest.approx(total * 1e-3)    # nothing overlaps here
    assert table.ms(("none",)) == pytest.approx(5e-3)
    assert table.cells[("mlp", "replay")] == pytest.approx(26e-3)  # a .remat name among them
    phases = sum(table.ms(phase=p) for p in ("fwd", "bwd", "replay", "optimizer", "other"))
    assert phases == pytest.approx(table.total_ms)
    # ONE note, with the longest ops and their sections; and the tracing cost
    notes = [s for s in rec.said if s.startswith("note step_sections_ms")]
    assert len(notes) == 1
    described = table.describe()
    assert described["top_ops"][0] == ["fusion.3 kLoop f32[8]", pytest.approx(50e-3), "mlp.bwd"]
    assert described["top_none"][0][2] == ss.UNNAMED
    assert described["program"] == "jit__step_body"
    assert described["program_attr_bytes"] == sum(len(v) for v in ATTRS.values())
    assert json.dumps(described)
    assert any("tracing_cost step_s" in s and "300.0" in s and "310.0" in s for s in rec.said)
    # by hand on a capture, whose planes can be walked once
    by_hand = ss.reduce_trace(iter(training_planes(devices=chips)))
    assert (by_hand.cells, by_hand.steps, by_hand.ops) == (table.cells, 2, table.ops)


def test_a_trace_without_the_span_reads_none_in_every_reader():
    rec = record(training_planes(span=False))
    for name in list(READERS) + ["step_unattributed_share"]:
        assert _read(name, rec) is None, name
    untraced = NS(trace=None, samples={}, chips=1, say=print)
    assert _read("step_fwd_ms", untraced) is None
    assert not [s for s in rec.said if "step_sections_ms" in s]
    assert ss.reduce_trace(iter(training_planes(span=False))) is None


def test_the_nine_entries_are_declared_and_read_by_files_of_their_own():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    train = [c["name"] for c in bench["workloads"] if "train" in c["name"]]
    for name in list(READERS) + ["step_unattributed_share"]:
        m = declared[name]
        assert (m["source"], m["moves"], m["better"]) == (
            "device_trace", "train_tokens_per_s", "lower")
        assert os.path.exists(os.path.join(REPO, "benchmarks", "metrics", name + ".py"))
        moe = name == "step_moe_dispatch_ms"
        assert sorted(m["workloads"]) == sorted(
            [c for c in train if "share" in c] if moe else train)
    names = [m["name"] for m in bench["per_layer"]]  # later PRs append after them
    first = names.index(next(iter(READERS)))
    assert names[first:first + 9] == list(READERS) + ["step_unattributed_share"]
