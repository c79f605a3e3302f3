"""Continuous-batching decode engine (Orca OSDI '22 iteration-level
scheduling + vLLM SOSP '23 paged KV) over the models/transformer.py LM.

The unit of scheduling is ONE engine step, not one request: at every
step boundary the engine admits newly-arrived requests into free batch
slots, then either pushes one prefill chunk for each still-prefilling
slot — each chunk carrying, in the same program run, the next token of
every slot that is decoding as the run goes out — or, with nobody
prefilling, runs one batched decode step; finished sequences are
evicted immediately (pages back to the free list the same step — the
next admission reuses them copy-free). There is no drain-the-batch
barrier anywhere. A program run streams every weight once whatever rows
it carries, so a busy step (a chunk AND decoding slots) pays for the
weights once, not twice.

The loop keeps ONE step ahead of the device (``run`` says how): the
token every slot decodes from is an ``int32[max_slots]`` array that
stays on the device — each program run takes the array the run before
returned and returns the next (the decode rows write their active slots,
a chunk its own slot), undonated — and the host reads the same arrays
one step late.

Two compiled functions, both fixed-shape; which one a run is follows
from one thing the loop sees, whether a slot is prefilling:

- the DECODE step (``decode_step``), for a step in which nobody
  prefills: every slot advances one token. Each layer computes
  single-position q/k/v, rotates at the token's absolute position
  (rope_at_positions), stores k/v into the slot's current page row
  (kvcache.write_rows), and attends through the page table
  (ops.flash_attention_decode — kernel on TPU, gather reference
  off-TPU). Inactive slots steer their writes to the pool's trash page
  and mask attention with seq_len 0.

- the run that CARRIES A CHUNK (``prefill_chunk``): ``prefill_chunk``
  prompt tokens of ONE sequence followed by the decode step's row for
  every slot, C + ``max_slots`` rows through the shared body ONCE, so
  every product with a weight, the MLP and the norms see both kinds of
  row, and the head runs once (over the decode rows and the chunk's last
  valid row). Inside a layer the two kinds part only where they must:
  k/v of ALL rows are written first, then the SAME paged attention runs
  twice — the chunk's C positions as ONE query tile of its sequence
  (``q_start`` = the chunk's first position, a causal limit per row: the
  sequence's pages are walked once for the whole chunk, and prefill has
  no attention implementation of its own), and the slots' one-row tiles
  over the page table as in the decode step. A slot prefills or decodes,
  never both, so the two kinds write disjoint pages and disjoint state
  slots. The last chunk's final logits yield the request's first
  generated token (the TTFT boundary), written into the slot's entry of
  the token array; that sequence's first decode row rides the NEXT
  run (the next chunk's, or the next step's). A chunk with no decode row
  to carry (nobody decodes) is the same program with every decode row
  inactive: there is no chunk-only program.

What the engine serves: a dense decoder of models/transformer.py — GQA
layers with or without rotary embedding (a NoPE layer), pre- or post-norm
(``norm_order``), q/k norms, a tied or an untied head — and layers of ONE
RECURRENT KIND among them (``layer_pattern`` entries ``"linear"``, Gated
DeltaNet, or ``"mamba"``, Mamba-1's selective scan). So it
holds TWO KINDS of sequence state (serve/kvcache.py): pages of K and V for
the layers that attend, and for the recurrent layers a fixed-size recurrent
state and convolution tail a batch SLOT (``StateStore``; slot ``max_slots``
is the trash slot). A layer's place among the layers of its kind
(``cfg.kind_index``) is its index into its kind's store and into its
mixer's stacked weights. On the decode rows (of either program) a recurrent
layer runs its kind's step (``ops.gated_delta_step`` /
``ops.selective_scan_step``) on every slot's state in place (an
inactive slot's reads and writes are steered to the trash slot, as its page
writes go to the trash page); on a chunk's rows it runs the kind's scan
over many tokens (``ops.gated_delta_chunk`` / ``ops.selective_scan_chunk``)
from the sequence's state in its slot and
leaves the state after the chunk's last VALID row there (padding rows
write and decay nothing). Nothing resets a slot between requests: a
sequence's FIRST chunk (``start == 0``) starts from zeros inside the
program. It refuses, by name, what does not run: experts, latent
attention, a prediction module, a window layer.

Both take the pair of KV pools and the pair of state arrays (``()`` for a
model without recurrent layers) donated and hand them back, and between parameter and result the pool is never copied, sliced or
relaid: the
write is a scatter whose only window dimension is head_dim (the pool's
minor-most, so XLA updates the head-major pool where it lies) and the
kernel takes the whole 5-D pool with the layer in its BlockSpec index
map. The earlier ``kp.at[l, pid, :, row].set(k)`` + ``kp[l]`` forced a
row-major-pages layout on the pool for the scatter's (head, head_dim)
window: four whole-pool relayout copies a call and a slice of a whole
layer in front of every kernel. ``compile()`` counts what is left of
that (``<program>_pool_copies``, 0; ``<program>_state_copies`` for the
recurrent state).

The weights are read where they lie too: every product of both programs has
its weight's slice of the stacked float32 parameter as an operand of the
fusion that multiplies it, whatever is done to the product's result
afterwards (``whole`` in ``_jit_build`` says how and why); ``compile()``
counts the copies of a weight a program makes (``<program>_weight_copies``,
0).

Greedy argmax sampling, f32 compute throughout: serving determinism is
what the correctness oracle (tests/test_serve.py) and the seeded bench
artifact pin against.

``run`` is instrumented with ``jax.profiler.TraceAnnotation`` spans
(``serve.admit``, ``serve.step`` and its children ``serve.prefill``,
``serve.prefill_fetch``, ``serve.decode_prep``, ``serve.decode``,
``serve.decode_fetch`` — ``serve.decode`` is around every program call
that advances decode rows, INSIDE ``serve.prefill`` when the run carries
a chunk; the fetches of a step are of the step BEFORE it; ``serve.idle``;
the closing ``serve.counters``);
inside both programs the mixers carry ``jax.named_scope``s
(``serve.lin_mixer`` / ``serve.mamba_mixer`` / ``serve.full_attn``: metadata on the compiled
instructions, nothing at run time).
They cost well under a microsecond each while no profiler session is
open and land in the xplane of ``profile_ctx`` / ``tpujob profile`` on
the device trace's own clock; docs/design.md ("On-demand profiling")
lists what each covers. ``EngineCounters`` are plain ints, always on.
"""

from __future__ import annotations

import re
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import asdict, dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from tf_operator_tpu.serve.kvcache import (
    PagePool,
    PoolExhausted,
    SequencePages,
    StateStore,
    pages_needed,
    read_slot,
    write_rows,
    write_slot,
)


@dataclass
class ServeConfig:
    """Engine policy knobs (workload keys carry the same names with a
    ``kv_``/serve prefix — see workloads/serve.py)."""

    page_size: int = 16
    pool_pages: int = 64
    max_slots: int = 4
    prefill_chunk: int = 16


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    arrival: float = 0.0  # seconds offset from run start

    # filled in by the engine
    tokens: List[int] = field(default_factory=list)
    admitted: float = -1.0
    first_token: float = -1.0
    finished: float = -1.0
    token_times: List[float] = field(default_factory=list)


@dataclass
class EngineCounters:
    """What one ``run`` did, counted where it happens. The LIVE object
    rides every ``"step"`` payload (copy it to keep a reading) and ends
    on ``RunResult``; its final values are also written into an open
    profiler trace (``serve.counters``), so trace and object agree."""

    admitted: int = 0
    # step boundaries at which the head of the queue could not be admitted
    blocked_on_pool: int = 0   # ... for want of KV pages
    blocked_on_slots: int = 0  # ... for want of a batch slot
    prefill_chunks: int = 0    # program runs that carried a chunk
    # ... of which those that carried a decoding slot's token too (one
    # weight pass for both: a busy step is such a run, not two runs)
    chunks_carrying_decode: int = 0
    prefill_tokens: int = 0    # prompt tokens they carried
    prefill_padded: int = 0    # chunk positions they padded
    prefill_kv_pages: int = 0  # K/V pages their attention walked, once a chunk
    # program runs that advanced decoding slots: calls of the decode program
    # + ``chunks_carrying_decode`` (such a run is a chunk AND a decode step,
    # and counts as both)
    decode_steps: int = 0
    decode_slot_tokens: int = 0  # tokens they produced (active slots, summed)
    idle_sleeps: int = 0       # sleeps of an empty engine waiting for an arrival
    # chunks and decode steps enqueued while the host had not yet read the
    # result of the run before: the device had its next program before the
    # host looked at this one's tokens. In the unit of ``prefill_chunks +
    # decode_steps``: a chunk that carries decode rows counts twice.
    runs_enqueued_ahead: int = 0
    # a model with recurrent layers, "linear" or "mamba" (0 without): its
    # slots' recurrent state
    state_resets: int = 0      # first chunks: a slot's state started from zeros
    prefill_state_carries: int = 0  # later chunks: started from the slot's state
    lin_slot_steps: int = 0    # recurrent steps: active slots x recurrent layers


@dataclass
class RunResult:
    requests: List[Request]
    steps: int
    wall_s: float
    generated_tokens: int
    free_pages_start: int
    free_pages_end: int
    counters: EngineCounters = field(default_factory=EngineCounters)
    pool_peak_in_use: int = 0
    pool_alloc_failures: int = 0
    # the recurrent layers' (state, convolution tail) arrays as the last
    # program left them, () for a model without: a slot holds what its last
    # sequence left until the next one's first chunk
    state: Tuple[Any, ...] = ()

    @property
    def completed(self) -> int:
        return sum(1 for r in self.requests if r.finished >= 0)

    @property
    def tokens_per_s(self) -> float:
        return self.generated_tokens / self.wall_s if self.wall_s > 0 else 0.0

    def ttfts(self) -> List[float]:
        return [r.first_token - r.arrival for r in self.requests
                if r.first_token >= 0]

    def token_latencies(self) -> List[float]:
        """Inter-token gaps per request (the per-token latency the bench
        quotes p50/p99 of; TTFT is excluded — it has its own metric)."""
        out: List[float] = []
        for r in self.requests:
            ts = r.token_times
            out.extend(b - a for a, b in zip(ts, ts[1:]))
        return out


def greedy_reference_gaps(cfg, params, prompt: List[int], tokens: List[int]):
    """Hold one finished request against plain un-paged greedy decoding.

    The reference is ``models.transformer.transformer_forward`` — no
    pages, no KV cache, dense attention — on the same weights in f32 at
    the highest matmul precision, run ONCE over prompt + generated tokens
    (teacher-forced: causal attention makes row i exactly what greedy
    decoding would have seen when it chose token i). Returns
    ``(n_exact, max_gap)``: how many of the engine's tokens are the
    reference's argmax, and the largest amount by which the reference's
    best logit exceeds the logit of the token the engine chose (0.0 when
    every token is exact). Random weights leave thin top-2 margins, so a
    caller states a tolerance on ``max_gap`` rather than demanding token
    equality at every position."""
    from dataclasses import replace

    import jax
    import jax.numpy as jnp

    from tf_operator_tpu.models.transformer import transformer_forward

    ref_cfg = replace(cfg, dtype=jnp.float32, remat=False, attn_impl="dense")
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    seq = jnp.asarray([list(prompt) + list(tokens[:-1])], jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(
            lambda p, t: transformer_forward(p, t, ref_cfg)
        )(f32, seq)[0]
    rows = logits[len(prompt) - 1:]  # row i chose tokens[i]
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(tokens, jnp.int32)[:, None], axis=1
    )[:, 0]
    gaps = jnp.max(rows, axis=-1) - chosen
    return int(jnp.sum(gaps == 0.0)), float(jnp.max(gaps))


# one HLO instruction: ``%name = f32[2,321,8,64,128]{layout} opcode(...``, or
# with a tuple of such arrays for its result (a multi-output fusion)
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([\w\-]+)\((.*)$")
_HLO_ARRAY = re.compile(r"\w+\[([\d,]*)\]")
_HLO_FREE = frozenset({
    # name or view a buffer, or update it where it lies
    "parameter", "get-tuple-element", "bitcast", "while", "conditional",
    "call", "scatter", "dynamic-update-slice", "tuple",
})


def _executed(hlo_text: str):
    """The instructions a compiled program EXECUTES, less those that only
    name or view a buffer or update it where it lies (``_HLO_FREE``), as
    (the result arrays' dims — one, or each of a multi-output fusion's
    tuple —, opcode, the opcodes INSIDE the computation a fusion calls, the
    rest of the line): fusion bodies are read only to classify their
    fusion, and an asynchronous op's result is its ``-done``'s."""
    bodies: Dict[str, List[str]] = {}
    name = None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo_text))

    def opcodes(rest: str) -> set:
        called = re.search(r"calls=%([\w.\-]+)", rest)
        return {m.group(2) for m in map(
            _HLO_INSTR.match, bodies.get(called.group(1), ()) if called else ()) if m}

    instrs = []
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for line in lines:
            m = _HLO_INSTR.match(line)
            if m and m.group(2) not in _HLO_FREE and not m.group(2).endswith("-start"):
                results, op, rest = m.groups()
                instrs.append((_HLO_ARRAY.findall(results), op,
                               opcodes(rest) if op == "fusion" else set(), rest))
    return instrs


def pool_copies(hlo_text: str, pool_shape) -> int:
    """How many instructions of a compiled program MATERIALISE a whole KV
    pool side or one whole layer of it: executed instructions whose result
    has the pool's shape ``[L, P+1, h_kv, page, hd]`` or a layer's (``[1,
    P+1, …]`` / ``[P+1, …]``) and that are neither a name or view of a
    buffer nor an update in place (a scatter or dynamic-update-slice, bare
    or as a fusion's root; a custom call that aliases its operand). What is
    left is a ``copy``, ``slice``, ``copy-done`` or relayout fusion: data
    movement of 84 MB to 1 GB at the -serve1 shapes that changes no value.
    0 when the pool is written and read in one layout, in place."""
    dims = [str(int(d)) for d in pool_shape]
    shapes = {",".join(dims), ",".join(["1"] + dims[1:]), ",".join(dims[1:])}
    count = 0
    for results, op, inside, rest in _executed(hlo_text):
        if len(results) != 1 or results[0] not in shapes:
            continue
        if inside & {"scatter", "dynamic-update-slice"}:
            continue
        if op == "custom-call" and "output_to_operand_aliasing" in rest:
            continue
        count += 1
    return count


def weight_copies(hlo_text: str, weight_shapes) -> int:
    """How many results of a compiled program are a COPY of one layer's
    slice of a stacked matmul weight: results of executed instructions
    (each array of a multi-output fusion's tuple is one) whose dimensions
    are a weight's ``[in, out]`` in either order (1s aside), made by
    something that multiplies nothing — a ``copy``, a ``transpose``, a
    ``slice``, a fusion that holds no ``convolution`` / ``dot``. A product
    reads its weight as an operand of its own fusion, from the stacked
    parameter, so such a result is the weight written out again (converted,
    transposed, relaid: 17 to 67 MB a time at the -serve1 widths) before
    anything multiplies it. 0 when every product reads its weight where it
    lies. ``weight_shapes`` are the slices' ``(in, out)``; matched by
    dimensions, not by element count: a run's activations can have a small
    weight's count (320 rows x 2,560 = 160 x 5,120 at the Jamba shapes). Not
    counted: the compiler's own PREFETCH of a parameter's slice into faster
    memory (an asynchronous ``copy-done`` / ``slice-done``: the same type
    and layout, moved once, and the product reads it there)."""
    shapes = {tuple(sorted(int(d) for d in s)) for s in weight_shapes}
    count = 0
    for results, op, inside, _ in _executed(hlo_text):
        if op.endswith("-done") or (inside | {op}) & {"convolution", "dot"}:
            continue
        count += sum(
            tuple(sorted(int(d) for d in dims.split(",") if d not in ("", "1")))
            in shapes for dims in results)
    return count


def multiplied_shapes(jaxpr) -> set:
    """The shapes of every operand of every product (``dot_general``) a
    traced program holds, nested jaxprs included."""
    from jax.core import jaxprs_in_params

    shapes = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes.update(tuple(v.aval.shape) for v in eqn.invars)
        for sub in jaxprs_in_params(eqn.params):
            shapes |= multiplied_shapes(sub)
    return shapes


def pallas_grid_steps(jaxpr) -> int:
    """Grid steps of every ``pallas_call`` a traced program holds, summed
    (calls inside nested jaxprs included; a program that runs the gather
    reference holds none: 0). What a kernel does in a step is its own
    affair — this counts how often its body is entered."""
    from jax.core import jaxprs_in_params

    steps = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            steps += int(np.prod(eqn.params["grid_mapping"].grid))
        else:
            steps += sum(map(pallas_grid_steps, jaxprs_in_params(eqn.params)))
    return steps


class _Slot:
    """A batch slot's tenant as COUNTS of what has been enqueued for it:
    the scheduler never waits for a token's value."""

    __slots__ = ("req", "pages", "seq_len", "prefill_pos", "generated")

    def __init__(self, req: Request, pages: SequencePages):
        self.req = req
        self.pages = pages
        self.seq_len = 0        # K/V positions whose write is enqueued
        self.prefill_pos = 0    # prompt tokens whose chunk is enqueued
        self.generated = 0      # output tokens whose run is enqueued


class _Unread(NamedTuple):
    """A program run's token array that the host has yet to read."""

    fetch: str              # the span its fetch is timed under
    attrs: Dict[str, Any]   # ... and that span's attributes
    run: int                # the run's ordinal among the engine run's runs
    tokens: Any             # int32[max_slots], on the device
    # whose token it holds and where — the request, not the slot's tenant:
    # the slot may have its next one by the time the array is read
    owners: List[Tuple[Request, int]]


class ServeEngine:
    def __init__(self, cfg, params, scfg: ServeConfig):
        import jax

        if cfg.n_experts:
            raise ValueError(
                "serve engine: MoE presets not supported (attending, 'linear' "
                "and 'mamba' layers are served with the dense MLP only)")
        if cfg.attn_kind != "gqa" or cfg.mtp_depth:
            raise ValueError(
                "serve engine: latent attention and a prediction module "
                "run in training only")
        if any(window for window, _ in cfg.attn_kinds):
            raise ValueError(
                "serve engine: the paged kernel has no sliding window; a "
                "window layer runs in training only, beside 'linear' or "
                "'mamba' layers too")
        if getattr(cfg, "pp_stages", 0):
            raise ValueError("serve engine: pipeline presets not supported")
        if scfg.page_size < 1:
            raise ValueError(f"kv_page_size must be >= 1, got {scfg.page_size}")
        if scfg.pool_pages < 1:
            raise ValueError(f"kv_pool_pages must be >= 1, got {scfg.pool_pages}")
        self.cfg = cfg
        self.scfg = scfg
        # f32 master weights: serving determinism + the logits-parity
        # oracle; pools match. Abstract parameters (ShapeDtypeStructs,
        # with the sharding of a described device if any) pass through:
        # such an engine can ``compile()`` and nothing else.
        import jax.numpy as jnp

        def f32(a):
            if isinstance(a, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(a.shape, jnp.float32,
                                            sharding=a.sharding)
            return jnp.asarray(a, jnp.float32)

        self.params = jax.tree_util.tree_map(f32, params)
        self.max_pages_per_seq = pages_needed(cfg.max_seq, scfg.page_size)
        # the recurrent layers' state, a slot a batch slot; None without any
        self.store = StateStore.for_model(cfg, scfg.max_slots)
        self._jit_build()

    # -- compiled step functions -----------------------------------------

    def _jit_build(self) -> None:
        import jax
        import jax.numpy as jnp

        from tf_operator_tpu.models.transformer import (
            LINEAR,
            MAMBA,
            RECURRENT,
            _head,
            _rms_norm,
            lin_conv_taps,
            lin_gates,
            lin_output,
            lin_project,
            mamba_gates,
            mamba_output,
            mamba_project,
            rope_at_positions,
            stacked_by,
        )
        from tf_operator_tpu.ops.flash_attention import flash_attention_decode
        from tf_operator_tpu.ops.gated_delta import (
            gated_delta_chunk,
            gated_delta_step,
        )
        from tf_operator_tpu.ops.selective_scan import (
            selective_scan_chunk,
            selective_scan_step,
        )

        cfg = self.cfg
        ps = self.scfg.page_size
        trash = self.scfg.pool_pages  # PagePool.trash_page
        hd = cfg.head_dim
        eps = cfg.norm_eps
        post = cfg.norm_order == "post"
        taps = cfg.mamba_d_conv if cfg.recurrent_kind == MAMBA else cfg.lin_conv
        scopes = {LINEAR: "serve.lin_mixer", MAMBA: "serve.mamba_mixer"}
        trash_slot = self.store.trash_slot if self.store else None
        kinds = [cfg.pattern[l % len(cfg.pattern)] for l in range(cfg.n_layers)]
        # the recurrent mixer's leaves in SORTED order, as these programs have
        # always sliced them: the order of the slices is part of the
        # program's text, and the text is the persistent compile cache's key
        mixer_leaves = sorted(n for n, kind in stacked_by(cfg).items() if kind in RECURRENT)

        def whole(*products):
            """The products ``rows @ weight`` [n, out] whose result is about to
            be split by heads, held WHOLE until they are computed. THE RULE: a
            product's weight is an operand of the fusion that multiplies it,
            read once from the stacked f32 parameter where it lies. Left to
            itself the TPU compiler folds the head reshape into the product,
            the folded product wants its weight in a layout the stacked
            parameter does not have, and the weight's slice is first written
            out as a transposed bfloat16 copy and then relaid — the weight
            moved three times a run where once would do. Behind the barrier
            the 2-D product is the one ``wo`` and the MLP have always had;
            the reshape that follows is a view of ``[n, out]`` rows. No value
            changes: the same DEFAULT-precision product of the same float32
            operands. ``compile()`` counts what a program still materialises
            (``<program>_weight_copies``, 0)."""
            return jax.lax.optimization_barrier(products)

        def _body(params, kp, vp, state, x, pos, attend, write_pid, write_row,
                  recurrent):
            """Shared per-layer body: x [n, d] at absolute positions pos
            [n]. A layer that ATTENDS writes each row's k/v to
            (write_pid[i], write_row[i]) then ``attend(q [n, h, hd], kp, vp,
            i)`` reads them back through the caller's page table(s). The
            pools [attending layers, page, h_kv, row, hd] are carried WHOLE
            through every layer — written by ``write_rows``, read by the
            kernel at ``layer=i`` — and never indexed by layer here:
            ``kp[i]`` is a copy of a layer. A RECURRENT layer hands its input
            to the caller's ``recurrent(h, weights, i, state)``, which runs the
            mixer around the program's own view of the state store and
            returns (output [n, d], state). ``i`` is the layer's place among
            the layers of its kind: its index into its mixer's stacked
            weights and into its kind's store. Returns (kp, vp, state,
            final hidden [n, d])."""
            n = x.shape[0]
            lp = params["layers"]
            for l, kind in enumerate(kinds):
                i = cfg.kind_index(l)
                # norm_order "post" (OLMo-2): the gain stands on the
                # sublayer's OUTPUT, x + norm(F(x))
                h = x if post else _rms_norm(x, lp["attn_norm"][l], eps)
                if kind in RECURRENT:
                    with jax.named_scope(scopes[kind]):
                        y, state = recurrent(
                            h, {name: lp[name][i] for name in mixer_leaves}, i, state)
                else:
                    with jax.named_scope("serve.full_attn"):
                        q, k, v = whole(
                            h @ lp["wq"][i], h @ lp["wk"][i], h @ lp["wv"][i])
                        if cfg.qk_norm:
                            q = _rms_norm(q, lp["q_norm"][i], eps)
                            k = _rms_norm(k, lp["k_norm"][i], eps)
                        q = q.reshape(n, -1, hd)
                        k = k.reshape(n, -1, hd)
                        v = v.reshape(n, -1, hd)
                        if kind[1]:  # rotary; a NoPE layer attends by content alone
                            q = rope_at_positions(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
                            k = rope_at_positions(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
                        kp, vp = write_rows(kp, vp, i, k, v, write_pid, write_row)
                        attn = attend(q, kp, vp, i)
                        y = attn.reshape(n, -1) @ lp["wo"][i]
                x = x + (_rms_norm(y, lp["attn_norm"][l], eps) if post else y)
                h2 = x if post else _rms_norm(x, lp["mlp_norm"][l], eps)
                y = (
                    jax.nn.silu(h2 @ lp["w_gate"][l]) * (h2 @ lp["w_up"][l])
                ) @ lp["w_down"][l]
                x = x + (_rms_norm(y, lp["mlp_norm"][l], eps) if post else y)
            return kp, vp, state, x

        def slot_rows(table, seq_lens, active):
            """One decode row a slot, at position seq_lens[i]: (the page its
            k/v go to, the K/V length its attention sees) — the trash page
            and 0 for a slot that is not active."""
            pid = table[jnp.arange(seq_lens.shape[0]), seq_lens // ps]
            return jnp.where(active, pid, trash), jnp.where(active, seq_lens + 1, 0)

        def slot_mixer(pre, b, a, w, i, state, active):
            """One token a slot through a linear layer's sequence mixing,
            from ``lin_project``'s outputs for the decode rows: the
            convolution over the slot's tail and this input, the recurrent
            step on the slot's state in place. An inactive slot reads and
            writes the trash slot. Returns (o [s, H, d_v], state)."""
            st, cv = state
            slot = jnp.where(active, jnp.arange(active.shape[0]), trash_slot)
            ext = jnp.concatenate([cv[i, slot], pre[:, None]], axis=1)
            u = lin_conv_taps(ext, w["lin_conv"], 1)[:, 0]
            cv = cv.at[i, slot].set(ext[:, 1:])
            q, k, v, alpha_log, beta = lin_gates(u, b, a, w, cfg)
            o, st = gated_delta_step(q, k, v, alpha_log, beta, st,
                                     valid=active, layer=i, slots=slot)
            return o, (st, cv)

        def mamba_slot_taps(x, w, i, cv, active):
            """One token a slot through a Mamba layer's convolution, over the
            slot's tail and this input (an inactive slot reads and writes the
            trash slot). Returns (u [s, inner], the slots, cv)."""
            slot = jnp.where(active, jnp.arange(active.shape[0]), trash_slot)
            ext = jnp.concatenate([cv[i, slot], x[:, None]], axis=1)
            u = lin_conv_taps(ext, w["mamba_conv"], 1, w["mamba_conv_bias"])[:, 0]
            return u, slot, cv.at[i, slot].set(ext[:, 1:])

        def decode_step(params, pools, state, table, seq_lens, tokens, active):
            """One token for every ACTIVE slot. tokens[i] sits at position
            seq_lens[i]; returns the token each slot decodes from NEXT: an
            active slot's greedy choice, any other slot's entry as it came
            in — the array is the next run's ``tokens`` as it stands, and
            the host reads the same array later. ``pools`` is the (K, V)
            pair of page pools, ``state`` the recurrent layers' (recurrent
            state, convolution tail) pair, or () for a model without; both
            come back updated."""
            kp, vp = pools
            x = params["embed"][tokens]
            pid, lens = slot_rows(table, seq_lens, active)

            def attend(q, kp, vp, i):  # one row a slot
                return flash_attention_decode(q, kp, vp, table, lens, layer=i)

            def linear(h, w, i, state):
                pre, z, b, a = lin_project(h, w, cfg)
                z, = whole(z)  # ``lin_output`` splits it by heads
                o, state = slot_mixer(pre, b, a, w, i, state, active)
                return lin_output(o, z, w, cfg, h.dtype), state

            def mamba(h, w, i, state):
                """The convolution over each slot's tail, the gates, the
                recurrent step on each slot's state in place."""
                st, cv = state
                x, z = mamba_project(h, w, cfg)
                u, slot, cv = mamba_slot_taps(x, w, i, cv, active)
                delta, B, C, A = mamba_gates(u, w, cfg)
                y, st = selective_scan_step(u, delta, B, C, A, w["mamba_D"], st,
                                            valid=active, layer=i, slots=slot)
                return mamba_output(y, z, w, h.dtype), (st, cv)

            kp, vp, state, x = _body(
                params, kp, vp, state, x, seq_lens, attend, pid, seq_lens % ps,
                mamba if cfg.recurrent_kind == MAMBA else linear)
            logits = _rms_norm(x, params["final_norm"], eps) @ _head(params, cfg).T
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (kp, vp), state, jnp.where(active, nxt, tokens)

        def prefill_chunk(params, pools, state, table_row, start, tokens_c,
                          n_valid, slot, table, seq_lens, tokens, active):
            """A run that CARRIES A CHUNK: one chunk of the prompt of the
            sequence in batch slot ``slot`` AND ``decode_step``'s token for
            every active slot, in ONE pass over the weights. The rows of
            ``_body`` are the chunk's C positions followed by the slots'
            decode rows; what differs by kind of row runs on its slice of
            them. The chunk's rows are one query tile over the sequence's
            page-table row, causal per row; rows past ``n_valid`` lie past
            the sequence's length, see nothing and write to the trash page.
            The recurrent layers carry the sequence's state in the slot from
            chunk to chunk; the first chunk (``start == 0``) starts from
            zeros. The decode rows are ``decode_step``'s (``table``,
            ``seq_lens``, ``tokens``, ``active`` as there; the chunk's own
            slot is never active — a slot prefills or decodes — so the two
            kinds write disjoint pages and disjoint state slots), all
            inactive when the run has none to carry. The head runs once,
            over the decode rows and the chunk's last valid row. Returns
            the token each slot decodes from next, as ``decode_step`` does,
            with ``slot``'s entry set to the greedy token after the chunk's
            last valid row: the sequence's first generated token when the
            chunk is its prompt's last, and read by nothing before that
            chunk has written it."""
            kp, vp = pools
            c = tokens_c.shape[0]
            idx = jnp.arange(c)
            pos_c = start + idx
            valid = idx < n_valid
            pid_d, lens = slot_rows(table, seq_lens, active)
            x = params["embed"][jnp.concatenate([tokens_c, tokens])]
            pos = jnp.concatenate([pos_c, seq_lens])
            pid = jnp.concatenate(
                [jnp.where(valid, table_row[pos_c // ps], trash), pid_d])

            def attend(q, kp, vp, i):  # ONE sequence's c rows, then one row a slot
                return jnp.concatenate([
                    flash_attention_decode(
                        q[:c][None], kp, vp, table_row[None],
                        (start + n_valid)[None], layer=i, q_start=start[None])[0],
                    flash_attention_decode(q[c:], kp, vp, table, lens, layer=i)])

            def linear(h, w, i, state):
                """The projections over all the rows; then the chunk through
                the chunked scan from its slot's state (a padding row writes
                and decays nothing) behind the convolution over the slot's
                tail + the chunk — the slot keeps the state after the last
                valid row and that row's last inputs — and the decode rows as
                in ``decode_step``."""
                st, cv = state
                fresh = start == 0
                pre, z, b, a = lin_project(h, w, cfg)
                z, = whole(z)  # ``lin_output`` splits it by heads
                ext = jnp.concatenate([read_slot(cv, i, slot, fresh), pre[:c]])
                u = lin_conv_taps(ext, w["lin_conv"], c)
                # ext row r is position start + r - (taps - 1)
                cv = write_slot(cv, i, slot, jax.lax.dynamic_slice_in_dim(
                    ext, n_valid, taps - 1))
                q, k, v, alpha_log, beta = lin_gates(u, b[:c], a[:c], w, cfg)
                o_c, s1 = gated_delta_chunk(
                    q, k, v, alpha_log, beta, read_slot(st, i, slot, fresh),
                    valid=valid)
                st = write_slot(st, i, slot, s1)
                o_d, state = slot_mixer(pre[c:], b[c:], a[c:], w, i, (st, cv), active)
                return lin_output(jnp.concatenate([o_c, o_d]), z, w, cfg,
                                  h.dtype), state

            def mamba(h, w, i, state):
                """As ``linear``, for a Mamba layer: the projection over all
                the rows; the convolution over the slot's tail + the chunk
                and over each decoding slot's tail; the gates (two products
                with a weight) over all the rows at once; the chunk through
                the scan from its slot's state, the decode rows through the
                step."""
                st, cv = state
                fresh = start == 0
                x, z = mamba_project(h, w, cfg)
                ext = jnp.concatenate([read_slot(cv, i, slot, fresh), x[:c]])
                u_c = lin_conv_taps(ext, w["mamba_conv"], c, w["mamba_conv_bias"])
                cv = write_slot(cv, i, slot, jax.lax.dynamic_slice_in_dim(
                    ext, n_valid, taps - 1))
                u_d, slots_d, cv = mamba_slot_taps(x[c:], w, i, cv, active)
                u = jnp.concatenate([u_c, u_d])
                delta, B, C, A = mamba_gates(u, w, cfg)
                y_c, s1 = selective_scan_chunk(
                    u_c, delta[:c], B[:c], C[:c], A, w["mamba_D"],
                    read_slot(st, i, slot, fresh)[0], valid=valid)
                st = write_slot(st, i, slot, s1[None])
                y_d, st = selective_scan_step(
                    u_d, delta[c:], B[c:], C[c:], A, w["mamba_D"], st,
                    valid=active, layer=i, slots=slots_d)
                return mamba_output(jnp.concatenate([y_c, y_d]), z, w,
                                    h.dtype), (st, cv)

            kp, vp, state, x = _body(
                params, kp, vp, state, x, pos, attend, pid, pos % ps,
                mamba if cfg.recurrent_kind == MAMBA else linear)
            rows = jnp.concatenate([x[c:], x[n_valid - 1][None]])
            logits = _rms_norm(rows, params["final_norm"], eps) @ _head(params, cfg).T
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (kp, vp), state, jnp.where(
                active, nxt[:-1], tokens).at[slot].set(nxt[-1])

        self._decode = jax.jit(decode_step, donate_argnums=(1, 2))
        self._prefill = jax.jit(prefill_chunk, donate_argnums=(1, 2))

    def compile(self) -> Dict[str, Any]:
        """AOT-compile the engine's TWO programs at its fixed shapes —
        ``decode``, the decode step, and ``prefill``, the run that carries
        a chunk AND the decode rows (there is no chunk-only program: a
        chunk with nobody decoding rides it with every decode row
        inactive) — and serve with the compiled executables from here on: a server
        warms up before it takes traffic, so no request's TTFT carries a
        compile, and a program the device's compiler refuses fails here,
        by name. Returns each program's compile seconds, how many
        ``tpu_custom_call``s (Pallas kernels) its compiled text holds —
        0 means the step runs the gather reference, not the kernel —
        ``<program>_pool_copies``: how many of its instructions
        materialise a whole pool side or a whole layer of one
        (``pool_copies``; 0 while the pool is written and read in place,
        in one layout), ``<program>_weight_copies``: how many of its results
        are one layer's slice of a stacked matmul weight written out again by
        something that multiplies nothing (``weight_copies``; 0 while every
        product reads its weight as its own fusion's operand, from the
        stacked parameter), and ``<program>_attn_grid_steps``: the grid steps
        of its attention kernels, all layers (``pallas_grid_steps``; a
        step is one page of as many KV heads as the kernel's VMEM holds,
        and a chunk walks its sequence's pages once: slots x groups of
        KV heads x page slots a layer; the recurrent layers' kernels count
        here too: a step of ``gdn_step`` is one slot's heads, of
        ``gdn_chunk_fwd`` one head's 64 positions, of ``ssm_step`` one
        slot's channels, of ``ssm_chunk_fwd`` 512 channels' 256 positions;
        ``prefill`` holds the
        decode rows' kernels and grid steps beside the chunk's own: the
        paged kernel twice an attending layer, ``gdn_chunk_fwd`` and
        ``gdn_step`` a linear one, ``ssm_chunk_fwd`` and ``ssm_step`` a
        Mamba one). ``<program>_paged_reference_calls`` counts the paged
        attention calls of the COMPILED program that are not the kernel (one
        call an attending layer, two in ``prefill``, less its
        ``paged_attention`` instructions): they run the gather reference —
        every one off the TPU, on it a call whose q tile no cut fits (0:
        every attending layer runs the kernel). ``<program>_kernels``
        names the Pallas kernels (instructions by kernel name), and for a
        model with recurrent layers ``<program>_state_copies`` counts for the
        recurrent-state array what ``_pool_copies`` counts for the pool (0
        while a slot's state is read and written where it lies) and
        ``<program>_conv_tail_copies`` the same for the convolution tails
        (a few MB: the TPU compiler may stage them through faster memory
        around the decode step's gather and scatter)."""
        import jax
        import jax.numpy as jnp

        from tf_operator_tpu.models.transformer import ATTN
        from tf_operator_tpu.parallel.collectives import compiled_kernels

        scfg = self.scfg

        def arr(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        pools = (arr(self._pool_shape(), jnp.float32),) * 2
        state = () if self.store is None else (
            arr(self.store.state_shape, jnp.float32),
            arr(self.store.conv_shape, jnp.float32))
        s_n, p = scfg.max_slots, self.max_pages_per_seq
        i32 = jnp.int32
        programs = {
            "decode": (self._decode, (
                self.params, pools, state, arr((s_n, p), i32), arr((s_n,), i32),
                arr((s_n,), i32), arr((s_n,), jnp.bool_),
            )),
            "prefill": (self._prefill, (
                self.params, pools, state, arr((p,), i32), arr((), i32),
                arr((scfg.prefill_chunk,), i32), arr((), i32), arr((), i32),
                arr((s_n, p), i32), arr((s_n,), i32), arr((s_n,), i32),
                arr((s_n,), jnp.bool_),
            )),
        }
        out: Dict[str, Any] = {}
        for name, (fn, args) in programs.items():
            t0 = time.perf_counter()
            traced = fn.trace(*args)
            compiled = traced.lower().compile()
            out[f"{name}_compile_s"] = round(time.perf_counter() - t0, 3)
            out[f"{name}_attn_grid_steps"] = pallas_grid_steps(traced.jaxpr.jaxpr)
            text = compiled.as_text()
            out[f"{name}_tpu_custom_calls"] = text.count("tpu_custom_call")
            out[f"{name}_pool_copies"] = pool_copies(text, self._pool_shape())
            # a stacked matmul weight: a leaf [layers, in, out] whose slice is
            # an operand of one of the program's products (the convolutions'
            # taps and a decay's logarithm are stacked the same way and
            # multiply nothing)
            out[f"{name}_weight_copies"] = weight_copies(text, {
                leaf.shape[1:] for leaf in self.params["layers"].values()
                if len(leaf.shape) == 3
            } & multiplied_shapes(traced.jaxpr.jaxpr))
            kernels = out[f"{name}_kernels"] = dict(compiled_kernels(text))
            # an attending layer calls paged attention once over the decode
            # rows and, in ``prefill``, once over the chunk
            out[f"{name}_paged_reference_calls"] = (
                self.cfg.n_of_kind(ATTN) * (1 + (name == "prefill"))
                - kernels.get("paged_attention", 0))
            if self.store is not None:
                out[f"{name}_state_copies"] = pool_copies(text, self.store.state_shape)
                out[f"{name}_conv_tail_copies"] = pool_copies(
                    text, self.store.conv_shape)
            setattr(self, f"_{name}", compiled)
        return out

    def _pool_shape(self):
        from tf_operator_tpu.models.transformer import ATTN

        cfg, scfg = self.cfg, self.scfg
        return (  # the layers that attend: a recurrent layer keeps no pages
            cfg.n_of_kind(ATTN), scfg.pool_pages + 1, cfg.n_kv_heads,
            scfg.page_size, cfg.head_dim,
        )

    def _fresh_pools(self):
        import jax.numpy as jnp

        shape = self._pool_shape()
        return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)

    # -- the scheduler loop ----------------------------------------------

    def run(
        self,
        requests: List[Request],
        clock: Callable[[], float] = time.perf_counter,
        on_event: Optional[Callable[[str, Any], None]] = None,
    ) -> RunResult:
        """Serve ``requests`` (arrival offsets in seconds from run start)
        to completion. ``on_event(kind, payload)`` fires with kinds
        "admitted"/"first_token"/"finished" (payload: the Request) and
        "step" (payload: dict with step/active/waiting/completed/
        generated/free_pages and the live ``EngineCounters``) — the
        workload's span + live-count seam.

        The loop runs ONE engine step ahead of the device. A request has
        no stop token, so who decodes at which position, who finishes and
        whose pages come back are arithmetic on counts the host holds;
        only the next run's embedding lookup needs a token's VALUE, and
        the token array stays on the device, each run handing the next
        its operand. So a loop iteration ENQUEUES step n+1 (a chunk per
        prefilling slot, each carrying the decode rows of every slot
        that decodes as that run goes out; with nobody prefilling, the
        decode run) and only then COLLECTS step n:
        blocks on its token arrays in device order, stamps and appends
        the tokens, and marks a request finished when its last token is
        on the host. A sequence decodes from the run AFTER its last
        chunk's: its first token is that run's result. A
        slot and its pages are released when the
        sequence's last token has been enqueued — a later program cannot
        overtake the one that writes it. With nothing in flight (the
        first step, the first after the engine was empty) this is the
        synchronous loop; an engine that runs empty collects what is in
        flight, in a step that enqueues nothing, before it sleeps."""
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation as span

        scfg = self.scfg
        for r in requests:
            if not r.prompt:
                raise ValueError(f"request {r.rid}: empty prompt")
            if len(r.prompt) + r.max_new > self.cfg.max_seq:
                raise ValueError(
                    f"request {r.rid}: prompt {len(r.prompt)} + max_new "
                    f"{r.max_new} exceeds max_seq {self.cfg.max_seq}"
                )
            if pages_needed(len(r.prompt) + r.max_new, scfg.page_size) > scfg.pool_pages:
                raise ValueError(
                    f"request {r.rid} alone needs "
                    f"{pages_needed(len(r.prompt) + r.max_new, scfg.page_size)} "
                    f"pages but the pool holds {scfg.pool_pages} — it could "
                    f"never be admitted"
                )
        pool = PagePool(scfg.pool_pages)
        free_start = pool.free_count
        pools = self._fresh_pools()
        # nothing resets it between requests: a slot's first chunk does
        state = () if self.store is None else self.store.fresh()
        n_lin = 0 if self.store is None else self.store.n_layers
        s_n = scfg.max_slots
        # the token each slot decodes from next; every run returns the next's
        toks = jnp.zeros(s_n, jnp.int32)
        table = np.full((s_n, self.max_pages_per_seq), pool.trash_page - 1,
                        np.int32)
        slots: List[Optional[_Slot]] = [None] * s_n

        pending = deque(sorted(requests, key=lambda r: (r.arrival, r.rid)))
        waiting: deque = deque()
        emit = on_event or (lambda kind, payload: None)
        counters = EngineCounters()
        t0 = clock()
        step = 0
        completed = 0   # requests whose last token is on the host
        generated = 0   # tokens on the host
        fetched = 0     # program runs the host has seen the device reach
        flight: List[_Unread] = []  # the step in flight, in device order

        def _try_admit(now: float) -> None:
            while waiting:
                free = [i for i, sl in enumerate(slots) if sl is None]
                if not free:
                    counters.blocked_on_slots += 1
                    break
                req = waiting[0]
                # the worst case (prompt + max_new) is reserved here, so a
                # running sequence can never hit PoolExhausted mid-decode
                sp = SequencePages(scfg.page_size)
                try:
                    sp.ensure(len(req.prompt) + req.max_new, pool)
                except PoolExhausted:
                    counters.blocked_on_pool += 1
                    break  # head-of-line blocks: FIFO admission, no bypass
                waiting.popleft()
                i = free[0]
                slots[i] = _Slot(req, sp)
                table[i, : len(sp.pages)] = sp.pages
                req.admitted = now
                counters.admitted += 1
                emit("admitted", req)

        def _count_run(carries: bool = False) -> int:
            """One more program run goes to the device; returns its
            ordinal among chunks and decode steps — a chunk that
            ``carries`` decode rows is one of each. It runs AHEAD when the
            host has not yet read the result of the run before it."""
            runs = counters.prefill_chunks + counters.decode_steps
            if fetched < runs:
                counters.runs_enqueued_ahead += 1 + carries
            return runs + 1 + carries

        def _release_if_done(i: int) -> None:
            """The run that writes slot i's last token is enqueued: the
            slot and its pages are free from the next admission on."""
            sl = slots[i]
            if sl.generated >= sl.req.max_new:
                sl.pages.release(pool)
                table[i, :] = pool.trash_page - 1
                slots[i] = None

        # Host arrays go to the programs as numpy (no transfer program of
        # their own), and what the loop goes on to write is copied first:
        # the CPU backend may alias a numpy operand instead of copying it.

        def _decode_rows(dec: List[Tuple[int, _Slot]]):
            """(table, seq_lens, active): the decode operands for ``dec``
            (nobody: every row inactive)."""
            with span("serve.decode_prep", active=len(dec)):
                active = np.zeros(s_n, bool)
                lens = np.zeros(s_n, np.int32)
                for i, sl in dec:
                    active[i] = True
                    lens[i] = sl.seq_len
                return table.copy(), lens, active

        def _decoded(dec: List[Tuple[int, _Slot]]) -> None:
            """A run that advances ``dec`` one token each is enqueued."""
            counters.decode_steps += 1
            counters.decode_slot_tokens += len(dec)
            counters.lin_slot_steps += n_lin * len(dec)
            for i, sl in dec:
                sl.seq_len += 1
                sl.generated += 1
                _release_if_done(i)

        def _decoding() -> List[Tuple[int, _Slot]]:
            """The slots whose next run is a decode row, as counted now."""
            return [
                (i, sl) for i, sl in enumerate(slots)
                if sl is not None
                and sl.prefill_pos >= len(sl.req.prompt)
                and sl.generated < sl.req.max_new
            ]

        def _enqueue_step(ahead: List[_Unread]) -> None:
            """The step's program runs: one chunk per still-prefilling
            slot, EACH with a decode row for every slot that decodes as
            the run goes out (one pass over the weights for both — a
            decode row costs a chunk's run next to nothing, so a step of
            k chunks advances the decoding slots k tokens); with nobody
            prefilling, the decode step."""
            nonlocal pools, state, toks
            c = scfg.prefill_chunk
            prefilling = [
                (i, sl) for i, sl in enumerate(slots)
                if sl is not None and sl.prefill_pos < len(sl.req.prompt)
            ]
            for i, sl in prefilling:
                # a sequence whose last chunk was an earlier run of this
                # step is among them: the token it decodes from is that
                # run's result, in the array this run takes
                carried = _decoding()
                prompt = sl.req.prompt
                chunk = prompt[sl.prefill_pos : sl.prefill_pos + c]
                n_valid = len(chunk)
                last = sl.prefill_pos + n_valid >= len(prompt)
                kv_pages = pages_needed(sl.prefill_pos + n_valid,
                                        scfg.page_size)
                tbl, lens, active = _decode_rows(carried)
                with span("serve.prefill", rid=sl.req.rid, slot=i,
                          start=sl.prefill_pos, n_valid=n_valid, chunk=c,
                          last=int(last), kv_pages=kv_pages):
                    buf = np.zeros(c, np.int32)
                    buf[:n_valid] = chunk
                    with (span("serve.decode", active=len(carried), slots=s_n)
                          if carried else nullcontext()):
                        run_no = _count_run(bool(carried))
                        pools, state, toks = self._prefill(
                            self.params, pools, state, table[i].copy(),
                            np.int32(sl.prefill_pos), buf, np.int32(n_valid),
                            np.int32(i), tbl, lens, toks, active,
                        )
                if n_lin:
                    if sl.prefill_pos == 0:
                        counters.state_resets += 1
                    else:
                        counters.prefill_state_carries += 1
                counters.prefill_chunks += 1
                counters.prefill_tokens += n_valid
                counters.prefill_padded += c - n_valid
                counters.prefill_kv_pages += kv_pages
                sl.prefill_pos += n_valid
                sl.seq_len = sl.prefill_pos
                owners = [(d.req, j) for j, d in carried]
                fetch, attrs = "serve.decode_fetch", {}
                if carried:
                    counters.chunks_carrying_decode += 1
                    _decoded(carried)
                if last:
                    # last chunk's logits ARE the first generated token
                    sl.generated = 1
                    owners.append((sl.req, i))
                    fetch, attrs = "serve.prefill_fetch", {"rid": sl.req.rid}
                    _release_if_done(i)
                if owners:
                    ahead.append(_Unread(fetch, attrs, run_no, toks, owners))
            dec = [] if prefilling else _decoding()
            if dec:  # nobody prefills: the decode program
                tbl, lens, active = _decode_rows(dec)
                with span("serve.decode", active=len(dec), slots=s_n):
                    run_no = _count_run()
                    pools, state, toks = self._decode(
                        self.params, pools, state, tbl, lens, toks, active)
                ahead.append(_Unread("serve.decode_fetch", {}, run_no, toks,
                                     [(sl.req, i) for i, sl in dec]))
                _decoded(dec)

        def _collect(unread: List[_Unread]) -> None:
            """Block on a step's token arrays in device order; a token is
            stamped with the clock at its fetch's return, a request is
            finished when its last token is on the host."""
            nonlocal fetched, generated, completed
            for u in unread:
                with span(u.fetch, **u.attrs):
                    host = np.asarray(u.tokens)
                now = clock() - t0
                fetched = u.run
                for req, i in u.owners:
                    req.tokens.append(int(host[i]))
                    req.token_times.append(now)
                    generated += 1
                    if len(req.tokens) == 1:
                        req.first_token = now
                        emit("first_token", req)
                    if len(req.tokens) >= req.max_new:
                        req.finished = now
                        completed += 1
                        emit("finished", req)

        try:
            while completed < len(requests):
                with span("serve.admit"):
                    now = clock() - t0
                    while pending and pending[0].arrival <= now:
                        waiting.append(pending.popleft())
                    _try_admit(now)
                busy = [sl for sl in slots if sl is not None]
                if not busy and not flight:
                    if pending:
                        # idle until the next arrival — a serving engine,
                        # not a busy loop.
                        counters.idle_sleeps += 1
                        with span("serve.idle"):
                            time.sleep(
                                max(0.0, min(0.01, pending[0].arrival - (clock() - t0)))
                            )
                    continue
                # attributes as the step STARTS, after this boundary's
                # admissions (all 0 in the step that only collects, behind
                # an engine run empty); the fetches inside are of the step
                # BEFORE; the span's self time (duration less its children)
                # is the bookkeeping: token appends, the on_event callbacks.
                with span(
                    "serve.step", step=step + 1, occupied=len(busy),
                    waiting=len(waiting), free_pages=pool.free_count,
                    kv_tokens=sum(sl.seq_len for sl in busy),
                    kv_reserved=scfg.page_size
                    * sum(len(sl.pages.pages) for sl in busy),
                ):
                    ahead: List[_Unread] = []
                    _enqueue_step(ahead)
                    _collect(flight)
                    flight = ahead
                    step += 1
                    emit("step", {
                        "step": step,
                        # admitted, last token not yet on the host
                        "active": counters.admitted - completed,
                        "waiting": len(waiting) + len(pending),
                        "completed": completed,
                        "generated": generated,
                        "free_pages": pool.free_count,
                        "counters": counters,
                    })
        finally:
            # also when on_event raised: every span above is closed by
            # its ``with``, and the trace gets the counters' last values
            with span("serve.counters", **asdict(counters),
                      pool_peak_in_use=pool.peak_in_use,
                      pool_alloc_failures=pool.alloc_failures):
                pass

        wall = clock() - t0
        return RunResult(
            requests=list(requests), steps=step, wall_s=wall,
            generated_tokens=generated, free_pages_start=free_start,
            free_pages_end=pool.free_count, counters=counters,
            pool_peak_in_use=pool.peak_in_use,
            pool_alloc_failures=pool.alloc_failures, state=state,
        )
