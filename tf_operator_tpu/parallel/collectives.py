"""Collective helpers for shard_map bodies.

Thin, named wrappers over the XLA collectives (psum / all_gather /
reduce_scatter / ppermute) — the framework NEVER reimplements collectives
(SURVEY.md §2.3: the reference delegated them to TF's runtime; we delegate
to XLA, which maps them onto ICI rings).
"""

from __future__ import annotations

import functools
import math
import re
from typing import Any, Dict, List, Optional, Tuple

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


# ---- what a COMPILED program's collectives and kernels are ---------------
# Read from the optimised HLO text (``jax.stages.Compiled.as_text()``), the
# way ``serve.engine.pool_copies`` reads whole-pool copies: a partition that
# propagation chose badly shows here, at compile time, as a large collective
# inside a ``while`` body — before a chip has run a step; so does a remat
# policy that replays a kernel it was meant to retire, or that saves more
# than the chip holds (the compiler then rebuilds values on its own).

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")
_COLLECTIVE_INSTR = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (?P<type>.*?) (?P<kind>" + "|".join(COLLECTIVE_KINDS)
    + r")(?P<start>-start)?\(")
_HLO_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_CALLED = re.compile(
    r"\b(body|calls|to_apply|branch_computations|called_computations)="
    r"(?:%([\w.\-]+)|\{([^}]*)\})")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}


def _computations(hlo_text: str) -> Tuple[Dict[str, List[str]], str]:
    """({computation name: its instruction lines}, the ENTRY's name) of an
    HLO module's text."""
    comps: Dict[str, List[str]] = {}
    name = entry = None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            is_entry = line.startswith("ENTRY")
            name = line.split()[1 if is_entry else 0].lstrip("%")
            comps[name] = []
            entry = name if is_entry else entry
        elif name is not None:
            comps[name].append(line)
    return comps, entry


_PALLAS_CALL = re.compile(
    r"%([\w\-]+?)(?:\.(?:\d+|remat\d*))* = [^\n]*custom-call\([^\n]*"
    r'custom_call_target="tpu_custom_call"')


_TRANSFORM_PREFIX = re.compile(r"^(?:(?:jvp|transpose|vmap|checkpoint|remat)_)+")


def compiled_kernels(hlo_text: str) -> Dict[str, int]:
    """The Pallas (Mosaic) kernels of a compiled program by name: how many
    ``tpu_custom_call`` INSTRUCTIONS carry each ``pallas_call`` name
    (``%flash_fwd.3 = ... custom-call(...)`` counts under ``flash_fwd``;
    one in a ``while`` body is one instruction, whatever its trips). The
    instance suffix and the transform wrappers jax adds outside a scan are
    cut (``transpose_jvp_flash_bwd_dqkv__.1`` is a ``flash_bwd_dqkv``), and so
    is the compiler's mark on a clone it rebuilds for want of memory
    (``%flash_fwd.3.remat2`` is one more ``flash_fwd``: a replay, the very
    thing the count is kept to show). The profiler's trace prints an op as
    its instruction WITHOUT metadata; what part of the model any other
    instruction belongs to is ``compiled_sections``' to say, from the
    ``sec_*`` scopes on the metadata this text still has.
    Empty off the TPU: the interpreter and the jnp references lower to
    plain HLO."""
    out: Dict[str, int] = {}
    for name in _PALLAS_CALL.findall(hlo_text):
        name = _TRANSFORM_PREFIX.sub("", name).rstrip("_")
        out[name] = out.get(name, 0) + 1
    return dict(sorted(out.items()))


_COMPILER_REMAT = re.compile(
    r"^\s*(?:ROOT )?%[\w\-.]*\.remat\d*(?:\.\d+)* = "
    r"(?![^\n]*? get-tuple-element\()", re.M)


def compiled_remats(hlo_text: str) -> int:
    """How many instructions of a compiled program are the COMPILER's own
    rematerialisations: clones XLA's scheduler made (``%fusion.382.remat2``,
    ``%copy.218.remat``) because the program did not fit the chip with
    every value held to its last use — it then compiles and runs, slower,
    instead of failing (PERF.md §6, PR 33: three names a layer where there
    was room for two cost the dense step an MLP matmul a layer). The reads
    of a rebuilt tuple (``%gte.remat``) are no work and are not counted;
    jax's own ``remat2`` regions carry the word without the dot. 0 is a
    step whose remat policy fits; more says the policy saves more than the
    chip holds at this batch."""
    return len(_COMPILER_REMAT.findall(hlo_text))


SECTION_PHASES = ("fwd", "bwd", "replay", "optimizer", "other")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = .*? (?P<opcode>[a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SECTION = re.compile(r"\bsec_([a-z0-9_]+)")
_NAME_REMAT = re.compile(r"\.remat\d*(?:\.\d+)*$")
_OPERAND = re.compile(r"%([\w.\-]+)")
# computations that run as part of ONE instruction of another (a fusion's
# body, a reduction's combiner): the trace shows the instruction, not them
_INLINED = re.compile(r" (?:fusion|reduce|reduce-window|scatter|select-and-scatter|"
                      r"sort|map|all-reduce|reduce-scatter)(?:-start)?\(.*?"
                      r"\b(?:calls|to_apply)=%([\w.\-]+)")
# opcodes that are no work of their own on the device's op line
_NO_WORK = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "opt-barrier",
    "partition-id", "replica-id"))


def _op_path(line: str, comps: Dict[str, List[str]]) -> str:
    """An instruction's ``op_name``; for a fusion the compiler left bare
    (the CPU backend's all are) its root's, else that of the named
    instruction nearest its root; "" where there is none."""
    op = _OP_NAME.search(line)
    called = None if op else re.search(r"\bcalls=%([\w.\-]+)", line)
    for inner in reversed(comps.get(called.group(1), ())) if called else ():
        op = _OP_NAME.search(inner)
        if op:
            break
    return op.group(1) if op else ""


def compiled_sections(hlo_text: str) -> Dict[str, List[str]]:
    """What part of the model and of the step every instruction of a compiled
    program belongs to, as ``{"<section>.<phase>": [instruction names]}`` —
    the names a profiler trace prints, each in exactly one key. Every
    instruction the device runs as an op of its own is filed (fusions,
    custom calls, dots, convolutions, copies, collectives, the few plain
    ops XLA leaves unfused; in the entry and every ``while`` body or
    branch — not inside a fusion or a reduction's combiner).

    Both halves come from the ``op_name`` jax wrote on the instruction.
    Section: the innermost ``sec_*`` ``jax.named_scope`` on it
    (``…/closed_call/sec_mlp/dot_general`` and ``transpose(jvp(sec_embed))/…``
    alike), without the prefix. Phase: ``replay`` where the path holds
    jax's ``rematted_computation`` or the instruction's name the compiler's
    own ``.remat`` mark (``compiled_remats``); else ``bwd`` under
    ``transpose(``; else ``fwd`` under ``jvp(``; else ``optimizer`` in
    section ``optimizer``; else ``other`` (what was hoisted out of the
    differentiated function: masks, casts of constants). A fusion that
    spans two sections is filed where XLA's own metadata puts it — as a
    rule the matmul it was built around.

    An instruction with no scope on it — as a rule one the compiler made
    itself, without metadata: the ``copy-start`` / ``copy-done`` and
    ``slice-start`` / ``slice-done`` pairs that prefetch an operand into
    fast memory, a layout ``copy`` — works for whatever reads its result,
    and is filed with the nearest reader that has a section: through
    bitcasts and the like, through a tuple to the ``get-tuple-element`` of
    ITS index, and through a ``while``'s operand to what reads that element
    of the carry inside the body (a weight cast hoisted out of the layer
    scan is the first matmul's that reads it), at most eight hops. ``none``
    is what is left: nothing reads it, or no reader has a scope either."""
    comps, _ = _computations(hlo_text)
    inlined = set(_INLINED.findall(hlo_text))
    filed: Dict[str, Tuple[str, str]] = {}   # instruction -> (section, phase)
    readers: Dict[str, List[str]] = {}       # instruction -> who reads it
    tuples: Dict[str, List[str]] = {}        # a tuple -> its operands, in order
    elements: Dict[Tuple[str, int], List[str]] = {}  # (its source, index) -> get-tuple-elements
    parameter: Dict[str, str] = {}           # computation -> its parameter (a body has one)
    body_of: Dict[str, str] = {}             # a while -> its body
    opaque = set()                           # results that are not their operands'
    for comp, lines in comps.items():
        if comp in inlined:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            name, opcode = m.group("name"), m.group("opcode")
            at = m.end()  # just past the opcode's "("
            operands = _OPERAND.findall(line[at:line.index(")", at)])
            for operand in operands:
                readers.setdefault(operand, []).append(name)
            if opcode == "tuple":
                tuples[name] = operands
            elif opcode == "get-tuple-element":
                index = int(re.search(r"\bindex=(\d+)", line).group(1))
                elements.setdefault((operands[0], index), []).append(name)
            elif opcode == "parameter":
                parameter[comp] = name
            elif opcode == "while":
                body_of[name] = re.search(r"\bbody=%([\w.\-]+)", line).group(1)
            elif opcode in ("conditional", "call"):
                opaque.add(name)
            if opcode in _NO_WORK:
                continue
            path = _op_path(line, comps)
            found = _SECTION.findall(path)
            if "rematted_computation" in path or _NAME_REMAT.search(name):
                phase = "replay"
            elif "transpose(" in path:
                phase = "bwd"
            elif "jvp(" in path:
                phase = "fwd"
            else:
                phase = "optimizer" if found and found[-1] == "optimizer" else "other"
            filed[name] = (found[-1] if found else "none", phase)

    def seen_through(name: str, reader: str) -> List[str]:
        """Where ``name`` goes on through ``reader``, which has no section."""
        if reader in body_of or reader in opaque:
            return []
        if reader not in tuples:
            return [reader]
        out = []  # by index: into a loop's body, else to the tuple's own elements
        for index, operand in enumerate(tuples[reader]):
            for user in readers.get(reader, ()) if operand == name else ():
                source = parameter.get(body_of[user], "") if user in body_of else user
                out += elements.get((source, index), [])
        return out

    def reader_with_a_section(name: str, hops: int = 8) -> Optional[Tuple[str, str]]:
        for reader in readers.get(name, ()) if hops else ():
            got = filed.get(reader)
            if got is not None and got[0] != "none":
                return got
            for onward in seen_through(name, reader):
                got = reader_with_a_section(onward, hops - 1)
                if got is not None:
                    return got
        return None

    out: Dict[str, List[str]] = {}
    for name, (section, phase) in filed.items():
        if section == "none":
            got = reader_with_a_section(name)
            if got is not None:  # its own ``.remat`` mark still says replay
                section, phase = got[0], "replay" if phase == "replay" else got[1]
        out.setdefault(f"{section}.{phase}", []).append(name)
    return dict(sorted(out.items()))


def sections_summary(sections: Dict[str, List[str]]) -> Dict[str, int]:
    """``compiled_sections`` as instruction counts by section."""
    out: Dict[str, int] = {}
    for key, names in sections.items():
        section = key.rsplit(".", 1)[0]
        out[section] = out.get(section, 0) + len(names)
    return out


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\[\d+,(\d+)\]", line)  # iota form [groups, size]
    if m:
        return int(m.group(1))
    m = re.search(r"replica_groups=\{\{([\d,]*)\}", line)
    return len(m.group(1).split(",")) if m else 1


def _trip_count(while_line: str, comps: Dict[str, List[str]]) -> int:
    """Trips of a ``while``: the compiler's ``known_trip_count`` where it
    prints one (CPU), else the bound its condition compares the counter
    against (TPU: ``compare(i, constant(N)), direction=LT`` — what a
    ``lax.scan`` lowers to); 1 where neither can be read."""
    m = re.search(r'"known_trip_count":\{"n":"(\d+)"', while_line)
    if m:
        return int(m.group(1))
    cond = re.search(r"\bcondition=%([\w.\-]+)", while_line)
    lines = comps.get(cond.group(1), ()) if cond else ()
    bounds = [int(n) for ln in lines
              for n in re.findall(r" s32\[\]\S* constant\((\d+)\)", ln)]
    if len(bounds) == 1 and any("direction=LT" in ln for ln in lines):
        return bounds[0]
    return 1


def compiled_collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """Every collective instruction of a compiled program, sync or the
    ``-start`` of an async pair, in any computation (the TPU backend wraps
    async ones in fusions): ``kind``; ``shapes``, the arrays on the
    instruction's FULL side (an all-gather's results, a reduce-scatter's
    operands, else either); ``bytes`` of its operands; ``runs``, how often
    a step executes it (the product of the trips of the ``while`` bodies
    around it, ``_trip_count``); ``in_loop``; and ``op_name``, the jax op it
    was made for."""
    comps, entry = _computations(hlo_text)

    # computation -> [(caller, executions of it a run of the caller)]
    callers: Dict[str, List[Tuple[str, int, bool]]] = {}
    for comp, lines in comps.items():
        for line in lines:
            for key, one, many in _CALLED.findall(line):
                if key == "to_apply" and _COLLECTIVE_INSTR.match(line):
                    continue  # the reduction's scalar combiner, not a call
                trips = _trip_count(line, comps) if key == "body" else 1
                for callee in [one] if one else re.findall(r"%([\w.\-]+)", many):
                    callers.setdefault(callee, []).append((comp, trips, key == "body"))

    seen: Dict[str, Tuple[int, bool]] = {}

    def runs(comp: str) -> Tuple[int, bool]:
        if comp == entry:
            return 1, False
        if comp not in seen:
            seen[comp] = (0, False)  # a cycle cannot occur in HLO; be safe
            total, looped = 0, False
            for caller, trips, is_body in callers.get(comp, ()):
                n, in_loop = runs(caller)
                total += n * trips
                looped |= (is_body or in_loop) and n > 0
            seen[comp] = (total, looped)
        return seen[comp]

    out = []
    for comp, lines in comps.items():
        for line in lines:
            m = _COLLECTIVE_INSTR.match(line)
            if not m:
                continue
            kind, n_runs = m.group("kind"), runs(comp)
            arrays = [(d, [int(x) for x in dims.split(",") if x])
                      for d, dims in _HLO_ARRAY.findall(m.group("type"))]
            if m.group("start") and kind in ("all-gather", "collective-permute"):
                # (operands..., results..., [two u32 contexts])
                arrays = [a for a in arrays if a[1] or a[0] != "u32"]
                arrays = arrays[len(arrays) // 2:]
            group = _group_size(line)
            nbytes = sum(_DTYPE_BYTES.get(d, 4) * math.prod(s) for d, s in arrays)
            if kind == "all-gather":
                nbytes //= group
            elif kind == "all-reduce" and comp.startswith("all-reduce-scatter"):
                # the TPU backend's reduce-scatter: an all-reduce and the
                # slice of it, in a fusion of that name
                kind = "reduce-scatter"
            elif kind == "reduce-scatter":
                nbytes *= group
                dim = re.search(r"dimensions=\{(\d+)\}", line)
                if dim:
                    for _, s in arrays:
                        s[int(dim.group(1))] *= group
            op = re.search(r'op_name="([^"]*)"', line)
            out.append({
                "kind": kind,
                "shapes": [f"{d}[{','.join(map(str, s))}]" for d, s in arrays],
                "bytes": nbytes, "runs": n_runs[0], "in_loop": n_runs[1],
                "op_name": op.group(1) if op else "",
            })
    return out


def collectives_summary(ops: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``compiled_collectives`` by kind, as a step executes them: ``count``
    and operand ``bytes`` (an instruction in a ``while`` body counts its
    trips), ``in_loop_max_bytes`` (the largest operand of that kind that
    sits in a ``while`` body; 0: none does), and the ``largest`` single
    instruction of all."""
    out: Dict[str, Any] = {}
    for op in ops:
        k = out.setdefault(
            op["kind"], {"count": 0, "bytes": 0, "in_loop_max_bytes": 0})
        k["count"] += op["runs"]
        k["bytes"] += op["runs"] * op["bytes"]
        if op["in_loop"]:
            k["in_loop_max_bytes"] = max(k["in_loop_max_bytes"], op["bytes"])
    if ops:
        top = max(ops, key=lambda op: op["bytes"])
        out["largest"] = f"{top['kind']} {' '.join(top['shapes'])}"
    return out
