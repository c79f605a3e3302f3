"""From the program's own spans in a profiler trace to numbers.

``serve/engine.py``, ``train/data.py`` and ``train/trainer.py`` write
``jax.profiler.TraceAnnotation`` spans (``serve.*``, ``train.*``; PERF.md
section 3 lists them) into the same xplane the device writes its ops to.
This reads that file a second time, beside ``trace_reduce``:

    python3 benchmarks/span_reduce.py <dir-or-file>      # describe a trace

- the span tree of every host thread, by containment, with self times;
  counters are the spans' attributes (``serve.counters`` holds the
  engine's final ``EngineCounters``);
- every device-idle interval of the ``bench.window``, cut at span and
  program boundaries and filed under exactly one of ``IDLE_CLASSES``, so
  the five sum to the idle time;
- device-busy seconds by program (one ``XLA Modules`` event per program
  run) and Pallas-kernel seconds by (program, kernel name);
- the longest ``*_fetch`` span with the device work under it: a trace that
  holds one of the 1.2-3.9 s pauses (PERF.md section 2) explains it.

A trace of a program without these spans (the parent of the PR that added
them) reduces to empty span lists: every span reader then returns None.
"""

from __future__ import annotations

import bisect
import glob
import os
import sys
from dataclasses import dataclass, field
from statistics import median
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

if not __package__:  # run as a file: make `benchmarks` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import trace_reduce as tr  # noqa: E402

PROGRAM_PREFIXES = ("serve.", "train.")
MODULES_LINE = "XLA Modules"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'  # every Pallas kernel
# where run_cell writes its traces (it empties the cell's directory first)
TRACE_HOME = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".cache", "bench_trace")
IDLE_CLASSES = ("in_program", "engine_empty", "device_side", "host_bound", "outside")


def idle_class(span_name: Optional[str]) -> str:
    """What a device-idle moment OUTSIDE any running program is filed under,
    from the innermost program span the serving thread is in: the engine is
    empty and asleep; the host already waits for the device's result; any
    other program span, or a step's own bookkeeping, is host work the
    device waits for; no program span at all is the benchmark's own code."""
    if span_name is None:
        return "outside"
    if span_name == "serve.idle":
        return "engine_empty"
    if span_name.endswith("_fetch"):
        return "device_side"
    return "host_bound"


@dataclass
class Span:
    name: str
    start: float
    end: float
    attrs: Dict[str, Any]
    children: List["Span"] = field(default_factory=list)

    @property
    def duration(self) -> float:
        return self.end - self.start

    @property
    def self_s(self) -> float:
        return self.duration - sum(c.duration for c in self.children)

    def walk(self) -> Iterable["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()


def build_tree(spans: Sequence[Span]) -> List[Span]:
    """Roots of ONE thread's spans nested by containment (context managers
    on one thread cannot overlap otherwise)."""
    roots: List[Span] = []
    stack: List[Span] = []
    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and not (stack[-1].start <= s.start and s.end <= stack[-1].end):
            stack.pop()
        (stack[-1].children if stack else roots).append(s)
        stack.append(s)
    return roots


def innermost_segments(roots: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """(start, end, name of the innermost span) over all the time any span
    of the thread covers: a span's own pieces are what its children leave."""
    out: List[Tuple[float, float, str]] = []
    for root in roots:
        for s in root.walk():
            kids = tr.union((c.start, c.end) for c in s.children)
            out += [(a, b, s.name) for a, b in tr.subtract([(s.start, s.end)], kids)]
    return out


def intersect(a: Sequence[tr.Interval], b: Sequence[tr.Interval]) -> List[tr.Interval]:
    """Parts of the merged intervals ``a`` that the merged ``b`` covers."""
    return tr.subtract(a, tr.subtract(a, b))


def program_of(module_event_name: str) -> str:
    """``jit_prefill_chunk(1282…)`` -> ``prefill_chunk``."""
    name = module_event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


@dataclass
class SpanReduced:
    window_s: float
    busy_s: float = 0.0                      # averaged over chips, like trace_reduce
    idle: Dict[str, float] = field(default_factory=dict)   # class -> seconds
    spans: Dict[str, List[Span]] = field(default_factory=dict)  # inside the window
    program_busy_s: Dict[str, float] = field(default_factory=dict)
    program_runs: Dict[str, int] = field(default_factory=dict)
    kernel_s: Dict[Tuple[str, str], float] = field(default_factory=dict)
    longest_fetch: Optional[Dict[str, Any]] = None

    @property
    def idle_s(self) -> float:
        return sum(self.idle.values())

    def counters(self) -> Dict[str, Any]:
        """The engine's final counters as the trace holds them (last run)."""
        found = self.spans.get("serve.counters")
        return dict(found[-1].attrs) if found else {}

    def kernel_seconds(self, program: str) -> float:
        return sum(v for (p, _), v in self.kernel_s.items() if p == program)

    # -- the per-layer metrics (None where the trace has nothing to read) ------

    def engine_host_step_ms(self) -> Optional[float]:
        """Median over ``serve.step`` of its duration less its ``*_fetch``
        children: the host time a step costs, during which nothing new
        reaches the device."""
        steps = self.spans.get("serve.step")
        if not steps:
            return None
        return 1e3 * median(
            s.duration - sum(c.duration for c in s.children
                             if c.name.endswith("_fetch")) for s in steps)

    def device_program_share(self, program: str) -> Optional[float]:
        if not self.program_busy_s or self.busy_s <= 0:
            return None
        return 100.0 * self.program_busy_s.get(program, 0.0) / self.busy_s

    def idle_share(self, cls: str) -> Optional[float]:
        """% of the window idle and filed under ``cls``; nothing to say
        about the span-made classes where the program wrote no span."""
        if not self.idle or (cls != "in_program" and not self.spans):
            return None
        return 100.0 * self.idle[cls] / self.window_s

    def decode_slot_occupancy(self) -> Optional[float]:
        calls = self.spans.get("serve.decode")
        if not calls:
            return None
        return 100.0 * sum(s.attrs["active"] for s in calls) \
            / sum(s.attrs["slots"] for s in calls)

    def prefill_padding_share(self) -> Optional[float]:
        calls = self.spans.get("serve.prefill")
        if not calls:
            return None
        return 100.0 * (1.0 - sum(s.attrs["n_valid"] for s in calls)
                        / sum(s.attrs["chunk"] for s in calls))

    def kv_reserved_unused_share(self) -> Optional[float]:
        steps = [s.attrs for s in self.spans.get("serve.step", ())
                 if s.attrs.get("kv_reserved")]
        if not steps:
            return None
        return 100.0 * sum(1.0 - a["kv_tokens"] / a["kv_reserved"]
                           for a in steps) / len(steps)

    def loader_wait_ms(self) -> Optional[float]:
        waits = self.spans.get("train.data_wait")
        return 1e3 * median(s.duration for s in waits) if waits else None

    def describe(self) -> Dict[str, Any]:
        return {
            "window_s": self.window_s, "busy_s": self.busy_s,
            "idle_s_by_class": dict(self.idle),
            "span_s": {k: sum(s.duration for s in v) for k, v in sorted(self.spans.items())},
            "span_self_s": {k: sum(s.self_s for s in v) for k, v in sorted(self.spans.items())},
            "span_n": {k: len(v) for k, v in sorted(self.spans.items())},
            "program_busy_s": self.program_busy_s, "program_runs": self.program_runs,
            "kernel_s": {f"{p}/{k}": v for (p, k), v in sorted(self.kernel_s.items())},
            "counters": self.counters(), "longest_fetch": self.longest_fetch,
        }


def _attrs(event) -> Dict[str, Any]:
    return dict(getattr(event, "stats", None) or ())


def reduce_planes(planes, n_devices: Optional[int] = None) -> SpanReduced:
    """``planes`` as ``trace_reduce.reduce_planes`` takes them; events of
    program spans may carry ``.stats`` (pairs or a dict): their attributes."""
    threads: List[List[Span]] = []   # program spans, one list per host thread
    main: Optional[int] = None       # the thread that holds bench.window
    windows: List[tr.Interval] = []
    devices = []
    for plane in planes:
        if plane.name.startswith(tr.DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if tr.OPS_LINE in lines:
                devices.append((plane.name, tr._events(lines[tr.OPS_LINE]),
                                tr._events(lines[MODULES_LINE])
                                if MODULES_LINE in lines else []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine: List[Span] = []
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES):
                        a = e.start_ns * 1e-9
                        mine.append(Span(e.name, a, a + e.duration_ns * 1e-9, _attrs(e)))
                    elif e.name == tr.WINDOW_ANNOTATION:
                        a = e.start_ns * 1e-9
                        windows.append((a, a + e.duration_ns * 1e-9))
                        main = len(threads)
                threads.append(mine)
    if not devices:
        raise ValueError(
            f"trace has no {tr.DEVICE_PLANE_PREFIX}* plane with an "
            f"{tr.OPS_LINE!r} line: planes {[p.name for p in planes]}")
    devices.sort(key=lambda d: d[0])
    if n_devices is not None:
        devices = devices[:n_devices]
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:  # a foreign trace: first op start to last op end, busiest thread
        lo = min(a for _, ev, _ in devices for _, a, _ in ev)
        hi = max(b for _, ev, _ in devices for _, _, b in ev)
        main = max(range(len(threads)), key=lambda i: len(threads[i]), default=None)

    out = SpanReduced(window_s=hi - lo)
    trees = [build_tree([s for s in t if s.start >= lo and s.end <= hi])
             for t in threads]
    for roots in trees:
        for root in roots:
            for s in root.walk():
                out.spans.setdefault(s.name, []).append(s)
    for v in out.spans.values():
        v.sort(key=lambda s: s.start)

    by_class: Dict[str, List[tr.Interval]] = {}
    for a, b, name in innermost_segments(trees[main]) if main is not None else ():
        by_class.setdefault(idle_class(name), []).append((a, b))
    by_class = {k: tr.union(v) for k, v in by_class.items()}

    n = len(devices)
    out.idle = dict.fromkeys(IDLE_CLASSES, 0.0)
    longest = max((s for k, v in out.spans.items() if k.endswith("_fetch")
                   for s in v), key=lambda s: s.duration, default=None)
    for d, (_, ops, modules) in enumerate(devices):
        every = [p for _, a, b in ops for p in tr.clip([(a, b)], lo, hi)]
        busy = tr.union(every)
        out.busy_s += tr.total(busy) / n
        runs = sorted((a, b, program_of(name)) for name, a, b in modules)
        running = tr.union(tr.clip([(a, b) for a, b, _ in runs], lo, hi))
        idle = tr.subtract([(lo, hi)], busy)
        out.idle["in_program"] += tr.total(intersect(idle, running)) / n
        rest = tr.subtract(idle, running)
        filed = 0.0
        for cls, where in by_class.items():
            if cls != "outside":
                part = tr.total(intersect(rest, where))
                out.idle[cls] += part / n
                filed += part
        out.idle["outside"] += (tr.total(rest) - filed) / n
        by_program: Dict[str, List[tr.Interval]] = {}
        for a, b, program in runs:
            piece = tr.clip([(a, b)], lo, hi)
            if piece:
                by_program.setdefault(program, []).extend(piece)
                if d == 0:
                    out.program_runs[program] = out.program_runs.get(program, 0) + 1
        for program, pieces in by_program.items():
            out.program_busy_s[program] = out.program_busy_s.get(program, 0.0) \
                + tr.total(intersect(tr.union(pieces), busy)) / n
        starts = [a for a, _, _ in runs]
        for text, a, b in ops:
            piece = tr.clip([(a, b)], lo, hi) if KERNEL_TARGET in text else None
            if not piece:
                continue
            i = bisect.bisect_right(starts, a) - 1
            program = runs[i][2] if i >= 0 and a < runs[i][1] else "(no program)"
            key = (program, tr.op_family(tr.parse_op(text)[0]))
            out.kernel_s[key] = out.kernel_s.get(key, 0.0) + tr.total(piece) / n
        if d == 0 and longest is not None:
            out.longest_fetch = _under(longest, lo, ops, runs, busy)
    return out


def _under(span: Span, lo: float, ops, runs, busy) -> Dict[str, Any]:
    """What the first device did while the host sat in ``span``."""
    a, b = span.start, span.end
    by_op: Dict[str, float] = {}
    for text, oa, ob in ops:
        s = tr.overlap((a, b), (oa, ob))
        if s > 0:
            _, opcode, label = tr.parse_op(text)
            if not tr.is_container(opcode):
                by_op[label] = by_op.get(label, 0.0) + s
    return {
        "span": span.name, "attrs": span.attrs, "seconds": span.duration,
        "at_s": a - lo, "device_busy_s": tr.total(tr.clip(busy, a, b)),
        "programs": sorted({p for ra, rb, p in runs if tr.overlap((a, b), (ra, rb)) > 0}),
        "top_ops": [[k, v] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:5]],
    }


def reduce_file(path: str, n_devices: Optional[int] = None) -> SpanReduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(tr.find_xplane(path)).planes, n_devices)


def newest_xplane() -> Optional[str]:
    files = glob.glob(os.path.join(TRACE_HOME, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def for_run(run) -> Optional[SpanReduced]:
    """The span reduction of the trace this run just wrote, parsed once and
    kept on the record; None for an untraced run. A reader's record has no
    path to the trace: it is the newest file under ``TRACE_HOME``."""
    if run.trace is None:
        return None
    if not hasattr(run, "spans"):
        path = newest_xplane()
        run.spans = reduce_file(path, run.chips) if path else None
        if run.spans is not None:
            _say(run, run.spans)
    return run.spans


def metric(run, name: str, *args) -> Optional[float]:
    """A reader's whole job: ``SpanReduced.<name>(*args)`` of this run's
    trace, or None where there is no trace."""
    spans = for_run(run)
    return None if spans is None else getattr(spans, name)(*args)


def _say(run, r: SpanReduced) -> None:
    idle = {k: 100.0 * v / r.window_s for k, v in r.idle.items()}
    run.say(f"note idle_share_by_class_pct: {idle!r} sum={sum(idle.values())!r} "
            f"device_idle_share={100.0 * run.trace.idle_share!r}")
    d = r.describe()
    for key in ("span_n", "span_s", "span_self_s", "program_busy_s",
                "program_runs", "kernel_s"):
        run.say(f"note {key}: {d[key]!r}")
    if r.counters():
        run.say(f"note engine_counters_in_trace: {r.counters()!r}")
    if r.longest_fetch:
        run.say(f"note longest_fetch: {r.longest_fetch!r}")
    traced = run.samples.get("traced") or {}
    for key in ("engine_step_s", "itl_s"):  # what the spans cost when on
        off, on = run.samples.get(key), traced.get(key)
        if off and on:
            run.say(f"note tracing_cost {key}: median untraced window "
                    f"{1e3 * median(off)!r} ms, traced window {1e3 * median(on)!r} ms")


# -- the paged-attention kernel's share of its roofline, by program --------------


def paged_calls(finished, chunk: int):
    """(prefill calls, decode calls) the finished requests needed, each a
    list of (rows, keys) as ``flops.paged_attention_cost`` takes them."""
    prefill, decode = [], []
    for _, prompt, tokens in finished:
        n = len(prompt)
        for start in range(0, n, chunk):
            rows = min(chunk, n - start)
            prefill.append((rows, start + rows))
        decode += [(1, n + j) for j in range(1, len(tokens))]
    return prefill, decode


def paged_attn_roofline(run, program: str) -> Optional[float]:
    """``flash_decode_roofline`` for the kernel events inside runs of
    ``program`` alone (``prefill_chunk`` or ``decode_step``), against the
    cost of that program's own calls."""
    from benchmarks import flops

    r = for_run(run)
    traced = run.samples.get("traced")
    if r is None or run.peaks is None or not traced:
        return None
    seconds = r.kernel_seconds(program)
    if seconds <= 0:
        return None
    prefill, decode = paged_calls(traced["finished"], traced["prefill_chunk"])
    f, b = flops.paged_attention_cost(
        run.sizes, prefill if program == "prefill_chunk" else decode)
    share, bound = flops.roofline_share_pct(f, b, seconds, run.peaks)
    run.say(f"note paged_attn_roofline {program}: bound by {bound}; {seconds!r} s in "
            f"{sorted(k for (p, k) in r.kernel_s if p == program)}")
    return share


if __name__ == "__main__":
    import json

    print(json.dumps(reduce_file(sys.argv[1]).describe(), indent=1, default=str))
