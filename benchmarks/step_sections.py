"""Device op-seconds of a traced train step by SECTION of the model and
PHASE of the step.

``Trainer.step`` writes one zero-length ``train.program`` span into every
profiler session: the compiled step's module name (``program``) and, one
attribute a ``<section>.<phase>``, the names of its instructions
(``parallel.collectives.compiled_sections``: section from the ``sec_*``
scopes in the model, phase forward / backward / replay / optimizer from
jax's own path). An ``XLA Ops`` event's name is that instruction, so the
trace names its own ops:

    python3 benchmarks/step_sections.py <dir-or-file>      # any captured trace

Seconds are SUMS of op durations (what ``device_ops`` in a result line's
``breakdown`` are), averaged over chips, a step: an async-collective fusion
that overlaps a matmul counts both, and the union busy time is printed
beside the sum. An op the map does not name (another program's, or an
instruction the compile-time parse does not file) is ``none.other``, so
the table's cells add up to every op-second of the window.

A trace of a program without the span (the parent of the PR that added
it) reads None in every reader.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass
from statistics import median
from typing import Any, Dict, List, Optional, Tuple

if not __package__:  # run as a file: make `benchmarks` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import span_reduce as sr  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402

SPAN = "train.program"
UNNAMED = "none.other"


@dataclass
class StepSections:
    program: str
    steps: int
    cells: Dict[Tuple[str, str], float]   # (section, phase) -> op-ms a step a chip
    ops: List[Tuple[str, float, str]]     # (op label, ms a step a chip, "<section>.<phase>"), longest first
    busy_ms: float                        # union busy time a step a chip
    attr_bytes: int                       # what the span's attributes weigh

    @property
    def total_ms(self) -> float:
        return sum(self.cells.values())

    def ms(self, sections: Tuple[str, ...] = (), phase: Optional[str] = None) -> float:
        """Op-ms a step a chip in ``sections`` (all when empty), in ``phase``
        (all when None)."""
        return sum(v for (s, p), v in self.cells.items()
                   if (not sections or s in sections) and phase in (None, p))

    def unattributed_share(self) -> float:
        return 100.0 * self.ms(("none",)) / self.total_ms

    def describe(self, top: int = 10) -> Dict[str, Any]:
        table: Dict[str, Dict[str, float]] = {}
        for (section, phase), v in sorted(self.cells.items()):
            table.setdefault(section, {})[phase] = v
        return {
            "program": self.program, "steps": self.steps,
            "op_ms": self.total_ms, "busy_ms": self.busy_ms,
            "unattributed_pct": self.unattributed_share(),
            "program_attr_bytes": self.attr_bytes, "table": table,
            "top_ops": [list(o) for o in self.ops[:top]],
            "top_none": [list(o) for o in self.ops if o[2].startswith("none.")][:5],
        }


def reduce(trace: tr.Reduced, attrs: Dict[str, Any], steps: int) -> StepSections:
    """``trace``'s op seconds filed by the ``train.program`` span's ``attrs``."""
    where = {name: key for key, names in attrs.items() if key != "program"
             for name in str(names).split()}
    n = len(trace.devices) * steps
    cells: Dict[Tuple[str, str], float] = {}
    ops: Dict[str, Tuple[float, str]] = {}
    for d in trace.devices:
        for label, seconds in d.ops.items():
            key = where.get(tr.parse_op(d.texts[label])[0], UNNAMED)
            ms = 1e3 * seconds / n
            cell = tuple(key.rsplit(".", 1))
            cells[cell] = cells.get(cell, 0.0) + ms
            ops[label] = (ops.get(label, (0.0, key))[0] + ms, key)
    return StepSections(
        program=str(attrs.get("program", "")), steps=steps, cells=cells,
        ops=sorted(((k, v, key) for k, (v, key) in ops.items()), key=lambda o: -o[1]),
        busy_ms=1e3 * trace.busy_s / steps,
        attr_bytes=sum(len(str(v)) for v in attrs.values()))


def for_run(run) -> Optional[StepSections]:
    """The table of this run's traced steps, made once and kept on the
    record; None for an untraced run and for a trace without the span."""
    if not hasattr(run, "step_sections"):
        spans = sr.for_run(run)
        found = spans.spans.get(SPAN) if spans is not None else None
        steps = int((run.samples.get("traced") or {}).get("steps", 0))
        run.step_sections = None
        if found and steps:
            run.step_sections = reduce(run.trace, found[-1].attrs, steps)
            _say(run, run.step_sections)
    return run.step_sections


def metric(run, sections: Tuple[str, ...] = (), phase: Optional[str] = None) -> Optional[float]:
    """A reader's whole job: op-ms a step a chip of ``sections`` / ``phase``."""
    table = for_run(run)
    return None if table is None else table.ms(sections, phase)


def unattributed_share(run) -> Optional[float]:
    table = for_run(run)
    return None if table is None else table.unattributed_share()


def _say(run, table: StepSections) -> None:
    run.say(f"note step_sections_ms: {table.describe()!r}")
    off, on = run.samples.get("step_s"), run.samples["traced"].get("step_s")
    if off and on:  # what the profiler session costs a step
        run.say(f"note tracing_cost step_s: median untraced window "
                f"{1e3 * median(off)!r} ms, traced window {1e3 * median(on)!r} ms")


def reduce_trace(planes) -> Optional[StepSections]:
    """The table of any captured trace's planes: the span is read from the
    host planes directly (a trace without ``bench.window`` starts at its
    first device op, after the span), the steps are the program's runs."""
    planes = list(planes)  # ProfileData's are a one-shot iterator
    found = [dict(e.stats) for p in planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events if e.name == SPAN]
    if not found:
        return None
    runs = sr.reduce_planes(planes).program_runs
    steps = runs.get(sr.program_of(str(found[-1].get("program", ""))), 0)
    return reduce(tr.reduce_planes(planes), found[-1], steps or 1)


if __name__ == "__main__":
    import json

    from jax.profiler import ProfileData

    reduced = reduce_trace(ProfileData.from_file(tr.find_xplane(sys.argv[1])).planes)
    print(json.dumps(reduced.describe(int(sys.argv[2]) if len(sys.argv) > 2 else 10)
                     if reduced else None, indent=1))
