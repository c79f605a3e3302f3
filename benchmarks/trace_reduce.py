"""From a profiler trace (``*.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. How
this backend names things (TPU v5 lite, jax 0.9.0, looked at by hand in
PR 23 — see PERF.md §3) is kept in the few constants below.

    python3 benchmarks/trace_reduce.py <dir-or-file>      # describe a trace

Per device plane: busy time is the UNION of the device-op intervals
inside the traced window, idle share is 1 - busy/window; device time by
op name, collectives and Pallas kernels told apart; the part of
collective time during which no other op runs on that device; and the
longest idle gaps, each labelled by the ``bench.*`` host annotation that
covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # [start, end) in seconds on the trace's clock

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW_ANNOTATION = "bench.window"
ANNOTATION_PREFIX = "bench."
COLLECTIVE_PREFIXES = (
    "all-gather", "reduce-scatter", "all-reduce", "all-to-all",
    "collective-permute",
)
# ops that only CONTAIN other ops on the same line (their time is their
# children's): left out of per-op totals, harmless to the busy union
CONTAINER_OPCODES = ("while", "conditional", "call")


# ---- interval arithmetic ------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by the merged ``b``."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# ---- the reduction -------------------------------------------------------------


_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


def parse_op(text: str) -> Tuple[str, str, str]:
    """An 'XLA Ops' event's name is the whole HLO instruction,
    ``%fusion.12 = f32[8]{...} fusion(...), kind=kLoop, calls=...``: give
    (short name ``fusion.12``, opcode ``fusion``, a label for people)."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, op_family(text), text[:120]
    short = head.lstrip("%")
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else op_family(short)
    kind = _KIND.search(rest)
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    label = " ".join(x for x in (short, kind.group(1) if kind else "", shape[:48]) if x)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    if target:
        label += " " + target.group(1)
    return short, opcode, label


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVE_PREFIXES)


def is_container(opcode: str) -> bool:
    return opcode in CONTAINER_OPCODES


def op_family(name: str) -> str:
    """Op name without XLA's instance suffix (``fusion.123`` -> ``fusion``)."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


@dataclass
class DeviceReduced:
    name: str
    busy_s: float
    ops: Dict[str, float]           # seconds by op label (see parse_op)
    texts: Dict[str, str]           # op label -> the HLO text, for matching
    collective_s: float
    collective_exposed_s: float
    gaps: List[Tuple[str, float]]   # (label, seconds), longest first


@dataclass
class Reduced:
    window_s: float
    devices: List[DeviceReduced] = field(default_factory=list)

    @property
    def busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def collective_exposed_s(self) -> float:
        return sum(d.collective_exposed_s for d in self.devices) / len(self.devices)

    def op_seconds(self, *needles: str) -> float:
        """Device seconds, averaged over chips, of ops whose HLO text
        contains any of ``needles``."""
        s = sum(v for d in self.devices for k, v in d.ops.items()
                if any(n in d.texts[k] for n in needles))
        return s / len(self.devices)

    def op_names(self, *needles: str) -> List[str]:
        return sorted({k for d in self.devices for k in d.ops
                       if any(n in d.texts[k] for n in needles)})

    def top_ops(self, n: int) -> List[List]:
        tot: Dict[str, float] = {}
        for d in self.devices:
            for k, v in d.ops.items():
                tot[k] = tot.get(k, 0.0) + v / len(self.devices)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int) -> List[List]:
        """Idle seconds of the first device by what the host was doing."""
        by: Dict[str, float] = {}
        for label, s in self.devices[0].gaps:
            by[label] = by.get(label, 0.0) + s
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def reduce_planes(planes, n_devices: Optional[int] = None) -> Reduced:
    """``planes``: objects with ``.name`` and ``.lines`` (each with ``.name``
    and ``.events`` of ``.name/.start_ns/.duration_ns``) — ProfileData's
    own, or a test's stand-ins."""
    host: List[Tuple[str, float, float]] = []
    device_lines = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: _events(line) for line in plane.lines}
            if OPS_LINE in lines:
                device_lines.append(
                    (plane.name, lines[OPS_LINE], lines.get(ASYNC_LINE, [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e[0].startswith(ANNOTATION_PREFIX)]
    if not device_lines:
        raise ValueError(
            f"trace has no {DEVICE_PLANE_PREFIX}* plane with an {OPS_LINE!r} "
            f"line: planes {[p.name for p in planes]}")
    device_lines.sort(key=lambda d: d[0])
    if n_devices is not None:
        device_lines = device_lines[:n_devices]
    windows = [(a, b) for name, a, b in host if name == WINDOW_ANNOTATION]
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:  # no annotation (a foreign trace): first op start to last op end
        lo = min(a for _, ev, _ in device_lines for _, a, _ in ev)
        hi = max(b for _, ev, _ in device_lines for _, _, b in ev)
    spans = [(n, a, b) for n, a, b in host if n != WINDOW_ANNOTATION]

    out = Reduced(window_s=hi - lo)
    for plane_name, events, async_events in device_lines:
        ops: Dict[str, float] = {}
        texts: Dict[str, str] = {}
        coll, other, every = [], [], []
        for text, a, b in events:
            piece = clip([(a, b)], lo, hi)
            if not piece:
                continue
            every += piece
            _, opcode, label = parse_op(text)
            if is_container(opcode):
                continue
            ops[label] = ops.get(label, 0.0) + total(piece)
            texts[label] = text
            (coll if is_collective(opcode) else other).extend(piece)
        # an async collective is a start and a done op on the ops line and
        # one span from the one to the other on the async line
        for text, a, b in async_events:
            if is_collective(parse_op(text)[1]):
                coll += clip([(a, b)], lo, hi)
        busy = union(every)
        coll_u, other_u = union(coll), union(other)
        gaps = []
        for a, b in subtract([(lo, hi)], busy):
            best = max(spans, key=lambda s: overlap((a, b), (s[1], s[2])),
                       default=None)
            label = best[0] if best and overlap((a, b), (best[1], best[2])) > 0 \
                else "(no annotation)"
            gaps.append((label, b - a))
        gaps.sort(key=lambda g: -g[1])
        out.devices.append(DeviceReduced(
            name=plane_name, busy_s=total(busy), ops=ops, texts=texts,
            collective_s=total(coll_u),
            collective_exposed_s=total(subtract(coll_u, other_u)),
            gaps=gaps,
        ))
    return out


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return files[-1]


def reduce_dir(path: str, n_devices: Optional[int] = None) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(find_xplane(path)).planes, n_devices)


def describe(path: str, per_line: int = 6) -> None:
    """Print planes, lines and the first events of each: the look by hand."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(find_xplane(path)).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            ev = list(line.events)
            print(f"  LINE {line.name!r}: {len(ev)} events")
            for e in ev[:per_line]:
                print(f"    {e.name!r} start_ns={e.start_ns} dur_ns={e.duration_ns} "
                      f"stats={dict(list(e.stats)[:6])}")


if __name__ == "__main__":
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6)
    r = reduce_dir(sys.argv[1])
    print(f"window_s={r.window_s} busy_s={r.busy_s} idle_share={r.idle_share}")
    print("top ops:", r.top_ops(15))
    print("top gaps:", r.top_gaps(10))
