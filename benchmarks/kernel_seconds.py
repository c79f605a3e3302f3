"""Device seconds of Pallas kernels by NAME in a reduced trace.

Since PR 24 every ``pallas_call`` of the program has a ``name``, which is
the instruction's name in the trace (``flash_fwd.3``, ``gmm_dw.1``; a call
that sits in a jvp / transpose wrapper reads ``transpose_jvp_gmm_dx__``
and still carries it). An op is matched by the name of the instruction
itself — the text before `` = `` — never by the rest of its HLO text,
where a kernel's name also appears as an operand of the ops that read its
result.
"""

from __future__ import annotations

from typing import List

from benchmarks.trace_reduce import parse_op


def _matches(trace, kernels):
    for d in trace.devices:
        for label, seconds in d.ops.items():
            name = parse_op(d.texts[label])[0]
            if any(k in name for k in kernels):
                yield label, seconds


def seconds(trace, *kernels: str) -> float:
    """Summed device time, averaged over chips, of the ops whose
    instruction name contains any of ``kernels``."""
    return sum(s for _, s in _matches(trace, kernels)) / len(trace.devices)


def names(trace, *kernels: str) -> List[str]:
    return sorted({label for label, _ in _matches(trace, kernels)})
