"""Profiler capture: one switch around the hot loop.

The reference has no profiling story at all (SURVEY.md §5: per-sync
latency logs only); here any workload can capture an XLA trace by
pointing a directory at it — ``profile_dir`` in the workload dict. The
output is a TensorBoard-loadable xplane (host + device timelines, op
breakdown), written per process under ``<dir>/<process_index>`` so
multi-host gangs don't clobber each other.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional


@contextlib.contextmanager
def profile_ctx(trace_dir: Optional[str]) -> Iterator[None]:
    """jax.profiler.trace around the body when ``trace_dir`` is set; a
    no-op otherwise (so call sites need no branching)."""
    if not trace_dir:
        yield
        return
    import os

    import jax

    path = os.path.join(str(trace_dir), str(jax.process_index()))
    with jax.profiler.trace(path):
        yield
