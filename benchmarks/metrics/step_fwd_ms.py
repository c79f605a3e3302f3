"""model layer: device op-milliseconds a step a chip in phase ``fwd``, every
section (``step_sections.py``: the ``train.program`` span names each op)."""
from benchmarks import step_sections


def read(run):
    return step_sections.metric(run, phase="fwd")
