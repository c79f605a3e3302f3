"""engine layer: median wall time between engine steps that decoded."""
from statistics import median


def read(run):
    gaps = run.samples.get("engine_step_s")
    return 1e3 * median(gaps) if gaps else None
