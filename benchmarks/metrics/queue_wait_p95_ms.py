"""engine layer: 95th percentile of admitted - arrival over admitted requests."""
from benchmarks.stats import percentile


def read(run):
    waits = run.samples.get("queue_wait_s")
    return 1e3 * percentile(waits, 0.95) if waits else None
