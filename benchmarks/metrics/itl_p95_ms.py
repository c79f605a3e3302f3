"""engine layer: 95th percentile of the gap between a request's consecutive
output tokens, over all gaps of the window: the tail over itl_p50_ms. The
engine steps that carry prefill chunks decide it, and how many those are
follows the seed's arrangement (spread 20 % over seeds), so it has no bound."""
from benchmarks.stats import percentile


def read(run):
    gaps = run.samples.get("itl_s")
    return 1e3 * percentile(gaps, 0.95) if gaps else None
