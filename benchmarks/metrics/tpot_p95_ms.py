"""engine layer: per finished request (last token - first token) / (tokens - 1),
95th percentile over requests: the slowest streams, which prefill chunks of
other requests' long prompts interrupt most (spread 19-24 % over seeds)."""
from benchmarks.stats import percentile


def read(run):
    tpot = run.samples.get("tpot_s")
    return 1e3 * percentile(tpot, 0.95) if tpot else None
