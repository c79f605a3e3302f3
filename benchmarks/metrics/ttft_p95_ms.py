"""engine layer: 95th percentile of first token - due time over every request
of the window; a request without a first token counts as +inf. With some
tens of requests a window it is a property of the seed's arrangement
(spread 35 % over seeds, PERF.md section 2), so it carries no bound."""
import math

from benchmarks.stats import percentile


def read(run):
    ttft = run.samples.get("ttft_s")
    if not ttft:
        return None
    v = 1e3 * percentile(ttft, 0.95)
    return v if math.isfinite(v) else None
