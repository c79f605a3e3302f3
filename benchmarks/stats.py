"""The one percentile every reader and runner uses."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile; a missing sample is +inf and sorts last."""
    ys = sorted(values)
    return ys[max(0, math.ceil(q * len(ys)) - 1)]
