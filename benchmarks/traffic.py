"""The one general traffic generator: every mix is a data file of
parameters under ``benchmarks/traffic/`` that these functions read.

Serving: ``rate * seconds`` requests, open loop. Every seed gets the SAME set
of prompt lengths, output lengths and arrival gaps — the quantiles of the
mix's log-normal lengths and of the Poisson process's exponential gaps at
(i + 0.5) / n — each in an order of its own drawn from the seed. So the
work a mix OFFERS does not change with the seed, and what the seed draws is
what real arrivals draw: which requests follow which, how closely, and
where the long ones clump. A mix whose window serves only part of what it
offers (above capacity) also sets ``stratify_every``, so that the part
served carries the same work too (see ``seeded_order``). The length arithmetic follows
``tf_operator_tpu/workloads/serve.py:47-75`` (seeded arrivals, ragged
prompts and budgets) with the mix's distributions in place of its uniform
ones.
"""

from __future__ import annotations

import json
import os
from statistics import NormalDist
from typing import Any, Dict, Iterator, List

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = _HERE) -> Dict[str, Any]:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


# ---- training ---------------------------------------------------------------


def token_rows(seed: int, vocab: int, rows: int, seq_len: int) -> np.ndarray:
    """``rows`` distinct seeded token sequences."""
    return np.random.default_rng(seed).integers(
        0, vocab, (rows, seq_len), dtype=np.int32)


def token_batches(seed: int, vocab: int, mix) -> Iterator[Dict[str, np.ndarray]]:
    """Endless batches [batch_size, seq_len] walking the seeded rows."""
    b, n = int(mix["batch_size"]), int(mix.get("rows", 64))
    data = token_rows(seed, vocab, n, int(mix["seq_len"]))
    i = 0
    while True:
        yield {"tokens": data[(i * b + np.arange(b)) % n]}
        i += 1


# ---- serving ----------------------------------------------------------------


def length_quantiles(n: int, spec) -> np.ndarray:
    """``n`` integer lengths: the (i + 0.5)/n quantiles of the log-normal
    (median, sigma) that ``spec`` gives, clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(np.int64)


def gap_quantiles(n: int, rate: float) -> np.ndarray:
    """``n`` gaps between arrivals: the (i + 0.5)/n quantiles of the
    exponential distribution of a Poisson process at ``rate`` a second."""
    return -np.log1p(-(np.arange(n) + 0.5) / n) / rate


def seeded_order(n: int, every: int, rng) -> np.ndarray:
    """An order of ``n`` sorted quantiles drawn from ``rng``. ``every`` 0: any
    of the n! orders. ``every`` k: the quantiles are cut into k bands (the
    lowest n/k, the next n/k, ...), and every run of k consecutive requests
    holds one quantile of each band — which one, and in what order, the seed
    draws band by band — so any stretch of the window carries nearly the
    same work, which a mix needs when its window serves only part of what it
    offers."""
    if not every:
        return rng.permutation(n)
    runs = -(-n // every)
    bands = [rng.permutation(np.arange(b * runs, min((b + 1) * runs, n)))
             for b in range(every)]
    return np.concatenate([
        rng.permutation([band[r] for band in bands if r < len(band)])
        for r in range(runs)])


def requests(seed: int, vocab: int, mix, seconds: float) -> List[Dict[str, Any]]:
    """The window's requests: rate * seconds of them, each set of quantiles
    in an order drawn from ``seed`` (``mix["stratify_every"]``, default 0,
    goes to ``seeded_order``), due times the running sum of the gaps."""
    rng = np.random.default_rng(seed)
    rate = float(mix["rate_per_s"])
    every = int(mix.get("stratify_every", 0))
    n = max(1, int(round(rate * seconds)))
    prompts = length_quantiles(n, mix["prompt_len"])[seeded_order(n, every, rng)]
    outputs = length_quantiles(n, mix["output_len"])[seeded_order(n, every, rng)]
    due = np.cumsum(gap_quantiles(n, rate)[seeded_order(n, every, rng)])
    return [
        {
            "rid": i,
            "prompt": rng.integers(1, vocab, size=int(prompts[i])).tolist(),
            "max_new": int(outputs[i]),
            "arrival": float(due[i]),
        }
        for i in range(n)
    ]
