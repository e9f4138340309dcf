"""The one traffic generator: reads a traffic mix's parameters (a JSON
file under ``bench/traffic/``) and makes the requests of a run from
``--seed``.

Every seed gets the same set of requests: prompt lengths, output
lengths and inter-arrival gaps at evenly spaced quantiles of the stated
distributions, paired and ordered by a permutation that the mix fixes
(``order_seed``).  The run's seed draws the token ids and the weights.

Why the order is not the seed's: at this system's knee a 51-s window
holds some 14 chat requests, and which of them overlap sets the
medians.  Drawn from the seed, the order moved the TPOT and TTFT
medians by 17 to 26% (quartile spread across six seeds, TPU v5e), where
a fixed order moves them by a few percent; no bound of at most 25%
could hold.  A mix that fills a window with some hundreds of requests
can let the seed draw the order (``order_seed`` left out).

Parameters:

- ``arrivals``: ``{"kind": "poisson", "rate_per_s": r, "drain_s": d}``
  gives ``round(r * (seconds - d))`` requests with exponential gaps
  (open loop); ``{"kind": "closed", "requests_per_s": r, "drain_s": d}``
  queues as many at t = 0.
- ``prompt_tokens`` / ``output_tokens``: ``{"dist": "lognormal",
  "median": m, "sigma": s, "min": a, "max": b}``.
- ``order_seed``: the permutation of the schedule; without it the
  run's seed draws it.
"""
from __future__ import annotations

import json
import statistics
from typing import Dict, List, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


def load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / max(n, 1)


def length_set(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``dist``, clipped."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([_NORMAL.inv_cdf(q) for q in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def arrival_set(arr: Dict, n: int) -> np.ndarray:
    """Arrival times of ``n`` requests, the first at 0; the n - 1 gaps
    are the exponential quantiles (Poisson) or all zero (closed)."""
    if arr["kind"] == "closed":
        return np.zeros(n)
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    q = _quantiles(n - 1)
    return -np.log1p(-q) / arr["rate_per_s"]


def request_count(traffic: Dict, seconds: float) -> int:
    """Requests of a run: the rate times the seconds of the window that
    arrivals fill, which is ``seconds`` less the ``drain_s`` the mix's
    last requests take to finish, so that a window lasts about
    ``seconds`` in all."""
    arr = traffic["arrivals"]
    rate = arr["rate_per_s"] if arr["kind"] == "poisson" \
        else arr["requests_per_s"]
    return max(2, int(round(rate * max(seconds - arr.get("drain_s", 0),
                                       1.0))))


def make(traffic: Dict, vocab: int, seed: int, seconds: float
         ) -> List[Tuple[int, np.ndarray, int, float]]:
    """``(uid, prompt ids, max_new, arrival_s)`` for every request of a
    run of ``seconds``, from ``seed``."""
    n = request_count(traffic, seconds)
    order = np.random.default_rng(
        traffic["order_seed"] if "order_seed" in traffic
        else [int(seed), 0x0DE])
    plens = order.permutation(length_set(traffic["prompt_tokens"], n))
    outs = order.permutation(length_set(traffic["output_tokens"], n))
    g = arrival_set(traffic["arrivals"], n)
    arrivals = np.concatenate([[0.0], np.cumsum(order.permutation(g))]) \
        if traffic["arrivals"]["kind"] == "poisson" else g
    rng = np.random.default_rng([int(seed), 0x7EA])
    return [(i, rng.integers(0, vocab, int(p), dtype=np.int32), int(o),
             float(t))
            for i, (p, o, t) in enumerate(zip(plens, outs, arrivals))]


def cache_len(reqs, bucket) -> int:
    """The KV cache length the engine sizes for ``reqs`` (its own rule:
    prompt bucket plus output plus one, rounded up to a bucket)."""
    return bucket(max(bucket(len(p), 16) + o for _, p, o, _ in reqs) + 1)


def warmup(reqs, bucket, chunk: int, vocab: int):
    """Requests that compile every shape ``reqs`` will use and nothing
    else: one prompt at the top of each prompt bucket they fill, and a
    request on the largest bucket whose output length gives the same
    cache length and runs at least two decode chunks."""
    want = cache_len(reqs, bucket)
    buckets = sorted({bucket(len(p), 16) for _, p, _, _ in reqs})
    top = buckets[-1]
    lo = max(1, want // 2 - top)      # smallest output reaching `want`
    hi = want - top - 1
    m = min(max(lo, 2 * chunk + 1), hi)
    if bucket(top + m + 1) != want:
        raise ValueError(f"no warm-up output length gives cache length "
                         f"{want} on a {top}-token prompt")
    ids = np.arange(top, dtype=np.int32) % vocab
    out = [(-1 - i, ids[:b].copy(), 1, 0.0) for i, b in enumerate(buckets)]
    out[-1] = (out[-1][0], out[-1][1], m, 0.0)
    assert cache_len(out, bucket) == want
    return out
