"""The one traffic generator: a mix file of parameters -> a list of requests.

A mix (``chipbench/traffic/<mix>.json``) gives the arrival process, the
length distributions and the serving sizes; this module turns it and a seed
into requests.  Every seed gets the same set of (prompt length, output
length) pairs and the same inter-arrival gaps (stratified quantiles of the
mix's distributions, paired by a fixed shuffle), with token ids drawn from
the seed.  With ``"order": "seed"`` the seed also draws the order of the
requests and of the gaps, so two seeds offer the same work in another
order; with ``"order": "fixed"`` every seed offers it in one fixed order,
arrival times included, for a window that holds too few requests for the
order to average out.

Arrivals:

* ``backlog``: ``ceil(seconds * backlog_rate)`` requests, all due at t=0
  (offline batch generation);
* ``poisson``: ``ceil(seconds * rate)`` requests, the first due at t=0,
  with exponential gaps of mean ``1 / rate`` between them (an open loop:
  arrivals do not wait for completions).

Copied in spirit from ``src/repro/serving/loadgen.py`` (seeded open-loop
Poisson trace), with heavy-tailed lengths and ids over the whole vocabulary;
the yardstick keeps its own copy so that changes to the program cannot move
it.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the benchmark plans it."""

    uid: int
    prompt: np.ndarray        # (prompt_len,) int32
    max_new_tokens: int
    arrival_s: float


def load_mix(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` stratified draws (the (i + 1/2) / n quantiles) of a length
    distribution, rounded and clipped to its [min, max]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif dist["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


PAIRING_SEED = 20240117   # the fixed shuffle that pairs prompt and output lengths


def n_requests(mix: Dict, seconds: float) -> int:
    rate = mix["backlog_rate"] if mix["arrivals"] == "backlog" else mix["rate"]
    return max(1, math.ceil(round(seconds * rate, 6)))


def generate(mix: Dict, seed: int, seconds: float, vocab: int
             ) -> List[Planned]:
    """The requests of one run, sorted by arrival time."""
    n = n_requests(mix, seconds)
    pair = np.random.default_rng(PAIRING_SEED).permutation(n)
    plens = _quantiles(mix["prompt_len"], n)
    # a request never outgrows the slot: prompt + output < max_len
    olens = np.minimum(_quantiles(mix["output_len"], n)[pair],
                       mix["max_len"] - 1 - plens)
    rng = np.random.default_rng(seed)
    if mix["order"] == "seed":
        order = rng.permutation(n)
    elif mix["order"] == "fixed":
        order = np.random.default_rng(PAIRING_SEED + 1).permutation(n)
    else:
        raise ValueError(f"unknown order {mix['order']!r}")
    plens, olens = plens[order], olens[order]
    if mix["arrivals"] == "backlog":
        arrivals = np.zeros(n)
    elif mix["arrivals"] == "poisson":
        k = max(n - 1, 1)
        gaps = -np.log1p(-(np.arange(k) + 0.5) / k) / mix["rate"]
        gaps = (rng.permutation(gaps) if mix["order"] == "seed" else
                np.random.default_rng(PAIRING_SEED + 2).permutation(gaps))
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)])[:n]
    else:
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    reqs = [Planned(uid=i,
                    prompt=rng.integers(0, vocab, int(plens[i]),
                                        dtype=np.int64).astype(np.int32),
                    max_new_tokens=int(olens[i]),
                    arrival_s=float(arrivals[i]))
            for i in range(n)]
    return sorted(reqs, key=lambda r: (r.arrival_s, r.uid))
