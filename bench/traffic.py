"""The one traffic generator: a traffic file in, a timed schedule out.

A traffic mix is a JSON file under ``bench/traffic/``:

    {"generator": "hot_cluster_trace",          # a function of loadgen.py
     "params": {"rate_qps": 120.0, "hot_frac": 0.05, "hot_weight": 0.9},
     "pool_order": "mode",                      # corpus.order_pool
     "k": 10}

The generator, called with ``params`` by keyword and with ``duration_s``,
``seed``, ``n_queries`` (the pool size) and ``topk`` (``k`` for every
request), chooses the query rows.  The arrival times are a homogeneous
Poisson process at ``rate_qps`` conditioned on its expected count: exactly
``round(rate_qps * seconds)`` arrivals, the sorted draws of as many uniform
points in the window.  Every seed then offers the same amount of work in
another order, so two seeds differ by the order of the traffic, not by its
volume.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

from . import loadgen


@dataclasses.dataclass(frozen=True)
class Schedule:
    t: np.ndarray          # (n,) seconds from the window's start, ascending
    qrow: np.ndarray       # (n,) row of the ordered query pool
    k: int

    def __len__(self) -> int:
        return len(self.t)


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if not hasattr(loadgen, mix.get("generator", "")):
        raise ValueError(f"{path}: unknown generator {mix.get('generator')!r}")
    if "rate_qps" not in mix.get("params", {}):
        raise ValueError(f"{path}: params need rate_qps, the arrival rate")
    return mix


def schedule(mix: dict, seed: int, seconds: float, n_pool: int) -> Schedule:
    fn = getattr(loadgen, mix["generator"])
    params = dict(mix.get("params", {}))
    k = int(mix.get("k", 10))
    kw = dict(seed=seed, n_queries=n_pool, topk=(k, k), **params)
    n = int(round(float(params["rate_qps"]) * seconds))
    rows: list[int] = []
    span = seconds
    while len(rows) < n:          # long enough to hold n arrivals
        span *= 2
        rows = [a.qrow for a in fn(duration_s=span, **kw)]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 29]))
    t = np.sort(rng.uniform(0.0, seconds, size=n))
    return Schedule(t, np.asarray(rows[:n], np.int64), k)
