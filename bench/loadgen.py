"""Open-loop arrival generators, copied from ``repro.runtime.loadgen``.

The benchmark keeps its own copy so that a later change to the program's
load generator cannot move the yardstick.  The functions are verbatim;
``bench/traffic.py`` turns a traffic file into a call of one of them.

* ``poisson_trace``     -- memoryless arrivals at a target rate;
* ``bursty_trace``      -- piecewise-Poisson on/off bursts;
* ``hot_cluster_trace`` -- ``hot_weight`` of the traffic draws its query
  rows from the first ``hot_frac`` of the pool, the rest uniformly.

Every trace is a pure function of its arguments and seed.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One query arrival: time is seconds from trace start (virtual clock)."""
    t: float
    index: str                     # which co-resident index this query hits
    qrow: int                      # row into the tenant's query pool
    topk: int
    deadline_s: Optional[float]    # latency budget (None = best-effort)

    def deadline_at(self, t0: float) -> Optional[float]:
        return None if self.deadline_s is None else t0 + self.t + self.deadline_s


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """Per-tenant traffic shape for multi-index mixes."""
    index: str
    rate_qps: float
    topk_lo: int = 10
    topk_hi: int = 100
    deadline_s: Optional[float] = None
    n_queries: int = 1 << 30       # query-pool size qrow is drawn from


def _draw_arrivals(
    rng: np.random.Generator,
    spec: TenantSpec,
    duration_s: float,
    rate_fn=None,
) -> list[Arrival]:
    """Thinned Poisson process: homogeneous at spec.rate_qps, or modulated by
    ``rate_fn(t) in [0, 1]`` (Lewis–Shedler thinning, so bursty traces stay
    exactly Poisson within each regime)."""
    out: list[Arrival] = []
    t = 0.0
    if spec.rate_qps <= 0:
        return out
    while True:
        t += rng.exponential(1.0 / spec.rate_qps)
        if t >= duration_s:
            break
        if rate_fn is not None and rng.uniform() > rate_fn(t):
            continue
        topk = int(np.exp(rng.uniform(np.log(spec.topk_lo),
                                      np.log(spec.topk_hi + 1))))
        topk = min(max(topk, spec.topk_lo), spec.topk_hi)
        out.append(Arrival(t=float(t), index=spec.index,
                           qrow=int(rng.integers(0, spec.n_queries)),
                           topk=topk, deadline_s=spec.deadline_s))
    return out


def poisson_trace(
    rate_qps: float,
    duration_s: float,
    seed: int = 0,
    index: str = "default",
    topk: tuple[int, int] = (10, 100),
    deadline_s: Optional[float] = None,
    n_queries: int = 1 << 30,
) -> list[Arrival]:
    """Open-loop memoryless arrivals at ``rate_qps`` for ``duration_s``."""
    rng = np.random.default_rng(seed)
    spec = TenantSpec(index, rate_qps, topk[0], topk[1], deadline_s, n_queries)
    return _draw_arrivals(rng, spec, duration_s)


def bursty_trace(
    base_qps: float,
    burst_qps: float,
    period_s: float,
    duty: float,
    duration_s: float,
    seed: int = 0,
    index: str = "default",
    topk: tuple[int, int] = (10, 100),
    deadline_s: Optional[float] = None,
    n_queries: int = 1 << 30,
) -> list[Arrival]:
    """On/off bursts: ``burst_qps`` for the first ``duty`` fraction of every
    ``period_s`` window, ``base_qps`` otherwise (flash-crowd shape)."""
    rng = np.random.default_rng(seed)
    peak = max(base_qps, burst_qps)
    spec = TenantSpec(index, peak, topk[0], topk[1], deadline_s, n_queries)

    def rate_fn(t: float) -> float:
        in_burst = (t % period_s) < duty * period_s
        return (burst_qps if in_burst else base_qps) / peak

    return _draw_arrivals(rng, spec, duration_s, rate_fn)


def hot_cluster_trace(
    rate_qps: float,
    duration_s: float,
    n_queries: int,
    hot_frac: float = 0.05,
    hot_weight: float = 0.9,
    seed: int = 0,
    index: str = "default",
    topk: tuple[int, int] = (10, 100),
    deadline_s: Optional[float] = None,
) -> list[Arrival]:
    """Hot-cluster skew: ``hot_weight`` of the traffic draws qrows from the
    first ``hot_frac`` slice of the query pool, the rest uniformly from the
    whole pool.  With a centroid-sorted pool the hot slice maps to a handful
    of clusters — the celebrity-item regime where most batches should share
    most of their gather union."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 13]))
    spec = TenantSpec(index, rate_qps, topk[0], topk[1], deadline_s, n_queries)
    raw = _draw_arrivals(rng, spec, duration_s)
    n_hot = max(int(n_queries * hot_frac), 1)
    out = []
    for a in raw:
        if rng.uniform() < hot_weight:
            qrow = int(rng.integers(0, n_hot))
        else:
            qrow = int(rng.integers(0, n_queries))
        out.append(dataclasses.replace(a, qrow=qrow))
    return out
