"""The benchmark's own copy of the SIFT-shaped corpus and query generator.

Copied from ``repro.data.synthetic`` (``make_vectors`` / ``make_queries``)
so that the data the reference answers against cannot move with the
program: the program builds its index from ``repro.data.make_vectors`` of
the same spec, and a change there that alters the corpus makes the served
answers disagree with the reference here.

The corpus is a Gaussian mixture of ``n_modes`` modes at the published
width, a pure function of the configuration's ``corpus_seed``: one dataset
per configuration, as SIFT1M is one dataset.  Queries are drawn near the
same modes at ``temp`` times the intra-mode spread, a pure function of the
run's seed.
"""
from __future__ import annotations

import numpy as np


def make_vectors(n: int, dim: int, n_modes: int, spread: float,
                 seed: int) -> np.ndarray:
    """(n, dim) float32 corpus; identical to ``repro.data.make_vectors``."""
    rng = np.random.default_rng(seed)
    modes = rng.normal(size=(n_modes, dim)).astype(np.float32)
    weights = rng.dirichlet(np.full(n_modes, 1.5))
    which = rng.choice(n_modes, size=n, p=weights)
    x = modes[which] + spread * rng.normal(size=(n, dim))
    return x.astype(np.float32)


def make_pool(n_queries: int, dim: int, n_modes: int, spread: float,
              corpus_seed: int, seed: int,
              temp: float) -> tuple[np.ndarray, np.ndarray]:
    """(n_queries, dim) float32 query pool near the modes of the corpus of
    ``corpus_seed``, and the mode each query was drawn near.  Drawn from the
    run's own stream (``[seed, 1]``), so the pool never repeats the queries
    the program trains its router on."""
    modes = np.random.default_rng(corpus_seed).normal(
        size=(n_modes, dim)).astype(np.float32)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    which = rng.choice(n_modes, size=n_queries)
    q = modes[which] + temp * spread * rng.normal(size=(n_queries, dim))
    return q.astype(np.float32), which.astype(np.int64)


def order_pool(pool: np.ndarray, modes: np.ndarray, order: str) -> np.ndarray:
    """The pool in the order a traffic mix indexes it: ``drawn`` keeps the
    draw order; ``mode`` sorts it by the mode each query was drawn near (a
    stable sort), so a contiguous slice of the pool is a few modes and the
    clusters they probe."""
    if order == "drawn":
        return pool
    if order == "mode":
        return pool[np.argsort(modes, kind="stable")]
    raise ValueError(f"unknown pool order {order!r}")
