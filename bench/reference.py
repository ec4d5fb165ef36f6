"""The plain reference and the comparison that decides ``correct``.

The reference is exact brute force: the squared L2 distance of a query to
every corpus vector, computed on the device in float32 at HIGHEST matmul
precision in blocks of corpus rows, and the k nearest ids.  It imports
nothing of the program and takes only the corpus and the queries, both of
which the benchmark makes itself from the seed (``bench/corpus.py``).

Numbers compared, each against its limit in the configuration file
(``correct``):

* ``recall_miss`` -- 1 - mean recall@k of the served ids against the
  reference's k nearest.  The configuration states the recall floor.
  Catches routing, gather, merge and top-k faults: an answer from the
  wrong clusters or a dropped candidate loses true neighbours.
* ``dist_rel_err`` -- the largest relative gap between a served distance
  and the distance of the served id recomputed in float64 on the host.
  Catches a scan or re-rank computed in lower precision, and an answer
  altered after it was computed.
* ``bad_answers`` -- answers that say something wrong: an id out of range,
  repeated, or after a no-neighbour mark, distances not ascending or not
  finite (``answer_faults``).  A ``(-1, +inf)`` mark at an answer's tail is
  a neighbour missed, counted by ``recall_miss``.  Exact: limit 0.
* ``missing`` -- requests due in the window that never completed.  Exact.

The control (``control_answers``) is the same brute force with the
products computed in bfloat16 -- the next precision below the float32 the
configurations state -- put in the program's place.
"""
from __future__ import annotations

import numpy as np

ROW_BLOCK = 65_536
QUERY_BLOCK = 1024


def _topk_blocks(x: np.ndarray, q: np.ndarray, k: int, dtype) -> tuple:
    """Exact k nearest (distances, ids) of ``q`` in ``x`` by blocks of
    corpus rows; the products in ``dtype`` (float32 at HIGHEST precision,
    or bfloat16 for the control)."""
    import jax
    import jax.numpy as jnp

    prec = jax.lax.Precision.HIGHEST

    @jax.jit
    def block(xb, qb, base):
        xc, qc = xb.astype(dtype), qb.astype(dtype)
        dot = jnp.dot(qc, xc.T, precision=prec,
                      preferred_element_type=jnp.float32)
        xn = jnp.sum(xc.astype(jnp.float32) ** 2, axis=1)
        qn = jnp.sum(qc.astype(jnp.float32) ** 2, axis=1)
        d = qn[:, None] - 2.0 * dot + xn[None, :]
        nd, ni = jax.lax.top_k(-d, k)
        return -nd, ni.astype(jnp.int32) + base

    pad = -len(x) % ROW_BLOCK
    xp = np.concatenate([x, np.full((pad, x.shape[1]), 1e15, np.float32)]) \
        if pad else x
    outs_d, outs_i = [], []
    for lo in range(0, len(xp), ROW_BLOCK):
        xb = jnp.asarray(xp[lo:lo + ROW_BLOCK])
        ds, is_ = [], []
        for qlo in range(0, len(q), QUERY_BLOCK):
            qb = q[qlo:qlo + QUERY_BLOCK]
            qpad = -len(qb) % QUERY_BLOCK
            qb = np.concatenate([qb, np.zeros((qpad, q.shape[1]), np.float32)])
            d, i = block(xb, jnp.asarray(qb), lo)
            ds.append(np.asarray(d)[:QUERY_BLOCK - qpad])
            is_.append(np.asarray(i)[:QUERY_BLOCK - qpad])
        outs_d.append(np.concatenate(ds))
        outs_i.append(np.concatenate(is_))
    best_d = np.concatenate(outs_d, axis=1)
    best_i = np.concatenate(outs_i, axis=1)
    order = np.argsort(best_d, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(best_d, order, axis=1),
            np.take_along_axis(best_i, order, axis=1))


def exact_topk(x: np.ndarray, q: np.ndarray, k: int) -> tuple:
    import jax.numpy as jnp

    return _topk_blocks(x, q, k, jnp.float32)


def control_answers(x: np.ndarray, q: np.ndarray, k: int) -> tuple:
    """The reference in bfloat16, as the program would answer with it:
    ids and distances both from the bfloat16 products."""
    import jax.numpy as jnp

    return _topk_blocks(x, q, k, jnp.bfloat16)


def answer_faults(ids: np.ndarray, dists: np.ndarray, n: int) -> dict:
    """For answers ``(ids, dists)`` (m, k), one (m,) mask per fault that
    makes an answer say something wrong.  A slot ``(-1, +inf)`` is the
    program's mark for "no further neighbour found" (its merge pads an
    answer so when the probed clusters hold fewer than k vectors); at the
    tail of an answer it says nothing wrong: it is a neighbour missed, and
    ``recall_miss`` counts it.  The faults: an id out of range that is no
    such mark, a mark followed by an id, a real id repeated, distances not
    ascending, and a real id's distance not finite."""
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    mark = (ids == -1) & (dists == np.inf)
    real = (ids >= 0) & (ids < n)
    srt = np.sort(np.where(real, ids, -1 - np.arange(ids.shape[1])), axis=1)
    with np.errstate(invalid="ignore"):        # inf - inf between marks
        step = np.diff(dists, axis=1)
    return {
        "out_of_range": ~(real | mark).all(axis=1),
        "mark_then_id": np.any(mark[:, :-1] & ~mark[:, 1:], axis=1),
        "repeated": np.any(srt[:, 1:] == srt[:, :-1], axis=1),
        "not_ascending": ~np.all((step >= 0) | (mark[:, :-1] & mark[:, 1:]),
                                 axis=1),
        "not_finite": ~np.all(np.isfinite(dists) | ~real, axis=1),
    }


def short_answers(ids: np.ndarray, dists: np.ndarray) -> int:
    """Answers holding at least one ``(-1, +inf)`` no-neighbour mark."""
    mark = (np.asarray(ids) == -1) & (np.asarray(dists) == np.inf)
    return int(mark.any(axis=1).sum())


def compare(x: np.ndarray, q: np.ndarray, ids: np.ndarray,
            dists: np.ndarray, true_ids: np.ndarray, missing: int) -> dict:
    """The numbers compared for answers ``(ids, dists)`` (m, k) to queries
    ``q`` (m, D), against the reference's ``true_ids`` (m, k)."""
    m, k = ids.shape
    n = len(x)
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    in_range = (ids >= 0) & (ids < n)
    bad = np.any(list(answer_faults(ids, dists, n).values()), axis=0) \
        if m else np.zeros(0, bool)
    safe = np.where(in_range, ids, 0)
    exact = np.sum((q[:, None, :].astype(np.float64)
                    - x[safe].astype(np.float64)) ** 2, axis=-1)
    rel = np.abs(dists - exact) / np.maximum(exact, 1e-12)
    # an id out of range or a distance that is not finite is a bad answer,
    # counted above; the gap is read over the rest
    rel = np.where(in_range & np.isfinite(dists), rel, 0.0)
    hits = np.array([len(set(a.tolist()) & set(b.tolist()))
                     for a, b in zip(ids, true_ids)], np.float64)
    return {
        "recall_miss": float(1.0 - hits.mean() / k) if m else 1.0,
        "dist_rel_err": float(rel.max()) if m else 0.0,
        "bad_answers": int(bad.sum()),
        "missing": int(missing),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, list]:
    """``(correct, [(name, value, limit), ...])``; every number must be at
    most its limit."""
    rows = [(name, numbers[name], limits[name]) for name in limits]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
