"""Accelerated k-means for construction stage 1 (paper Fig. 13 / 21a).

Two E/M-step data paths, selected per call (``BuildConfig.fused_assign``
routes the whole pipeline):

* ``fused=True`` (default in the pipeline) — the Pallas fused
  assign-and-accumulate kernel (kernels/kmeans_assign.py on TPU, its jnp
  oracle elsewhere): one pass emits assignments + per-centroid sums/counts,
  the (N, K) distance matrix stays in VMEM, and the M-step is a device
  matmul instead of a host ``np.add.at`` scatter.
* ``fused=False`` — the legacy A/B reference: kernels/ops.kmeans_assign
  (argmin over the materialized distance tile) + host-side float64
  scatter-add.

Both paths share the empty-cluster reseeding rule (worst-served points), and
their per-step assignments are bit-identical on the same inputs (the fused
oracle argmins over the same pairwise_l2_ref distances).
``balanced_hierarchical_kmeans`` is the SPANN-style recursive splitter that
bounds every leaf cluster at ``max_cluster_size`` so posting lists stay
fixed-size (the serving layout's contract).
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops as kops


def _bucketed(x: np.ndarray) -> tuple[jax.Array, jax.Array]:
    """(points padded to a power-of-two row bucket, live count).  The
    recursive splitter runs k-means at a different n for every tree node;
    padding makes one compiled assign program serve a whole bucket (the
    fused kernel treats rows past the live count as dead)."""
    n = x.shape[0]
    nb = max(256, 1 << (n - 1).bit_length())
    return jnp.asarray(np.pad(x, ((0, nb - n), (0, 0)))), jnp.int32(n)


def kmeans_assign_step(
    x: np.ndarray, cents: np.ndarray, fused: bool = False
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """One Lloyd E+M data pass. Returns (assign (N,) i64, min_dist (N,) f32,
    sums (K, D), counts (K,) i64).

    Fused: a single device pass (kernel/oracle) over the row-bucketed
    points returns everything; counts come back exact (integer cross-chunk
    fold).  Unfused: device argmin + host float64 scatter-add — the legacy
    reference the bench pairs against.
    """
    k, d = cents.shape
    if fused:
        n = x.shape[0]
        xd, nv = _bucketed(x)
        a, md, sums, counts = kops.kmeans_assign_update(
            xd, jnp.asarray(cents), n_valid=nv)
        return (np.asarray(a, np.int64)[:n], np.asarray(md)[:n],
                np.asarray(sums, np.float64),
                np.asarray(counts, np.int64))
    a, md = kops.kmeans_assign(jnp.asarray(x), jnp.asarray(cents))
    assign = np.asarray(a, np.int64)
    sums = np.zeros((k, d), np.float64)
    np.add.at(sums, assign, x)
    counts = np.bincount(assign, minlength=k)
    return assign, np.asarray(md), sums, counts


def kmeans(
    x: np.ndarray, k: int, iters: int = 10, seed: int = 0,
    fused: bool = False, device_mstep: Optional[bool] = None,
) -> tuple[np.ndarray, np.ndarray, float]:
    """Lloyd's algorithm. Returns (centroids (k, D), assign (N,), inertia).

    ``device_mstep`` (default: follows ``fused``) finishes each iteration
    with the fused M-step kernel — division + empty-cluster reseed stay on
    device (kernels/kmeans_mstep.py), so a whole Lloyd iteration runs without
    a host round trip: assign/accumulate kernel -> top-k worst-served gather
    -> M-step kernel, all async-dispatched.  ``device_mstep=False`` is the
    host reference path the parity tests pin the kernel against.
    """
    x = np.asarray(x, np.float32)
    n, d = x.shape
    k = max(1, min(int(k), n))
    if device_mstep is None:
        device_mstep = fused
    rng = np.random.default_rng(seed)
    cents = x[rng.choice(n, size=k, replace=False)].astype(np.float32).copy()
    if fused and device_mstep:
        xd, nv = _bucketed(x)       # padding rows: no sums, min-dist -inf
        cd = jnp.asarray(cents)
        for _ in range(max(1, iters)):
            a, md, sums, counts = kops.kmeans_assign_update(xd, cd,
                                                            n_valid=nv)
            # worst-served candidates for however many clusters come up
            # empty (ties resolve by lowest index — top_k order, the
            # canonical semantics kmeans_mstep documents)
            _, worst = jax.lax.top_k(md, k)
            cd = kops.kmeans_mstep(sums, counts, xd[worst])
        return (np.asarray(cd), np.asarray(a, np.int32)[:n],
                float(np.asarray(md)[:n].sum()))
    assign = np.zeros(n, np.int64)
    mind = np.zeros(n, np.float32)
    for _ in range(max(1, iters)):
        assign, mind, sums, counts = kmeans_assign_step(x, cents, fused=fused)
        nonz = counts > 0
        cents[nonz] = (sums[nonz] / counts[nonz, None]).astype(np.float32)
        if (~nonz).any():  # reseed empty clusters at the worst-served points
            # descending with lowest-index-first ties: the same order as the
            # device path's jax.lax.top_k, so the two M-steps stay parity
            far = np.argsort(-mind, kind="stable")[: int((~nonz).sum())]
            cents[~nonz] = x[far]
    return cents, assign.astype(np.int32), float(mind.sum())


def balanced_hierarchical_kmeans(
    x: np.ndarray,
    max_cluster_size: int,
    iters: int = 8,
    seed: int = 0,
    branch: int = 8,
    fused: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Recursive balanced clustering: split until every leaf fits the bound.

    Returns (centroids (C, D) f32 = leaf means, assign (N,) int32).  A
    degenerate split (k-means collapses everything into one cluster) falls
    back to a median split along the highest-variance axis, so termination is
    guaranteed.
    """
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    stack = [np.arange(n)]
    leaves: list[np.ndarray] = []
    task_seed = seed
    while stack:
        idxs = stack.pop()
        if idxs.size <= max_cluster_size:
            leaves.append(idxs)
            continue
        k = int(min(branch, max(2, -(-idxs.size // max_cluster_size))))
        task_seed += 1
        _, a, _ = kmeans(x[idxs], k, iters=iters, seed=task_seed, fused=fused)
        sizes = np.bincount(a, minlength=k)
        if (sizes == idxs.size).any():  # degenerate: force a median split
            dim = int(np.argmax(x[idxs].var(axis=0)))
            order = idxs[np.argsort(x[idxs][:, dim], kind="stable")]
            half = idxs.size // 2
            stack.append(order[:half])
            stack.append(order[half:])
            continue
        for j in range(k):
            sub = idxs[a == j]
            if sub.size:
                stack.append(sub)
    leaves.sort(key=lambda l: int(l[0]))  # deterministic leaf order
    cents = np.stack([x[l].mean(axis=0) for l in leaves]).astype(np.float32)
    assign = np.empty(n, np.int32)
    for ci, l in enumerate(leaves):
        assign[l] = ci
    return cents, assign


def enforce_size_bound(
    x: np.ndarray,
    centroids: np.ndarray,
    bound: int,
    max_rounds: int = 20,
    seed: int = 0,
    fused: bool = False,
) -> np.ndarray:
    """Split Voronoi cells larger than ``bound`` until none remain.

    Chunk-local clustering (stage-1 elastic tasks) bounds leaf sizes per
    chunk, but the MERGED centroid set's global Voronoi cells can still
    exceed the posting-list capacity; any primary overflow would be silently
    truncated by the fixed-size posting build.  Each round reassigns all
    points and 2-way-splits every oversized cell.  The fused path reads the
    cell sizes straight off the kernel's in-VMEM counts — no (N, K) matrix,
    no host bincount.
    """
    x = np.asarray(x, np.float32)
    cents = np.asarray(centroids, np.float32).copy()
    for rnd in range(max_rounds):
        if fused:
            a, _, _, counts = kops.kmeans_assign_update(
                jnp.asarray(x), jnp.asarray(cents))
            a = np.asarray(a)
            counts = np.asarray(counts, np.int64)
        else:
            a, _ = kops.kmeans_assign(jnp.asarray(x), jnp.asarray(cents))
            a = np.asarray(a)
            counts = np.bincount(a, minlength=cents.shape[0])
        over = np.nonzero(counts > bound)[0]
        if over.size == 0:
            break
        new_rows = []
        for c in over:
            pts = x[a == c]
            sub, _, _ = kmeans(pts, 2, iters=4, seed=seed + 131 * rnd + int(c),
                               fused=fused)
            cents[c] = sub[0]
            if sub.shape[0] > 1:
                new_rows.append(sub[1])
        if new_rows:
            cents = np.concatenate([cents, np.stack(new_rows)], axis=0)
    return cents


def kmeans_sharded_step(mesh, x, cents, k: int, fused: bool = True):
    """One distributed Lloyd iteration (stage-1 build cell for dry-runs).

    x sharded over the data axes, centroids replicated; per-shard partial
    sums + counts are psum'd so every shard ends with the same new centroids.
    ``fused`` routes the per-shard pass through the fused assign/update tile
    (Pallas kernel on TPU) so the (N_local, K) distance matrix stays in VMEM;
    the unfused branch keeps the original inline one-hot as the reference.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.distance import squared_l2

    data_axes = tuple(n for n in mesh.axis_names if n != "model")

    def step(xl, c):
        if fused:
            _, _, sums, counts = kops.kmeans_assign_update_tile(xl, c)
        else:
            d = squared_l2(xl, c)
            a = jnp.argmin(d, axis=1)
            oh = jax.nn.one_hot(a, c.shape[0], dtype=jnp.float32)
            sums = oh.T @ xl
            counts = jnp.sum(oh, axis=0)
        for ax in data_axes:
            sums = jax.lax.psum(sums, ax)
            counts = jax.lax.psum(counts, ax)
        safe = jnp.maximum(counts[:, None], 1.0)
        return jnp.where(counts[:, None] > 0, sums / safe, c)

    return jax.shard_map(
        step, mesh=mesh, in_specs=(P(data_axes), P()), out_specs=P(),
        check_vma=False,
    )(x, cents)
