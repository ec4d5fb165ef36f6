"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_l2_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """(N, D) x (M, D) -> (N, M) squared L2, f32 accumulation."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    a2 = jnp.sum(a * a, axis=-1, keepdims=True)
    b2 = jnp.sum(b * b, axis=-1, keepdims=True).T
    return jnp.maximum(a2 - 2.0 * (a @ b.T) + b2, 0.0)


def assign_distances_f64(x, centroids, assign):
    """Float64 point-to-assigned-centroid squared distances (numpy).

    The shared core of every tie-tolerant parity check: when two assign
    paths disagree on a point, both picks must realize ~the same minimum —
    callers compare assign_distances_f64(..., a) against (..., b) under
    their own tolerance."""
    import numpy as np

    xf = np.asarray(x, np.float64)
    cf = np.asarray(centroids, np.float64)
    return ((xf - cf[np.asarray(assign)]) ** 2).sum(-1)


def kmeans_assign_update_ref(
    x: jax.Array,          # (N, D)
    centroids: jax.Array,  # (K, D)
    n_valid=None,          # live rows; rows past it are dead (see kernel)
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Oracle for the fused assign-and-accumulate kernel.

    Returns (assign (N,) i32, min_dist (N,) f32, sums (K, D) f32,
    counts (K,) f32) — the exact output contract of
    kernels.kmeans_assign.kmeans_assign_update.  Distances go through
    pairwise_l2_ref, so the argmin is bit-identical to the unfused
    ops.kmeans_assign path on the same backend.
    """
    d = pairwise_l2_ref(x, centroids)                    # (N, K)
    a = jnp.argmin(d, axis=1).astype(jnp.int32)
    md = jnp.min(d, axis=1)
    oh = jax.nn.one_hot(a, centroids.shape[0], dtype=jnp.float32)
    if n_valid is not None:
        live = jnp.arange(x.shape[0]) < n_valid
        md = jnp.where(live, md, -jnp.inf)
        oh = oh * live[:, None]
    sums = jax.lax.dot_general(                          # (K, D)
        oh, x.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    counts = jnp.sum(oh, axis=0)
    return a, md, sums, counts


def kmeans_mstep_ref(
    sums: jax.Array,       # (K, D) f32
    counts: jax.Array,     # (K,)
    reseed: jax.Array,     # (K, D) worst-served points, descending min-dist
) -> jax.Array:
    """Oracle for the fused M-step kernel: division + empty-cluster reseed.

    Empty cluster k takes reseed[rank(k)] where rank(k) counts the empty
    clusters before k (the e-th empty cluster gets the e-th worst-served
    point — the host reseed rule of build/kmeans.kmeans).
    """
    counts = counts.astype(jnp.float32)
    empty = counts <= 0.0
    rank = jnp.cumsum(empty.astype(jnp.int32)) - empty.astype(jnp.int32)
    mean = sums.astype(jnp.float32) / jnp.maximum(counts, 1.0)[:, None]
    return jnp.where(empty[:, None], reseed.astype(jnp.float32)[rank], mean)


def ivf_scan_ref(
    postings: jax.Array,   # (C, L, D)
    cids: jax.Array,       # (B, P) int32 (clamped valid)
    mask: jax.Array,       # (B, P) bool — True = scan this cluster
    queries: jax.Array,    # (B, D)
) -> jax.Array:
    """Gather selected posting lists and compute squared L2 distances.

    Returns (B, P, L) f32; masked probes are +inf.
    """
    q = queries.astype(jnp.float32)
    gathered = postings[jnp.clip(cids, 0, postings.shape[0] - 1)]  # (B,P,L,D)
    gathered = gathered.astype(jnp.float32)
    diff2 = (
        jnp.sum(q * q, axis=-1)[:, None, None]
        - 2.0 * jnp.einsum("bd,bpld->bpl", q, gathered)
        + jnp.sum(gathered * gathered, axis=-1)
    )
    diff2 = jnp.maximum(diff2, 0.0)
    return jnp.where(mask[:, :, None], diff2, jnp.inf)


def ivf_scan_topk_ref(
    postings: jax.Array,     # (C, L, D)
    posting_ids: jax.Array,  # (C, L) int32, -1 = pad slot
    cids: jax.Array,         # (B, P) int32
    mask: jax.Array,         # (B, P) bool
    queries: jax.Array,      # (B, D)
    k2: int,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused-topk kernel: full scan then dedup-top-k2.

    Returns ((B, k2) dists ascending, (B, k2) global ids), unique-by-id with
    per-id min distance, padded with (+inf, -1) — the exact candidate
    contract of kernels.ivf_scan.ivf_scan_topk (up to tie ordering).
    """
    from repro.core.distance import dedup_topk  # lazy: avoid import cycle

    d = ivf_scan_ref(postings, cids, mask, queries)               # (B, P, L)
    ids = posting_ids[jnp.clip(cids, 0, postings.shape[0] - 1)]   # (B, P, L)
    d = jnp.where(ids < 0, jnp.inf, d)
    b = queries.shape[0]
    return dedup_topk(d.reshape(b, -1), ids.reshape(b, -1), k2)


def ivf_scan_q8_topk_ref(
    q8: jax.Array,           # (C, L, D) int8 residual codes
    scale: jax.Array,        # (C, 1, 1) f32
    norm2: jax.Array,        # (C, L) f32
    centroids: jax.Array,    # (C, D) f32
    posting_ids: jax.Array,  # (C, L) int32
    cids: jax.Array,         # (B, P) int32
    mask: jax.Array,         # (B, P) bool
    queries: jax.Array,      # (B, D)
    k2: int,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused-topk q8 kernel (same candidate contract)."""
    from repro.core.distance import dedup_topk  # lazy: avoid import cycle

    q = queries.astype(jnp.float32)
    safe = jnp.clip(cids, 0, q8.shape[0] - 1)
    g8 = q8[safe].astype(jnp.float32)                    # (B, P, L, D)
    s = scale[safe][:, :, :, 0]                          # (B, P, 1)
    qc = q[:, None, :] - centroids[safe]                 # (B, P, D)
    cross = jnp.einsum("bpd,bpld->bpl", qc, g8)
    d = jnp.sum(qc * qc, axis=-1)[:, :, None] - 2.0 * s * cross + norm2[safe]
    d = jnp.maximum(d, 0.0)
    d = jnp.where(mask[:, :, None], d, jnp.inf)
    ids = posting_ids[safe]                              # (B, P, L)
    d = jnp.where(ids < 0, jnp.inf, d)
    b = queries.shape[0]
    return dedup_topk(d.reshape(b, -1), ids.reshape(b, -1), k2)


def ivf_scan_clustermajor_ref(
    postings: jax.Array,   # (C, L, D)
    active: jax.Array,     # (A,) int32 cluster ids to visit (union of probes)
    qsel: jax.Array,       # (A, B) bool — query b probes active cluster a
    queries: jax.Array,    # (B, D)
) -> jax.Array:
    """Cluster-major scan (beyond-paper MXU-friendly variant).

    Returns (A, L, B) f32 distances, +inf where the query did not select the
    cluster.
    """
    q = queries.astype(jnp.float32)                      # (B, D)
    g = postings[jnp.clip(active, 0, postings.shape[0] - 1)].astype(jnp.float32)
    d = (
        jnp.sum(g * g, axis=-1)[:, :, None]
        - 2.0 * jnp.einsum("ald,bd->alb", g, q)
        + jnp.sum(q * q, axis=-1)[None, None, :]
    )
    d = jnp.maximum(d, 0.0)
    return jnp.where(qsel[:, None, :], d, jnp.inf)
