"""Clustering-based (IVF/SPANN-style) index structures.

Layout mirrors the paper's serving data layout (§4.2, Fig. 10):

* ``centroids`` — the in-DRAM part (replicated across devices at serving).
* ``postings`` / ``posting_ids`` — fixed-size padded cluster lists, the
  "raw-block" part (sharded over the ``model`` mesh axis at serving; each
  cluster occupies one contiguous extent on one shard).
* optional two-level centroid quantizer (``group_centroids``/``group_members``)
  — the TPU-native replacement for SPANN's in-memory centroid graph.

Every array is a plain jax.Array so the whole index is a pytree that can be
checkpointed, device_put with shardings, or passed to jitted functions.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .distance import squared_l2_chunked, topk_smallest, dedup_topk


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IVFIndex:
    centroids: jax.Array            # (C, D) f32
    postings: jax.Array             # (C, L, D) vector payloads (pad: repeat)
    posting_ids: jax.Array          # (C, L) int32, -1 = padding slot
    group_centroids: Optional[jax.Array] = None  # (G, D)
    group_members: Optional[jax.Array] = None    # (G, Cg) int32, -1 pad
    # optional int8-residual payload (core/quantize.py) — when attached, the
    # serve paths can run their first pass over these instead of `postings`
    # (SearchConfig.tier == "q8"); `postings` stays the f32 re-rank truth.
    q8: Optional[jax.Array] = None               # (C, L, D) int8 residuals
    qscale: Optional[jax.Array] = None           # (C, 1, 1) f32 per-cluster
    qnorm2: Optional[jax.Array] = None           # (C, L) f32 s^2*||r8||^2

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def cluster_len(self) -> int:
        return self.postings.shape[1]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]

    def nbytes(self) -> int:
        tot = 0
        for leaf in jax.tree_util.tree_leaves(self):
            tot += leaf.size * leaf.dtype.itemsize
        return tot


def build_postings(
    x: np.ndarray,
    assign: np.ndarray,
    n_clusters: int,
    cluster_len: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Materialize fixed-size posting lists from a (N, R) closure assignment.

    Clusters larger than ``cluster_len`` keep their closest members (the
    overflow replicas are boundary duplicates by construction); smaller ones
    pad with the last valid vector and id=-1 (distance contributions of pads
    are masked at merge via the -1 id).
    """
    n, r = assign.shape
    d = x.shape[1]
    members: list[list[int]] = [[] for _ in range(n_clusters)]
    for col in range(r):
        col_assign = assign[:, col]
        valid = np.nonzero(col_assign >= 0)[0]
        for i in valid:
            members[col_assign[i]].append(i)

    postings = np.zeros((n_clusters, cluster_len, d), dtype=np.float32)
    ids = np.full((n_clusters, cluster_len), -1, dtype=np.int32)
    for c in range(n_clusters):
        mem = members[c]
        if not mem:
            continue
        mem = np.asarray(mem[:cluster_len])
        postings[c, : len(mem)] = x[mem]
        ids[c, : len(mem)] = mem
        if len(mem) < cluster_len:  # pad payload with last vector, id stays -1
            postings[c, len(mem):] = x[mem[-1]]
    return postings, ids


def make_group_quantizer(
    centroids: np.ndarray, n_groups: int, seed: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Two-level centroid quantizer (TPU stand-in for the centroid graph)."""
    from repro.build.kmeans import kmeans

    gc, gassign, _ = kmeans(centroids, n_groups, iters=10, seed=seed)
    sizes = np.bincount(gassign, minlength=n_groups)
    cap = int(sizes.max())
    members = np.full((n_groups, cap), -1, dtype=np.int32)
    fill = np.zeros(n_groups, dtype=np.int64)
    for cid, g in enumerate(gassign):
        members[g, fill[g]] = cid
        fill[g] += 1
    return gc.astype(np.float32), members


def brute_force_topk(
    x: jax.Array, queries: jax.Array, k: int, chunk: int = 8192
) -> tuple[jax.Array, jax.Array]:
    """Exact ground truth: (B, k) distances + ids over the raw vectors.

    The inner products run at HIGHEST matmul precision: the TPU's default
    rounds f32 operands to bf16, which reorders near neighbours."""
    with jax.default_matmul_precision("highest"):
        d = squared_l2_chunked(queries, x, chunk=chunk)
        return topk_smallest(d, k)


def search_flat(
    index: IVFIndex,
    queries: jax.Array,
    k: int,
    nprobe: int,
) -> tuple[jax.Array, jax.Array]:
    """Reference (non-pruned, single-device, pure-jnp) IVF search.

    Used as the oracle for the sharded/fused engine in core/search.py.
    """
    cd = squared_l2_chunked(queries, index.centroids)
    _, cids = topk_smallest(cd, nprobe)                   # (B, nprobe)
    gathered = index.postings[cids]                       # (B, n, L, D)
    gids = index.posting_ids[cids]                        # (B, n, L)
    q = queries[:, None, None, :]
    dist = jnp.sum((gathered - q) ** 2, axis=-1)          # (B, n, L)
    b = queries.shape[0]
    dist = dist.reshape(b, -1)
    gids = gids.reshape(b, -1)
    dist = jnp.where(gids < 0, jnp.inf, dist)
    return dedup_topk(dist, gids, k)
