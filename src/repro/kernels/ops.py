"""Jit'd public wrappers around the Pallas kernels.

On TPU the kernels lower to Mosaic.  On CPU — the test backend — they run
with ``interpret=True`` (Pallas executes the kernel body with jnp
semantics).  Any other backend is refused rather than silently interpreted.
Callers never pass ``interpret`` themselves — ``_interp()`` resolves it.

``FALLBACKS`` counts the calls that a TPU run routed to the jnp oracle
because the kernel's VMEM estimate exceeded the budget, keyed by kernel
name, so a run can report that it did not serve through the kernel.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from . import ivf_scan as _ivf
from . import ivf_scan_q8 as _q8
from . import pairwise_l2 as _pw
from . import ref as ref

FALLBACKS: collections.Counter = collections.Counter()

# op names of the served scan kernels, pinned on their pallas_call: device
# trace readers find the kernels by these
SCAN_KERNEL_NAMES = (_ivf.TOPK_KERNEL_NAME, _q8.TOPK_KERNEL_NAME)


def _interp() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels lower to Mosaic on TPU and are interpreted on "
            f"CPU; backend {backend!r} is not supported")
    return backend == "cpu"


def pairwise_l2(a, b, *, bn: int = 128, bm: int = 128, bd: int = 512):
    """Pairwise squared L2 (N, D) x (M, D) -> (N, M)."""
    return _pw.pairwise_l2(a, b, bn=bn, bm=bm, bd=bd, interpret=_interp())


def kmeans_assign(x, centroids, *, chunk: int = 16384):
    """argmin-distance assignment + distances (the k-means E-step).

    Returns (assign (N,), min_dist (N,)). Chunked over N to bound the
    (chunk, C) distance tile.  On TPU the tile is the pairwise_l2 Pallas
    kernel; elsewhere the jnp oracle (interpret-mode grids are a correctness
    harness, not a fast path).
    """
    n = x.shape[0]
    tile = pairwise_l2 if jax.default_backend() == "tpu" else _ref_tile
    outs_a, outs_d = [], []
    for s in range(0, n, chunk):
        d = tile(x[s:s + chunk], centroids)
        outs_a.append(jnp.argmin(d, axis=1).astype(jnp.int32))
        outs_d.append(jnp.min(d, axis=1))
    return jnp.concatenate(outs_a), jnp.concatenate(outs_d)


_ASSIGN_VMEM_FLOATS = 1 << 21   # ~8 MiB f32 working set (half of v5e VMEM,
                                # leaving headroom for grid double-buffering)
_ASSIGN_BN = 512                # point-block rows per grid step


def kmeans_assign_update_tile(x, centroids, n_valid=None):
    """Single-tile fused assign+accumulate (jittable; kernel on TPU, jnp
    oracle elsewhere).  Returns (assign, min_dist, sums, counts) — the
    building block of kmeans_assign_update and kmeans_sharded_step.
    Rows at or past ``n_valid`` (a traced scalar; None = all) are padding:
    they accumulate nothing and report min-dist -inf.

    The kernel's per-step VMEM working set is the whole (Kp, Dp) centroid
    block PLUS the revisited (Kp, Dp) sums accumulator PLUS the (BN, Kp)
    distance and one-hot tiles and the (BN, Dp) point block (K-chunking is
    impossible without a second pass: the argmin must be global before
    accumulation).  Shapes whose estimate exceeds the budget fall back to
    the jnp oracle instead of failing Mosaic compilation."""
    k, d = centroids.shape
    kp = ((k + 127) // 128) * 128
    dp = ((d + 127) // 128) * 128
    need = 2 * kp * dp + 2 * _ASSIGN_BN * kp + _ASSIGN_BN * dp
    if jax.default_backend() == "tpu":
        if need <= _ASSIGN_VMEM_FLOATS:
            from . import kmeans_assign as _km
            return _km.kmeans_assign_update(x, centroids, n_valid,
                                            bn=_ASSIGN_BN, interpret=False)
        FALLBACKS["kmeans_assign_update"] += 1
    return _ref_assign_tile(x, centroids, n_valid)


def kmeans_assign_update(x, centroids, *, chunk: int = 16384, n_valid=None):
    """Fused Lloyd iteration: E-step argmin + M-step accumulation in one pass.

    Returns (assign (N,), min_dist (N,), sums (K, D) f32, counts (K,) i32).
    Chunked over N like kmeans_assign; per-centroid partial sums/counts from
    each chunk are folded on device, so the (N, K) distance matrix AND the
    host scatter-add both disappear — only (K, D) + (K,) + 2*(N,) cross HBM.
    Per-chunk counts are exact small integers in f32 (chunk <= 2^24); the
    cross-chunk fold is integer, so counts stay exact at any corpus size.
    ``n_valid`` marks the rows past it as padding (see the tile).
    """
    n = x.shape[0]
    outs_a, outs_m = [], []
    sums = None
    counts = None
    for s in range(0, n, chunk):
        nv = None if n_valid is None else jnp.clip(n_valid - s, 0, chunk)
        a, md, ps, pc = kmeans_assign_update_tile(x[s:s + chunk], centroids,
                                                  nv)
        pc = jnp.round(pc).astype(jnp.int32)
        outs_a.append(a)
        outs_m.append(md)
        sums = ps if sums is None else sums + ps
        counts = pc if counts is None else counts + pc
    return (jnp.concatenate(outs_a), jnp.concatenate(outs_m), sums, counts)


def kmeans_mstep(sums, counts, reseed):
    """Fused M-step finisher: new centroids from (sums, counts) with empty
    clusters reseeded at the worst-served points (kernel on TPU, jnp oracle
    elsewhere — the same routing rule as kmeans_assign_update_tile).

    The kernel's working set is three (Kp, Dp) blocks plus the (Kp, Kp)
    rank/selection tiles; shapes whose estimate exceeds the VMEM budget fall
    back to the oracle instead of failing Mosaic compilation.
    """
    k, d = sums.shape
    kp = ((k + 127) // 128) * 128
    dp = ((d + 127) // 128) * 128
    need = 3 * kp * dp + 2 * kp * kp
    if jax.default_backend() == "tpu":
        if need <= _ASSIGN_VMEM_FLOATS:
            from . import kmeans_mstep as _km_mstep
            return _km_mstep.kmeans_mstep(sums, counts, reseed,
                                          interpret=False)
        FALLBACKS["kmeans_mstep"] += 1
    return _ref_mstep_tile(sums, counts, reseed)


@jax.jit
def _ref_mstep_tile(sums, counts, reseed):
    return ref.kmeans_mstep_ref(sums, counts, reseed)


@jax.jit
def _ref_tile(a, b):
    return ref.pairwise_l2_ref(a, b)


@jax.jit
def _ref_assign_tile(x, centroids, n_valid=None):
    return ref.kmeans_assign_update_ref(x, centroids, n_valid)


def ivf_scan(postings, cids, mask, queries):
    """Fused posting gather + L2 scan. (B, P, L) f32, masked probes +inf."""
    return _ivf.ivf_scan(postings, cids, mask, queries, interpret=_interp())


def ivf_scan_clustermajor(postings, active, qsel, queries):
    """Cluster-major fused scan. (A, L, B) f32."""
    return _ivf.ivf_scan_clustermajor(
        postings, active, qsel, queries, interpret=_interp()
    )


def ivf_scan_q8(q8, scale, norm2, centroids, cids, mask, queries):
    """Fused int8-residual posting scan (hillclimb it.3 hot path)."""
    return _q8.ivf_scan_q8(q8, scale, norm2, centroids, cids, mask, queries,
                           interpret=_interp())


def ivf_scan_topk(postings, posting_ids, cids, mask, queries, *, k2, bq=8,
                  with_stats=False):
    """Candidate-compressed scan: fused gather + L2 + in-kernel top-k2.

    Returns ((B, k2) dists, (B, k2) ids) — the (B, P, L) distance tensor
    never crosses the pallas_call boundary — and, ``with_stats``, the
    kernel's (5,) int32 counters (``kernels.ivf_scan.scan_stats``)."""
    out = _ivf.ivf_scan_topk(postings, posting_ids, cids, mask, queries,
                             k2=k2, bq=bq, interpret=_interp())
    return out if with_stats else out[:2]


def ivf_scan_q8_topk(q8, scale, norm2, centroids, posting_ids, cids, mask,
                     queries, *, k2, bq=8, with_stats=False):
    """Candidate-compressed int8-residual scan (see ivf_scan_topk)."""
    out = _q8.ivf_scan_q8_topk(q8, scale, norm2, centroids, posting_ids,
                               cids, mask, queries, k2=k2, bq=bq,
                               interpret=_interp())
    return out if with_stats else out[:2]
