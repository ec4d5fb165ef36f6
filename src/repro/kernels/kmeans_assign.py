"""Fused k-means assign-and-accumulate — the construction hot path as a
Pallas kernel (paper §4.4 / Fig. 13: GPU-offloaded clustering, re-expressed
for the TPU memory hierarchy).

The unfused Lloyd E-step materializes the full (N, K) distance matrix in HBM
every iteration, reads it back for the argmin, and then runs the M-step as a
host-side scatter-add — three round trips through the slowest tier for one
logical reduction.  This kernel fuses E and M: each grid step DMAs one
(BN, D) point block into VMEM, distances it against the WHOLE centroid block
with a single (BN, D) x (D, K) MXU matmul, takes the per-point argmin, and
immediately folds the block into per-centroid partial sums and counts that
stay RESIDENT in VMEM across the entire point-grid dimension (the same
output-block-revisiting trick as ``ivf_scan_topk``'s candidate accumulator:
the sums/counts BlockSpecs map every grid step to block (0, 0), so they are
flushed to HBM exactly once).  What crosses the pallas_call boundary is the
ANSWER of one Lloyd iteration —

    assignments (N,) i32 + min-dists (N,) f32 + sums (K, D) f32 + counts (K,)

— never the (N, K) intermediate.  Writeback drops from N*K*4 bytes to
(K*D + K + 2N)*4 bytes: ~300x at N=50k, K=1024, D=64.

The one-hot fold is itself an MXU op: onehot(assign)^T @ points is a
(K, BN) x (BN, D) matmul, so the M-step rides the systolic array instead of
a gather/scatter unit.  Padding contract: padded D columns are zeros (exact
for every distance term), padded K rows are masked to +inf before the argmin
(so they accumulate nothing), padded N rows are masked out of the one-hot
(so they perturb no sums) and sliced off the assignment outputs.

The live-row count is a run-time scalar (SMEM scalar prefetch), not a
compile-time constant: a caller that pads its points to a shape bucket
passes ``n_valid`` and reuses one compiled program for every point count
in the bucket.  Rows at or past ``n_valid`` accumulate nothing and report
min-dist ``-inf``, so a worst-served selection never picks them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(nv_ref, x_ref, c_ref, a_ref, m_ref, s_ref, cnt_ref, *,
            n_cents: int, bn: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = x_ref[...].astype(jnp.float32)                  # (BN, Dp)
    c = c_ref[...].astype(jnp.float32)                  # (Kp, Dp)
    d = (
        jnp.sum(x * x, axis=1, keepdims=True)
        - 2.0 * jax.lax.dot_general(
            x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        + jnp.sum(c * c, axis=1)[None, :]
    )                                                   # (BN, Kp) — one MXU op
    d = jnp.maximum(d, 0.0)
    col = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    d = jnp.where(col < n_cents, d, jnp.inf)            # padded centroids dead
    a = jnp.argmin(d, axis=1).astype(jnp.int32)         # (BN,)
    md = jnp.min(d, axis=1)
    row = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)[:, 0] + i * bn
    live = row < nv_ref[0]                              # padded points dead
    a_ref[...] = a[:, None]
    m_ref[...] = jnp.where(live, md, -jnp.inf)[:, None]
    oh = ((col == a[:, None]) & live[:, None]).astype(jnp.float32)  # (BN, Kp)
    s_ref[...] += jax.lax.dot_general(                  # (Kp, Dp) — MXU M-step
        oh, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    cnt_ref[...] += jnp.sum(oh, axis=0)[None, :]


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def kmeans_assign_update(
    x: jax.Array,          # (N, D) points
    centroids: jax.Array,  # (K, D)
    n_valid=None,          # live rows (scalar, traced); None = all N
    *,
    bn: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused Lloyd iteration's data movement.

    Returns (assign (N,) i32, min_dist (N,) f32, sums (K, D) f32,
    counts (K,) f32) where sums[k] = Σ x[i] over assign[i] == k and
    counts[k] = |{i : assign[i] == k}|.  The (N, K) distance matrix never
    leaves VMEM.  Centroids (and the sums accumulator) are kept WHOLE in
    VMEM as (Kp, Dp) f32 blocks — the kernel does not chunk K, because the
    argmin must be global before any accumulation.  Callers whose working
    set (centroids + sums + the (BN, Kp) distance/one-hot tiles) exceeds
    the VMEM budget go through ops.kmeans_assign_update_tile, which
    estimates that footprint and falls back to the jnp oracle; the build
    pipeline itself stays far below it (hierarchical splitting keeps
    per-call K small).
    """
    n, d = x.shape
    k = centroids.shape[0]
    bn_ = min(bn, _ceil_mult(n, 8))
    kp = _ceil_mult(k, 128)
    dp = _ceil_mult(d, 128)
    xp = jnp.pad(x, ((0, (-n) % bn_), (0, dp - d)))
    cp = jnp.pad(centroids, ((0, kp - k), (0, dp - d)))
    n_blocks = xp.shape[0] // bn_
    nv = jnp.reshape(n if n_valid is None else n_valid, (1,)).astype(
        jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((bn_, dp), lambda i, _: (i, 0)),
            pl.BlockSpec((kp, dp), lambda i, _: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn_, 1), lambda i, _: (i, 0)),
            pl.BlockSpec((bn_, 1), lambda i, _: (i, 0)),
            # revisited across the whole point grid: VMEM-resident accumulators
            pl.BlockSpec((kp, dp), lambda i, _: (0, 0)),
            pl.BlockSpec((1, kp), lambda i, _: (0, 0)),
        ],
    )
    a, md, sums, counts = pl.pallas_call(
        functools.partial(_kernel, n_cents=k, bn=bn_),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, kp), jnp.float32),
        ),
        interpret=interpret,
    )(nv, xp, cp)
    return a[:n, 0], md[:n, 0], sums[:k, :d], counts[0, :k]
