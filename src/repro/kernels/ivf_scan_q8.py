"""Fused int8-residual posting scan (Pallas) — the optimized serving hot
path from EXPERIMENTS §Perf it.3.

Same structure as ivf_scan (scalar-prefetch block table, one posting block
DMA'd HBM->VMEM per grid step) but the payload is the int8 RESIDUAL code from
core/quantize.py at 1/4 the HBM bytes; the kernel dequantizes in registers
and applies the closed-form residual expansion:

    ||q - (c + s r8)||^2 = ||q - c||^2 - 2 s (q - c).r8 + s^2 ||r8||^2

Operands per grid step: q8 block (L, D) int8, centroid row (D,), per-cluster
scale, precomputed s^2||r8||^2 row (L,).  Centroid and norm rows arrive as
the (8, X) tile that holds them (see kernels/ivf_scan.py on the Mosaic
tiling rule); the per-step scale is gathered on the host side of the call
into a scalar-prefetch table, so it is read from SMEM as a scalar.

Two variants:

* ``ivf_scan_q8``      — legacy (B, P, L) full-distance writeback.
* ``ivf_scan_q8_topk`` — candidate-compressed: query-tiled grid + in-VMEM
  running top-k2 with in-kernel posting-id resolution, emitting (B, k2)
  candidates.  See kernels/ivf_scan.py for the grid/scratch design; this
  kernel shares its probe plan and top-k merge helpers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ivf_scan import (
    _dot_t, _merge_block, _pick_row, _row_spec, tile_plan, topk_outputs,
)

# the served scan's op name in the compiled program and the device trace
TOPK_KERNEL_NAME = "ivf_scan_q8_topk"


def _residual_l2(q, cent, r8, scale, n2):
    """||q - (c + s r8)||^2 = ||q - c||^2 - 2 s (q - c).r8 + s^2 ||r8||^2
    for (n, D) queries against one (L, D) int8 block -> (n, L), clamped."""
    qc = q - cent                                  # (n, D)
    cross = _dot_t(qc, r8.astype(jnp.float32))     # (n, L) — one MXU op
    d = jnp.sum(qc * qc, axis=1, keepdims=True) - 2.0 * scale * cross + n2
    return jnp.maximum(d, 0.0)


def _kernel(cids_ref, mask_ref, scale_ref, q_ref, cent_ref, norm2_ref,
            q8_ref, o_ref):
    b = pl.program_id(0)
    p = pl.program_id(1)
    c = cids_ref[b, p]
    d = _residual_l2(_pick_row(q_ref, b).astype(jnp.float32),
                     _pick_row(cent_ref, c).astype(jnp.float32), q8_ref[0],
                     scale_ref[b, p], _pick_row(norm2_ref, c))   # (1, L)
    live = mask_ref[b, p] > 0
    o_ref[0, pl.ds(p, 1), :] = jnp.where(live, d, jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ivf_scan_q8(
    q8: jax.Array,         # (C, L, D) int8 residual codes
    scale: jax.Array,      # (C, 1, 1) f32
    norm2: jax.Array,      # (C, L) f32
    centroids: jax.Array,  # (C, D) f32
    cids: jax.Array,       # (B, P) int32
    mask: jax.Array,       # (B, P) bool
    queries: jax.Array,    # (B, D)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, P, L) f32 distances; masked probes +inf."""
    C, L, D = q8.shape
    B, P = cids.shape
    safe = jnp.clip(cids, 0, C - 1).astype(jnp.int32)
    mask_i = mask.astype(jnp.int32)
    step_scale = scale.reshape(C).astype(jnp.float32)[safe]      # (B, P)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P),
        in_specs=[
            _row_spec(B, D, lambda b, p, *_: b),
            _row_spec(C, D, lambda b, p, c_p, *_: c_p[b, p]),
            _row_spec(C, L, lambda b, p, c_p, *_: c_p[b, p]),
            pl.BlockSpec((1, L, D), lambda b, p, c_p, *_: (c_p[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, P, L), lambda b, p, *_: (b, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P, L), jnp.float32),
        interpret=interpret,
    )(safe, mask_i, step_scale, queries, centroids, norm2, q8)


# --------------------------------------------------------------------------
# fused in-kernel top-k over int8 residual postings
# --------------------------------------------------------------------------
def _qtile_topk_q8_kernel(tc_ref, sc_ref, q_ref, cent_ref, norm2_ref,
                          pids_ref, qsel_ref, q8_ref, od_ref, oi_ref):
    t = pl.program_id(0)
    s = pl.program_id(1)
    c = tc_ref[t, s]
    d = _residual_l2(q_ref[...].astype(jnp.float32),
                     _pick_row(cent_ref, c).astype(jnp.float32), q8_ref[0],
                     sc_ref[t, s], _pick_row(norm2_ref, c))
    _merge_block(d, _pick_row(pids_ref, c), qsel_ref, od_ref, oi_ref)


@functools.partial(jax.jit, static_argnames=("k2", "bq", "interpret"))
def ivf_scan_q8_topk(
    q8: jax.Array,           # (C, L, D) int8 residual codes
    scale: jax.Array,        # (C, 1, 1) f32
    norm2: jax.Array,        # (C, L) f32
    centroids: jax.Array,    # (C, D) f32
    posting_ids: jax.Array,  # (C, L) int32, -1 = pad slot
    cids: jax.Array,         # (B, P) int32
    mask: jax.Array,         # (B, P) bool
    queries: jax.Array,      # (B, D)
    *,
    k2: int,
    bq: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused q8 scan + in-kernel top-k2: ((B, k2) dists, (B, k2) ids).

    Same candidate contract as ivf_scan_topk; the per-id min collapses the
    slightly-different residual distances of closure duplicates (each copy is
    quantized against its own centroid)."""
    C, L, D = q8.shape
    B = cids.shape[0]
    queries, tile_cids, qsel = tile_plan(cids, mask, queries, bq, C)
    nb, s_len = tile_cids.shape
    step_scale = scale.reshape(C).astype(jnp.float32)[tile_cids]  # (nb, S)
    out_specs, out_shape = topk_outputs(nb, bq, k2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, s_len),
        in_specs=[
            pl.BlockSpec((bq, D), lambda t, s, *_: (t, 0)),
            _row_spec(C, D, lambda t, s, tc, _: tc[t, s]),
            _row_spec(C, L, lambda t, s, tc, _: tc[t, s]),
            _row_spec(C, L, lambda t, s, tc, _: tc[t, s]),
            pl.BlockSpec((1, bq, s_len), lambda t, s, *_: (t, 0, 0)),
            pl.BlockSpec((1, L, D), lambda t, s, tc, _: (tc[t, s], 0, 0)),
        ],
        out_specs=out_specs,
    )
    od, oi = pl.pallas_call(
        _qtile_topk_q8_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name=TOPK_KERNEL_NAME,
    )(tile_cids, step_scale, queries, centroids, norm2,
      posting_ids.astype(jnp.int32), qsel, q8)
    return od[:B], oi[:B]
