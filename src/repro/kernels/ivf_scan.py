"""Fused posting-list scan — the Helmsman serving hot path as Pallas kernels.

Paper (§4.2): cluster reads are fixed-size, batched, dependency-free; SPDK
bypasses the kernel so one PCIe doorbell serves a whole batch.  TPU-native
adaptation: the posting tensor lives in HBM and the Pallas grid pipeline
streams one posting block per grid step into VMEM (double-buffered DMA — the
"doorbell batch").  The kernels in this module differ in what they send BACK
to HBM:

* ``ivf_scan`` / ``ivf_scan_clustermajor`` — legacy full-distance kernels.
  They write the entire (B, P, L) / (A, L, B) distance tensor to HBM, which
  the frontend then re-reads to run a global top-k.  Kept for comparison and
  for consumers that want raw distances.

* ``ivf_scan_topk`` — the candidate-compressed serving data path (default).
  Grid/scratch design:

    - **Query tiling.**  Queries are tiled into blocks of ``bq`` rows; the
      grid is ``(B/bq, bq*P)``.  Each grid step DMAs ONE posting block
      (L, D) and distances it against the whole query tile with a single
      (bq, D) x (D, L) MXU matmul — not the (1, D) matvec of the legacy
      query-major kernel.

    - **Probe plan.**  ``plan_tile_probes`` (host/jnp, jittable) flattens and
      SORTS each tile's cluster list, so duplicate clusters (probe overlap
      across the tile — §6.2 "transient query bursts target the same
      clusters") land on adjacent grid steps: Pallas skips the HBM->VMEM DMA
      when the block index repeats, and the per-query selection mask ``qsel``
      routes one block's distances to every query in the tile that probed it.
      Dead slots (duplicates / masked probes) have an all-false ``qsel``.

    - **In-VMEM running top-k.**  The (bq, k2) candidate block is the
      kernel's accumulator: the output BlockSpec maps every probe step of a
      tile to the same block, so it stays resident in VMEM across the whole
      probe dimension (the standard revisited-output accumulation pattern)
      and is flushed to HBM exactly once per tile.  Each step merges the
      fresh (bq, L) distance tile into the accumulator with a k2-pass
      min-extraction that also suppresses duplicate ids (closure duplicates),
      so the emitted candidates are unique-by-id with per-id MIN distance —
      i.e. exactly the first k2 rows of the legacy dedup-top-k.

    - **In-kernel id resolution.**  The global id row (posting_ids) is a
      blocked input indexed by the same block table, so ids never materialize
      as a (B, P, L) gather in HBM either.

  HBM writeback per query drops from P*L*(4+4) bytes (distances + gathered
  ids) to k2*(4+4) bytes — O(P*L/k) compression (≥ 100x at P=64, L=128,
  k=10).  This is the §4.2 "no redundant copies between engine and device"
  claim re-expressed for the HBM<->VMEM hierarchy: what crosses the memory
  boundary is the answer, not the intermediate.

The data-dependent block index (which cluster to DMA) uses Pallas scalar
prefetch: the per-tile block table is a scalar-prefetch operand consumed by
the BlockSpec index_map — the same mechanism as paged-attention block tables.

Mosaic tiling rule: the last two dims of every block must be divisible by
(8, 128) or equal the array's.  Per-cluster rows of a 2-D table (posting
ids, centroids, norms) are therefore DMA'd as the (8, X) sublane tile that
holds the row, and the kernel picks the row with a dynamic sublane slice
(:func:`_pick_row`); the tables keep their (C, X) layout, so no padded copy
is ever made.  The per-tile query-selection mask rides in as one
(bq, S) block per tile and the kernel selects step ``s``'s column with a
lane mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# --------------------------------------------------------------------------
# shared in-kernel helpers
# --------------------------------------------------------------------------
def _row_spec(n_rows: int, width: int, row_of):
    """BlockSpec DMAing the (rb, width) tile that holds row ``row_of(...)``:
    one sublane tile (rb = 8), or the whole table when it is shorter (block
    dim == array dim).  Pair with :func:`_pick_row` in the kernel."""
    rb = min(8, n_rows)
    return pl.BlockSpec((rb, width), lambda *a: (row_of(*a) // rb, 0))


def _pick_row(ref, row):
    """Row ``row`` of a table DMA'd through :func:`_row_spec`, as (1, X)."""
    return ref[pl.ds(row % ref.shape[0], 1), :]


def _dot_t(a, b):
    """(n, D) x (m, D) -> (n, m) on the MXU at full f32 precision."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _sq_norms_row(blk):
    """(L, D) -> (1, L) squared row norms, lane-major (an MXU matvec, so no
    sublane->lane relayout is needed)."""
    ones = jnp.ones((1, blk.shape[1]), jnp.float32)
    return _dot_t(ones, blk * blk)


def _l2_tile(q, blk):
    """(n, D) x (L, D) -> (n, L) squared L2 clamped at 0 — one MXU matmul."""
    d = (jnp.sum(q * q, axis=1, keepdims=True) - 2.0 * _dot_t(q, blk)
         + _sq_norms_row(blk))
    return jnp.maximum(d, 0.0)


# --------------------------------------------------------------------------
# legacy full-distance kernels
# --------------------------------------------------------------------------
def _qmajor_kernel(cids_ref, mask_ref, q_ref, post_ref, o_ref):
    b = pl.program_id(0)
    p = pl.program_id(1)
    q = _pick_row(q_ref, b).astype(jnp.float32)        # (1, D)
    d = _l2_tile(q, post_ref[0].astype(jnp.float32))   # (1, L)
    live = mask_ref[b, p] > 0
    o_ref[0, pl.ds(p, 1), :] = jnp.where(live, d, jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ivf_scan(
    postings: jax.Array,   # (C, L, D)
    cids: jax.Array,       # (B, P) int32
    mask: jax.Array,       # (B, P) bool
    queries: jax.Array,    # (B, D)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, P, L) f32 distances; masked probes +inf.  (Legacy path.)

    The (1, P, L) output block of query b stays resident across its P probe
    steps (revisited-output pattern); each step writes one row."""
    C, L, D = postings.shape
    B, P = cids.shape
    safe_cids = jnp.clip(cids, 0, C - 1).astype(jnp.int32)
    mask_i = mask.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            _row_spec(B, D, lambda b, p, cids_p, mask_p: b),
            pl.BlockSpec((1, L, D), lambda b, p, cids_p, mask_p: (cids_p[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, P, L), lambda b, p, cids_p, mask_p: (b, 0, 0)),
    )
    return pl.pallas_call(
        _qmajor_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P, L), jnp.float32),
        interpret=interpret,
    )(safe_cids, mask_i, queries, postings)


def _cmajor_kernel(active_ref, qsel_ref, q_ref, post_ref, o_ref):
    a = pl.program_id(0)
    blk = post_ref[0].astype(jnp.float32)         # (L, D)
    q = q_ref[...].astype(jnp.float32)            # (B, D)
    d = _l2_tile(blk, q)                          # (L, B) — one MXU matmul
    sel = _pick_row(qsel_ref, a) > 0              # (1, B)
    o_ref[...] = jnp.where(sel, d, jnp.inf)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ivf_scan_clustermajor(
    postings: jax.Array,   # (C, L, D)
    active: jax.Array,     # (A,) int32
    qsel: jax.Array,       # (A, B) bool
    queries: jax.Array,    # (B, D)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns (A, L, B) f32 distances; unselected (cluster, query) pairs +inf."""
    C, L, D = postings.shape
    A = active.shape[0]
    B = queries.shape[0]
    safe = jnp.clip(active, 0, C - 1).astype(jnp.int32)
    qsel_i = qsel.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(A,),
        in_specs=[
            _row_spec(A, B, lambda a, act_p: a),
            pl.BlockSpec((B, D), lambda a, act_p: (0, 0)),
            pl.BlockSpec((1, L, D), lambda a, act_p: (act_p[a], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, L, B), lambda a, act_p: (a, 0, 0)),
    )
    return pl.pallas_call(
        _cmajor_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((A, L, B), jnp.float32),
        interpret=interpret,
    )(safe, qsel_i, queries, postings)


# --------------------------------------------------------------------------
# fused in-kernel top-k (the candidate-compressed serving data path)
# --------------------------------------------------------------------------
def plan_tile_probes(
    cids: jax.Array,   # (B, P) int32 — per-query probe cluster ids
    mask: jax.Array,   # (B, P) bool — live probes
    bq: int,
    n_clusters: int,
    *,
    tile_chunk: int = 0,   # 0 = auto: bound the membership intermediate
) -> tuple[jax.Array, jax.Array]:
    """Build the per-tile block table + query-selection mask.

    Flattens each query tile's (bq, P) probe list to S = bq*P slots, sorts by
    cluster id (dead probes sort to the end), and keeps only the FIRST
    occurrence of each cluster live.  Returns

      tile_cids (B/bq, S) int32 — sorted cluster per grid step (duplicates
        adjacent, so the Pallas pipeline skips the repeat DMAs),
      qsel      (B/bq, S, bq) int32 — qsel[t, s, j] != 0 iff query j of tile
        t probes cluster tile_cids[t, s] (any live probe slot).

    A (query, cluster) pair probed more than once contributes a single scan,
    which matches the dedup-top-k semantics downstream.

    The membership test materializes an O(S·bq·P) boolean per tile; at the
    runtime batcher's large coalesced batches (B >= 1e4) the full
    (nb, S, bq, P) intermediate would be hundreds of MB, so tiles are
    processed in chunks of ``tile_chunk`` (auto-sized to keep each chunk's
    intermediate under ~16M elements).  Chunking is over the tile dim only —
    per-tile outputs are independent — so chunked and one-shot plans are
    bit-identical.
    """
    B, P = cids.shape
    nb = B // bq
    s_len = bq * P
    cl = jnp.clip(cids, 0, n_clusters - 1).astype(jnp.int32)
    live = jnp.asarray(mask, bool) & (cids >= 0)
    key = jnp.where(live, cl, n_clusters).reshape(nb, s_len)
    sc = jnp.sort(key, axis=1)                                   # (nb, S)
    uniq = jnp.concatenate(
        [jnp.ones((nb, 1), bool), sc[:, 1:] != sc[:, :-1]], axis=1
    ) & (sc < n_clusters)
    cl3 = cl.reshape(nb, bq, P)
    lv3 = live.reshape(nb, bq, P)
    if tile_chunk <= 0:
        per_tile = s_len * bq * P
        tile_chunk = max(1, (1 << 24) // max(per_tile, 1))
    qsel_chunks = []
    for lo in range(0, nb, tile_chunk):
        hi = min(lo + tile_chunk, nb)
        member = jnp.any(
            (cl3[lo:hi, None, :, :] == sc[lo:hi, :, None, None])
            & lv3[lo:hi, None, :, :],
            axis=-1,
        )                                                        # (c, S, bq)
        qsel_chunks.append(
            (member & uniq[lo:hi, :, None]).astype(jnp.int32)
        )
    qsel = (qsel_chunks[0] if len(qsel_chunks) == 1
            else jnp.concatenate(qsel_chunks, axis=0))
    tile_cids = jnp.minimum(sc, n_clusters - 1).astype(jnp.int32)
    return tile_cids, qsel


def _extract_topk(acc_d, acc_i, new_d, new_i, k2: int):
    """k2-pass min-extraction with duplicate-id suppression over the running
    accumulator (bq, k2) followed by a fresh block (bq, L).

    Returns ((bq, k2) dists ascending, (bq, k2) ids); exhausted slots are
    (+inf, -1).  Each pass takes the global min (ties resolve to the
    accumulator first, then the lowest column — the order of the two parts
    laid side by side), emits it, and kills every remaining entry carrying
    the same id — so the output is unique-by-id with the per-id MIN distance
    (dedup-top-k semantics; closure duplicates of one vector collapse to a
    single candidate).  The two parts are never concatenated: a lane concat
    at an unaligned k2 is a relayout Mosaic need not support, and the
    emitted column is written with a lane mask for the same reason."""
    bq = acc_d.shape[0]
    acol = jax.lax.broadcasted_iota(jnp.int32, acc_d.shape, 1)
    ncol = jax.lax.broadcasted_iota(jnp.int32, new_d.shape, 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (bq, k2), 1)
    none = acc_d.shape[1] + new_d.shape[1]
    out_d = jnp.full((bq, k2), jnp.inf, jnp.float32)
    out_i = jnp.full((bq, k2), -1, jnp.int32)
    for j in range(k2):
        m = jnp.minimum(jnp.min(acc_d, axis=1, keepdims=True),
                        jnp.min(new_d, axis=1, keepdims=True))   # (bq, 1)
        apos = jnp.min(jnp.where(acc_d == m, acol, none), axis=1,
                       keepdims=True)
        npos = jnp.min(jnp.where(new_d == m, ncol, none), axis=1,
                       keepdims=True)
        ahit = acol == apos                                      # one-hot
        nhit = (ncol == npos) & (apos == none)
        pid = (jnp.sum(jnp.where(ahit, acc_i, 0), axis=1, keepdims=True)
               + jnp.sum(jnp.where(nhit, new_i, 0), axis=1, keepdims=True))
        ok = m < jnp.inf
        out_d = jnp.where((kcol == j) & ok, m, out_d)
        out_i = jnp.where((kcol == j) & ok, pid, out_i)
        dup = (pid >= 0) & ok
        acc_d = jnp.where(ahit | ((acc_i == pid) & dup), jnp.inf, acc_d)
        new_d = jnp.where(nhit | ((new_i == pid) & dup), jnp.inf, new_d)
    return out_d, out_i


def _merge_block(d, pids, qsel_ref, od_ref, oi_ref):
    """Fold one scanned (bq, L) distance block into the resident (bq, k2)
    candidate accumulator.  ``pids`` (1, L) are the block's global ids;
    ``qsel_ref`` is the tile's (1, bq, S) query-selection block, whose
    column ``s`` (this grid step) says which queries of the tile probed the
    block."""
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        od_ref[...] = jnp.full(od_ref.shape, jnp.inf, od_ref.dtype)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, oi_ref.dtype)

    qs = qsel_ref[0]                                             # (bq, S)
    scol = jax.lax.broadcasted_iota(jnp.int32, qs.shape, 1)
    sel = jnp.max(jnp.where(scol == s, qs, 0), axis=1, keepdims=True) > 0
    ids = jnp.broadcast_to(pids.astype(jnp.int32), d.shape)
    d = jnp.where(sel & (ids >= 0), d, jnp.inf)
    nd, ni = _extract_topk(od_ref[...], oi_ref[...], d, ids,
                           od_ref.shape[-1])
    od_ref[...] = nd
    oi_ref[...] = ni


def _qtile_topk_kernel(tc_ref, q_ref, pids_ref, qsel_ref, post_ref,
                       od_ref, oi_ref):
    c = tc_ref[pl.program_id(0), pl.program_id(1)]
    d = _l2_tile(q_ref[...].astype(jnp.float32),
                 post_ref[0].astype(jnp.float32))       # (bq, L) — one MXU op
    _merge_block(d, _pick_row(pids_ref, c), qsel_ref, od_ref, oi_ref)


def tile_plan(cids, mask, queries, bq: int, n_clusters: int):
    """Pad the batch to the query tile and build the probe plan shared by
    both fused kernels.  Returns (queries (bp, D), tile_cids (nb, S),
    qsel (nb, bq, S) int32) — qsel transposed so one (bq, S) block per
    tile is DMA'd once and stays resident across the tile's S steps."""
    padb = (-cids.shape[0]) % bq
    if padb:
        queries = jnp.pad(queries, ((0, padb), (0, 0)))
        cids = jnp.pad(cids, ((0, padb), (0, 0)))
        mask = jnp.pad(jnp.asarray(mask, bool), ((0, padb), (0, 0)))
    tile_cids, qsel = plan_tile_probes(cids, mask, bq, n_clusters)
    return queries, tile_cids, jnp.swapaxes(qsel, 1, 2)


def topk_outputs(nb: int, bq: int, k2: int):
    """(out_specs, out_shape) of the fused kernels: one (bq, k2) candidate
    accumulator block per tile for dists and ids, revisited across the
    tile's probe steps."""
    spec = pl.BlockSpec((bq, k2), lambda t, s, *_: (t, 0))
    return [spec, spec], (jax.ShapeDtypeStruct((nb * bq, k2), jnp.float32),
                          jax.ShapeDtypeStruct((nb * bq, k2), jnp.int32))


@functools.partial(jax.jit, static_argnames=("k2", "bq", "interpret"))
def ivf_scan_topk(
    postings: jax.Array,     # (C, L, D)
    posting_ids: jax.Array,  # (C, L) int32, -1 = pad slot
    cids: jax.Array,         # (B, P) int32
    mask: jax.Array,         # (B, P) bool
    queries: jax.Array,      # (B, D)
    *,
    k2: int,
    bq: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused scan + in-kernel top-k2: returns ((B, k2) dists, (B, k2) ids).

    Candidates are unique-by-id, ascending by distance, padded with
    (+inf, -1).  Only (B, k2) crosses the pallas_call boundary — never the
    (B, P, L) distance tensor.
    """
    C, L, D = postings.shape
    B = cids.shape[0]
    queries, tile_cids, qsel = tile_plan(cids, mask, queries, bq, C)
    nb, s_len = tile_cids.shape
    out_specs, out_shape = topk_outputs(nb, bq, k2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, s_len),
        in_specs=[
            pl.BlockSpec((bq, D), lambda t, s, tc: (t, 0)),
            _row_spec(C, L, lambda t, s, tc: tc[t, s]),
            pl.BlockSpec((1, bq, s_len), lambda t, s, tc: (t, 0, 0)),
            pl.BlockSpec((1, L, D), lambda t, s, tc: (tc[t, s], 0, 0)),
        ],
        out_specs=out_specs,
    )
    od, oi = pl.pallas_call(
        _qtile_topk_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(tile_cids, queries, posting_ids.astype(jnp.int32), qsel, postings)
    return od[:B], oi[:B]
