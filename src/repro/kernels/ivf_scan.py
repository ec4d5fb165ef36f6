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
      Dead slots (duplicates / masked probes) have an all-false ``qsel``;
      a scalar-prefetch ``live`` table marks them, and a dead step computes
      nothing.

    - **In-VMEM running top-k.**  The (bq, k2) candidate block is the
      kernel's accumulator: the output BlockSpec maps every probe step of a
      tile to the same block, so it stays resident in VMEM across the whole
      probe dimension (the standard revisited-output accumulation pattern)
      and is flushed to HBM exactly once per tile.  The merge is gated on
      the running k2-th distance: a live step counts per query the entries
      strictly below it and runs that many insertion passes (at most k2,
      none when no entry can enter), each moving one entry into the
      unsorted accumulator with duplicate-id suppression (closure
      duplicates); the tile's last step sorts it.  Past ``NARROW_K2``
      (k2 > 128, a search service's k) the merge is buffered instead: the
      entries below the threshold are appended to a VMEM stack that
      bitonic compactions keep to the k2 best distinct ids, so a live step
      costs a bounded number of passes and the compiled kernel does not
      grow with k2 (:func:`_append_wide`, :func:`_settle_wide`).  The
      emitted candidates are unique-by-id with per-id MIN distance — i.e.
      exactly the first k2 rows of the legacy dedup-top-k.  Each tile also
      emits counters of its live steps, merging steps, admitted entries
      and compaction passes (:func:`scan_stats`).

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

# the served scan's op name in the compiled program and the device trace
TOPK_KERNEL_NAME = "ivf_scan_topk"


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


_FIRST = jnp.iinfo(jnp.int32).min   # below every arrival key
_LAST = jnp.iinfo(jnp.int32).max    # above every arrival key and id
_LANES = 128

# Widest k2 the per-entry insertion merge serves.  Its live step runs one
# pass over the (bq, k2) accumulator per entry that can enter, and its last
# step k2 unrolled extraction passes, which is cheap while the accumulator
# is one lane tile; a wider k2 takes the buffered merge (:func:`_append_wide`),
# whose live step costs a fixed number of passes over the block and whose
# compiled size does not grow with k2.
NARROW_K2 = _LANES


def _insert_pass(acc, new_d, new_i, ncol, base):
    """One pass of the count-bounded merge: move each row's smallest
    remaining block entry into the unsorted (bq, k2) accumulator.

    ``acc`` is (dists, ids, arrival keys); a key is ``s * L + column`` of
    the entry's grid step and block column, so ascending (distance, key)
    is the order in which an ascending merge of the steps would have met
    the entries (the accumulator wins ties).  An id already held replaces
    its own slot only when it is strictly smaller (per-id minimum);
    otherwise the entry replaces the row's last slot in that order when it
    is strictly before it.  The taken entry and every other copy of its id
    in the block leave the block."""
    acc_d, acc_i, acc_a = acc
    m = jnp.min(new_d, axis=1, keepdims=True)                    # (bq, 1)
    pos = jnp.min(jnp.where(new_d == m, ncol, new_d.shape[1]), axis=1,
                  keepdims=True)
    hit = ncol == pos                                            # one-hot
    pid = jnp.sum(jnp.where(hit, new_i, 0), axis=1, keepdims=True)
    same = acc_i == pid                       # ids are unique in acc
    held = jnp.min(jnp.where(same, acc_d, jnp.inf), axis=1, keepdims=True)
    top = jnp.max(acc_d, axis=1, keepdims=True)
    last = jnp.max(jnp.where(acc_d == top, acc_a, _FIRST), axis=1,
                   keepdims=True)
    present = held < jnp.inf
    put = (((same & present) | ((acc_a == last) & ~present))
           & (m < jnp.where(present, held, top)))
    acc = (jnp.where(put, m, acc_d), jnp.where(put, pid, acc_i),
           jnp.where(put, base + pos, acc_a))
    return acc, jnp.where(hit | (new_i == pid), jnp.inf, new_d)


def _sorted_candidates(acc_d, acc_i, acc_a):
    """The unsorted accumulator as (bq, k2) candidates ascending by
    (distance, arrival key), padded with (+inf, -1).  Each emitted column
    is written with a lane mask: a lane slice at an unaligned k2 is a
    relayout Mosaic need not support."""
    k2 = acc_d.shape[1]
    kcol = jax.lax.broadcasted_iota(jnp.int32, acc_d.shape, 1)
    out_d = jnp.full(acc_d.shape, jnp.inf, jnp.float32)
    out_i = jnp.full(acc_i.shape, -1, jnp.int32)
    for j in range(k2):
        m = jnp.min(acc_d, axis=1, keepdims=True)
        first = jnp.min(jnp.where(acc_d == m, acc_a, _LAST), axis=1,
                        keepdims=True)
        hit = acc_a == first
        pid = jnp.sum(jnp.where(hit, acc_i, 0), axis=1, keepdims=True)
        emit = (kcol == j) & (m < jnp.inf)
        out_d = jnp.where(emit, m, out_d)
        out_i = jnp.where(emit, pid, out_i)
        acc_d = jnp.where(hit, jnp.inf, acc_d)
    return out_d, out_i


# ---- the buffered merge for k2 > NARROW_K2 ------------------------------
#
# The accumulator is a (W/128 + 1, bq, 128) stack of lane tiles per array
# (distances, ids, arrival keys), W = 2 * next_pow2(k2 rounded to 128):
# positions c * 128 + lane, the last tile a spill for unaligned appends.
# A live step appends the entries below the row's threshold at a shared
# fill point; a step after which the next block might not fit, and the
# tile's last step, compact: sort by (id, distance, key), drop every copy
# of an id after its first, sort by (distance, key), keep the first k2
# and set the threshold to the k2-th.  Every comparison is a strict
# lexicographic order on tuples that differ for every entry that can be
# emitted, so the sorts need no stability.  A bitonic network sorts:
# within a lane tile with lane rotations, across tiles with tile pairs,
# every stage a loop step, so the compiled size depends on neither W nor
# the tile's 128 lanes.

def _less(a, b):
    """Strict lexicographic ``a < b`` over tuples of equal-shape arrays."""
    lt = a[-1] < b[-1]
    for x, y in zip(a[-2::-1], b[-2::-1]):
        lt = (x < y) | ((x == y) & lt)
    return lt


def _exchange(vals, partner, nkey, lower, up):
    """One compare-exchange step: each lane keeps the smaller of itself and
    its partner (by the first ``nkey`` arrays) when it is the lower lane of
    an ascending pair or the upper lane of a descending one."""
    keep = _less(vals[:nkey], partner[:nkey]) == (lower == up)
    return tuple(jnp.where(keep, x, y) for x, y in zip(vals, partner))


def _tile_stage(vals, nkey, j, up):
    """Compare-exchange at lane distance ``j`` < 128 (a traced scalar)
    within each tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, vals[0].shape, 1)
    upper = (lane & j) != 0
    partner = tuple(jnp.where(upper, pltpu.roll(x, j, 1),
                              pltpu.roll(x, _LANES - j, 1)) for x in vals)
    return _exchange(vals, partner, nkey, ~upper, up)


def _merge_tile(vals, nkey, up, log_size=_LANES.bit_length() - 1):
    """Bitonic merge within each tile: each run of ``2**log_size`` lanes
    that is bitonic comes out sorted, ascending where ``up``.  One stage a
    loop step, so the trace holds one stage whatever the size."""
    def stage(b, vals):                    # lane distance 2**(log_size-1-b)
        return _tile_stage(vals, nkey, jnp.left_shift(1, log_size - 1 - b),
                           up)

    return jax.lax.fori_loop(0, log_size, stage, vals)


def _sort_tile(vals, nkey, ascending):
    """Bitonic sort of each row's 128 lanes, ascending or descending: runs
    of 2, 4, ... 128 lanes merged in turn, in a loop."""
    lane = jax.lax.broadcasted_iota(jnp.int32, vals[0].shape, 1)
    top = _LANES.bit_length() - 1
    flip = jnp.logical_not(ascending)

    def level(a, vals):                    # runs of 2**a lanes
        up = ((lane & jnp.left_shift(1, a)) == 0) != ((a == top) & flip)
        return _merge_tile(vals, nkey, up, a)

    return jax.lax.fori_loop(1, top + 1, level, vals)


def _sort_stack(refs, nkey, n_tiles):
    """Bitonic sort, ascending by the first ``nkey`` arrays, of the first
    ``n_tiles`` (a power of two) lane tiles of ``refs`` read as one row of
    ``n_tiles * 128`` positions per query."""
    def load(c):
        return tuple(r[c] for r in refs)

    def store(c, vals):
        for r, v in zip(refs, vals):
            r[c] = v

    def sort_tile(c, carry):
        store(c, _sort_tile(load(c), nkey, c % 2 == 0))
        return carry

    jax.lax.fori_loop(0, n_tiles, sort_tile, 0)
    levels = n_tiles.bit_length() - 1

    def level(a, carry):                   # runs of 2**a tiles merged
        def across(b, carry):              # tile distance 2**(a - 1 - b)
            sh = a - 1 - b

            def pair(i, carry):
                lo = ((i >> sh) << (sh + 1)) | (i & ((1 << sh) - 1))
                hi = lo + (1 << sh)
                x, y = load(lo), load(hi)
                keep = _less(x[:nkey], y[:nkey]) == (((lo >> a) & 1) == 0)
                store(lo, tuple(jnp.where(keep, u, v) for u, v in zip(x, y)))
                store(hi, tuple(jnp.where(keep, v, u) for u, v in zip(x, y)))
                return carry

            return jax.lax.fori_loop(0, n_tiles // 2, pair, carry)

        jax.lax.fori_loop(0, a, across, 0)

        def merge_tile(c, carry):
            store(c, _merge_tile(load(c), nkey, ((c >> a) & 1) == 0))
            return carry

        return jax.lax.fori_loop(0, n_tiles, merge_tile, carry)

    jax.lax.fori_loop(1, levels + 1, level, 0)


def _sort_passes(n_tiles: int) -> int:
    """Compare-exchange passes of a bitonic sort over ``n_tiles`` tiles."""
    lg = (n_tiles * _LANES).bit_length() - 1
    return lg * (lg + 1) // 2


def _compact(acc_refs, k2: int):
    """Keep each row's k2 best distinct ids: sort the stack by (id,
    distance, key) with empty slots last, drop each id's later copies,
    sort by (distance, key) and empty every position from k2 on.  Returns
    the number of passes over the stack it ran."""
    d_ref, i_ref, a_ref = acc_refs
    n_tiles = d_ref.shape[0] - 1

    def empty_last(c, carry):
        i_ref[c] = jnp.where(d_ref[c] < jnp.inf, i_ref[c], _LAST)
        return carry

    jax.lax.fori_loop(0, n_tiles, empty_last, 0)
    _sort_stack((i_ref, d_ref, a_ref), 3, n_tiles)

    def first_copies(r, carry):            # from the last tile down, so
        c = n_tiles - 1 - r                # each reads an unchanged left
        ids = i_ref[c]                     # neighbour
        lane = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)
        prev = jnp.where(lane == 0,
                         pltpu.roll(i_ref[jnp.maximum(c - 1, 0)], 1, 1),
                         pltpu.roll(ids, 1, 1))
        has_prev = (lane > 0) | (c > 0)
        dup = has_prev & (prev == ids)
        keep = (ids != _LAST) & ~dup
        d_ref[c] = jnp.where(keep, d_ref[c], jnp.inf)
        i_ref[c] = jnp.where(keep, ids, -1)
        return carry

    jax.lax.fori_loop(0, n_tiles, first_copies, 0)
    _sort_stack((d_ref, a_ref, i_ref), 2, n_tiles)

    def cut(c, carry):
        pos = c * _LANES + jax.lax.broadcasted_iota(
            jnp.int32, d_ref.shape[1:], 1)
        d_ref[c] = jnp.where(pos < k2, d_ref[c], jnp.inf)
        i_ref[c] = jnp.where(pos < k2, i_ref[c], -1)
        return carry

    jax.lax.fori_loop(0, n_tiles, cut, 0)
    return 2 * _sort_passes(n_tiles) + 3


def _stack_tiles(k2: int) -> int:
    """Lane tiles the buffered merge sorts: a power of two, at least twice
    the tiles k2 fills, so a compaction leaves room for many appends."""
    tiles = -(-k2 // _LANES)
    return 2 * (1 << (tiles - 1).bit_length())


def _append_wide(d, ids, s, st_ref, scratch):
    """A live step of the buffered merge (k2 > NARROW_K2): ``d`` (bq, L)
    are the step's masked distances and ``ids`` their global ids;
    ``scratch`` holds the accumulator stack, the fill point (SMEM) and the
    per-row threshold.  Each row's entries below its threshold are sorted
    to the front of a lane tile and appended at the shared fill point;
    :func:`_settle_wide` keeps room for a whole block.  Counts into
    ``st_ref`` as :func:`_merge_block` says."""
    d_ref, i_ref, a_ref, fill_ref, thr_ref = scratch
    bq, l = d.shape
    keys = s * l + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    below = d < thr_ref[:, :l]
    n = jnp.max(jnp.sum(below.astype(jnp.int32), axis=1, keepdims=True),
                axis=0, keepdims=True)                           # (1, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, st_ref.shape, 1)
    st_ref[...] += (jnp.where(lane == 0, 1, 0)
                    + jnp.where(lane == 1, (n > 0).astype(jnp.int32), 0)
                    + jnp.where(lane == 2, n, 0))
    count = jnp.max(n)

    @pl.when(count > 0)
    def _append():
        vals = (jnp.where(below, d, jnp.inf), keys,
                jnp.where(below, ids, -1))
        if l < _LANES:                     # short lists (interpret only)
            pad = ((0, 0), (0, _LANES - l))
            vals = (jnp.pad(vals[0], pad, constant_values=jnp.inf),
                    jnp.pad(vals[1], pad), jnp.pad(vals[2], pad,
                                                   constant_values=-1))
        d_s, k_s, i_s = _sort_tile(vals, 2, True)  # entries below first
        fill = fill_ref[0]
        c0, r = fill // _LANES, fill % _LANES
        tl = jax.lax.broadcasted_iota(jnp.int32, d_s.shape, 1)
        for ref, v in zip((d_ref, i_ref, a_ref), (d_s, i_s, k_s)):
            v = pltpu.roll(v, r, 1)
            ref[c0] = jnp.where(tl >= r, v, ref[c0])
            ref[c0 + 1] = jnp.where(tl < r, v, ref[c0 + 1])
        fill_ref[0] = fill + count


def _settle_wide(last, od_ref, oi_ref, st_ref, scratch):
    """End of every step of the buffered merge, and its one compaction
    site: compact when the next block might not fit, or at the tile's last
    step, which then writes the first k2 positions as the tile's
    candidates.  A compaction sets each row's threshold to its k2-th
    distance and the fill point to k2, which leaves room for a block."""
    d_ref, i_ref, a_ref, fill_ref, thr_ref = scratch
    k2 = od_ref.shape[-1]
    n_tiles = d_ref.shape[0] - 1

    @pl.when(last | (fill_ref[0] > (n_tiles - 1) * _LANES))
    def _compact_stack():
        passes = _compact((d_ref, i_ref, a_ref), k2)
        c, r = (k2 - 1) // _LANES, (k2 - 1) % _LANES
        rl = jax.lax.broadcasted_iota(jnp.int32, thr_ref.shape, 1)
        thr = jnp.max(jnp.where(rl == r, d_ref[c], -jnp.inf), axis=1,
                      keepdims=True)
        thr_ref[...] = jnp.broadcast_to(thr, thr_ref.shape)
        fill_ref[0] = k2
        lane = jax.lax.broadcasted_iota(jnp.int32, st_ref.shape, 1)
        st_ref[...] += jnp.where(lane == 3, passes, 0)

    @pl.when(last)
    def _emit():
        full, rest = divmod(k2, _LANES)

        def emit(c, carry):
            at = pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)
            od_ref[:, at] = d_ref[c]
            oi_ref[:, at] = i_ref[c]
            return carry

        if full:
            jax.lax.fori_loop(0, full, emit, 0)
        if rest:
            od_ref[:, full * _LANES:] = d_ref[full][:, :rest]
            oi_ref[:, full * _LANES:] = i_ref[full][:, :rest]


def _init_wide(scratch):
    """An empty stack (distinct keys below every real one), fill point 0
    and threshold +inf."""
    d_ref, i_ref, a_ref, fill_ref, thr_ref = scratch
    shape = d_ref.shape[1:]
    pos = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def clear(c, carry):
        d_ref[c] = jnp.full(shape, jnp.inf, jnp.float32)
        i_ref[c] = jnp.full(shape, -1, jnp.int32)
        a_ref[c] = -1 - (c * _LANES + pos)  # distinct, before any real key
        return carry

    jax.lax.fori_loop(0, d_ref.shape[0], clear, 0)
    fill_ref[0] = 0
    thr_ref[...] = jnp.full(thr_ref.shape, jnp.inf, jnp.float32)


def _merge_block(scan, live, pids_ref, c, qsel_ref, od_ref, oi_ref, st_ref,
                 scratch):
    """Fold grid step ``s``'s scanned block into the tile's running top-k2.

    ``live`` (a scalar from the plan's table) says whether any query of the
    tile selects the step's block; a dead step computes nothing.  A live
    step calls ``scan()`` for its (bq, L) distances, keeps the entries of
    the queries whose ``qsel_ref`` column ``s`` is set and whose global id
    (row ``c`` of ``pids_ref``) is not a pad, and counts per row the
    entries strictly below the row's threshold.

    Up to ``NARROW_K2`` the threshold is the largest distance the
    accumulator holds (+inf until it is full), and the step runs one
    insertion pass (:func:`_insert_pass`) per such entry, for the most such
    entries of any row, and no pass when there are none.  The accumulator
    (``od_ref``/``oi_ref``, revisited across the tile, with the arrival
    keys in ``scratch``) is sorted once, at the tile's last step.  Above
    it, the buffered merge appends the entries below the threshold its
    last compaction left (:func:`_append_wide`); every step ends in
    :func:`_settle_wide`, which compacts when the stack is nearly full and
    at the last step.

    ``st_ref``, the tile's (8, 128) counter block, adds per step: lane 0
    a live step, lane 1 a step with an entry below the threshold, lane 2
    the most such entries of a row (the insertion passes, or the entries
    appended); lane 3 the passes of the buffered merge's compactions."""
    s = pl.program_id(1)
    k2 = od_ref.shape[-1]
    wide = k2 > NARROW_K2

    @pl.when(s == 0)
    def _init():
        st_ref[...] = jnp.zeros(st_ref.shape, st_ref.dtype)
        if wide:
            _init_wide(scratch)
            return
        od_ref[...] = jnp.full(od_ref.shape, jnp.inf, od_ref.dtype)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, oi_ref.dtype)
        # distinct keys before any real one: an empty slot is the last
        arr_ref, = scratch
        arr_ref[...] = -1 - jax.lax.broadcasted_iota(jnp.int32,
                                                     arr_ref.shape, 1)

    @pl.when(live > 0)
    def _live():
        d = scan()                                               # (bq, L)
        qs = qsel_ref[0]                                         # (bq, S)
        scol = jax.lax.broadcasted_iota(jnp.int32, qs.shape, 1)
        sel = jnp.max(jnp.where(scol == s, qs, 0), axis=1, keepdims=True) > 0
        ids = jnp.broadcast_to(_pick_row(pids_ref, c).astype(jnp.int32),
                               d.shape)
        d = jnp.where(sel & (ids >= 0), d, jnp.inf)
        if wide:
            _append_wide(d, ids, s, st_ref, scratch)
            return
        arr_ref, = scratch
        acc_d = od_ref[...]
        thr = jnp.max(acc_d, axis=1, keepdims=True)
        below = jnp.sum((d < thr).astype(jnp.int32), axis=1, keepdims=True)
        n = jnp.minimum(jnp.max(below, axis=0, keepdims=True), k2)  # (1, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, st_ref.shape, 1)
        st_ref[...] += (jnp.where(lane == 0, 1, 0)
                        + jnp.where(lane == 1, (n > 0).astype(jnp.int32), 0)
                        + jnp.where(lane == 2, n, 0))
        passes = jnp.max(n)

        @pl.when(passes > 0)
        def _merge():
            ncol = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
            base = s * d.shape[1]
            acc, _ = jax.lax.fori_loop(
                0, passes,
                lambda _, a: _insert_pass(*a, ids, ncol, base),
                ((acc_d, oi_ref[...], arr_ref[...]), d))
            od_ref[...], oi_ref[...], arr_ref[...] = acc

    last = s == pl.num_programs(1) - 1
    if wide:
        _settle_wide(last, od_ref, oi_ref, st_ref, scratch)
        return

    @pl.when(last)
    def _flush():
        arr_ref, = scratch
        od_ref[...], oi_ref[...] = _sorted_candidates(
            od_ref[...], oi_ref[...], arr_ref[...])


def _qtile_topk_kernel(tc_ref, lv_ref, q_ref, pids_ref, qsel_ref, post_ref,
                       od_ref, oi_ref, st_ref, *scratch):
    t = pl.program_id(0)
    s = pl.program_id(1)
    _merge_block(
        lambda: _l2_tile(q_ref[...].astype(jnp.float32),
                         post_ref[0].astype(jnp.float32)),  # one MXU op
        lv_ref[t, s], pids_ref, tc_ref[t, s], qsel_ref,
        od_ref, oi_ref, st_ref, scratch)


def tile_plan(cids, mask, queries, bq: int, n_clusters: int):
    """Pad the batch to the query tile and build the probe plan shared by
    both fused kernels.  Returns (queries (bp, D), tile_cids (nb, S),
    live (nb, S) int32, qsel (nb, bq, S) int32): ``live`` is 1 where some
    query of the tile selects the step's block (a scalar-prefetch table),
    and qsel is transposed so one (bq, S) block per tile is DMA'd once and
    stays resident across the tile's S steps."""
    padb = (-cids.shape[0]) % bq
    if padb:
        queries = jnp.pad(queries, ((0, padb), (0, 0)))
        cids = jnp.pad(cids, ((0, padb), (0, 0)))
        mask = jnp.pad(jnp.asarray(mask, bool), ((0, padb), (0, 0)))
    tile_cids, qsel = plan_tile_probes(cids, mask, bq, n_clusters)
    live = jnp.any(qsel > 0, axis=-1).astype(jnp.int32)
    return queries, tile_cids, live, jnp.swapaxes(qsel, 1, 2)


def topk_call_specs(nb: int, bq: int, k2: int):
    """(out_specs, out_shape, scratch_shapes) of the fused kernels: per
    tile one (bq, k2) candidate block for dists and ids and one (8, 128)
    counter block, each revisited across the tile's probe steps, and the
    merge's VMEM state: up to ``NARROW_K2`` the (bq, k2) arrival keys of
    the accumulator the candidate blocks hold; above it the buffered
    merge's stack of (bq, 128) tiles for distances, ids and keys, its fill
    point and its per-row threshold."""
    spec = pl.BlockSpec((bq, k2), lambda t, s, *_: (t, 0))
    stat = pl.BlockSpec((8, 128), lambda t, s, *_: (t, 0))
    if k2 > NARROW_K2:
        tiles = _stack_tiles(k2) + 1
        scratch = [pltpu.VMEM((tiles, bq, _LANES), jnp.float32),
                   pltpu.VMEM((tiles, bq, _LANES), jnp.int32),
                   pltpu.VMEM((tiles, bq, _LANES), jnp.int32),
                   pltpu.SMEM((1,), jnp.int32),
                   pltpu.VMEM((bq, _LANES), jnp.float32)]
    else:
        scratch = [pltpu.VMEM((bq, k2), jnp.int32)]
    return ([spec, spec, stat],
            (jax.ShapeDtypeStruct((nb * bq, k2), jnp.float32),
             jax.ShapeDtypeStruct((nb * bq, k2), jnp.int32),
             jax.ShapeDtypeStruct((nb * 8, 128), jnp.int32)),
            scratch)


def scan_stats(stat_blocks, n_steps: int):
    """The kernel's (nb * 8, 128) counter blocks as (5,) int32: grid steps,
    live steps, steps with an entry below the threshold, the entries
    admitted (insertion passes, or entries appended), and the buffered
    merge's compaction passes over its stack."""
    per_tile = stat_blocks.reshape(-1, 8, 128)[:, 0, :4]
    return jnp.concatenate([jnp.full((1,), n_steps, jnp.int32),
                            jnp.sum(per_tile, axis=0)])


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
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused scan + in-kernel top-k2: returns ((B, k2) dists, (B, k2) ids,
    (5,) int32 counters of :func:`scan_stats`).

    Candidates are unique-by-id, ascending by distance, padded with
    (+inf, -1).  Only (B, k2) and the counters cross the pallas_call
    boundary — never the (B, P, L) distance tensor.
    """
    C, L, D = postings.shape
    B = cids.shape[0]
    queries, tile_cids, live, qsel = tile_plan(cids, mask, queries, bq, C)
    nb, s_len = tile_cids.shape
    out_specs, out_shape, scratch = topk_call_specs(nb, bq, k2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, s_len),
        in_specs=[
            pl.BlockSpec((bq, D), lambda t, s, *_: (t, 0)),
            _row_spec(C, L, lambda t, s, tc, _: tc[t, s]),
            pl.BlockSpec((1, bq, s_len), lambda t, s, *_: (t, 0, 0)),
            pl.BlockSpec((1, L, D), lambda t, s, tc, _: (tc[t, s], 0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    od, oi, st = pl.pallas_call(
        _qtile_topk_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name=TOPK_KERNEL_NAME,
    )(tile_cids, live, queries, posting_ids.astype(jnp.int32), qsel,
      postings)
    return od[:B], oi[:B], scan_stats(st, nb * s_len)
