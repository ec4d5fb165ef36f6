"""HBM / host-memory tiering (the DRAM:SSD = 1:20 capacity split, adapted).

The paper keeps centroids + models in DRAM and posting lists on SSD.  On a
TPU pod the analogous hierarchy is device HBM (fast, small) over host DRAM
(large, behind PCIe).  ``TieredPostings`` keeps the posting payload in host
memory (numpy) and streams only the probed clusters to the device per batch —
mirroring the paper's "read only the selected cluster lists" I/O behaviour —
while centroids and LLSP weights stay device-resident.

Two modes:
* ``resident`` — postings fully device-resident (the all-HBM fast path used
  when the index fits; this is what the sharded engine shards over `model`).
* ``streamed`` — postings host-resident; ``fetch(cids)`` gathers the union of
  probed clusters on host and device_puts one packed tensor (one "doorbell
  batch" per query batch).

The byte counters feed the Fig.-18 bandwidth-utilization analogue: achieved
bytes moved vs the tier's peak bandwidth.  Per-fetch stage timestamps
(``TierStats.events``) feed the serving-runtime overlap analysis
(runtime/pipeline.py): they let the bench *measure* that batch i+1's
gather/stream interval lands inside batch i's scan-in-flight interval
instead of asserting it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs.trace import SPAN_GATHER, SPAN_H2D, region


@dataclasses.dataclass
class FetchEvent:
    """Wall-clock stamps + union accounting of one fetch: host gather, then
    device stream.  ``union_bytes`` counts the payload of the REAL union
    rows only (no sentinel, no bucket padding) — the per-batch quantity
    locality-aware batch formation minimizes, measured where the gather
    happens rather than inferred from probe counts upstream."""
    gather_start: float
    gather_end: float     # union gather materialized in host memory
    stream_end: float     # packed tensors handed to the device (device_put)
    rows: int             # packed rows streamed (incl. sentinel/pad rows)
    bytes: int
    clusters_requested: int = 0   # live probe slots across the batch
    clusters_union: int = 0       # after cross-query dedup (= gather rows)
    union_bytes: int = 0          # payload bytes of the deduped union


@dataclasses.dataclass
class TierStats:
    bytes_streamed: int = 0
    union_bytes_streamed: int = 0  # sum of per-fetch union_bytes (excludes
                                   # pad/sentinel rows — the locality metric)
    batches: int = 0
    clusters_fetched: int = 0
    clusters_deduped: int = 0
    gather_s: float = 0.0          # cumulative host union-gather seconds
    stream_s: float = 0.0          # cumulative host->device stream seconds
    events: list = dataclasses.field(default_factory=list)
    max_events: int = 4096         # ring-bounded so serving daemons don't grow
    dropped_events: int = 0        # ring evictions — nonzero means ``events``
                                   # is a truncated window, not the full run
                                   # (overlap analyses must check this)

    def reset(self) -> None:
        self.bytes_streamed = 0
        self.union_bytes_streamed = 0
        self.batches = 0
        self.clusters_fetched = 0
        self.clusters_deduped = 0
        self.gather_s = 0.0
        self.stream_s = 0.0
        self.events.clear()
        self.dropped_events = 0

    def record(self, ev: FetchEvent) -> None:
        self.gather_s += ev.gather_end - ev.gather_start
        self.stream_s += ev.stream_end - ev.gather_end
        if len(self.events) >= self.max_events:
            drop = self.max_events // 2
            del self.events[:drop]
            self.dropped_events += drop
        self.events.append(ev)


def _plan_union(cids: np.ndarray, mask: Optional[np.ndarray],
                lut: np.ndarray, n_clusters: int,
                pad_rows: Optional[int], bucket: int):
    """Shared fetch planning: dedup the probed clusters across the batch and
    build the (B, P) remap into the packed row space.

    Returns (wanted (U,) unique cluster ids, u, rows, remap) where ``rows``
    is U + 1 sentinel, quantized up to ``bucket`` / ``pad_rows`` — the jit
    shape contract both the f32 and the quantized tier obey identically."""
    cids = np.asarray(cids)
    if mask is None:
        mask = np.ones_like(cids, dtype=bool)
    live = np.asarray(mask) & (cids >= 0)
    wanted = np.unique(cids[live])
    u = int(wanted.size)
    sentinel = u
    rows = max(u + 1, int(pad_rows or 0))
    rows = -(-rows // max(bucket, 1)) * max(bucket, 1)
    lut[wanted] = np.arange(u)
    remap = np.where(live, lut[np.clip(cids, 0, n_clusters - 1)], sentinel)
    return wanted, u, rows, remap.astype(np.int32), live


class TieredPostings:
    """Host-resident posting store with batched device streaming.

    ``epoch`` tags the tier with the index version it backs (lifecycle
    runtime): every epoch gets its own tier, and :meth:`release` frees the
    host payload when the epoch retires — called only after the version
    manager has seen the epoch's last in-flight batch harvest, so a live
    gather can never race the free.
    """

    def __init__(self, postings: np.ndarray, posting_ids: np.ndarray,
                 epoch: int = 0):
        self.postings = np.ascontiguousarray(postings)
        self.posting_ids = np.ascontiguousarray(posting_ids)
        self.epoch = int(epoch)
        self.released = False
        self.stats = TierStats()
        # Remap LUT hoisted out of fetch(): one reusable O(n_clusters) buffer
        # instead of a fresh allocation per call.  Only entries of the current
        # union are ever read back (masked probes bypass the LUT entirely via
        # the sentinel), so stale entries from earlier fetches are harmless.
        self._lut = np.zeros(self.postings.shape[0], dtype=np.int64)

    def release(self) -> None:
        """Drop the host payload (retired-epoch reclamation).  Idempotent;
        a fetch after release is a routing bug and fails loudly."""
        self.released = True
        self.postings = None
        self.posting_ids = None
        self._lut = None

    @property
    def cluster_bytes(self) -> int:
        return int(
            self.postings[0].nbytes + self.posting_ids[0].nbytes
        )

    def fetch(
        self,
        cids: np.ndarray,
        mask: Optional[np.ndarray] = None,
        pad_rows: Optional[int] = None,
        bucket: int = 1,
        seq: int = -1,
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Gather the union of probed clusters and stream them once.

        cids: (B, P) int32; mask: (B, P) bool.  Returns
        (packed_postings (R, L, D), packed_ids (R, L), remap (B, P)) with
        R = union size U plus one sentinel row, rounded up to a multiple of
        ``bucket`` and to at least ``pad_rows`` when given — callers that
        jit over the packed tensors quantize R to bound their compile
        cache.  remap[b, p] indexes into the packed tensors; masked or
        negative probes map to the SENTINEL row (all ids -1, zero payload)
        so downstream id-masking drops them even if a caller forgets the
        probe mask.  Duplicate clusters across queries are fetched once
        (the paper's burst-overlap observation, §6.2).  ``seq`` is the
        batch id its profiler spans carry (``StageTimes.seq``).
        """
        if self.released:
            raise RuntimeError(
                f"fetch on released tier (epoch {self.epoch}): a batch was "
                f"routed to a retired index version")
        t0 = time.perf_counter()
        with region(SPAN_GATHER, batch=seq):
            wanted, u, rows, remap, live = _plan_union(
                cids, mask, self._lut, self.postings.shape[0], pad_rows,
                bucket)
            c, l, d = self.postings.shape
            # single-copy gather: np.take writes straight into the packed
            # buffer (no (U, L, D) temporary), and sentinel/pad payload rows
            # stay uninitialized — their ids are -1, which every consumer
            # masks on.
            packed = np.empty((rows, l, d), dtype=self.postings.dtype)
            np.take(self.postings, wanted, axis=0, out=packed[:u])
            packed_ids = np.full((rows, l), -1, dtype=self.posting_ids.dtype)
            np.take(self.posting_ids, wanted, axis=0, out=packed_ids[:u])
        t1 = time.perf_counter()
        with region(SPAN_H2D, batch=seq):
            dev_packed = jnp.asarray(packed)
            dev_ids = jnp.asarray(packed_ids)
            dev_remap = jnp.asarray(remap.astype(np.int32))
        t2 = time.perf_counter()
        nbytes = int(packed.nbytes + packed_ids.nbytes)
        requested = int(live.sum())
        union_bytes = u * self.cluster_bytes
        self.stats.bytes_streamed += nbytes
        self.stats.union_bytes_streamed += union_bytes
        self.stats.batches += 1
        self.stats.clusters_fetched += requested
        self.stats.clusters_deduped += u
        self.stats.record(FetchEvent(t0, t1, t2, rows, nbytes,
                                     clusters_requested=requested,
                                     clusters_union=u,
                                     union_bytes=union_bytes))
        return dev_packed, dev_ids, dev_remap


class QuantizedTieredPostings:
    """Host hot tier over the int8-residual payload (core/quantize.py).

    The paper's cost thesis made concrete: the first-pass payload resident in
    host memory is q8 codes + per-slot norms + ids (~1/4 the f32 bytes), and
    the f32 vectors demote to the flash tier (storage/flash_tier.py) where
    only re-rank candidates touch them.  ``fetch`` speaks the same union /
    sentinel / remap / bucket contract as :class:`TieredPostings` but packs
    five tensors: (q8 (R, L, D) int8, scale (R, 1, 1), norm2 (R, L),
    cluster centroids (R, D), ids (R, L)) — the centroids ride along because
    the residual distance form needs the owning centroid per packed row.
    """

    quantized = True

    def __init__(self, q8: np.ndarray, scale: np.ndarray, norm2: np.ndarray,
                 centroids: np.ndarray, posting_ids: np.ndarray,
                 epoch: int = 0):
        self.q8 = np.ascontiguousarray(q8)
        # store scale flat (C,); re-expanded per packed row at fetch
        self.scale = np.ascontiguousarray(
            np.asarray(scale, np.float32).reshape(-1))
        self.norm2 = np.ascontiguousarray(np.asarray(norm2, np.float32))
        self.centroids = np.ascontiguousarray(
            np.asarray(centroids, np.float32))
        self.posting_ids = np.ascontiguousarray(posting_ids)
        self.epoch = int(epoch)
        self.released = False
        self.stats = TierStats()
        self._lut = np.zeros(self.q8.shape[0], dtype=np.int64)

    def release(self) -> None:
        self.released = True
        self.q8 = None
        self.scale = None
        self.norm2 = None
        self.centroids = None
        self.posting_ids = None
        self._lut = None

    @property
    def cluster_bytes(self) -> int:
        return int(self.q8[0].nbytes + self.norm2[0].nbytes
                   + self.posting_ids[0].nbytes + self.scale[0].nbytes
                   + self.centroids[0].nbytes)

    def nbytes(self) -> int:
        """Hot-tier resident payload bytes (the DRAM term of the cost model)."""
        return int(self.q8.nbytes + self.scale.nbytes + self.norm2.nbytes
                   + self.posting_ids.nbytes + self.centroids.nbytes)

    def fetch(
        self,
        cids: np.ndarray,
        mask: Optional[np.ndarray] = None,
        pad_rows: Optional[int] = None,
        bucket: int = 1,
        seq: int = -1,
    ):
        """Union-gather the probed clusters' quantized payload.

        Returns (q8 (R, L, D), scale (R, 1, 1), norm2 (R, L), cents (R, D),
        ids (R, L), remap (B, P)).  Sentinel/pad rows carry ids=-1, zero
        norms and scale=1 so downstream id-masking drops them; the q8
        payload of pad rows stays uninitialized (never read past the mask).
        """
        if self.released:
            raise RuntimeError(
                f"fetch on released tier (epoch {self.epoch}): a batch was "
                f"routed to a retired index version")
        t0 = time.perf_counter()
        with region(SPAN_GATHER, batch=seq):
            wanted, u, rows, remap, live = _plan_union(
                cids, mask, self._lut, self.q8.shape[0], pad_rows, bucket)
            c, l, d = self.q8.shape
            packed_q8 = np.empty((rows, l, d), dtype=self.q8.dtype)
            np.take(self.q8, wanted, axis=0, out=packed_q8[:u])
            packed_scale = np.ones((rows,), dtype=np.float32)
            np.take(self.scale, wanted, axis=0, out=packed_scale[:u])
            packed_norm2 = np.zeros((rows, l), dtype=np.float32)
            np.take(self.norm2, wanted, axis=0, out=packed_norm2[:u])
            packed_cent = np.zeros((rows, d), dtype=np.float32)
            np.take(self.centroids, wanted, axis=0, out=packed_cent[:u])
            packed_ids = np.full((rows, l), -1, dtype=self.posting_ids.dtype)
            np.take(self.posting_ids, wanted, axis=0, out=packed_ids[:u])
        t1 = time.perf_counter()
        with region(SPAN_H2D, batch=seq):
            out = (jnp.asarray(packed_q8),
                   jnp.asarray(packed_scale).reshape(rows, 1, 1),
                   jnp.asarray(packed_norm2),
                   jnp.asarray(packed_cent),
                   jnp.asarray(packed_ids),
                   jnp.asarray(remap))
        t2 = time.perf_counter()
        nbytes = int(packed_q8.nbytes + packed_scale.nbytes
                     + packed_norm2.nbytes + packed_cent.nbytes
                     + packed_ids.nbytes)
        requested = int(live.sum())
        union_bytes = u * self.cluster_bytes
        self.stats.bytes_streamed += nbytes
        self.stats.union_bytes_streamed += union_bytes
        self.stats.batches += 1
        self.stats.clusters_fetched += requested
        self.stats.clusters_deduped += u
        self.stats.record(FetchEvent(t0, t1, t2, rows, nbytes,
                                     clusters_requested=requested,
                                     clusters_union=u,
                                     union_bytes=union_bytes))
        return out
