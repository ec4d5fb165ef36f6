"""Double-buffered prefetch pipeline — §4.1's I/O/compute overlap on TPU.

The paper's userspace stack keeps the SSD and the scan engine busy at the
same time: while one batch's posting lists are being scanned, the next
batch's lists are already being read.  The TPU translation: while batch i
runs the fused-topk scan on device, batch i+1's probed-cluster union is
gathered from the host tier and ``device_put`` in flight, so streamed-mode
serving overlaps PCIe with MXU instead of serializing them.

Stage protocol (each stage returns a handle consumed by the next):

  ``plan``     -> centroid scan + LLSP routing/pruning on device, probe set
                  resolved to host (the paper's in-DRAM index walk);
  ``prefetch`` -> host gather of the probed-cluster union + device stream,
                  on a dedicated worker thread (the SQ-side DMA engine);
  ``dispatch`` -> join the gather, launch the fused-topk scan (JAX async
                  dispatch — returns immediately, scan in flight);
  ``harvest``  -> block on the scan outputs, truncate padding.

``run_sequential`` chains the stages strictly (the pre-PR-2 serve loop);
``run_pipelined`` double-buffers them.  Every stage is wall-clock stamped
(:class:`StageTimes`) so :func:`overlap_efficiency` can *measure* how much
of batch i+1's gather/stream interval lands inside batch i's
scan-in-flight interval — the bench asserts overlap from these stamps, not
from throughput alone.

Ordering note: the plan stage of batch i+1 is always enqueued BEFORE batch
i's scan (both in ``run_pipelined`` and in the engine's poller).  The CPU /
TPU backends execute queued computations in order, so planning after the
scan dispatch would serialize the whole pipeline behind the scan.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.distance import (
    dedup_topk, merge_candidate_topk, squared_l2, topk_smallest,
)
from repro.core.search import SearchConfig, _auto_ncand, _scan_and_rank, decide_nprobe
from repro.kernels import ops as kops
from repro.obs.trace import (
    GATHER_THREAD, RERANK_THREAD, SPAN_DISPATCH, SPAN_HARVEST, SPAN_RERANK,
    SPAN_WAIT_GATHER, name_os_thread, region,
)
from repro.storage.host_tier import QuantizedTieredPostings, TieredPostings
from repro.storage.flash_tier import FlashTier


@dataclasses.dataclass
class StageTimes:
    """Wall-clock stamps of one batch through the pipeline (seconds)."""
    size: int = 0                  # true batch size (pre-padding)
    rows: int = 0                  # packed posting rows streamed
    plan_start: float = 0.0
    plan_end: float = 0.0
    gather_start: float = 0.0
    gather_end: float = 0.0        # host union gather materialized
    stream_end: float = 0.0        # packed tensors on device
    scan_dispatch: float = 0.0
    scan_done: float = 0.0
    routed: bool = False           # plan reused an admission-time RoutePlan
    clusters_requested: int = 0    # probe slots across the batch (pre-dedup)
    union_clusters: int = 0        # deduped gather-union size (real clusters)
    union_bytes: int = 0           # payload bytes of the union (measured at
                                   # fetch, excludes pad/sentinel rows) — the
                                   # locality-grouping objective, per batch
    # flash-tier f32 re-rank stage (quantized serving; zeros = no rerank ran)
    rerank_start: float = 0.0
    rerank_end: float = 0.0
    rerank_io_s: float = 0.0       # seconds spent inside flash read bursts
    rerank_rounds: int = 0         # adaptive-stop rounds actually executed
    rerank_cands: int = 0          # candidates exact-scored before the stop
    rerank_stable_stop: bool = False  # True = top-k went stable before the
                                      # candidate list was exhausted
    rerank_round_size: int = 0     # round width this batch actually used
                                   # (== config unless auto_round adapted it)
    # admission (set by ServeEngine at formation; -1/0 outside an engine)
    seq: int = -1                  # batch id: the ``batch`` arg of its
                                   # profiler spans (repro.obs.trace)
    formed_at: float = 0.0         # batcher release time (engine clock)
    queue_wait_s: float = 0.0      # its requests' summed wait from submit
                                   # to formation (MicroBatch.waits)

    @property
    def total(self) -> float:
        end = self.rerank_end if self.rerank_end > 0.0 else self.scan_done
        return end - self.plan_start


@dataclasses.dataclass
class BatchResult:
    ids: np.ndarray                # (b, k) int32
    dists: np.ndarray              # (b, k) float32
    nprobe: np.ndarray             # (b,) int32
    times: StageTimes
    fresh_seq: int = -1            # freshness snapshot this batch scanned
                                   # against (-1 = no fresh view attached)
    partial: Optional[np.ndarray] = None   # (b,) bool — query answered from
                                           # an incomplete shard set (fabric
                                           # degraded mode); None = complete
    partial_reason: str = "no_replica"     # why the shard set was incomplete
                                           # ("no_replica" | "timeout")
    quality: Optional[np.ndarray] = None   # (b,) float32 per-query recall
                                           # proxy (rerank agreement on the
                                           # q8 path, probed-cluster coverage
                                           # on the fabric path); None = the
                                           # serving path produces no proxy
    shards: Optional[np.ndarray] = None    # (b,) int32 primary shard per
                                           # query (fabric only) — lets the
                                           # quality streams label per-shard


@dataclasses.dataclass
class _Plan:
    queries_dev: jax.Array         # (bp, D) padded, on device
    cids: np.ndarray               # (bp, P)
    pmask: np.ndarray              # (bp, P) bool
    nprobe: np.ndarray             # (bp,)
    times: StageTimes
    queries_host: Optional[np.ndarray] = None  # (bp, D) — kept for the
                                               # flash-tier re-rank stage


@dataclasses.dataclass
class _Prep:
    plan: _Plan
    fut: Optional[object]          # gather future (None in resident mode)


@dataclasses.dataclass
class _Inflight:
    out_d: jax.Array
    out_i: jax.Array
    nprobe: np.ndarray
    times: StageTimes
    size: int
    fresh_seq: int = -1
    queries_host: Optional[np.ndarray] = None


@dataclasses.dataclass(frozen=True)
class RerankConfig:
    """FusionANNS-style adaptive re-rank over the flash tier (2409.16576 §5).

    Candidates arrive sorted by approximate (q8) distance; re-ranking walks
    them in rounds of ``round_size``, reading the f32 rows from the flash
    tier and exact-scoring them.  After each round the current exact top-k
    is compared against the previous round's: once it survives
    ``stable_rounds`` consecutive rounds unchanged (per the whole batch —
    the TPU batch is the scheduling unit), further candidates are provably
    unlikely to displace it and the walk stops.  ``max_rounds`` caps the
    walk (0 = only the candidate width bounds it).

    ``auto_round`` derives the NEXT batch's round width from the stamped
    per-slot flash I/O cost (EWMA over ``rerank_io_s``) so one round's
    read burst targets a fraction of the measured scan window — wide
    enough to amortize read setup, narrow enough that the adaptive stop
    still saves I/O.  Off by default: with it off the configured
    ``round_size`` is used verbatim (parity-tested)."""
    round_size: int = 64
    stable_rounds: int = 1
    max_rounds: int = 0
    auto_round: bool = False


def max_id_replicas(posting_ids) -> int:
    """Largest number of posting slots any single id occupies — the build's
    REALIZED closure replication (<= BuildConfig.max_replicas, but measured
    from the artifact rather than trusted from config).  This is the exact
    bound on how many duplicates of one id can precede the k2-th unique
    candidate, so it is the safe ``dup_bound`` for the oracle's
    pre-selection: a hardcoded bound below it silently drops candidates on
    high-replication builds (the ROADMAP dup_bound=8 hazard)."""
    ids = np.asarray(posting_ids).ravel()
    ids = ids[ids >= 0]
    if ids.size == 0:
        return 1
    return int(np.bincount(ids).max())


@functools.partial(jax.jit, static_argnames=("cfg",))
def _plan_jit(centroids, llsp_params, queries, topk, cfg: SearchConfig):
    d = squared_l2(queries, centroids)
    cdists, cids = topk_smallest(d, min(cfg.nprobe_max, centroids.shape[0]))
    nprobe = decide_nprobe(cfg, llsp_params, queries, topk, cdists)
    return cids.astype(jnp.int32), nprobe


@functools.partial(jax.jit, static_argnames=("cfg", "dup_bound"))
def _scan_streamed_jit(packed, packed_ids, remap, pmask, queries,
                       cfg: SearchConfig, *, dup_bound: int):
    """Candidate-compressed scan over the STREAMED (packed) posting rows.

    use_kernel: the fused Pallas kernel runs directly on the packed tensors
    (remap plays the role of cids).  Oracle path: instead of re-gathering a
    (B, P, L, D) probe tensor from rows we just streamed, distance the whole
    packed payload against the batch with ONE matmul (rows are unique, so
    this does no duplicate work), mask each query to its probed rows via a
    scatter of the remap table, and top-k in the packed domain.  ``dup_bound``
    caps how many closure replicas of one id can precede the k2-th unique
    candidate, so the dedup runs on an O(k2·dup_bound) pre-selection, not on
    all R·L slots.  It is REQUIRED (no default on purpose): the bound must
    cover the build's realized replication or candidates are silently lost —
    PrefetchPipeline derives it from the posting table (max_id_replicas).
    """
    k2 = cfg.n_cand or _auto_ncand(cfg.k)
    if cfg.use_kernel:
        cd, ci = kops.ivf_scan_topk(packed, packed_ids, remap, pmask,
                                    queries, k2=k2)
    else:
        r, l, dim = packed.shape
        b = queries.shape[0]
        d = squared_l2(queries, packed.reshape(r * l, dim))      # (B, R*L)
        member = jnp.zeros((b, r), jnp.int32).at[
            jnp.arange(b)[:, None], remap
        ].add(pmask.astype(jnp.int32))                           # (B, R)
        live = (member > 0)[:, :, None] & (packed_ids >= 0)[None, :, :]
        d = jnp.where(live.reshape(b, r * l), d, jnp.inf)
        ids = jnp.broadcast_to(packed_ids.reshape(1, r * l), (b, r * l))
        m = min(k2 * dup_bound, r * l)
        nd, pos = topk_smallest(d, m)
        cd, ci = dedup_topk(nd, jnp.take_along_axis(ids, pos, axis=-1), k2)
    return merge_candidate_topk(cd, ci, cfg.k)


@functools.partial(jax.jit, static_argnames=("cfg", "dup_bound"))
def _scan_streamed_q8_jit(packed_q8, packed_scale, packed_norm2, packed_cent,
                          packed_ids, remap, pmask, queries,
                          cfg: SearchConfig, *, dup_bound: int):
    """Candidate-compressed scan over STREAMED int8-residual rows — the
    quantized twin of :func:`_scan_streamed_jit`, same packed-domain
    contract (remap-as-cids for the kernel; one int8->f32 matmul + the
    closed-form residual correction for the oracle).  ``packed_cent`` is
    the owning centroid per packed row (the residual distance form needs
    it), gathered by the tier alongside the codes."""
    k2 = cfg.n_cand or _auto_ncand(cfg.k)
    if cfg.use_kernel:
        cd, ci = kops.ivf_scan_q8_topk(
            packed_q8, packed_scale, packed_norm2, packed_cent, packed_ids,
            remap, pmask, queries, k2=k2)
    else:
        r, l, dim = packed_q8.shape
        b = queries.shape[0]
        g8 = packed_q8.astype(jnp.float32)                       # (R, L, D)
        qc = queries[:, None, :] - packed_cent[None, :, :]       # (B, R, D)
        cross = jnp.einsum("brd,rld->brl", qc, g8)               # (B, R, L)
        s = packed_scale[:, 0, 0][None, :, None]                 # (1, R, 1)
        d = (jnp.sum(qc * qc, axis=-1)[:, :, None]
             - 2.0 * s * cross + packed_norm2[None, :, :])
        d = jnp.maximum(d, 0.0).reshape(b, r * l)
        member = jnp.zeros((b, r), jnp.int32).at[
            jnp.arange(b)[:, None], remap
        ].add(pmask.astype(jnp.int32))                           # (B, R)
        live = (member > 0)[:, :, None] & (packed_ids >= 0)[None, :, :]
        d = jnp.where(live.reshape(b, r * l), d, jnp.inf)
        ids = jnp.broadcast_to(packed_ids.reshape(1, r * l), (b, r * l))
        m = min(k2 * dup_bound, r * l)
        nd, pos = topk_smallest(d, m)
        cd, ci = dedup_topk(nd, jnp.take_along_axis(ids, pos, axis=-1), k2)
    return merge_candidate_topk(cd, ci, cfg.k)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _scan_resident_jit(index, queries, cids, pmask, cfg: SearchConfig):
    return _scan_and_rank(index, queries, cids, pmask, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _scan_reference_jit(packed, packed_ids, remap, pmask, queries,
                        cfg: SearchConfig):
    """The PRE-runtime streamed scan (A/B baseline): the PR 1 reference
    oracle on the packed tensors — re-gathers a (B, P, L, D) probe tensor
    from the rows the tier just streamed, exactly what serving on the
    streamed tier looked like before the packed-domain scan existed."""
    from repro.kernels.ref import ivf_scan_topk_ref

    k2 = cfg.n_cand or _auto_ncand(cfg.k)
    cd, ci = ivf_scan_topk_ref(packed, packed_ids, remap, pmask, queries, k2)
    return merge_candidate_topk(cd, ci, cfg.k)


class PrefetchPipeline:
    """Stage-structured streamed/resident serving over one index.

    streamed (``tier`` given): postings live on host in ``tier``; each batch
    streams only its probed-cluster union (§4.1 I/O path).  resident: the
    index is fully device-resident and prefetch is a no-op (all-HBM path) —
    the engine drives both through the same protocol.

    ``pad_batch`` / ``row_bucket`` quantize the jit-visible shapes (padded
    batch size, packed-row count) so long-running daemons compile a bounded
    program set.  ``row_bucket`` trades padding bytes for compile count: a
    coarse bucket wastes a few % of stream bandwidth on zero rows but keeps
    the scan-program set to ~ceil(C / row_bucket) entries — under live
    traffic (union size varies batch to batch) a fine bucket turns into a
    compile storm that dwarfs the padding it saves.
    """

    def __init__(self, index, llsp_params, cfg: SearchConfig,
                 tier: Optional[TieredPostings] = None, *,
                 pad_batch: int = 16, row_bucket: int = 256,
                 dup_bound: Optional[int] = None,
                 fresh_source=None,
                 flash: Optional[FlashTier] = None,
                 rerank: Optional[RerankConfig] = None,
                 quality_proxy: bool = True):
        self.index = index
        self.llsp_params = llsp_params
        self.cfg = cfg
        self.tier = tier
        # flash-tier f32 re-rank (quantized serving): when ``flash`` is set
        # the scan stage keeps its full ~2k candidate width and harvest
        # exact-rescores candidates from the flash tier with adaptive stop.
        self.flash = flash
        self.rerank = rerank if rerank is not None else (
            RerankConfig() if flash is not None else None)
        self.pad_batch = pad_batch
        self.row_bucket = row_bucket
        # freshness hook (lifecycle/ingest.py): a zero-arg callable returning
        # the current FreshSnapshot.  When set, dispatch captures one
        # snapshot per batch and chains the §6.2 delta+tombstone merge onto
        # the in-flight scan — delta brute force folded in, tombstoned main
        # AND delta ids filtered, all before readback.  The scan stage then
        # OVER-FETCHES (k -> n_cand-wide main candidates) so tombstoned
        # slots cannot starve the final top-k — the paper's §6.2 compensation
        # for serving under a growing tombstone set.
        self.fresh_source = fresh_source
        if dup_bound is None:
            # derive the oracle's duplicate pre-selection bound from the
            # build's realized replication (dup_bound=8 hazard: a bound
            # below max replicas drops candidates on max_replicas>8 builds)
            pids = tier.posting_ids if tier is not None else index.posting_ids
            dup_bound = max_id_replicas(pids)
        self.dup_bound = max(int(dup_bound), 1)
        self._gatherer = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=GATHER_THREAD,
            initializer=name_os_thread)
        # rerank reads get their own single-lane SQ (same DMA-engine idiom
        # as the prefetch gatherer): sharing the gatherer would queue batch
        # i's rerank I/O behind batch i+1's union gather and serialize the
        # two stages the overlap argument needs concurrent.
        self._reranker = (ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=RERANK_THREAD,
            initializer=name_os_thread)
            if flash is not None else None)
        # per-query recall proxy (quality observability): the overlap of the
        # pre-rerank approximate top-k with the post-rerank exact top-k,
        # stamped on BatchResult.quality.  Free signal on the q8 path — the
        # candidates are already in host memory at harvest.
        self.quality_proxy = bool(quality_proxy)
        # auto_round state (RerankConfig.auto_round): EWMA of the measured
        # per-slot flash read cost and the round width derived from it
        self._io_per_slot: Optional[float] = None
        self._auto_round: Optional[int] = None

    @property
    def _scan_cfg(self) -> SearchConfig:
        """Scan-stage config: with a fresh view attached — or the flash
        re-rank enabled — the main scan keeps n_cand-wide candidates
        (instead of k): the tombstone filter must not starve the final
        merge, and the re-ranker needs the full ~2k candidate set, not the
        already-collapsed top-k."""
        if self.fresh_source is None and self.flash is None:
            return self.cfg
        k2 = self.cfg.n_cand or _auto_ncand(self.cfg.k)
        # pin n_cand too: otherwise the scan derives a fresh auto width
        # from the widened k (~2x wider in-kernel top-k + a redundant merge)
        return dataclasses.replace(self.cfg, k=k2, n_cand=k2)

    @property
    def streamed(self) -> bool:
        return self.tier is not None

    @property
    def quantized(self) -> bool:
        return getattr(self.tier, "quantized", False) \
            or (self.cfg.tier == "q8" and self.tier is None)

    @property
    def tier_kind(self) -> str:
        """"q8" | "f32" first-pass payload (lifecycle reporting)."""
        return "q8" if self.quantized else "f32"

    # -- stages ------------------------------------------------------------
    def _padded_inputs(self, queries, topk):
        """Pad (queries, topk) to the jit batch quantum by repeating the
        last row; returns (q (bp, D), tk (bp,), true b).  The ONE copy of
        the pad idiom: route() and plan() must agree bit-for-bit on it or
        admission-route reuse silently drifts from replanning."""
        q = np.asarray(queries, np.float32)
        tk = np.broadcast_to(np.asarray(topk, np.int32), (len(q),))
        b = len(q)
        bp = -(-b // self.pad_batch) * self.pad_batch
        if bp != b:
            q = np.concatenate([q, np.repeat(q[-1:], bp - b, axis=0)])
            tk = np.concatenate([tk, np.repeat(tk[-1:], bp - b)])
        return q, tk, b

    def route(self, queries: np.ndarray, topk
              ) -> tuple[np.ndarray, np.ndarray]:
        """Admission-time probe routing: the plan stage's centroid scan +
        LLSP level decision ONLY (cheap pre-search features, §4.3), returned
        as host arrays ``(cids (b, P), nprobe (b,))``.

        This is bit-identical to what :meth:`plan` computes (same padded
        inputs, same jit program), so the engine tags each drained request
        with its row and ``plan(routed=...)`` reuses it verbatim — the
        centroid scan moves to admission (where the batcher needs the
        probe signature to group by locality), it is not run twice."""
        q, tk, b = self._padded_inputs(queries, topk)
        cids, nprobe = _plan_jit(self.index.centroids, self.llsp_params,
                                 jnp.asarray(q), jnp.asarray(tk), self.cfg)
        return np.asarray(cids)[:b], np.asarray(nprobe)[:b].astype(np.int32)

    def plan(self, queries: np.ndarray, topk,
             nprobe_cap: Optional[np.ndarray] = None,
             routed: Optional[tuple] = None) -> _Plan:
        """Centroid scan + LLSP pruning; probe set resolved to host arrays.

        ``nprobe_cap`` (b,) int32 caps per-query nprobe (0 = uncapped) —
        the batcher's deadline-degradation hook.  ``routed`` is the
        admission-time probe plan ``(cids (b, P), nprobe (b,))`` from
        :meth:`route`: when given, the centroid scan is skipped and the
        plan stage is pure host bookkeeping (pad + mask)."""
        t = StageTimes(size=len(queries))
        t.plan_start = time.perf_counter()
        q, tk, b = self._padded_inputs(queries, topk)
        bp = len(q)
        qd = jnp.asarray(q)
        if routed is not None:
            rcids, rnp = routed
            rcids = np.asarray(rcids, np.int32)
            cids = np.full((bp, rcids.shape[1]), -1, np.int32)
            cids[:b] = rcids
            nprobe = np.zeros((bp,), np.int32)
            nprobe[:b] = np.asarray(rnp, np.int32)
            t.routed = True
        else:
            cids, nprobe = _plan_jit(self.index.centroids, self.llsp_params,
                                     qd, jnp.asarray(tk), self.cfg)
            cids = np.asarray(cids)
            nprobe = np.asarray(nprobe).copy()
        if nprobe_cap is not None:
            cap = np.zeros((bp,), np.int32)
            cap[:b] = np.asarray(nprobe_cap, np.int32)
            capped = cap > 0
            nprobe[capped] = np.minimum(nprobe[capped], cap[capped])
        nprobe[b:] = 0                     # padding rows probe nothing
        pmask = (np.arange(cids.shape[1])[None, :] < nprobe[:, None]) \
            & (cids >= 0)
        t.plan_end = time.perf_counter()
        return _Plan(qd, cids, pmask, nprobe, t, queries_host=q)

    def _gather(self, plan: _Plan):
        fetched = self.tier.fetch(
            plan.cids, plan.pmask, bucket=self.row_bucket,
            seq=plan.times.seq)
        ev = self.tier.stats.events[-1]    # same thread as the fetch: safe
        plan.times.gather_start = ev.gather_start
        plan.times.gather_end = ev.gather_end
        plan.times.stream_end = ev.stream_end
        plan.times.rows = ev.rows
        plan.times.clusters_requested = ev.clusters_requested
        plan.times.union_clusters = ev.clusters_union
        plan.times.union_bytes = ev.union_bytes
        return fetched

    def prefetch(self, plan: _Plan) -> _Prep:
        """Start the host gather + device stream on the worker thread."""
        if not self.streamed:
            return _Prep(plan, None)
        return _Prep(plan, self._gatherer.submit(self._gather, plan))

    def dispatch(self, prep: _Prep, *, reference: bool = False) -> _Inflight:
        """Join the gather, launch the scan (async — returns immediately).

        With a ``fresh_source`` attached, the §6.2 freshness merge is
        chained onto the scan on device: the snapshot is captured HERE (at
        dispatch), so the batch's visibility point is exactly the state a
        concurrent updater had published when the scan launched."""
        plan = prep.plan
        t = plan.times
        fetched = None
        if self.streamed:
            with region(SPAN_WAIT_GATHER, batch=t.seq):
                fetched = prep.fut.result()
        t.scan_dispatch = time.perf_counter()
        with region(SPAN_DISPATCH, batch=t.seq):
            od, oi = self._launch_scan(plan, fetched, reference)
        seq = -1
        if self.fresh_source is not None:
            snap = self.fresh_source()
            if snap is not None:
                from repro.core.fresh import merge_fresh

                # with the re-ranker on, stay candidate-wide through the
                # fresh merge — the narrowing to k happens after rescoring
                keep = self._scan_cfg.k if self.flash is not None else self.cfg.k
                od, oi = merge_fresh(
                    od, oi, plan.queries_dev, snap.delta_vecs,
                    snap.delta_ids, snap.tombstone, keep)
                seq = snap.seq
        return _Inflight(od, oi, plan.nprobe, t, t.size, fresh_seq=seq,
                         queries_host=plan.queries_host)

    def _launch_scan(self, plan: _Plan, fetched, reference: bool):
        """Enqueue the batch's scan over the streamed rows ``fetched`` (or
        the resident index when there are none); returns its outputs."""
        pmask = jnp.asarray(plan.pmask)
        if not self.streamed:
            return _scan_resident_jit(
                self.index, plan.queries_dev, jnp.asarray(plan.cids),
                pmask, self._scan_cfg)
        if getattr(self.tier, "quantized", False):
            if reference:
                raise ValueError(
                    "reference scan is an f32-tier A/B baseline; the "
                    "quantized tier has no pre-runtime twin")
            q8, scale, norm2, cents, pids, remap = fetched
            return _scan_streamed_q8_jit(
                q8, scale, norm2, cents, pids, remap, pmask,
                plan.queries_dev, self._scan_cfg, dup_bound=self.dup_bound)
        packed, pids, remap = fetched
        if reference:
            return _scan_reference_jit(packed, pids, remap, pmask,
                                       plan.queries_dev, self._scan_cfg)
        return _scan_streamed_jit(packed, pids, remap, pmask,
                                  plan.queries_dev, self._scan_cfg,
                                  dup_bound=self.dup_bound)

    def harvest(self, infl: _Inflight) -> BatchResult:
        """Block on the scan outputs; truncate batch padding.  With the
        flash tier attached, exact-rescore the candidates here — harvest of
        batch i runs while batch i+1's scan is already in flight (the
        poller/pipelined drivers dispatch ahead), so the rerank I/O lands
        inside the next scan window by construction, and the stamps prove
        it per run (:func:`rerank_overlap_efficiency`)."""
        with region(SPAN_HARVEST, batch=infl.times.seq):
            ids = np.asarray(infl.out_i)[: infl.size]
            dists = np.asarray(infl.out_d)[: infl.size]
        infl.times.scan_done = time.perf_counter()
        quality = None
        if self.flash is not None and infl.size > 0:
            # pre-rerank approximate top-k (candidates arrive ascending by
            # q8 distance) — captured before rescoring reorders them, so the
            # rerank-agreement proxy costs one (b, k) copy on the hot path
            pre_top = ids[:, : self.cfg.k].copy() if self.quality_proxy \
                else None
            with region(SPAN_RERANK, batch=infl.times.seq):
                dists, ids = self._rerank(
                    infl.queries_host[: infl.size], dists, ids, infl.times)
            if pre_top is not None:
                from repro.obs.quality import recall_proxy

                quality = recall_proxy(pre_top, ids, self.cfg.k)
        return BatchResult(ids, dists, infl.nprobe[: infl.size].copy(),
                           infl.times, fresh_seq=infl.fresh_seq,
                           quality=quality)

    def _rerank(self, queries: np.ndarray, cand_d: np.ndarray,
                cand_i: np.ndarray, t: StageTimes
                ) -> tuple[np.ndarray, np.ndarray]:
        """Flash-tier exact re-rank with FusionANNS adaptive stop.

        Candidates arrive ascending by q8-approx distance.  Rounds of
        ``rerank.round_size`` columns are exact-scored from the flash tier;
        each round's read is issued on the rerank SQ one round AHEAD of the
        scoring (double-buffered), so flash I/O overlaps the host math the
        same way the prefetch gather overlaps the device scan.  Ids outside
        the flash tier (fresh-delta candidates, already exact) and padding
        (-1) keep their incoming distance.  Stops once the batch's exact
        top-k survives ``stable_rounds`` rounds unchanged."""
        rc = self.rerank
        k = self.cfg.k
        b, n = cand_i.shape
        t.rerank_start = time.perf_counter()
        exact = np.array(cand_d, np.float32, copy=True)
        step = max(int(rc.round_size), 1)
        if rc.auto_round and self._auto_round is not None:
            step = self._auto_round
        t.rerank_round_size = step
        n_rounds = -(-n // step)
        if rc.max_rounds > 0:
            n_rounds = min(n_rounds, int(rc.max_rounds))
        futs: dict[int, object] = {}

        def _submit(r):
            if r < n_rounds and r not in futs:
                futs[r] = self._reranker.submit(
                    self.flash.read, cand_i[:, r * step:(r + 1) * step],
                    seq=t.seq)

        prev_top = None
        stable = 0
        rounds = 0
        hi = 0
        _submit(0)
        for r in range(n_rounds):
            _submit(r + 1)                 # double-buffer the next read
            uids, rows = futs.pop(r).result()
            ev = self.flash.stats.events[-1]
            t.rerank_io_s += ev.end - ev.start
            lo, hi = r * step, min(n, (r + 1) * step)
            cols = cand_i[:, lo:hi]
            in_flash = (cols >= 0) & (cols < self.flash.n)
            if uids.size:
                pos = np.searchsorted(uids, np.clip(cols, 0, None))
                pos = np.clip(pos, 0, uids.size - 1)
                hit = in_flash & (uids[pos] == np.clip(cols, 0, None))
                vecs = rows[pos]                       # (b, w, D)
                d = np.sum((queries[:, None, :] - vecs) ** 2, axis=-1)
                exact[:, lo:hi] = np.where(hit, d, exact[:, lo:hi])
            rounds = r + 1
            # adaptive stop: current exact top-k over the scored prefix
            if hi >= k:
                part = np.argpartition(exact[:, :hi], k - 1, axis=1)[:, :k]
                rowd = np.take_along_axis(exact[:, :hi], part, axis=1)
                order = np.argsort(rowd, axis=1, kind="stable")
                sel = np.take_along_axis(part, order, axis=1)
                top = np.take_along_axis(cand_i[:, :hi], sel, axis=1)
                if prev_top is not None and np.array_equal(top, prev_top):
                    stable += 1
                    if stable >= max(int(rc.stable_rounds), 1):
                        t.rerank_stable_stop = hi < n
                        break
                else:
                    stable = 0
                prev_top = top
        for f in futs.values():            # a speculative read may be queued
            f.cancel()
        # final top-k: exact over the rescored prefix (unvisited tail keeps
        # approx order and, by the stop rule, cannot displace the stable set)
        hi = max(hi, min(n, k))
        part = np.argpartition(exact[:, :hi], min(k, hi) - 1, axis=1)[:, :k]
        rowd = np.take_along_axis(exact[:, :hi], part, axis=1)
        order = np.argsort(rowd, axis=1, kind="stable")
        sel = np.take_along_axis(part, order, axis=1)
        out_d = np.take_along_axis(exact[:, :hi], sel, axis=1)
        out_i = np.take_along_axis(cand_i[:, :hi], sel, axis=1)
        t.rerank_rounds = rounds
        t.rerank_cands = int(hi)
        t.rerank_end = time.perf_counter()
        if rc.auto_round and hi > 0 and t.rerank_io_s > 0.0:
            # learn the per-slot flash read cost from this batch's stamps
            # and retarget the NEXT batch's round width so one round's read
            # burst is ~1/4 of the measured scan window: rounds stay small
            # enough for the adaptive stop to save I/O, wide enough to
            # amortize per-read setup
            per_slot = t.rerank_io_s / float(b * hi)
            self._io_per_slot = per_slot if self._io_per_slot is None \
                else 0.7 * self._io_per_slot + 0.3 * per_slot
            scan_win = max(t.scan_done - t.scan_dispatch, 1e-5)
            want = (scan_win / 4.0) / max(self._io_per_slot * b, 1e-12)
            self._auto_round = int(np.clip(want, 16, max(n, 16)))
        return out_d, out_i

    def warmup(self, batch_sizes=(16, 32), max_rows: Optional[int] = None
               ) -> int:
        """Pre-compile every (padded batch, row-bucket) scan/plan shape a
        live engine can hit, so traffic never pays a compile.  A cold
        compile (~0.5-1 s) landing mid-trace queues hundreds of arrivals
        past their deadline and the admission controller sheds them — the
        warmup turns that cliff into a one-time startup cost.  A batch of
        ``bp`` queries streams at most ``min(C, bp * nprobe_max)`` clusters
        plus the sentinel row, so larger row buckets are never warmed.
        Returns the number of programs compiled."""
        if not self.streamed:
            for b in batch_sizes:
                bp = -(-b // self.pad_batch) * self.pad_batch
                self.serve_batch(np.zeros((bp, self.index.dim), np.float32),
                                 10)
            return len(batch_sizes) + self._warm_fresh(batch_sizes)
        c = self._payload.shape[0]
        max_rows = max_rows or c + 1
        max_rows = -(-max_rows // self.row_bucket) * self.row_bucket
        n = 0
        for b in batch_sizes:
            bp = -(-b // self.pad_batch) * self.pad_batch
            _plan_jit(self.index.centroids, self.llsp_params,
                      jnp.zeros((bp, self.index.dim), jnp.float32),
                      jnp.full((bp,), 10, jnp.int32), self.cfg)
            top = min(max_rows, min(c, bp * self.cfg.nprobe_max) + 1)
            for rows in range(self.row_bucket, top + self.row_bucket,
                              self.row_bucket):
                fn, shapes, kw = self._scan_program(bp, rows)
                fn(*[jnp.zeros(a.shape, a.dtype) for a in shapes], **kw)
                n += 1
        return n + self._warm_fresh(batch_sizes)

    @property
    def _payload(self):
        """The streamed tier's (C, L, D) first-pass posting payload."""
        return self.tier.q8 if self.quantized else self.tier.postings

    def _scan_program(self, bp: int, rows: int):
        """``(jit, argument shapes, static kwargs)`` of the streamed scan
        for a padded batch of ``bp`` queries over ``rows`` packed rows."""
        c, l, d = self._payload.shape
        p = min(self.cfg.nprobe_max, c)
        sds = jax.ShapeDtypeStruct
        tail = (sds((bp, p), jnp.int32), sds((bp, p), jnp.bool_),
                sds((bp, d), jnp.float32))
        kw = dict(cfg=self._scan_cfg, dup_bound=self.dup_bound)
        if self.quantized:
            return _scan_streamed_q8_jit, (
                sds((rows, l, d), jnp.int8), sds((rows, 1, 1), jnp.float32),
                sds((rows, l), jnp.float32), sds((rows, d), jnp.float32),
                sds((rows, l), jnp.int32)) + tail, kw
        return _scan_streamed_jit, (
            sds((rows, l, d), jnp.float32), sds((rows, l), jnp.int32)) + tail, kw

    def lower_scan(self, bp: int, rows: int):
        """The streamed scan program that serves a padded batch of ``bp``
        queries over ``rows`` packed rows, lowered: its text shows whether
        the scan runs as a Mosaic kernel (``tpu_custom_call``)."""
        fn, shapes, kw = self._scan_program(bp, rows)
        return fn.lower(*shapes, **kw)

    def _warm_fresh(self, batch_sizes) -> int:
        """Pre-compile the freshness-merge program per padded batch size
        (snapshot array shapes are epoch-constant, so one program each)."""
        if self.fresh_source is None:
            return 0
        snap = self.fresh_source()
        if snap is None:
            return 0
        from repro.core.fresh import merge_fresh

        kw = self._scan_cfg.k              # over-fetched main-candidate width
        n = 0
        for b in batch_sizes:
            bp = -(-b // self.pad_batch) * self.pad_batch
            merge_fresh(
                jnp.full((bp, kw), jnp.inf, jnp.float32),
                jnp.full((bp, kw), -1, jnp.int32),
                jnp.zeros((bp, self.index.dim), jnp.float32),
                snap.delta_vecs, snap.delta_ids, snap.tombstone, self.cfg.k)
            n += 1
        return n

    # -- convenience drivers ----------------------------------------------
    def serve_batch(self, queries, topk,
                    nprobe_cap: Optional[np.ndarray] = None) -> BatchResult:
        plan = self.plan(queries, topk, nprobe_cap=nprobe_cap)
        return self.harvest(self.dispatch(self.prefetch(plan)))

    def run_sequential(self, batches, *, reference: bool = False
                       ) -> list[BatchResult]:
        """Strictly serial stage chain per batch — the A/B baseline: host
        idle during scan, device idle during gather.  ``reference=True``
        additionally swaps in the pre-runtime reference scan (the full
        pre-PR-2 loop); False isolates the overlap effect alone (identical
        scan program, only the stage ordering differs vs run_pipelined)."""
        out = []
        for queries, topk in batches:
            plan = self.plan(queries, topk)
            prep = self.prefetch(plan)
            if prep.fut is not None:
                prep.fut.result()          # block: no overlap, by design
            infl = self.dispatch(prep, reference=reference)
            jax.block_until_ready(infl.out_d)
            out.append(self.harvest(infl))
        return out

    def run_pipelined(self, batches, *, depth: int = 1) -> list[BatchResult]:
        """N-deep pipelining: the next batch is planned before the prepared
        batch's scan is dispatched, then gathered/streamed while up to
        ``depth`` scans are in flight.  depth=1 is the PR 2 double buffer;
        deeper windows keep the device fed when scan ≪ gather (the harvest
        of batch i is deferred until the window is full, so batch i+1's —
        and i+2's — scans launch behind it without blocking on readback)."""
        batches = list(batches)
        if not batches:
            return []
        depth = max(int(depth), 1)
        out: list[BatchResult] = []
        inflight: collections.deque = collections.deque()
        prep = self.prefetch(self.plan(*batches[0]))
        i = 1
        while prep is not None or inflight:
            if prep is not None and len(inflight) < depth:
                nxt = self.plan(*batches[i]) if i < len(batches) else None
                i += 1
                inflight.append(self.dispatch(prep))
                prep = self.prefetch(nxt) if nxt is not None else None
            else:
                out.append(self.harvest(inflight.popleft()))
        return out


def inflight_depth(times: list[StageTimes]) -> int:
    """Peak number of batches simultaneously in flight on the device stream,
    measured from the stage stamps: a batch is in flight from its scan
    dispatch to its harvest.  The N-deep-window evidence is this value
    (>= 2 means a second scan was dispatched before the first's readback),
    not an inference from throughput."""
    events: list[tuple[float, int]] = []
    for t in times:
        if t.scan_done > t.scan_dispatch:
            events.append((t.scan_dispatch, 1))
            events.append((t.scan_done, -1))
    events.sort()                  # (-1 sorts before +1 at equal stamps:
    cur = peak = 0                 # touching intervals don't count as deep)
    for _, d in events:
        cur += d
        peak = max(peak, cur)
    return peak


def overlap_efficiency(times: list[StageTimes]) -> float:
    """Fraction of gather+stream seconds hidden under the previous batch's
    scan-in-flight window (0 = fully serial, ~1 = fully hidden)."""
    tot = 0.0
    hidden = 0.0
    for prev, cur in zip(times, times[1:]):
        g0, g1 = cur.gather_start, cur.stream_end
        if g1 <= g0:
            continue
        tot += g1 - g0
        s0, s1 = prev.scan_dispatch, prev.scan_done
        hidden += max(0.0, min(g1, s1) - max(g0, s0))
    return hidden / tot if tot > 0 else 0.0


def stage_spans(t: StageTimes) -> list[tuple[str, float, float]]:
    """(name, t0, t1) trace spans for one batch, from the stamps StageTimes
    already holds — the obs layer emits these with zero extra clock reads.
    Unstamped stages (e.g. gather on the fabric path, where stream_end ==
    gather_end) drop out."""
    spans = [("plan", t.plan_start, t.plan_end),
             ("gather", t.gather_start, t.gather_end),
             ("stream", t.gather_end, t.stream_end),
             ("scan", t.scan_dispatch, t.scan_done),
             ("rerank", t.rerank_start, t.rerank_end)]
    return [(n, a, b) for n, a, b in spans if b > a > 0.0]


def rerank_overlap_efficiency(times: list[StageTimes]) -> float:
    """Fraction of batch i's re-rank seconds landing inside batch i+1's
    scan-in-flight window — the quantized-serving twin of
    :func:`overlap_efficiency`.  The poller dispatches batch i+1's scan
    before harvesting batch i, so the flash reads + exact rescoring of i
    run while i+1 occupies the device; this measures that claim from the
    stamps instead of asserting it.  Batches that didn't re-rank drop out;
    returns 0.0 when nothing re-ranked or nothing followed."""
    tot = 0.0
    hidden = 0.0
    for cur, nxt in zip(times, times[1:]):
        r0, r1 = cur.rerank_start, cur.rerank_end
        if r1 <= r0:
            continue
        tot += r1 - r0
        s0, s1 = nxt.scan_dispatch, nxt.scan_done
        hidden += max(0.0, min(r1, s1) - max(r0, s0))
    return hidden / tot if tot > 0 else 0.0


def _vectors_from_postings(index) -> np.ndarray:
    """Reconstruct the (N, D) f32 corpus from the posting payload: every
    live slot carries its vector, closure replicas carry identical copies,
    so a scatter by global id is exact.  This is what lets the lifecycle
    rebuild path mint a flash tier without threading the raw corpus through
    every delta build."""
    pids = np.asarray(index.posting_ids)
    payload = np.asarray(index.postings, np.float32)
    dim = payload.shape[-1]
    flat_ids = pids.reshape(-1)
    live = flat_ids >= 0
    n = int(flat_ids[live].max()) + 1 if live.any() else 0
    out = np.zeros((n, dim), np.float32)
    out[flat_ids[live]] = payload.reshape(-1, dim)[live]
    return out


def make_quantized_pipeline(index, llsp_params, cfg: SearchConfig, *,
                            epoch: int = 0, arena=None, flash_path=None,
                            name: str = "helmsman", vectors=None,
                            rerank: Optional[RerankConfig] = None,
                            with_flash: bool = True,
                            fresh_source=None, **pipe_kw) -> PrefetchPipeline:
    """Build the quantized-default serving pipeline for one index version:
    q8 hot tier (dead slots masked out of the scale), f32 corpus demoted to
    the mmap flash tier, adaptive re-rank on.  Used by launch/serve.py at
    deploy AND as the lifecycle ``make_pipeline`` hook so delta rebuilds
    emit quantized shards — the tier choice survives a rebuild+swap.

    ``vectors`` (N, D) is the id-addressed f32 corpus; when omitted it is
    reconstructed from the posting payload (exact — pads are masked).
    ``with_flash=False`` serves raw q8 distances with no re-rank tier
    (the --no-rerank A/B arm).
    """
    from repro.core.quantize import quantize_postings
    from repro.storage.host_tier import QuantizedTieredPostings

    qp = quantize_postings(index.postings, index.centroids,
                           index.posting_ids)
    tier = QuantizedTieredPostings(
        np.asarray(qp.q8), np.asarray(qp.scale), np.asarray(qp.norm2),
        np.asarray(index.centroids), np.asarray(index.posting_ids),
        epoch=epoch)
    flash = None
    if with_flash:
        if vectors is None:
            vectors = _vectors_from_postings(index)
        flash = FlashTier(vectors, flash_path, arena=arena, name=name,
                          epoch=epoch)
    cfg = dataclasses.replace(cfg, tier="q8")
    return PrefetchPipeline(index, llsp_params, cfg, tier,
                            flash=flash, rerank=rerank,
                            fresh_source=fresh_source, **pipe_kw)


def latency_percentiles(lat_s: list[float]) -> dict:
    if not lat_s:
        return {"p50_ms": 0.0, "p99_ms": 0.0, "mean_ms": 0.0}
    a = np.asarray(lat_s) * 1e3
    return {
        "p50_ms": float(np.percentile(a, 50)),
        "p99_ms": float(np.percentile(a, 99)),
        "mean_ms": float(a.mean()),
    }
