"""Quantized serving tier (PR 8): q8 hot tier + flash f32 re-rank.

Covers the tentpole's layers end to end:
  * FlashTier — mmap read/dedup semantics, stamped ReadEvents, arena
    extent accounting, idempotent release;
  * QuantizedTieredPostings — union/sentinel/remap fetch contract parity
    with the f32 tier, hot-bytes ratio;
  * PrefetchPipeline in q8 mode — recall parity with the f32 pipeline,
    re-rank exactness vs brute force, adaptive-stop behavior, and the
    stamp-measured rerank/scan overlap on pipelined runs;
  * lifecycle — a delta rebuild through ``make_quantized_pipeline``
    reports (and preserves) the q8 tier across the epoch swap.
"""
import dataclasses as dc
import os

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.distance import recall_at_k
from repro.core.ivf import brute_force_topk
from repro.core.quantize import ivf_scan_quantized, quantize_postings
from repro.core.search import SearchConfig
from repro.runtime import (
    PrefetchPipeline,
    RerankConfig,
    make_quantized_pipeline,
    overlap_efficiency,
    rerank_overlap_efficiency,
)
from repro.storage import (
    ChunkArena,
    FlashTier,
    QuantizedTieredPostings,
    TieredPostings,
)

CFG = SearchConfig(k=10, nprobe_max=16, pruning="none", use_kernel=False,
                   fused_topk=True)


# -------------------------------------------------------------------------
# FlashTier
# -------------------------------------------------------------------------
def test_flash_tier_read_dedup_and_stats(tmp_path, rng):
    x = rng.normal(size=(100, 8)).astype(np.float32)
    ft = FlashTier(x, str(tmp_path / "t.f32"))
    assert ft.nbytes == 100 * 8 * 4
    ids = np.array([[5, 3, 5, -1], [3, 7, -1, -1]])
    uids, rows = ft.read(ids)
    np.testing.assert_array_equal(uids, [3, 5, 7])   # sorted unique, no -1
    np.testing.assert_allclose(rows, x[[3, 5, 7]])
    ev = ft.stats.events[-1]
    assert ev.rows == 3 and ev.requested == 5        # dedup is visible
    assert ev.bytes == rows.nbytes and ev.end >= ev.start
    assert ft.stats.reads == 1 and ft.stats.rows_read == 3
    ft.release()
    ft.release()                                     # idempotent
    assert not os.path.exists(ft.path)
    with pytest.raises(RuntimeError):
        ft.read(np.array([0]))


def test_flash_tier_arena_accounting(tmp_path, rng):
    x = rng.normal(size=(5000, 8)).astype(np.float32)   # 2 extents @ 4096
    arena = ChunkArena(1, 64 << 20, chunk_bytes=1 << 20)
    free0 = arena.free_bytes
    ft = FlashTier(x, str(tmp_path / "a.f32"), arena=arena, name="fx",
                   epoch=3)
    assert len(ft.extents) == 2
    assert arena.free_bytes < free0
    ft.release()
    assert arena.free_bytes == free0                 # extents recycled


# -------------------------------------------------------------------------
# QuantizedTieredPostings
# -------------------------------------------------------------------------
@pytest.fixture()
def q8_tier(small_index):
    qp = quantize_postings(small_index.postings, small_index.centroids,
                           small_index.posting_ids)
    return QuantizedTieredPostings(
        np.asarray(qp.q8), np.asarray(qp.scale), np.asarray(qp.norm2),
        np.asarray(small_index.centroids),
        np.asarray(small_index.posting_ids)), qp


def test_q8_tier_fetch_matches_quantized_scan(small_index, q8_tier, rng):
    """Scoring the packed fetch output reproduces the resident quantized
    scan — the streamed path serves the same distances the flat path does."""
    tier, qp = q8_tier
    b, p = 4, 5
    cids = rng.integers(0, small_index.n_clusters, (b, p)).astype(np.int32)
    mask = rng.random((b, p)) > 0.3
    g8, scale, norm2, cents, ids, remap = tier.fetch(cids, mask)
    q = rng.normal(size=(b, small_index.dim)).astype(np.float32)
    qc = q[:, None, :] - np.asarray(cents)[None]            # (B, R, D)
    cross = np.einsum("brd,rld->brl", qc,
                      np.asarray(g8, np.float32))
    d_rows = ((qc ** 2).sum(-1)[:, :, None]
              - 2.0 * np.asarray(scale).reshape(1, -1, 1) * cross
              + np.asarray(norm2)[None])                    # (B, R, L)
    rm = np.asarray(remap)
    got = np.take_along_axis(d_rows, rm[:, :, None], axis=1)
    want = np.asarray(ivf_scan_quantized(
        qp, small_index.centroids, jnp.asarray(cids), jnp.asarray(mask),
        jnp.asarray(q)))
    live = np.asarray(ids)[rm] >= 0                     # (B, P, L)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-4, atol=1e-3)
    # masked probes land on the sentinel: ids -1, norm2 0 (no live slot)
    assert (rm[~mask] >= 0).all()
    assert (np.asarray(ids)[rm[~mask]] == -1).all()


def test_q8_tier_hot_bytes_ratio(small_index, q8_tier):
    tier, _ = q8_tier
    f32 = TieredPostings(np.asarray(small_index.postings),
                         np.asarray(small_index.posting_ids))
    f32_bytes = (f32.postings.nbytes + f32.posting_ids.nbytes
                 + np.asarray(small_index.centroids).nbytes)
    assert tier.nbytes() <= 0.35 * f32_bytes


def test_q8_tier_release_fails_loudly(small_index, q8_tier):
    tier, _ = q8_tier
    tier.release()
    with pytest.raises(RuntimeError):
        tier.fetch(np.zeros((1, 1), np.int32))


# -------------------------------------------------------------------------
# q8 pipeline + flash re-rank
# -------------------------------------------------------------------------
def _batches(q, topk, batch=16, n=4):
    return [(q[i * batch:(i + 1) * batch], topk[i * batch:(i + 1) * batch])
            for i in range(n)]


@pytest.fixture()
def q8_pipeline(small_index, small_corpus, tmp_path):
    x, _, _ = small_corpus
    return make_quantized_pipeline(
        small_index, None, CFG, vectors=x,
        flash_path=str(tmp_path / "pipe.f32"), pad_batch=8, row_bucket=32)


def test_q8_pipeline_recall_matches_f32(small_index, small_corpus,
                                        q8_pipeline):
    x, q, topk = small_corpus
    f32 = PrefetchPipeline(
        small_index, None, CFG,
        TieredPostings(np.asarray(small_index.postings),
                       np.asarray(small_index.posting_ids)),
        pad_batch=8, row_bucket=32)
    bs = _batches(q, topk)
    out_q8 = q8_pipeline.run_pipelined(bs, depth=2)
    out_f32 = f32.run_pipelined(bs, depth=2)
    _, t10 = brute_force_topk(jnp.asarray(x), jnp.asarray(q), 10)
    n = sum(b[0].shape[0] for b in bs)
    r_q8 = recall_at_k(np.concatenate([r.ids for r in out_q8])[:, :10],
                       np.asarray(t10)[:n])
    r_f32 = recall_at_k(np.concatenate([r.ids for r in out_f32])[:, :10],
                        np.asarray(t10)[:n])
    assert r_q8 >= r_f32 - 0.01, (r_q8, r_f32)


def test_q8_rerank_distances_are_exact(small_corpus, q8_pipeline):
    """Every returned id inside the flash corpus carries its TRUE f32
    distance after re-rank — not the quantized approximation."""
    x, q, topk = small_corpus
    res = q8_pipeline.serve_batch(q[:16], topk[:16])
    want = ((q[:16, None, :] - x[None]) ** 2).sum(-1)
    live = res.ids >= 0
    got = res.dists[live]
    true = want[np.nonzero(live)[0], res.ids[live]]
    np.testing.assert_allclose(got, true, rtol=1e-4, atol=1e-3)
    t = res.times
    assert t.rerank_end > t.rerank_start > 0
    assert t.rerank_rounds >= 1 and t.rerank_cands > 0


def test_q8_rerank_overlap_measured_from_stamps(small_corpus, q8_pipeline):
    x, q, topk = small_corpus
    out = q8_pipeline.run_pipelined(_batches(q, topk), depth=2)
    times = [r.times for r in out]
    assert all(t.rerank_end > t.rerank_start for t in times)
    # batch i's rerank must overlap batch i+1's scan window (the poller
    # dispatches ahead) — measured, not asserted by construction
    assert rerank_overlap_efficiency(times) > 0.0
    assert overlap_efficiency(times) > 0.0           # gather overlap intact


def test_q8_adaptive_stop_stable_topk(small_index, small_corpus, tmp_path):
    """With a tiny round size the re-ranker should stop before exhausting
    the candidate list once the top-k is stable — and the answer must match
    the exhaustive re-rank exactly."""
    x, q, topk = small_corpus
    full = make_quantized_pipeline(
        small_index, None, CFG, vectors=x,
        flash_path=str(tmp_path / "full.f32"), pad_batch=8, row_bucket=32,
        rerank=RerankConfig(round_size=10_000))
    adaptive = make_quantized_pipeline(
        small_index, None, CFG, vectors=x,
        flash_path=str(tmp_path / "adap.f32"), pad_batch=8, row_bucket=32,
        rerank=RerankConfig(round_size=16, stable_rounds=2))
    rf = full.serve_batch(q[:16], topk[:16])
    ra = adaptive.serve_batch(q[:16], topk[:16])
    assert rf.times.rerank_rounds == 1
    assert ra.times.rerank_rounds >= 2
    if ra.times.rerank_stable_stop:
        assert ra.times.rerank_cands < rf.times.rerank_cands
    # adaptive stop may only cut candidates that cannot enter the top-k:
    # identical ids, identical exact distances
    np.testing.assert_array_equal(ra.ids, rf.ids)
    np.testing.assert_allclose(ra.dists, rf.dists, rtol=1e-5, atol=1e-5)


def test_q8_rerank_capped_walk_serves_exact_distances(small_index,
                                                      small_corpus, tmp_path):
    """When ``max_rounds`` ends the walk before k candidates were scored,
    the rest of the first k are scored before serving: every served id in
    the flash tier carries its exact f32 distance, none a q8 one."""
    x, q, topk = small_corpus
    pipe = make_quantized_pipeline(
        small_index, None, CFG, vectors=x,
        flash_path=str(tmp_path / "cap.f32"), pad_batch=8, row_bucket=32,
        rerank=RerankConfig(round_size=4, max_rounds=1))
    res = pipe.serve_batch(q[:16], topk[:16])
    assert res.times.rerank_rounds == 1
    assert res.times.rerank_cands == CFG.k
    live = res.ids >= 0
    want = ((q[:16, None, :].astype(np.float64)
             - x[np.maximum(res.ids, 0)].astype(np.float64)) ** 2).sum(-1)
    np.testing.assert_allclose(res.dists[live], want[live], rtol=1e-5)
    assert np.all(np.diff(res.dists, axis=1) >= 0)


@pytest.fixture(scope="module")
def search_shaped():
    """A seeded 20,000 x 64-d corpus of 16 modes, clustered into lists of
    <= 96 primaries in 128 slots with closure replicas, and 16 queries."""
    import dataclasses

    import jax.numpy as jnp
    from repro.build.kmeans import balanced_hierarchical_kmeans
    from repro.core.ivf import IVFIndex, build_postings
    from repro.core.spann_rules import closure_assign
    from repro.data import PAPER_DATASETS, make_queries, make_vectors

    spec = dataclasses.replace(PAPER_DATASETS["redsrch"], n=20_000, dim=64,
                               n_modes=16, seed=11)
    x = make_vectors(spec)
    q, _ = make_queries(spec, 16)
    cents, _ = balanced_hierarchical_kmeans(x, max_cluster_size=96, iters=8)
    ca = np.asarray(closure_assign(jnp.asarray(x), jnp.asarray(cents),
                                   eps=0.2, max_replicas=4))
    postings, pids = build_postings(x, ca, cents.shape[0], 128)
    index = IVFIndex(jnp.asarray(cents), jnp.asarray(postings),
                     jnp.asarray(pids))
    return x, q, index


def test_q8_pipeline_serves_top_1000(search_shaped, tmp_path):
    """The served q8 path at k = 1000 (fused kernel at k2 = 2000, its
    buffered merge, the 2000-wide re-rank) against float32 brute force:
    recall@1000 >= 0.95, every served distance the exact one of its id,
    no answer malformed."""
    x, q, index = search_shaped
    cfg = SearchConfig(k=1000, nprobe_max=64, pruning="none",
                       use_kernel=True, fused_topk=True)
    pipe = make_quantized_pipeline(index, None, cfg, vectors=x,
                                   flash_path=str(tmp_path / "s.f32"),
                                   pad_batch=16, row_bucket=256)
    res = pipe.serve_batch(q, 1000)
    _, truth = brute_force_topk(jnp.asarray(x), jnp.asarray(q), 1000)
    assert res.ids.shape == (16, 1000)
    assert recall_at_k(res.ids, np.asarray(truth)) >= 0.95
    assert np.all(res.ids >= 0)
    assert all(len(set(r.tolist())) == 1000 for r in res.ids)
    assert np.all(np.diff(res.dists, axis=1) >= 0)
    want = ((q[:, None, :].astype(np.float64)
             - x[res.ids].astype(np.float64)) ** 2).sum(-1)
    np.testing.assert_allclose(res.dists, want, rtol=1e-5)
    t = res.times
    assert t.scan_select_passes > 0        # the buffered merge compacted
    assert 1000 <= t.rerank_rows <= 2000 * 16    # deduplicated per round


def test_no_rerank_arm_serves_quantized_distances(small_index, small_corpus):
    """``with_flash=False`` (--no-rerank) serves raw q8 first-pass results:
    no rerank stamps, tier still quantized."""
    x, q, topk = small_corpus
    pipe = make_quantized_pipeline(small_index, None, CFG, vectors=x,
                                   with_flash=False, pad_batch=8,
                                   row_bucket=32)
    assert pipe.flash is None and pipe.quantized
    assert pipe.tier_kind == "q8"
    res = pipe.serve_batch(q[:16], topk[:16])
    assert res.times.rerank_end == 0.0
    _, t10 = brute_force_topk(jnp.asarray(x), jnp.asarray(q[:16]), 10)
    assert recall_at_k(res.ids[:, :10], np.asarray(t10)) >= 0.9


def test_q8_pipeline_warmup_compiles(q8_pipeline, small_corpus):
    """Warm-up compiles the scan as a served batch calls it, so the batch
    served after it traces no new scan program."""
    from repro.runtime import pipeline as pp

    assert q8_pipeline.warmup(batch_sizes=(8,)) >= 1
    before = pp._scan_streamed_q8_jit._cache_size()
    _, q, topk = small_corpus
    q8_pipeline.serve_batch(q[:5], topk[:5])
    assert pp._scan_streamed_q8_jit._cache_size() == before


# -------------------------------------------------------------------------
# lifecycle: rebuilds preserve the serving tier
# -------------------------------------------------------------------------
def test_rebuild_preserves_q8_tier(small_corpus, tmp_path):
    from repro.build.kmeans import balanced_hierarchical_kmeans
    from repro.lifecycle import (
        CorpusStore, LiveFreshState, RebuildPolicy, RebuildScheduler,
        UpdateLane, VersionManager, delta_build,
    )
    from repro.runtime import BatchPolicy, DynamicBatcher, ServeEngine

    x, q, _ = small_corpus
    wd = str(tmp_path)
    cents, _ = balanced_hierarchical_kmeans(x, max_cluster_size=48, iters=8)
    corpus = CorpusStore(x)
    index, _ = delta_build(corpus.view(), cents, wd, cluster_len=64,
                           eps=0.2, max_replicas=4, per_task=1000)
    st = LiveFreshState(dim=x.shape[1], capacity=64, n_main=corpus.n)
    lane = UpdateLane(st)

    def mk(index, state):
        p = make_quantized_pipeline(
            index, None, CFG, with_flash=True, pad_batch=8, row_bucket=32,
            fresh_source=state.snapshot,
            flash_path=os.path.join(wd, f"reb-{id(state)}.f32"))
        p.warmup(batch_sizes=(8,))
        return p

    pipe = mk(index, st)
    assert pipe.tier_kind == "q8"
    vm = VersionManager()
    vm.deploy("idx", pipe, fresh=st)
    batcher = DynamicBatcher(
        BatchPolicy(max_batch=16, max_wait_s=0.002, pad=8), ["idx"])
    eng = ServeEngine({"idx": pipe}, batcher, update_lanes={"idx": lane})
    vm.bind(eng)
    sched = RebuildScheduler(
        name="idx", corpus=corpus, centroids=cents, workdir=wd, lane=lane,
        versions=vm, make_pipeline=mk, cluster_len=64,
        policy=RebuildPolicy(delta_fill_frac=0.5, per_task=1000))
    eng.start()
    try:
        lane.submit_insert(
            np.random.default_rng(1).normal(
                loc=6.0, size=(40, x.shape[1])).astype(np.float32))
        rep = sched.rebuild_and_swap(trigger="test")
        # the report pins the serving tier the rebuilt epoch came up on
        assert rep.tier == "q8"
        # inserts reach the new epoch either folded (pumped before the
        # snapshot) or carried (raced the snapshot) — both preserve them
        assert rep.folded_inserts + rep.carried_ops == 40
        rid = eng.submit(q[0], 5, index="idx")
        assert rid >= 0
    finally:
        eng.stop(drain=True)
    comps = eng.qp.poll()
    assert any(c.req_id == rid and c.status == "ok" for c in comps)
