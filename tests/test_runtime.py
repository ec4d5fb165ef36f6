"""Serving-runtime tests: queue-pair semantics, pipelined-vs-sequential
parity, deadline shedding determinism, multi-index fairness.

Engine tests drive ``ServeEngine.step`` with a VIRTUAL clock: every
admission / shedding / batching decision is a function of (policy, trace
times) only, so replaying a seeded trace must reproduce the decision
sequence bit-for-bit (``BatchPolicy(ewma=0)`` freezes the service-time
estimate — the one input that otherwise comes from wall-clock measurement).
"""
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.search import SearchConfig, serve_step
from repro.runtime import (
    BatchPolicy,
    BatchResult,
    DynamicBatcher,
    PrefetchPipeline,
    QueuePair,
    RoutePlan,
    SearchRequest,
    ServeEngine,
    StageTimes,
    bursty_trace,
    hot_cluster_trace,
    inflight_depth,
    locality_skewed_trace,
    multi_tenant_trace,
    overlap_efficiency,
    poisson_trace,
    shard_skewed_trace,
    TenantSpec,
)
from repro.storage import TieredPostings


CFG = SearchConfig(k=5, nprobe_max=8, pruning="none", use_kernel=False,
                   fused_topk=True)


@pytest.fixture(scope="module")
def queries(small_corpus):
    _, q, topk = small_corpus
    return q.astype(np.float32), topk


@pytest.fixture()
def streamed_pipeline(small_index):
    tier = TieredPostings(np.asarray(small_index.postings),
                          np.asarray(small_index.posting_ids))
    return PrefetchPipeline(small_index, None, CFG, tier=tier,
                            pad_batch=8, row_bucket=32)


def _mk_engine(small_index, n_indexes=2, policy=None, clock=None, depth=1):
    pipes = {}
    for i in range(n_indexes):
        tier = TieredPostings(np.asarray(small_index.postings),
                              np.asarray(small_index.posting_ids))
        pipes[f"idx{i}"] = PrefetchPipeline(small_index, None, CFG, tier=tier,
                                            pad_batch=8, row_bucket=32)
    policy = policy or BatchPolicy(max_batch=16, max_wait_s=0.001, pad=8)
    batcher = DynamicBatcher(policy, list(pipes))
    return ServeEngine(pipes, batcher, clock=clock or (lambda: 0.0),
                       depth=depth)


# -------------------------------------------------------------------------
# queue pair
# -------------------------------------------------------------------------
def test_queue_pair_fifo_and_backpressure():
    qp = QueuePair(sq_depth=4)

    def req(i):
        return SearchRequest(req_id=i, index="a", query=np.zeros(4),
                             topk=5, deadline=None)

    for i in range(4):
        assert qp.submit(req(i))
    # full SQ: non-blocking submit is back-pressure, blocking times out
    assert not qp.submit(req(99))
    assert not qp.submit(req(99), block=True, timeout=0.01)
    got = qp.pop_submissions(2)
    assert [r.req_id for r in got] == [0, 1]          # FIFO
    assert qp.submit(req(4))                          # drained -> admits
    got = qp.pop_submissions()
    assert [r.req_id for r in got] == [2, 3, 4]
    assert not qp.wait_submissions(timeout=0.01)


def test_queue_pair_completion_order():
    from repro.runtime import Completion
    qp = QueuePair()
    qp.complete([Completion(i, "a", "ok", None, None, 0, 0.0, 1.0)
                 for i in range(5)])
    assert [c.req_id for c in qp.poll(3)] == [0, 1, 2]
    assert [c.req_id for c in qp.poll()] == [3, 4]


# -------------------------------------------------------------------------
# pipeline parity
# -------------------------------------------------------------------------
def test_pipelined_matches_sequential(streamed_pipeline, queries):
    q, topk = queries
    batches = [(q[i * 16:(i + 1) * 16], topk[i * 16:(i + 1) * 16])
               for i in range(4)]
    seq = streamed_pipeline.run_sequential(batches)
    pip = streamed_pipeline.run_pipelined(batches)
    ref = streamed_pipeline.run_sequential(batches, reference=True)
    for s, p, r in zip(seq, pip, ref):
        np.testing.assert_array_equal(s.ids, p.ids)
        np.testing.assert_allclose(s.dists, p.dists)
        np.testing.assert_array_equal(s.ids, r.ids)
    # overlap is measured, not asserted: sequential mode must show none
    assert overlap_efficiency([r.times for r in seq]) == 0.0
    assert overlap_efficiency([r.times for r in pip]) > 0.0


def test_streamed_matches_serve_step(streamed_pipeline, small_index, queries):
    q, topk = queries
    out = streamed_pipeline.serve_batch(q[:32], topk[:32])
    ref = serve_step(small_index, None, jnp.asarray(q[:32]),
                     jnp.asarray(topk[:32]), CFG)
    np.testing.assert_array_equal(np.asarray(ref["ids"]), out.ids)
    np.testing.assert_array_equal(np.asarray(ref["nprobe"]), out.nprobe)


def test_resident_mode_matches_streamed(small_index, queries):
    q, topk = queries
    res = PrefetchPipeline(small_index, None, CFG, pad_batch=8)
    tier = TieredPostings(np.asarray(small_index.postings),
                          np.asarray(small_index.posting_ids))
    str_ = PrefetchPipeline(small_index, None, CFG, tier=tier, pad_batch=8)
    a = res.serve_batch(q[:24], topk[:24])
    b = str_.serve_batch(q[:24], topk[:24])
    np.testing.assert_array_equal(a.ids, b.ids)


def test_nprobe_cap_degrades(streamed_pipeline, queries):
    q, topk = queries
    cap = np.zeros(16, np.int32)
    cap[:8] = 2
    out = streamed_pipeline.serve_batch(q[:16], topk[:16], nprobe_cap=cap)
    assert (out.nprobe[:8] <= 2).all()
    assert (out.nprobe[8:] == CFG.nprobe_max).all()   # pruning="none"


# -------------------------------------------------------------------------
# dup_bound: oracle pre-selection must cover the build's realized replication
# -------------------------------------------------------------------------
def _high_replication_index(max_replicas=12, n=20, c=16, d=8, seed=3):
    """Index built at max_replicas=12: every vector lands in its 12 nearest
    clusters (eps wide open, RNG rule off), so every id has exactly 12
    posting slots — the regime the hardcoded dup_bound=8 silently broke."""
    import jax.numpy as jnp
    from repro.core.ivf import IVFIndex, build_postings
    from repro.core.spann_rules import closure_assign

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    cents = rng.normal(size=(c, d)).astype(np.float32)
    ca = np.asarray(closure_assign(jnp.asarray(x), jnp.asarray(cents),
                                   eps=1e6, max_replicas=max_replicas,
                                   rng_rule=False))
    assert (ca >= 0).all()                  # replication saturated the cap
    postings, pids = build_postings(x, ca, c, cluster_len=32)
    return x, IVFIndex(jnp.asarray(cents), jnp.asarray(postings),
                       jnp.asarray(pids))


def test_dup_bound_derived_from_build_replication():
    """Regression for the ROADMAP dup_bound=8 hazard: at max_replicas=12 the
    oracle's pre-selection must widen to the realized replication, or the
    k2 frontier fills with closure duplicates and real neighbors drop out."""
    from repro.runtime import max_id_replicas

    x, index = _high_replication_index()
    assert max_id_replicas(index.posting_ids) == 12
    rng = np.random.default_rng(7)
    q = rng.normal(size=(8, x.shape[1])).astype(np.float32)
    # n_cand=12 == replication: with dup_bound=8 the top-96 pre-selection is
    # exactly the 8 nearest ids' slots -> only 8 uniques survive for k=10
    cfg = SearchConfig(k=10, nprobe_max=16, pruning="none", n_cand=12,
                       use_kernel=False, fused_topk=True)
    outs = {}
    for use_kernel in (False, True):
        c = SearchConfig(**{**cfg.__dict__, "use_kernel": use_kernel})
        tier = TieredPostings(np.asarray(index.postings),
                              np.asarray(index.posting_ids))
        pipe = PrefetchPipeline(index, None, c, tier=tier,
                                pad_batch=8, row_bucket=32)
        assert pipe.dup_bound == 12          # derived, not hardcoded
        outs[use_kernel] = pipe.serve_batch(q, 10)
    # oracle == kernel, and every query fills all k slots with real ids
    np.testing.assert_array_equal(outs[False].ids, outs[True].ids)
    np.testing.assert_allclose(outs[False].dists, outs[True].dists,
                               rtol=1e-5, atol=1e-5)
    assert (outs[False].ids >= 0).all()
    # the pre-fix behavior is reproducible on demand: a forced dup_bound=8
    # pipeline starves the frontier (candidates lost to duplicates)
    tier = TieredPostings(np.asarray(index.postings),
                          np.asarray(index.posting_ids))
    stale = PrefetchPipeline(index, None, cfg, tier=tier,
                             pad_batch=8, row_bucket=32, dup_bound=8)
    out8 = stale.serve_batch(q, 10)
    assert (out8.ids < 0).any(), "dup_bound=8 should starve k=10 here"


@pytest.mark.parametrize("tier_kind", ["f32", "q8"])
def test_kernel_pipeline_stamps_scan_counters(small_index, queries,
                                              tier_kind):
    """A streamed batch served through a fused kernel carries the kernel's
    counters, read back with its candidates, in its StageTimes; the jnp
    oracle path runs no kernel and leaves them zero."""
    import dataclasses

    from repro.core.quantize import quantize_postings
    from repro.storage import QuantizedTieredPostings

    if tier_kind == "f32":
        tier = TieredPostings(np.asarray(small_index.postings),
                              np.asarray(small_index.posting_ids))
    else:
        qp = quantize_postings(small_index.postings, small_index.centroids,
                               small_index.posting_ids)
        tier = QuantizedTieredPostings(
            np.asarray(qp.q8), np.asarray(qp.scale), np.asarray(qp.norm2),
            np.asarray(small_index.centroids),
            np.asarray(small_index.posting_ids))
    times = {}
    for use_kernel in (False, True):
        cfg = dataclasses.replace(CFG, use_kernel=use_kernel)
        pipe = PrefetchPipeline(small_index, None, cfg, tier=tier,
                                pad_batch=8, row_bucket=32)
        times[use_kernel] = pipe.serve_batch(queries[0][:5], 5).times
    t = times[True]
    # one tile of 8 padded queries x nprobe_max probe slots each
    assert t.scan_steps == 8 * CFG.nprobe_max
    assert 0 < t.scan_steps_merged <= t.scan_steps_live <= t.scan_steps
    k2 = 16                                # the auto candidate width at k=5
    assert t.scan_steps_merged <= t.scan_merge_passes \
        <= k2 * t.scan_steps_merged
    assert t.scan_select_passes == 0       # k2 <= NARROW_K2: no buffer
    o = times[False]
    assert (o.scan_steps, o.scan_steps_live, o.scan_steps_merged,
            o.scan_merge_passes, o.scan_select_passes) == (0, 0, 0, 0, 0)


# -------------------------------------------------------------------------
# batcher: deadline-estimate fixed point, shared due predicate, locality
# -------------------------------------------------------------------------
def _req(i, deadline, t=0.0, index="a", route=None):
    return SearchRequest(req_id=i, index=index,
                         query=np.zeros(4, np.float32), topk=5,
                         deadline=deadline, arrival=t, route=route)


def _routed_req(i, clusters, t=0.0, index="a"):
    cids = np.full(8, -1, np.int32)
    cids[: len(clusters)] = clusters
    return _req(i, None, t=t, index=index,
                route=RoutePlan(cids=cids, nprobe=len(clusters),
                                probe_set=frozenset(clusters), source=None))


def test_form_estimates_recomputed_on_kept_set():
    """Regression for the pre-shed estimate bug: ``form`` judged every
    request against ``est = overhead + est_query_s * len(reqs)`` computed
    BEFORE shedding, so a survivor was shed/degraded because of peers that
    were themselves just shed.  With overhead 1ms and 10ms/query: the
    pre-shed batch of 4 estimates 41ms full / 21ms degraded, which sheds
    S2 (13ms budget) and degrades S1 (25ms budget) — but once the two
    doomed 7ms requests are dropped, the kept batch of 2 runs in 21ms full
    / 11ms degraded, so S2 fits degraded and S1 fits at FULL quality."""
    policy = BatchPolicy(max_batch=8, max_wait_s=0.0, shed="degrade",
                         degrade_nprobe=2, degrade_speedup=2.0,
                         overhead_s=1e-3, init_query_s=10e-3, ewma=0.0)
    b = DynamicBatcher(policy, ["a"])
    for i, dl in enumerate((0.007, 0.007, 0.013, 0.025)):
        assert b.add(_req(i, dl), now=0.0) is None   # all pass admission
    mb, sheds = b.form(0.0)
    assert sorted(c.req_id for c in sheds) == [0, 1]   # truly doomed
    assert [r.req_id for r in mb.requests] == [2, 3]   # survivors KEPT
    assert mb.degraded.tolist() == [True, False]       # S1 at full quality
    assert mb.nprobe_cap.tolist() == [2, 0]
    assert b.stats.shed_deadline == 2 and b.stats.degraded == 1


def test_ready_and_form_share_due_predicate():
    policy = BatchPolicy(max_batch=4, max_wait_s=0.01, shed="none")
    b = DynamicBatcher(policy, ["a", "b"])
    b.add(_req(1, None, t=0.0), now=0.0)
    # young + underfull: not due — ready and form must agree (shared helper)
    assert not b.ready(0.005)
    assert b.form(0.005) == (None, [])
    # head-of-line aged: both flip together
    assert b.ready(0.011)
    mb, _ = b.form(0.011)
    assert mb is not None and len(mb.requests) == 1
    # fullness triggers regardless of age
    for i in range(4):
        b.add(_req(10 + i, None, t=0.02), now=0.02)
    assert b.ready(0.02)
    mb, _ = b.form(0.02)
    assert len(mb.requests) == 4
    # force drain: both queues drain, round-robin, deterministically
    b.add(_req(20, None, t=0.03), now=0.03)
    b.add(_req(21, None, t=0.03, index="b"), now=0.03)
    assert not b.ready(0.03)
    first, _ = b.form(0.03, force=True)
    second, _ = b.form(0.03, force=True)
    assert {first.index, second.index} == {"a", "b"}
    assert b.form(0.03, force=True) == (None, [])
    assert not first.released_idle and b.stats.idle_releases == 0
    # idle release: a young, underfull queue leaves at once under
    # form(idle=True), while ready() and form() without idle still hold it
    b.add(_req(30, None, t=0.04), now=0.04)
    assert not b.ready(0.041)
    assert b.form(0.041) == (None, [])
    mb, _ = b.form(0.041, idle=True)
    assert [r.req_id for r in mb.requests] == [30] and mb.released_idle
    assert b.stats.idle_releases == 1
    # a queue the old predicate releases anyway is no idle release
    b.add(_req(31, None, t=0.05), now=0.05)
    assert b.ready(0.07)
    mb, _ = b.form(0.07, idle=True)
    assert [r.req_id for r in mb.requests] == [31] and not mb.released_idle
    assert b.stats.idle_releases == 1
    assert b.form(0.07, idle=True) == (None, [])     # nothing pending


def test_locality_grouping_packs_by_probe_overlap():
    import dataclasses as _dc
    policy = BatchPolicy(max_batch=4, max_wait_s=10.0, shed="none",
                         grouping="locality")
    ga, gb = (1, 2, 3), (7, 8, 9)
    b = DynamicBatcher(policy, ["a"])
    # interleaved arrivals from two disjoint probe neighborhoods
    for i in range(8):
        b.add(_routed_req(i, ga if i % 2 == 0 else gb), now=0.0)
    mb1, _ = b.form(0.0)
    mb2, _ = b.form(0.0)
    assert [r.req_id for r in mb1.requests] == [0, 2, 4, 6]   # unmixed,
    assert [r.req_id for r in mb2.requests] == [1, 3, 5, 7]   # FIFO inside
    assert mb1.probe_union == frozenset(ga)
    assert mb2.probe_union == frozenset(gb)
    assert b.stats.locality_batches == 2
    # FIFO mode on the same arrivals mixes both groups (the A/B baseline)
    bf = DynamicBatcher(_dc.replace(policy, grouping="fifo"), ["a"])
    for i in range(8):
        bf.add(_routed_req(i, ga if i % 2 == 0 else gb), now=0.0)
    mbf, _ = bf.form(0.0)
    assert [r.req_id for r in mbf.requests] == [0, 1, 2, 3]
    assert mbf.probe_union == frozenset(ga) | frozenset(gb)


def test_locality_aging_guard_seeds_skipped_requests():
    policy = BatchPolicy(max_batch=4, max_wait_s=0.01, shed="none")
    hot, cold = (1, 2, 3), (40, 41)
    b = DynamicBatcher(policy, ["a"])
    b.add(_routed_req(0, hot, t=0.0), now=0.0)
    b.add(_routed_req(1, hot, t=0.0), now=0.0)
    b.add(_routed_req(2, cold, t=0.0), now=0.0)       # the outlier
    for i in range(3, 9):
        b.add(_routed_req(i, hot, t=0.001), now=0.001)
    # due by fullness at t=1ms: nothing aged yet, locality skips the outlier
    mb, _ = b.form(0.001)
    assert 2 not in [r.req_id for r in mb.requests]
    assert mb.probe_union == frozenset(hot)
    # by t=11ms the outlier has aged past max_wait_s: it MUST seed the next
    # batch even though it shares no clusters with anyone
    for i in range(9, 12):
        b.add(_routed_req(i, hot, t=0.011), now=0.011)
    mb2, _ = b.form(0.011)
    assert 2 in [r.req_id for r in mb2.requests]
    assert frozenset(cold) <= mb2.probe_union
    assert b.stats.aged_seeds > 0


def test_union_growth_cap_releases_tight_partial_batches():
    policy = BatchPolicy(max_batch=4, max_wait_s=10.0, shed="none",
                         union_growth_cap=1)
    b = DynamicBatcher(policy, ["a"])
    b.add(_routed_req(0, (1, 2, 3)), now=0.0)
    b.add(_routed_req(1, (1, 2, 3)), now=0.0)
    b.add(_routed_req(2, (50, 51, 52)), now=0.0)      # would add 3 clusters
    b.add(_routed_req(3, (1, 2, 4)), now=0.0)         # adds just 1
    mb, _ = b.form(0.0)
    assert [r.req_id for r in mb.requests] == [0, 1, 3]   # outlier deferred
    mb2, _ = b.form(10.5)                              # ages, then releases
    assert [r.req_id for r in mb2.requests] == [2]


def test_idle_release_keeps_locality_and_aging_guard():
    """``form(idle=True)`` only makes the queue due: the batch is still
    packed by probe overlap from the head of the line, and a request aged
    past ``max_wait_s`` still seeds it."""
    policy = BatchPolicy(max_batch=4, max_wait_s=0.01, shed="none",
                         union_growth_cap=1)
    b = DynamicBatcher(policy, ["a"])
    b.add(_routed_req(0, (1, 2, 3), t=0.0), now=0.0)
    b.add(_routed_req(1, (50, 51, 52), t=0.0), now=0.0)   # the outlier
    b.add(_routed_req(2, (1, 2, 4), t=0.0), now=0.0)
    mb, _ = b.form(0.001, idle=True)
    assert [r.req_id for r in mb.requests] == [0, 2]      # packed, capped
    assert mb.released_idle and b.stats.aged_seeds == 0
    # the outlier ages while two young requests arrive: it must seed the
    # next batch although it would grow the union most
    b.add(_routed_req(3, (1, 2, 3), t=0.009), now=0.009)
    b.add(_routed_req(4, (1, 2, 3), t=0.009), now=0.009)
    mb, _ = b.form(0.011, idle=True)
    assert [r.req_id for r in mb.requests] == [1]
    assert not mb.released_idle and b.stats.aged_seeds == 1
    mb, _ = b.form(0.011, idle=True)
    assert [r.req_id for r in mb.requests] == [3, 4] and mb.released_idle
    assert b.stats.idle_releases == 2


# -------------------------------------------------------------------------
# engine: ordering, shedding determinism, fairness
# -------------------------------------------------------------------------
def test_engine_per_index_fifo(small_index, queries):
    q, _ = queries
    eng = _mk_engine(small_index)
    with pytest.raises(KeyError):
        eng.submit(q[0], 5, index="no-such-index")   # client-thread error,
    for i in range(40):                              # never the poller's
        assert eng.submit(q[i % 64], 5, index=f"idx{i % 2}") >= 0
    while eng.step(now=1.0):
        pass
    comps = eng.qp.poll()
    assert len(comps) == 40
    for name in ("idx0", "idx1"):
        seq = [c.req_id for c in comps if c.index == name]
        assert seq == sorted(seq)
    assert {c.status for c in comps} == {"ok"}


def _run_trace(small_index, q, trace, policy):
    vt = [0.0]
    eng = _mk_engine(small_index, policy=policy, clock=lambda: vt[0])
    log = []
    for arr in trace:
        vt[0] = arr.t
        eng.submit(q[arr.qrow % 64], 5, index="idx0",
                   deadline_s=arr.deadline_s)
        eng.step(now=arr.t, force=False)
        log += [(c.req_id, c.status, c.nprobe) for c in eng.qp.poll()]
    vt[0] = trace[-1].t + 1.0
    while eng.step(now=vt[0], force=True):
        pass
    log += [(c.req_id, c.status, c.nprobe) for c in eng.qp.poll()]
    return log, eng.stats


def test_deadline_shedding_deterministic(small_index, queries):
    q, _ = queries
    # saturating arrivals with deadlines tighter than a full batch: some
    # shed, some degraded.  ewma=0 freezes the service estimate so the
    # decision sequence is a pure function of the seeded trace.
    policy = BatchPolicy(max_batch=16, max_wait_s=0.005, pad=8,
                         shed="degrade", degrade_nprobe=2,
                         init_query_s=2e-3, ewma=0.0, overhead_s=1e-3)
    trace = poisson_trace(2000.0, 0.25, seed=11, deadline_s=0.012)
    assert len(trace) > 100
    log1, st1 = _run_trace(small_index, q, trace, policy)
    log2, st2 = _run_trace(small_index, q, trace, policy)
    assert log1 == log2                       # decision-for-decision replay
    statuses = {s for _, s, _ in log1}
    assert "shed" in statuses and "degraded" in statuses
    assert st1.shed == st2.shed and st1.degraded == st2.degraded
    # degraded requests really ran at the capped level
    for _, s, nprobe in log1:
        if s == "degraded":
            assert 0 < nprobe <= 2


def test_multi_index_fairness(small_index, queries):
    q, _ = queries
    eng = _mk_engine(small_index, n_indexes=3)
    # saturate all three tenants equally, then let the batcher release
    served = []
    for i in range(96):
        eng.submit(q[i % 64], 5, index=f"idx{i % 3}")
    orig = eng._complete_batch

    def spy(mb, result, done, epoch=None):
        served.append(mb.index)
        orig(mb, result, done, epoch=epoch)

    eng._complete_batch = spy
    while eng.step(now=1.0):
        pass
    counts = {n: served.count(n) for n in ("idx0", "idx1", "idx2")}
    assert max(counts.values()) - min(counts.values()) <= 1
    # round-robin: no tenant served twice before the others under backlog
    assert served[:3] in ([
        ["idx0", "idx1", "idx2"], ["idx1", "idx2", "idx0"],
        ["idx2", "idx0", "idx1"]])


def test_engine_threaded_drain(small_index, queries):
    q, _ = queries
    import time as _time
    eng = _mk_engine(small_index, clock=None)
    eng.clock = _time.monotonic
    eng.start()
    n = 0
    for i in range(50):
        n += eng.submit(q[i % 64], 5, index=f"idx{i % 2}") >= 0
    eng.stop(drain=True)
    comps = eng.qp.poll()
    assert len(comps) == n == eng.stats.completed
    assert all(c.status == "ok" for c in comps)


def test_engine_deep_window_threaded_drain(small_index, queries):
    """depth=3: the poller keeps several batches in flight; every admitted
    request still completes exactly once, per-index FIFO preserved (fifo
    grouping — locality may legitimately reorder across batches, so the
    order assert would race the wall clock under it)."""
    q, _ = queries
    import time as _time
    policy = BatchPolicy(max_batch=16, max_wait_s=0.001, pad=8,
                         grouping="fifo")
    eng = _mk_engine(small_index, policy=policy, clock=None, depth=3)
    eng.clock = _time.monotonic
    eng.start()
    n = 0
    for i in range(60):
        n += eng.submit(q[i % 64], 5, index=f"idx{i % 2}") >= 0
    eng.stop(drain=True)
    comps = eng.qp.poll()
    assert len(comps) == n == eng.stats.completed
    assert all(c.status == "ok" for c in comps)
    for name in ("idx0", "idx1"):
        seq = [c.req_id for c in comps if c.index == name]
        assert seq == sorted(seq)


def test_engine_routes_at_admission(small_index, queries):
    """Requests carry a RoutePlan whose probe signature is exactly what the
    pipeline's plan stage would compute: bursts are routed eagerly at SQ
    drain (group >= pad amortizes the call), trickles in one pooled call
    at formation — either way, at most once per request."""
    q, _ = queries
    eng = _mk_engine(small_index, n_indexes=1)
    pipe = eng.pipelines["idx0"]
    # burst path: drained group of 8 >= pad=8 -> routed at admission
    for i in range(8):
        eng.submit(q[i], 5, index="idx0")
    eng._drain_sq(0.0)
    reqs = list(eng.batcher._pending["idx0"])
    assert len(reqs) == 8
    assert all(r.route is not None for r in reqs)
    cids, npb = pipe.route(q[:8], np.full(8, 5, np.int32))
    for i, r in enumerate(reqs):
        want = frozenset(int(c) for c in cids[i, : int(npb[i])] if c >= 0)
        assert r.route.probe_set == want and len(want) > 0
        assert r.route.source is pipe
    while eng.step(now=1.0):
        pass
    comps = eng.qp.poll()
    assert len(comps) == 8 and all(c.status == "ok" for c in comps)
    # trickle path: below-pad drains stay unrouted until formation pools
    # them into one routing call
    for i in range(3):
        eng.submit(q[i], 5, index="idx0")
        eng._drain_sq(0.0)
    reqs = list(eng.batcher._pending["idx0"])
    assert all(r.route is None for r in reqs)
    mb, _ = eng.batcher.form(1.0, force=False)     # head aged -> due
    assert mb is not None and len(mb.requests) == 3
    assert all(r.route is not None and r.route.source is pipe
               for r in mb.requests)


def test_route_reuse_matches_replan(streamed_pipeline, queries):
    """plan(routed=...) must be bit-identical to plan() recomputing the
    centroid scan — the admission-time routing is moved, not approximated."""
    q, topk = queries
    cids, nprobe = streamed_pipeline.route(q[:16], topk[:16])
    plan_r = streamed_pipeline.plan(q[:16], topk[:16],
                                    routed=(cids, nprobe))
    assert plan_r.times.routed
    out_r = streamed_pipeline.harvest(streamed_pipeline.dispatch(
        streamed_pipeline.prefetch(plan_r)))
    out = streamed_pipeline.serve_batch(q[:16], topk[:16])
    np.testing.assert_array_equal(out.ids, out_r.ids)
    np.testing.assert_allclose(out.dists, out_r.dists)
    np.testing.assert_array_equal(out.nprobe, out_r.nprobe)


def test_run_pipelined_depth(streamed_pipeline, queries):
    q, topk = queries
    batches = [(q[i * 8:(i + 1) * 8], topk[i * 8:(i + 1) * 8])
               for i in range(6)]
    base = streamed_pipeline.run_sequential(batches)
    deep = streamed_pipeline.run_pipelined(batches, depth=3)
    for s, p in zip(base, deep):
        np.testing.assert_array_equal(s.ids, p.ids)
    # stamp evidence: >= 2 scans in flight at once with a deep window,
    # never more than 1 in the sequential and 1-deep drivers
    assert inflight_depth([r.times for r in deep]) >= 2
    assert inflight_depth([r.times for r in base]) == 1
    shallow = streamed_pipeline.run_pipelined(batches, depth=1)
    assert inflight_depth([r.times for r in shallow]) == 1
    for s, p in zip(base, shallow):
        np.testing.assert_array_equal(s.ids, p.ids)


def test_multi_tenant_starvation_guard_under_locality(small_index, queries):
    """A hot-cluster tenant must not delay a cold tenant's head-of-line
    request past max_wait_s under locality grouping (seeded trace, virtual
    clock — the decision sequence replays bit-for-bit)."""
    from repro.runtime import merge_timelines
    q, _ = queries
    policy = BatchPolicy(max_batch=8, max_wait_s=0.002, pad=8, shed="none",
                         grouping="locality")
    hot = poisson_trace(3000.0, 0.1, seed=5, index="idx0")
    cold = poisson_trace(80.0, 0.1, seed=6, index="idx1")
    trace = merge_timelines(hot, cold)
    assert any(a.index == "idx1" for a in trace)
    logs = []
    for _ in range(2):
        vt = [0.0]
        eng = _mk_engine(small_index, policy=policy, clock=lambda: vt[0])
        log = []
        for arr in trace:
            vt[0] = arr.t
            eng.submit(q[arr.qrow % 64], 5, index=arr.index)
            eng.step(now=arr.t, force=False)   # drain SQ, form if due
            while eng.batcher.ready(arr.t):    # both tenants due: form all
                eng.step(now=arr.t, force=False)
            log += [(c.req_id, c.index) for c in eng.qp.poll()]
        vt[0] = trace[-1].t + policy.max_wait_s + 1e-4
        while eng.step(now=vt[0], force=False):
            pass
        log += [(c.req_id, c.index) for c in eng.qp.poll()]
        assert eng.batcher.pending() == 0
        # the aging bound: formation opportunities in this replay exist
        # only at arrival times, so no request (either tenant) may wait
        # past max_wait_s plus the largest inter-arrival gap
        slack = max(y.t - x.t for x, y in zip(trace, trace[1:])) + 2e-4
        assert eng.batcher.stats.max_queue_wait_s \
            <= policy.max_wait_s + slack
        assert len(log) == len(trace)
        logs.append(log)
    assert logs[0] == logs[1]                 # deterministic replay


# -------------------------------------------------------------------------
# loadgen: locality-skewed + hot-cluster traces
# -------------------------------------------------------------------------
def test_locality_traces_deterministic_and_skewed():
    kw = dict(n_queries=640, n_groups=8, concurrency=4, seed=2)
    a = locality_skewed_trace(500, 1.0, **kw)
    assert a == locality_skewed_trace(500, 1.0, **kw)
    assert all(x.t <= y.t for x, y in zip(a, a[1:]))
    gs = 640 // 8
    assert len({arr.qrow // gs for arr in a}) > 1   # interleaved groups
    # within a stream, group persistence: consecutive same-group arrivals
    # dominate (switch_p is small), so short windows are locality-skewed
    h = hot_cluster_trace(500, 1.0, n_queries=640, hot_frac=0.05,
                          hot_weight=0.9, seed=3)
    assert h == hot_cluster_trace(500, 1.0, n_queries=640, hot_frac=0.05,
                                  hot_weight=0.9, seed=3)
    n_hot = sum(1 for arr in h if arr.qrow < 32)
    assert n_hot > 0.7 * len(h)               # hot slice carries the mass


# -------------------------------------------------------------------------
# load generator
# -------------------------------------------------------------------------
def test_loadgen_deterministic_and_sorted():
    a = poisson_trace(500, 1.0, seed=3, deadline_s=0.05)
    b = poisson_trace(500, 1.0, seed=3, deadline_s=0.05)
    assert a == b
    assert all(x.t <= y.t for x, y in zip(a, a[1:]))
    assert abs(len(a) - 500) < 120            # ~Poisson(500)
    c = poisson_trace(500, 1.0, seed=4)
    assert c != a

    m = multi_tenant_trace([TenantSpec("x", 300), TenantSpec("y", 100)],
                           1.0, seed=0)
    assert all(p.t <= q.t for p, q in zip(m, m[1:]))
    nx = sum(1 for arr in m if arr.index == "x")
    ny = len(m) - nx
    assert nx > 2 * ny                        # rate mix respected

    bt = bursty_trace(50, 2000, period_s=0.2, duty=0.25, duration_s=1.0,
                      seed=5)
    in_burst = sum(1 for arr in bt if (arr.t % 0.2) < 0.05)
    assert in_burst > len(bt) * 0.6           # bursts carry the mass


# -------------------------------------------------------------------------
# shutdown / crash drain: no admitted request is ever abandoned
# -------------------------------------------------------------------------
class _HarvestBomb:
    """Delegating pipeline wrapper whose harvest raises for chosen batch
    ordinals — the poller-killing fault the engine's drain guards absorb."""

    def __init__(self, inner, fail_batches):
        self._inner = inner
        self._fail = set(fail_batches)
        self._n = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def harvest(self, handle):
        i = self._n
        self._n += 1
        if i in self._fail:
            raise RuntimeError("injected harvest fault")
        return self._inner.harvest(handle)


def test_harvest_fault_completes_batch_as_failed(small_index, queries):
    """Regression (pre-fix behavior FAILS this): a harvest exception used
    to unwind the poller thread with the depth-N window still holding
    batches — those and every later submission were abandoned, clients
    blocked on CQ entries that never came.  Now the faulted batch
    completes as "failed" and the poller keeps serving the rest."""
    q, _ = queries
    import time as _time
    tier = TieredPostings(np.asarray(small_index.postings),
                          np.asarray(small_index.posting_ids))
    pipe = _HarvestBomb(
        PrefetchPipeline(small_index, None, CFG, tier=tier,
                         pad_batch=8, row_bucket=32),
        fail_batches={1})
    policy = BatchPolicy(max_batch=8, max_wait_s=0.001, pad=8,
                         grouping="fifo")
    eng = ServeEngine({"a": pipe}, DynamicBatcher(policy, ["a"]),
                      clock=_time.monotonic, depth=2)
    eng.start()
    n = 0
    for i in range(48):
        n += eng.submit(q[i % 64], 5, index="a") >= 0
    eng.stop(drain=True)
    comps = eng.qp.poll()
    assert len(comps) == n == eng.stats.completed     # nothing abandoned
    n_failed = sum(1 for c in comps if c.status == "failed")
    assert n_failed >= 1                              # the bombed batch
    assert eng.stats.failed == n_failed
    assert all(c.ids is None for c in comps if c.status == "failed")
    assert sum(1 for c in comps if c.status == "ok") == n - n_failed


class _GatedPipe:
    """Stage-protocol pipeline that holds its first batch in flight: that
    batch's ``dispatch`` calls ``on_first_dispatch`` (requests arriving
    while the chip is busy) and its ``harvest`` waits for ``gate``.  Logs
    every planned batch as (size, batches harvested before its plan)."""
    pad_batch = 8

    def __init__(self):
        self.on_first_dispatch = lambda: None
        self.gate = threading.Event()
        self.holding = threading.Event()
        self.log = []
        self.harvested = 0

    def plan(self, queries, topk, nprobe_cap=None, routed=None):
        self.log.append((queries.shape[0], self.harvested))
        return queries.shape[0]

    def prefetch(self, b):
        return b

    def dispatch(self, b):
        if len(self.log) == 1:
            self.on_first_dispatch()
        return b

    def harvest(self, b):
        if self.harvested == 0:
            self.holding.set()
            self.gate.wait(timeout=10.0)
        self.harvested += 1
        return BatchResult(
            ids=np.zeros((b, 5), np.int32),
            dists=np.zeros((b, 5), np.float32),
            nprobe=np.full(b, 1, np.int32), times=StageTimes(size=b))


def _gated_engine():
    """A poller over a ``_GatedPipe`` whose ``max_wait_s`` (2 s) no test
    waits out: a batch leaves by the idle rule or not at all."""
    pipe = _GatedPipe()
    eng = ServeEngine({"a": pipe},
                      DynamicBatcher(BatchPolicy(max_batch=16, max_wait_s=2.0,
                                                 pad=8), ["a"]),
                      clock=time.monotonic, depth=2)
    return eng, pipe


def _submit(eng, n):
    return sum(eng.submit(np.zeros(4, np.float32), 5, index="a") >= 0
               for _ in range(n))


def test_stop_without_drain_sheds_instead_of_abandoning():
    """Regression: ``stop(drain=False)`` used to abandon requests pooled
    in the batcher (and SQ residents) — no CQ entry, blocked clients.
    Now every admitted-but-unformed request completes as "shed".  An idle
    poller releases a request at once, so the first batch is held in
    flight: three requests arrive at its dispatch (the poller drains them
    into the batcher's pool), two more while its harvest waits (the SQ)."""
    eng, pipe = _gated_engine()
    n_pooled = []
    pipe.on_first_dispatch = lambda: n_pooled.append(_submit(eng, 3))
    eng.start()
    n = _submit(eng, 1)
    assert pipe.holding.wait(timeout=10.0)
    n += _submit(eng, 2) + sum(n_pooled)
    stopper = threading.Thread(target=eng.stop, kwargs={"drain": False})
    stopper.start()
    assert eng._stop.wait(timeout=10.0)
    pipe.gate.set()
    stopper.join(timeout=10.0)
    assert not stopper.is_alive()
    comps = eng.qp.poll()
    assert len(comps) == n == 6 == eng.stats.completed
    assert [c.status for c in comps] == ["ok"] + ["shed"] * 5
    assert eng.batcher.pending() == 0


def test_idle_engine_releases_lone_request_at_once(small_index, queries):
    """An idle poller releases a lone request without waiting out
    ``max_wait_s`` (2 s), and stamps the batch ``released_idle``."""
    q, _ = queries
    eng = _mk_engine(small_index, n_indexes=1,
                     policy=BatchPolicy(max_batch=16, max_wait_s=2.0, pad=8))
    eng.clock = time.monotonic
    eng.submit(q[0], 5, index="idx0")
    # due by age at this clock: routes, plans and serves the batch's
    # shapes once, so the threaded run compiles nothing
    assert eng.step(now=time.monotonic() + 3.0, force=False) == 1
    eng.qp.poll()
    stamps = []
    orig = eng._complete_batch

    def spy(mb, result, done, epoch=None):
        stamps.append(result.times.released_idle)
        orig(mb, result, done, epoch=epoch)

    eng._complete_batch = spy
    eng.start()
    try:
        eng.submit(q[0], 5, index="idx0")
        assert eng.qp.wait_completions(1, timeout=10.0)
        (c,) = eng.qp.poll()
    finally:
        eng.stop()
    assert c.status == "ok"
    assert c.latency < 1.0
    assert stamps == [True] and eng.batcher.stats.idle_releases == 1


def test_busy_engine_coalesces_until_harvest():
    """While a batch is in flight the old predicate holds: requests that
    arrive meanwhile are not released early (``max_wait_s`` is 2 s), and
    form one batch once the harvest has freed the poller."""
    eng, pipe = _gated_engine()
    pipe.on_first_dispatch = lambda: _submit(eng, 3)
    eng.start()
    try:
        assert _submit(eng, 1) == 1
        assert pipe.holding.wait(timeout=10.0)
        assert _submit(eng, 2) == 2
        time.sleep(0.1)
        assert pipe.log == [(1, 0)]        # nothing formed while in flight
        pipe.gate.set()
        assert eng.qp.wait_completions(6, timeout=10.0)
    finally:
        pipe.gate.set()
        eng.stop()
    assert pipe.log == [(1, 0), (5, 1)]
    assert {c.status for c in eng.qp.poll()} == {"ok"}
    assert eng.batcher.stats.idle_releases == 2


def test_batcher_drain_pending_fifo():
    policy = BatchPolicy(max_batch=64, max_wait_s=10.0, pad=8)
    b = DynamicBatcher(policy, ["a", "b"])

    def req(i, idx):
        return SearchRequest(req_id=i, index=idx, query=np.zeros(4),
                             topk=5, deadline=None)

    for i in range(6):
        assert b.add(req(i, "a" if i % 2 == 0 else "b"), 0.0) is None
    out = b.drain_pending()
    # FIFO within each index, indexes in registration order
    assert [r.req_id for r in out] == [0, 2, 4, 1, 3, 5]
    assert b.pending() == 0
    mb, sheds = b.form(100.0, force=True)
    assert mb is None and sheds == []


class _PartialPipe:
    """Minimal stage-protocol pipeline: stamps row 0 of every batch as
    partial (the fabric's degraded-mode contract) and records the batch
    deadline the engine hands to deadline-aware pipelines."""
    pad_batch = 8
    accepts_deadline = True

    def __init__(self):
        self.saw_deadline = "unset"

    def plan(self, queries, topk, nprobe_cap=None, routed=None,
             deadline=None):
        self.saw_deadline = deadline
        return queries.shape[0]

    def prefetch(self, b):
        return b

    def dispatch(self, b):
        return b

    def harvest(self, b):
        partial = np.zeros(b, bool)
        partial[0] = True
        return BatchResult(
            ids=np.zeros((b, 5), np.int32),
            dists=np.zeros((b, 5), np.float32),
            nprobe=np.full(b, 1, np.int32),
            times=StageTimes(size=b), partial=partial)


def test_engine_stamps_partial_and_plumbs_deadline():
    eng = ServeEngine({"a": _PartialPipe()},
                      DynamicBatcher(BatchPolicy(max_batch=4,
                                                 max_wait_s=0.001, pad=4),
                                     ["a"]),
                      clock=lambda: 0.0)
    for i in range(3):
        assert eng.submit(np.zeros(4), 5, index="a",
                          deadline_s=1.0 + i) >= 0
    eng.step(now=0.0)
    comps = eng.qp.poll()
    assert [c.status for c in comps] == ["partial", "ok", "ok"]
    assert eng.stats.partial == 1
    # the batch deadline is the tightest request deadline
    assert eng.pipelines["a"].saw_deadline == 1.0


def test_shard_skewed_trace_deterministic_and_skewed():
    hot = [3, 7, 11]
    a = shard_skewed_trace(400, 1.0, 64, hot, seed=9)
    assert a == shard_skewed_trace(400, 1.0, 64, hot, seed=9)
    assert all(x.t <= y.t for x, y in zip(a, a[1:]))
    n_hot = sum(1 for arr in a if arr.qrow in set(hot))
    assert n_hot > 0.7 * len(a)               # hot shard carries the mass
    assert all(0 <= arr.qrow < 64 for arr in a)
    assert shard_skewed_trace(400, 1.0, 64, hot, seed=10) != a
    with pytest.raises(ValueError):
        shard_skewed_trace(400, 1.0, 64, [], seed=0)
