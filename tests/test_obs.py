"""Observability-layer tests (PR 7): streaming-histogram accuracy vs
np.percentile, trace-recorder ring/sampling/export semantics, the reason
taxonomy on every non-"ok" completion path, and end-to-end trace integrity
(well-nested spans, exactly one terminal per admitted request, trace_ids
surviving fabric requeue across a seeded kill drill)."""
import json
import time
import types

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.search import SearchConfig
from repro.distributed import FaultInjector, ShardedFabric
from repro.obs import (
    Counter, Gauge, Histogram, MetricsRegistry, Observability,
    TraceRecorder, check_well_nested,
)
from repro.runtime import (
    BatchPolicy, BatchResult, DynamicBatcher, ServeEngine, StageTimes,
    shard_skewed_trace,
)
from repro.storage import TieredPostings
from repro.storage.host_tier import FetchEvent, TierStats

CFG = SearchConfig(k=5, nprobe_max=8, pruning="none", use_kernel=False,
                   fused_topk=True)


# -------------------------------------------------------------------------
# metrics primitives
# -------------------------------------------------------------------------
def test_counter_labels_and_total():
    c = Counter("x")
    c.inc()
    c.inc(2, "deadline")
    c.inc(1, "drain")
    assert c.value() == 4
    assert c.value("deadline") == 2
    assert c.labels() == {"deadline": 2, "drain": 1}


def test_gauge_last_write_wins():
    g = Gauge("x")
    g.set(3)
    g.set(7)
    g.set(1, "shard0")
    assert g.value() == 7 and g.value("shard0") == 1


def test_histogram_accuracy_within_2pct_of_numpy():
    """ISSUE acceptance: streaming p50/p99 within 2% of np.percentile on a
    realistic latency-shaped (lognormal, ms-scale) stream."""
    rng = np.random.default_rng(42)
    xs = np.exp(rng.normal(np.log(0.020), 0.6, size=20_000))   # ~20ms median
    h = Histogram("lat")
    h.observe_many(xs)
    for q in (0.50, 0.90, 0.99):
        ref = float(np.percentile(xs, q * 100))
        got = h.quantile(q)
        assert abs(got - ref) / ref <= 0.02, (q, got, ref)
    assert abs(h.mean - xs.mean()) / xs.mean() < 1e-9


def test_histogram_single_sample_exact_and_bounded_memory():
    h = Histogram("x")
    h.observe(0.0123)
    assert h.quantile(0.5) == pytest.approx(0.0123)
    assert h.quantile(0.99) == pytest.approx(0.0123)
    n_cells = h.counts.size
    for v in np.linspace(1e-7, 2e4, 5000):     # incl. under/overflow
        h.observe(float(v))
    assert h.counts.size == n_cells            # O(1) memory, any stream
    assert h.n == 5001


def test_histogram_merge_equals_union():
    rng = np.random.default_rng(7)
    a, b = rng.exponential(0.05, 3000), rng.exponential(0.2, 2000)
    ha, hb, hu = Histogram("a"), Histogram("b"), Histogram("u")
    ha.observe_many(a)
    hb.observe_many(b)
    hu.observe_many(np.concatenate([a, b]))
    ha.merge(hb)
    assert ha.n == hu.n
    for q in (0.5, 0.99):
        assert ha.quantile(q) == pytest.approx(hu.quantile(q))


def test_registry_get_or_create_and_snapshot():
    m = MetricsRegistry()
    assert m.counter("a") is m.counter("a")
    with pytest.raises(AssertionError):
        m.gauge("a")                           # name/type collision
    m.counter("a").inc(3, "why")
    m.histogram("h").observe(0.5)
    snap = m.snapshot()
    assert snap["a"]["total"] == 3 and snap["a"]["why"] == 3
    assert snap["h"]["n"] == 1
    assert any("h:" in ln for ln in m.render())


# -------------------------------------------------------------------------
# trace recorder
# -------------------------------------------------------------------------
def test_mint_sampling_deterministic_and_off_is_free():
    tr = TraceRecorder(sample_rate=0.5)
    ids = [tr.mint() for _ in range(400)]
    tr2 = TraceRecorder(sample_rate=0.5)
    assert ids == [tr2.mint() for _ in range(400)]   # replayable
    sampled = [i for i in ids if i]
    assert 0 < len(sampled) < 400                    # rate actually applies
    off = TraceRecorder(enabled=False)
    assert off.mint() == 0
    off.span("x", 0.0, 1.0, trace_id=1)
    assert off.snapshot() == []


def test_ring_bound_drops_oldest_and_counts():
    tr = TraceRecorder(max_events_per_thread=64)
    for i in range(200):
        tr.instant(f"e{i}", t=float(i))
    assert tr.dropped_events > 0
    names = [e[1] for e in tr.snapshot()]
    assert len(names) <= 64
    assert "e199" in names and "e0" not in names     # recent kept


def test_export_perfetto_shape(tmp_path):
    tr = TraceRecorder()
    tr.span("stage", 1.0, 2.0, trace_id=5, track="batch-0", args={"n": 4})
    tr.instant("done:ok", t=2.0, trace_id=5, track="requests")
    tr.abegin("task", "task-1", t=1.1, trace_id=5, track="shard-0")
    tr.aend("task", "task-1", t=1.9, track="shard-0")
    path = str(tmp_path / "t.json")
    doc = tr.export(path)
    assert json.load(open(path)) == json.loads(json.dumps(doc))
    te = doc["traceEvents"]
    by_ph = {}
    for e in te:
        by_ph.setdefault(e["ph"], []).append(e)
    assert len(by_ph["X"]) == 1 and by_ph["X"][0]["dur"] == \
        pytest.approx(1e6)
    assert by_ph["X"][0]["args"]["trace_id"] == 5
    assert by_ph["i"][0]["s"] == "t"
    assert by_ph["b"][0]["id"] == by_ph["e"][0]["id"] == "task-1"
    tracks = {e["args"]["name"] for e in by_ph["M"]}
    assert {"batch-0", "requests", "shard-0"} <= tracks
    assert min(e["ts"] for e in te if e["ph"] != "M") == 0.0  # rebased
    assert check_well_nested(te) == []


def test_check_well_nested_catches_crossing_and_unmatched():
    cross = [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0, "dur": 10},
        {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 5, "dur": 10},
    ]
    assert any("crosses" in v for v in check_well_nested(cross))
    # same intervals on DIFFERENT tracks: fine
    cross[1]["tid"] = 2
    assert check_well_nested(cross) == []
    dangling = [{"ph": "b", "name": "t", "pid": 1, "tid": 1, "ts": 0,
                 "cat": "task", "id": "task-9"}]
    assert any("without end" in v for v in check_well_nested(dangling))
    orphan = [{"ph": "e", "name": "t", "pid": 1, "tid": 1, "ts": 0,
               "cat": "task", "id": "task-9"}]
    assert any("without begin" in v for v in check_well_nested(orphan))


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 60), st.integers(1, 40),
                          st.integers(0, 2)), min_size=1, max_size=24))
def test_well_nested_property_on_constructed_trees(spans):
    """Spans built nested-by-construction (children strictly inside their
    parent) always validate; shifting any span to straddle its parent's
    end always trips the checker."""
    events = []
    for i, (start, width, depth) in enumerate(spans):
        # nest by shrinking: each deeper level sits strictly inside
        ts = start * 1000.0 + depth * 10.0
        dur = width * 1000.0 / (depth + 1)
        events.append({"ph": "X", "name": f"s{i}", "pid": 1, "tid": 1,
                       "ts": ts, "dur": dur})
    # sort and keep only spans that nest (drop crossers) -> must validate
    kept = []
    for ev in sorted(events, key=lambda e: (e["ts"], -e["dur"])):
        end = ev["ts"] + ev["dur"]
        ok = True
        for k in kept:
            kend = k["ts"] + k["dur"]
            if ev["ts"] < kend < end and k["ts"] <= ev["ts"]:
                ok = False                     # would straddle k's end
        if ok:
            kept.append(ev)
    assert check_well_nested(kept) == []
    # now force a genuine crossing pair and expect a violation
    bad = kept + [{"ph": "X", "name": "crosser", "pid": 1, "tid": 1,
                   "ts": kept[0]["ts"] + kept[0]["dur"] / 2,
                   "dur": kept[0]["dur"]}]
    if kept[0]["dur"] > 0:
        assert any("crosses" in v for v in check_well_nested(bad))


# -------------------------------------------------------------------------
# reason taxonomy: every non-"ok" path stamps a non-empty reason
# -------------------------------------------------------------------------
class _StubPipe:
    """Minimal stage-protocol pipeline that errors at one chosen stage."""
    pad_batch = 8
    accepts_deadline = False

    def __init__(self, fail_stage=""):
        self.fail = fail_stage

    def plan(self, queries, topk, nprobe_cap=None, routed=None):
        if self.fail == "plan":
            raise RuntimeError("boom")
        b = len(queries)
        return types.SimpleNamespace(times=StageTimes(size=b),
                                     nprobe=np.full(b, 1, np.int32))

    def prefetch(self, plan):
        if self.fail == "prefetch":
            raise RuntimeError("boom")
        return plan

    def dispatch(self, h):
        if self.fail == "dispatch":
            raise RuntimeError("boom")
        return h

    def harvest(self, h):
        if self.fail == "harvest":
            raise RuntimeError("boom")
        b = h.times.size
        return BatchResult(ids=np.zeros((b, CFG.k), np.int32),
                           dists=np.zeros((b, CFG.k), np.float32),
                           nprobe=h.nprobe, times=h.times)


def _stub_engine(fail_stage, clock=None):
    eng = ServeEngine({"s": _StubPipe(fail_stage)},
                      DynamicBatcher(BatchPolicy(max_batch=8,
                                                 max_wait_s=0.001),
                                     ["s"]),
                      clock=clock or (lambda: 0.0),
                      obs=Observability(sample_rate=1.0))
    return eng


@pytest.mark.parametrize("stage,reason", [
    ("plan", "plan_error"), ("prefetch", "prefetch_error"),
    ("dispatch", "dispatch_error"), ("harvest", "harvest_error"),
])
def test_failed_paths_stamp_stage_reason(stage, reason):
    eng = _stub_engine(stage, clock=time.monotonic)
    eng.start()
    try:
        for _ in range(3):
            assert eng.submit(np.zeros(4, np.float32), CFG.k, index="s",
                              block=True) >= 0
        assert eng.qp.wait_completions(3, timeout=10.0)
    finally:
        eng.stop(drain=True)
    comps = eng.qp.poll()
    assert len(comps) == 3
    assert {c.status for c in comps} == {"failed"}
    assert {c.reason for c in comps} == {reason}
    assert eng.obs.metrics.counter("engine.not_ok").value(reason) == 3


@pytest.mark.parametrize("stage", ["", "plan", "prefetch", "dispatch",
                                   "harvest"])
def test_serve_exits_nonzero_on_failed_requests(stage):
    """launch/serve.py's end-of-run check: a failed completion (every one
    carries a *_error reason) turns into a non-zero exit that shows the
    error; a clean run passes."""
    from repro.launch.serve import fail_on_errors

    eng = _stub_engine(stage, clock=time.monotonic)
    eng.start()
    try:
        for _ in range(3):
            eng.submit(np.zeros(4, np.float32), CFG.k, index="s", block=True)
        assert eng.qp.wait_completions(3, timeout=10.0)
    finally:
        eng.stop(drain=True)
    if not stage:
        fail_on_errors(eng)
        return
    with pytest.raises(SystemExit) as ei:
        fail_on_errors(eng)
    assert ei.value.code != 0
    assert "3 request(s) failed" in str(ei.value.code)
    assert "RuntimeError: boom" in str(ei.value.code)


def test_shed_paths_stamp_deadline_and_drain_reasons():
    vt = [0.0]
    eng = _stub_engine("", clock=lambda: vt[0])
    # dead on arrival: deadline already unmeetable -> admission shed
    eng.submit(np.zeros(4, np.float32), CFG.k, index="s", deadline_s=-1.0)
    eng.step(now=0.0)
    shed = [c for c in eng.qp.poll() if c.status == "shed"]
    assert shed and all(c.reason == "deadline" for c in shed)
    # admitted but flushed at shutdown -> drain
    eng.submit(np.zeros(4, np.float32), CFG.k, index="s")
    eng._flush_pending()
    comps = eng.qp.poll()
    assert comps and all(c.status == "shed" and c.reason == "drain"
                         for c in comps)


def test_degraded_and_partial_reasons():
    vt = [0.0]
    eng = _stub_engine("", clock=lambda: vt[0])
    req = types.SimpleNamespace(req_id=1, index="s", arrival=0.0,
                                trace_id=0, deadline=None)
    mb = types.SimpleNamespace(requests=[req],
                               degraded=np.array([True]), index="s")
    times = StageTimes(size=1)
    res = BatchResult(ids=np.zeros((1, CFG.k), np.int32),
                      dists=np.zeros((1, CFG.k), np.float32),
                      nprobe=np.ones(1, np.int32), times=times)
    eng._complete_batch(mb, res, done=1.0)
    c = eng.qp.poll()[0]
    assert c.status == "degraded" and c.reason == "deadline"
    # fabric partial outranks degrade, and carries the fabric's reason
    res2 = BatchResult(ids=np.zeros((1, CFG.k), np.int32),
                       dists=np.zeros((1, CFG.k), np.float32),
                       nprobe=np.ones(1, np.int32), times=StageTimes(size=1),
                       partial=np.array([True]), partial_reason="timeout")
    eng._complete_batch(mb, res2, done=2.0)
    c = eng.qp.poll()[0]
    assert c.status == "partial" and c.reason == "timeout"


# -------------------------------------------------------------------------
# bounded accounting satellites
# -------------------------------------------------------------------------
def test_tier_stats_ring_drop_is_counted():
    st_ = TierStats(max_events=8)
    ev = FetchEvent(gather_start=0.0, gather_end=1.0, stream_end=2.0,
                    rows=1, bytes=64)
    for _ in range(20):
        st_.record(ev)
    assert len(st_.events) <= 8
    assert st_.dropped_events == 12            # 3 evictions x 4 events
    st_.reset()
    assert st_.dropped_events == 0 and not st_.events


def test_update_lane_visibility_streams_into_histograms(small_corpus):
    from repro.lifecycle import LiveFreshState, UpdateLane
    x, _, _ = small_corpus
    vt = [0.0]
    st_ = LiveFreshState(dim=x.shape[1], capacity=4096, n_main=x.shape[0])
    lane = UpdateLane(st_, clock=lambda: vt[0])
    lane._raw_cap = 32                         # tiny raw ring for the test
    for i in range(100):
        lane.submit_insert(np.ones((1, x.shape[1]), np.float32))
    lane.pump(vt[0], budget=0)
    vt[0] = 2.0
    lane.mark_visible(lane.state.seq, vt[0])
    vis = lane.visibility_stats()
    assert vis["n_visible"] == 100 and vis["n_pending"] == 0
    # raw window is bounded; the HISTOGRAM saw every sample
    assert len(lane.visible_log) <= 32
    assert lane._h_vis["insert"].n == 100
    assert vis["insert_to_visible"]["p50_ms"] == pytest.approx(2000.0)
    assert vis["insert_to_visible"]["mean_ms"] == pytest.approx(2000.0)


# -------------------------------------------------------------------------
# trace integrity through the real engine + fabric (seeded kill drill)
# -------------------------------------------------------------------------
def test_kill_drill_trace_integrity(small_index, small_corpus):
    """The satellite's end-to-end property: run the seeded kill-a-shard
    drill at sample_rate=1.0 and assert on the EXPORTED trace —
    (1) well-nested per track, (2) every admitted request has exactly one
    terminal event, (3) trace_ids survive the fabric's requeue path (the
    killed shard's task ids reappear on survivor tasks and reach merge)."""
    _, q, _ = small_corpus
    q = q.astype(np.float32)
    obs = Observability(sample_rate=1.0)
    probe = ShardedFabric(small_index, None, CFG, n_shards=4)
    hot = np.nonzero(probe.rmap0.replicas[:, 0] == 1)[0]
    inj = FaultInjector(seed=7).kill(0.2, shard=1)
    fab = ShardedFabric(small_index, None, CFG, n_shards=4,
                        hot_clusters=hot, injector=inj,
                        hedge_after_s=0.05, tick_s=0.02, obs=obs)
    fab.warmup()
    fab.start()
    eng = ServeEngine({"default": fab},
                      DynamicBatcher(BatchPolicy(max_batch=16,
                                                 max_wait_s=0.004),
                                     ["default"]),
                      obs=obs)
    eng.start()
    try:
        hot_rows = np.nonzero(fab.query_shards(q) == 1)[0]
        trace = shard_skewed_trace(150, 0.8, q.shape[0], hot_rows, seed=3)
        inj.arm(time.monotonic())
        t0 = time.monotonic()
        for a in trace:
            while time.monotonic() - t0 < a.t:
                time.sleep(0.0005)
            assert eng.submit(q[a.qrow], CFG.k) >= 0
    finally:
        eng.stop(drain=True)
        fab.stop()
    assert eng.stats.completed == len(trace)   # the drill itself held up
    assert fab.stats.requeued_tasks >= 1
    doc = obs.trace.export()
    te = doc["traceEvents"]
    # (1) structural validity
    assert check_well_nested(te) == []
    # (2) exactly one terminal per admitted request
    begun, terms = set(), {}
    requeued_tids, merged_tids = set(), set()
    for e in te:
        args = e.get("args") or {}
        if e["ph"] == "b" and e["name"] == "request":
            begun.add(args["trace_id"])
        elif e["ph"] == "i" and e["name"].startswith("done:"):
            t = args["trace_id"]
            terms[t] = terms.get(t, 0) + 1
        elif e["ph"] == "b" and e["name"] == "task" \
                and args.get("kind") == "requeue":
            requeued_tids.update(args["trace_ids"])
        elif e["ph"] == "X" and e["name"] == "merge":
            merged_tids.update(args["trace_ids"])
    assert len(begun) == len(trace)
    assert set(terms) == begun
    assert all(n == 1 for n in terms.values())
    # (3) requeued task trace_ids are real requests that reached merge and
    # terminated ok — identity survived kill -> requeue -> merge
    assert requeued_tids
    assert requeued_tids <= begun
    assert requeued_tids <= merged_tids
    # zero-drop drill => requeued requests still completed ok
    done_ok = {args["trace_id"] for e in te
               if e["ph"] == "i" and e["name"] == "done:ok"
               for args in [e.get("args") or {}]}
    assert requeued_tids <= done_ok
    # per-shard fan-out really traced: scan spans on >= 2 shard tracks
    track_names = {e["tid"]: e["args"]["name"] for e in te
                   if e["ph"] == "M"}
    scan_tracks = {track_names[e["tid"]] for e in te
                   if e["ph"] == "X" and e["name"] == "scan"}
    assert len([t for t in scan_tracks if t.startswith("shard-")]) >= 2


def test_tracing_off_records_nothing_but_metrics_stay_live(small_index):
    tier = TieredPostings(np.asarray(small_index.postings),
                          np.asarray(small_index.posting_ids))
    from repro.runtime import PrefetchPipeline
    pipe = PrefetchPipeline(small_index, None, CFG, tier=tier, pad_batch=8,
                            row_bucket=32)
    eng = ServeEngine({"idx": pipe},
                      DynamicBatcher(BatchPolicy(max_batch=8,
                                                 max_wait_s=0.001),
                                     ["idx"]),
                      clock=lambda: 0.0)      # default obs = off
    q = np.asarray(small_index.centroids)[0].astype(np.float32)
    eng.submit(q, CFG.k, index="idx")
    eng.step(now=0.0)
    comps = eng.qp.poll()
    assert comps and comps[0].trace_id == 0
    assert eng.obs.trace.snapshot() == []
    assert eng.obs.metrics.counter("engine.completions").value("ok") >= 1


# -------------------------------------------------------------------------
# profiler spans of the serving path (repro.obs.trace.region / SERVE_SPANS)
# -------------------------------------------------------------------------
def test_region_is_a_noop_outside_a_profile():
    import jax
    from repro.obs.trace import region

    assert not jax.profiler.TraceAnnotation.is_enabled()
    a = region("serve.test", batch=3)
    assert a is region("serve.other")          # one shared no-op object
    with a as span:
        span.set_metadata(batch=4)             # takes args, records nothing
    assert not isinstance(a, jax.profiler.TraceAnnotation)


def test_profile_holds_every_serving_span_on_its_thread(small_index,
                                                        small_corpus,
                                                        tmp_path):
    """A CPU profile of a threaded engine over the q8 pipeline with flash
    re-rank: every SERVE_SPANS name appears, on the line of the thread the
    table names, and every batch arg is the StageTimes.seq of a batch the
    pipeline harvested (and each such batch has each span)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from repro.obs.trace import SERVE_SPANS, SPAN_IDLE
    from repro.runtime.pipeline import make_quantized_pipeline

    x, q, _ = small_corpus
    cfg = SearchConfig(k=5, nprobe_max=8, pruning="none", use_kernel=False,
                       fused_topk=True)
    pipe = make_quantized_pipeline(small_index, None, cfg, vectors=x,
                                   name="q8", pad_batch=8, row_bucket=32,
                                   flash_path=str(tmp_path / "flash.f32"))
    seqs = []
    harvest = pipe.harvest

    def recorded(infl):
        out = harvest(infl)
        seqs.append(out.times.seq)
        return out

    pipe.harvest = recorded
    pipe.serve_batch(np.asarray(q[:8], np.float32), cfg.k)   # compile
    seqs.clear()
    eng = ServeEngine({"q8": pipe},
                      DynamicBatcher(BatchPolicy(max_batch=8,
                                                 max_wait_s=0.002, pad=8),
                                     ["q8"]),
                      clock=time.perf_counter, depth=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        eng.start()
        time.sleep(0.05)                          # the poller idles
        for burst in range(3):
            for i in range(6):
                eng.submit(np.asarray(q[burst * 6 + i], np.float32), cfg.k,
                           index="q8")
            assert eng.qp.wait_completions(6 * (burst + 1), timeout=60.0)
        eng.stop(drain=True)
    finally:
        jax.profiler.stop_trace()
    assert seqs and len(set(seqs)) == len(seqs) and min(seqs) >= 0
    path = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    seen: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SERVE_SPANS:
                    assert line.name.startswith(SERVE_SPANS[ev.name]), \
                        (ev.name, line.name)
                    batch = dict(ev.stats).get("batch")
                    seen.setdefault(ev.name, set()).add(batch)
    assert set(seen) == set(SERVE_SPANS)
    assert seen[SPAN_IDLE] == {None}
    for name, batches in seen.items():
        if name != SPAN_IDLE:
            assert batches - {None} == set(seqs), name


def test_queue_wait_is_the_batchers_summed_waits():
    """StageTimes.seq/formed_at/queue_wait_s, copied at formation: under a
    fixed clock the wait is the sum of MicroBatch.waits."""
    vt = [0.0]
    eng = _stub_engine("", clock=lambda: vt[0])
    formed = []
    form = eng.batcher.form

    def spy(now, force=False, idle=False):
        mb, sheds = form(now, force=force, idle=idle)
        if mb is not None:
            formed.append(mb)
        return mb, sheds

    eng.batcher.form = spy
    times = []
    harvest = eng.pipelines["s"].harvest

    def recorded(h):
        out = harvest(h)
        times.append(out.times)
        return out

    eng.pipelines["s"].harvest = recorded
    for t in (0.0, 0.001, 0.003):
        vt[0] = t
        eng.submit(np.zeros(4, np.float32), CFG.k, index="s")
    eng.step(now=0.010)
    vt[0] = 0.012
    eng.submit(np.zeros(4, np.float32), CFG.k, index="s")
    eng.step(now=0.020)
    assert [t.seq for t in times] == [0, 1]
    assert [t.formed_at for t in times] == [0.010, 0.020]
    assert formed[0].waits.tolist() == pytest.approx([0.010, 0.009, 0.007])
    for t, mb in zip(times, formed):
        assert t.queue_wait_s == pytest.approx(float(mb.waits.sum()))
    assert times[0].queue_wait_s == pytest.approx(0.026)
    assert times[1].queue_wait_s == pytest.approx(0.008)
    assert [t.released_idle for t in times] == [False, False]
