"""Serving launcher — the online half of Fig. 8 as a runnable node daemon.

PR 2 made this a thin driver over the async serving runtime
(``repro.runtime``): index deployment and node health stay here, but the
traffic loop is the runtime's SQ/CQ queue-pair engine — arrivals from a
seeded multi-tenant Poisson trace are submitted one query at a time, the
dynamic batcher coalesces them per index with deadline-aware admission
control, and the prefetch pipeline overlaps each batch's host gather +
device stream with the previous batch's fused-topk scan.

Responsibilities (container-scale versions of the production node):
  * index deployment: build or load indexes, allocate their cluster extents
    from the node's ChunkArena (multi-index hosting, §4.2), publish
    IndexMeta, wrap the postings in a streamed host tier + pipeline;
  * traffic: open-loop Poisson tenants through the ServeEngine (§4.1);
  * health: heartbeat table per logical shard, straggler detection, replica
    failover on shard failure (§6.2);
  * freshness: a mid-run rebuild + atomic ``swap_pipeline`` (the paper's
    daily/hourly rebuild flow) while the engine keeps serving.

The scan path is the PR 1 fused-topk data path: the Pallas kernel on TPU,
interpret-mode on CPU (``--no-kernel`` switches to the fast packed-domain
jnp oracle instead — same candidates, same recall).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --indexes 2 --duration 8
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import os
import tempfile
import time

import numpy as np
import jax.numpy as jnp

from repro.build.pipeline import BuildConfig, build_index
from repro.core.distance import recall_at_k
from repro.core.ivf import brute_force_topk
from repro.core.llsp import LLSPConfig
from repro.core.search import SearchConfig
from repro.data import PAPER_DATASETS, make_queries, make_vectors
from repro.distributed import (
    FaultInjector,
    HeartbeatMonitor,
    ShardedFabric,
    plan_failover,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.lifecycle import VersionManager
from repro.obs import (
    HarvestRing,
    Observability,
    QualityMonitor,
    SLOTracker,
    default_rules,
    health_snapshot,
    write_health,
)
from repro.runtime import (
    BatchPolicy,
    DynamicBatcher,
    PrefetchPipeline,
    RerankConfig,
    ServeEngine,
    TenantSpec,
    make_quantized_pipeline,
    multi_tenant_trace,
)
from repro.runtime.pipeline import _vectors_from_postings
from repro.storage import ChunkArena, IndexMeta, TieredPostings, \
    make_replica_map, plan_striping


@dataclasses.dataclass
class Deployment:
    name: str
    index: object
    llsp: object
    spec: object
    meta: IndexMeta
    striping: object
    replica_map: object
    pipeline: PrefetchPipeline
    queries: np.ndarray          # probe pool for recall spot checks
    true10: np.ndarray
    report: object = None        # BuildReport (stage seconds, clusters)


def build_config(nprobe_max: int = 16) -> BuildConfig:
    """The node's index build: SPANN-sized posting lists (<= 96 primaries
    in L=128 slots) and a two-level LLSP pruner whose top level is the
    serving ``nprobe_max``."""
    levels = (max(nprobe_max // 2, 1), nprobe_max)
    return BuildConfig(max_cluster_size=96, cluster_len=128,
                       coarse_per_task=5000, n_workers=2,
                       llsp=LLSPConfig(levels=levels, n_ratio_features=8))


def deploy(arena: ChunkArena, name: str, spec, workdir: str,
           n_shards: int, scfg: SearchConfig, tier: str = "q8",
           rerank: RerankConfig | None = None,
           with_rerank: bool = True) -> Deployment:
    x = make_vectors(spec)
    q, topk = make_queries(spec, 256)
    topk = np.minimum(topk, 50).astype(np.int32)
    index, llsp, report = build_index(x, build_config(scfg.nprobe_max),
                                      workdir, queries=q, query_topk=topk)
    cluster_bytes = index.cluster_len * index.dim * 4
    extents = arena.allocate_index(name, index.n_clusters, cluster_bytes)
    striping = plan_striping(index.n_clusters, n_shards, extents)
    hot = np.arange(index.n_clusters)[::3]
    rmap = make_replica_map(index.n_clusters, n_shards, striping,
                            hot_clusters=hot, n_replicas=2)
    meta = IndexMeta(name=name, n_clusters=index.n_clusters,
                     cluster_len=index.cluster_len, dim=index.dim,
                     dtype="int8" if tier == "q8" else "float32",
                     extents=extents)
    meta.save(os.path.join(workdir, f"{name}.meta.json"))
    # streamed-row quantum: a batch's union grows with nprobe, and so would
    # the count of row buckets the warmup compiles at a fixed quantum
    row_bucket = max(256, 4 * scfg.nprobe_max)
    if tier == "q8":
        # quantized serving default: q8 hot tier + mmap flash tier (f32
        # corpus, arena-accounted) + adaptive f32 re-rank at harvest
        pipeline = make_quantized_pipeline(
            index, llsp, scfg, arena=arena, name=name, vectors=x,
            flash_path=os.path.join(workdir, f"{name}.flash.f32"),
            rerank=rerank, with_flash=with_rerank, row_bucket=row_bucket)
    else:
        hot_tier = TieredPostings(np.asarray(index.postings),
                                  np.asarray(index.posting_ids))
        # dup_bound auto-derives from the build's realized replication, so a
        # rebuilt index with a different max_replicas can never outrun the
        # oracle's pre-selection (the ROADMAP dup_bound=8 hazard)
        pipeline = PrefetchPipeline(index, llsp, scfg, tier=hot_tier,
                                    row_bucket=row_bucket)
    _, t10 = brute_force_topk(jnp.asarray(x), jnp.asarray(q), 10)
    hot_note = ""
    if tier == "q8":
        f32_bytes = np.asarray(index.postings).nbytes \
            + np.asarray(index.posting_ids).nbytes
        fl = (f" + flash {pipeline.flash.nbytes >> 20} MiB"
              if pipeline.flash is not None else ", rerank off")
        hot_note = (f", hot {pipeline.tier.nbytes() >> 20} MiB "
                    f"({pipeline.tier.nbytes() / f32_bytes:.2f}x f32)" + fl)
    print(f"[deploy] {name}: {index.n_clusters} clusters, "
          f"{len({e.device for e in extents})} devices, "
          f"arena free {arena.free_bytes >> 20} MiB, "
          f"build overlap {report.shard_overlap:.2f} "
          f"({len(report.shard_stamps)} shards), "
          f"dup_bound {pipeline.dup_bound}, tier={pipeline.tier_kind}"
          + hot_note)
    return Deployment(name, index, llsp, spec, meta, striping, rmap,
                      pipeline, q, np.asarray(t10), report)


def undeploy(arena: ChunkArena, dep: Deployment) -> None:
    if dep.pipeline.flash is not None:
        dep.pipeline.flash.release()   # mmap file + its arena chunks
    arena.release_index(dep.name)
    print(f"[undeploy] {dep.name}: chunks recycled "
          f"(arena free {arena.free_bytes >> 20} MiB)")


def probe_recall(engine: ServeEngine, dep: Deployment,
                 lat: list[float], tenant: str, n: int = 64) -> float:
    """Submit known queries THROUGH the engine and score the completions —
    the health check exercises the exact serving path, not a side door.
    Non-probe completions drained along the way keep feeding ``lat``."""
    want = {}
    for i in range(n):
        rid = engine.submit(dep.queries[i], 10, index=tenant, block=True)
        if rid >= 0:
            want[rid] = i
    deadline = time.monotonic() + 60.0
    got: dict[int, np.ndarray] = {}
    while len(got) < len(want) and time.monotonic() < deadline:
        for c in engine.qp.poll():
            if c.req_id in want:
                if c.ids is not None:
                    got[c.req_id] = c.ids
                else:
                    want.pop(c.req_id)
            elif c.status != "shed":
                lat.append(c.latency)
        time.sleep(0.01)
    if not got:
        return float("nan")
    rows = [want[r] for r in got]
    ids = np.stack([got[r] for r in got])
    return recall_at_k(ids[:, :10], dep.true10[rows])


def fail_on_errors(engine: ServeEngine) -> None:
    """Exit non-zero when any request completed "failed".  Only a
    serving-path error completes a request that way (reason ``*_error`` or
    ``crash_drain``), so a run that printed its counts must not also report
    success."""
    if engine.stats.failed:
        raise SystemExit(
            f"[serve] {engine.stats.failed} request(s) failed on a "
            f"serving-path error; newest traceback:\n{engine.last_error}")


def make_obs(args) -> Observability:
    """One telemetry bundle per serve run: metrics are always live (they
    are the bounded-memory latency accounting), tracing turns on iff
    ``--trace-out`` was given, at ``--sample-rate``."""
    return Observability(args.sample_rate, enabled=bool(args.trace_out))


def finish_obs(obs: Observability, args) -> None:
    """End-of-run telemetry flush: metrics summary + Perfetto export."""
    if args.metrics_every > 0:
        for line in obs.metrics.render():
            print(f"[metrics] {line}")
    if args.trace_out:
        doc = obs.trace.export(args.trace_out)
        print(f"[trace] {len(doc['traceEvents'])} events -> "
              f"{args.trace_out} "
              f"(ring-dropped {doc['otherData']['dropped_events']}); "
              f"open in https://ui.perfetto.dev")


def make_quality_stack(args, obs: Observability, vectors=None):
    """Quality-observability bundle for one serve run: the per-query
    recall-proxy monitor (+ shadow audit lane when ``vectors`` is given),
    the structured harvest ring, and the burn-rate SLO tracker with the
    default serving rules.  ``--no-quality`` returns (None, None, None)
    — the A/B baseline the overhead bench measures against."""
    if args.no_quality:
        return None, None, None
    harvest = HarvestRing()
    quality = QualityMonitor(
        obs.metrics, vectors=vectors, shadow_rate=args.shadow_rate,
        harvest=harvest, trace=obs.trace if obs.tracing else None)
    slo = SLOTracker(metrics=obs.metrics,
                     trace=obs.trace if obs.tracing else None)
    # short drills need short windows: scale the multi-window pair to the
    # trace duration (capped at the workbook's 1m/5m defaults)
    fast = min(60.0, max(args.duration / 4.0, 1.0))
    slow = min(300.0, max(args.duration, 4.0))
    default_rules(slo, obs.metrics, quality=quality,
                  fast_s=fast, slow_s=slow)
    return quality, harvest, slo


def emit_health(args, quality, harvest, slo, registry) -> None:
    """Tick the SLO state machine and (when ``--health-out`` is set)
    atomically rewrite the health snapshot JSON an operator polls."""
    if slo is None:
        return
    slo.tick()
    if args.health_out:
        write_health(args.health_out, health_snapshot(
            slo=slo, quality=quality, registry=registry,
            extra={"harvest": {"records": len(harvest),
                               "appended": harvest.appended,
                               "dropped": harvest.dropped}}))


def finish_quality(args, quality, harvest, slo, registry) -> None:
    """End-of-run quality flush: drain the shadow-audit lane, write the
    final health snapshot, persist the harvest shard, print the rollup."""
    if quality is None:
        return
    quality.drain()
    emit_health(args, quality, harvest, slo, registry)
    if args.harvest_out:
        harvest.flush_npz(args.harvest_out)
        print(f"[quality] harvest shard: {len(harvest)} records -> "
              f"{args.harvest_out} (lifetime {harvest.appended}, "
              f"ring-dropped {harvest.dropped})")
    s = quality.summary()
    firing = [n for n, st in slo.snapshot().items()
              if st["state"] == "firing"]
    print(f"[quality] {s['queries']:.0f} queries, proxy p50="
          f"{s['proxy']['p50']:.3f} low_frac={s['low_frac']:.4f}, "
          f"audits done={s['audits_done']:.0f} "
          f"dropped={s['audits_dropped']:.0f}, "
          f"calib p99={s['calibration_err']['p99']:.4f}, "
          f"alerts firing={firing or 'none'}")
    quality.close()


FABRIC_TIER_ERROR = (
    "--tier q8 is not supported in fabric mode (--shards > 0): the fabric "
    "shards f32 postings and has no quantized tier; drop --tier q8 (fabric "
    "serves f32) or use the single-node pipeline (--shards 0)")


def run_fabric(args) -> None:
    """Fabric drill mode (``--shards > 0``): one index served behind the
    sharded, replicated fabric; optional seeded kill mid-trace.

    Rejects an explicit ``--tier q8`` outright: silently overriding the
    operator's tier choice made a drill look like a quantized-serving
    test when it never was (PR 8 follow-up)."""
    if getattr(args, "tier", None) == "q8":
        raise ValueError(FABRIC_TIER_ERROR)
    scfg = SearchConfig(k=10, nprobe_max=16, pruning="llsp", n_ratio=8,
                        use_kernel=not args.no_kernel, fused_topk=True)
    arena = ChunkArena(n_devices=12, device_bytes=1 << 30,
                       chunk_bytes=1 << 20)
    deadline_s = args.deadline_ms * 1e-3 or None
    name = list(PAPER_DATASETS)[0]
    with tempfile.TemporaryDirectory() as root:
        spec = dataclasses.replace(PAPER_DATASETS[name], n=args.n)
        dep = deploy(arena, name, spec, os.path.join(root, name),
                     args.shards, scfg, tier="f32")
        inj = None
        if args.kill_shard_at > 0:
            inj = FaultInjector(seed=0).kill(args.kill_shard_at)
        hot = (np.arange(dep.index.n_clusters) if args.replicas > 1
               else None)
        obs = make_obs(args)
        fab = ShardedFabric(dep.index, dep.llsp, scfg,
                            n_shards=args.shards,
                            n_replicas=args.replicas, hot_clusters=hot,
                            injector=inj, hedge_after_s=0.05, tick_s=0.02,
                            obs=obs)
        fab.warmup()
        fab.start()
        # fabric quality: the coverage proxy rides every BatchResult; the
        # shadow audit lane brute-forces against the reconstructed corpus
        quality, harvest, slo = make_quality_stack(
            args, obs, vectors=_vectors_from_postings(dep.index))
        engine = ServeEngine(
            {name: fab},
            DynamicBatcher(BatchPolicy(max_batch=args.batch,
                                       max_wait_s=0.05), [name]),
            depth=args.depth, obs=obs, quality=quality)
        engine.start()
        trace = multi_tenant_trace(
            [TenantSpec(name, args.rate, topk_lo=10, topk_hi=50,
                        deadline_s=deadline_s, n_queries=256)],
            args.duration)
        print(f"[fabric] {args.shards} shards x R={args.replicas}, "
              f"replaying {len(trace)} arrivals over {args.duration:.0f}s"
              + (f", kill drill at t={args.kill_shard_at:.1f}s"
                 if inj is not None else ""))
        t0 = time.monotonic()
        if inj is not None:
            inj.arm(t0)
        # bounded recent window (heartbeat means only); the full-run
        # percentiles come from the engine's streaming latency histogram
        lat: collections.deque = collections.deque(maxlen=2048)
        next_metrics = args.metrics_every or float("inf")
        next_health = args.health_every or float("inf")
        try:
            for arr in trace:
                lag = t0 + arr.t - time.monotonic()
                if lag > 0:
                    time.sleep(lag)
                engine.submit(dep.queries[arr.qrow], arr.topk, index=name,
                              deadline_s=arr.deadline_s)
                if time.monotonic() - t0 >= next_metrics:
                    next_metrics += args.metrics_every
                    for line in obs.metrics.render():
                        print(f"[metrics] {line}")
                if time.monotonic() - t0 >= next_health:
                    next_health += args.health_every
                    emit_health(args, quality, harvest, slo, obs.metrics)
            r = probe_recall(engine, dep, lat, name)
        finally:
            engine.stop(drain=True)
            fab.stop()
        engine.qp.poll()
        st, fs = engine.stats, fab.stats
        wall = time.monotonic() - t0
        pct = obs.metrics.histogram("engine.latency_s").summary_ms()
        print(f"[fabric] {st.completed} completions in {wall:.1f}s "
              f"({(st.completed - st.shed) / wall:.0f} q/s), "
              f"p50={pct['p50_ms']:.0f}ms p99={pct['p99_ms']:.0f}ms, "
              f"shed={st.shed} partial={st.partial} failed={st.failed}")
        for f in fs.failovers:
            print(f"[fault] shard {f['shard']} failed over: "
                  f"{f['moved']} clusters moved to replicas, "
                  f"{f['lost']} lost")
        if inj is not None:
            print(f"[fault] injector log: "
                  f"{[(round(t, 2), k, s) for t, k, s in inj.log]}, "
                  f"dead_replies={fs.dead_replies} "
                  f"requeued={fs.requeued_tasks} hedges={fs.hedges}")
        print(f"[fabric] busy_s per shard: "
              f"{[round(b, 3) for b in fs.busy_s.tolist()]}, tasks "
              f"{fs.tasks_per_shard.tolist()}")
        print(f"[health] {name}: recall@10={r:.3f} through the engine, "
              f"dropped={st.submitted - st.rejected - st.completed}")
        finish_quality(args, quality, harvest, slo, obs.metrics)
        finish_obs(obs, args)
        undeploy(arena, dep)
        arena.validate()
    fail_on_errors(engine)


FABRIC_RUNBOOK = """\
operator runbook — quantized tier + flash re-rank (single-node default):

  The first pass serves from the int8-residual hot tier (~0.3x the f32
  posting bytes resident in host DRAM); the f32 vectors live in a
  mmap-backed flash file and only the ~2k fused-topk candidates per query
  are read back and exact-rescored at harvest.  Re-ranking walks the
  candidates in rounds and stops once the exact top-k is stable
  (FusionANNS-style adaptive stop); the flash reads run on their own
  submission lane so batch i's re-rank I/O overlaps batch i+1's scan —
  verified from the stage stamps, see rerank_overlap_efficiency.

  --tier q8|f32       first-pass payload (default q8).  f32 restores the
                      all-resident PR 2 pipeline (A/B baseline; also what
                      benchmarks/bench_cost.py prices as the DRAM-heavy
                      row of the $/QPS table)
  --no-rerank         serve raw q8 distances (recall drops <1% on the
                      bench corpora; use to isolate re-rank cost)
  --rerank-round N    candidates exact-scored per re-rank round (64)
  --rerank-stable N   stop after N consecutive rounds leave the exact
                      top-k unchanged (1)

  reading the output:
    [deploy] ... tier=q8, hot X MiB (0.31x f32) + flash Y MiB
        the cost-model split: hot = DRAM-resident bytes, flash = SSD
    [metrics] engine.rerank_rounds / rerank_cands / rerank_io_s
        adaptive-stop behaviour under live traffic; rerank_stop counts
        stable vs exhausted walks
    --trace-out lanes gain a "rerank" span per batch; its overlap with
        the NEXT batch's scan span is the cost-thesis I/O overlap

  rebuilds inherit the tier: --rebuild under --tier q8 quantizes the new
  epoch's shards before the swap (RebuildReport.tier == "q8").

operator runbook — sharded fabric mode (--shards > 0):

  Serve one index behind the sharded, replicated fabric instead of the
  single-node pipeline.  Probed clusters fan out to owner shards by
  power-of-two-choices over live replicas; shard death is detected by
  dead-letter CQ replies or missed heartbeats, failover reroutes probes
  to replicas, stragglers are hedged, and clusters with no live replica
  degrade the touching responses to status="partial" — never a dropped
  query.

  --shards S          number of simulated shards (worker threads)
  --replicas R        copies per cluster: R=2 survives any single shard
                      death with zero loss; R=1 degrades to partial
  --kill-shard-at T   chaos drill: at T seconds a seeded FaultInjector
                      kills one live shard (victim drawn from a seeded
                      generator, so the drill replays exactly); watch
                      the [fault] lines for the failover plan and the
                      final [health] recall probe for parity

  drills:
    # zero-drop kill drill: 8 shards, R=2, shard dies mid-trace
    serve --shards 8 --replicas 2 --kill-shard-at 4 --duration 8
    # same but unreplicated: expect partial responses, not drops
    serve --shards 8 --replicas 1 --kill-shard-at 4 --duration 8

  --rebuild and --fail-shard belong to the single-node mode and are
  rejected when --shards is set (fabric epoch swap is future work).

operator runbook — observability (both modes):

  Metrics are always on: bounded-memory streaming histograms/counters/
  gauges replace the old grow-forever latency lists; --metrics-every N
  prints the full registry every N seconds (per-shard queue depth and
  outstanding gauges, shed/degrade/partial/hedge/requeue counters
  labeled by reason, latency and task-service histograms).

  Tracing turns on when --trace-out is given: every request admitted
  under --sample-rate carries a trace_id from submit through batcher,
  plan, fabric fan-out (per-shard tasks incl. requeues and hedges),
  and merge, and the run exports one Chrome/Perfetto trace_event JSON
  at exit.  Overhead at --sample-rate 1.0 is gated <= 5% q/s by
  benchmarks/bench_serving_pipeline.py.

  capture a failover flamegraph:
    # kill a shard mid-trace and trace every request
    serve --shards 8 --replicas 2 --kill-shard-at 4 --duration 8 \\
          --trace-out /tmp/drill.json --metrics-every 2
    # then open https://ui.perfetto.dev and drag /tmp/drill.json in:
    #   "requests" track  — request lifetimes + done:<status> terminals;
    #                       flow arrows link each request to the shard
    #                       tasks it fanned out to
    #   "shard-N" tracks  — task lifetimes (kind=dispatch/requeue/hedge)
    #                       and worker scan spans; the killed shard's
    #                       tasks reappear on survivors as kind=requeue
    #   "router" track    — failover/hedge/give_up instants, merge spans
    #   "batch-N" lanes   — plan/gather/stream/scan stage spans
    #   "lifecycle" track — rebuild snapshot/build/swap spans, per-shard
    #                       stage-2 stream lifetimes, epoch_swap instant
    #   "slo" track       — alert_fire:<rule> / alert_clear:<rule>
    #                       burn-rate transitions

operator runbook — quality observability (both modes):

  Latency telemetry answers "where did this query spend its time?";
  the quality layer answers "is recall degrading RIGHT NOW, and
  where?".  On by default; --no-quality is the A/B-baseline off switch
  (the overhead bench gates the on/off q/s ratio >= 0.95).

  per-query recall proxy (free, every query):
    single-node q8: overlap between the pre-rerank quantized top-k and
    the post-rerank exact top-k (rerank agreement).  fabric: coverage —
    the fraction of the query's probed clusters a live replica actually
    scanned (< 1.0 exactly on partial rows).  Streamed into
    quality.recall_proxy histograms labeled by route, nprobe bucket,
    degrade status, and (fabric) per shard — a kill drill shows the
    victim shard's histogram dip while survivors hold.

  shadow audit lane (--shadow-rate, default 0.01):
    a deterministic Knuth-hash sample of queries is brute-force
    rescored against the live corpus on a single background lane —
    measured true recall (quality.recall_true) plus per-audit
    |proxy - true| calibration error (quality.calibration_err).
    Submission never blocks serving: the lane is bounded and overflow
    audits are dropped + counted.  Multi-index nodes disable the lane
    (one corpus per auditor); proxies stay on.

  burn-rate SLO alerts (Google SRE multi-window):
    rules deadline/partial/failed/shed/quality fire when the windowed
    bad-event rate burns the error budget at >= 2x on BOTH a fast and
    a slow window, and clear with hysteresis at <= 1x — one transition
    per excursion, no flap storms.  Transitions land on the "slo"
    trace track and in the slo.alerts counter.

  --health-out F      atomically rewrite the health snapshot JSON at F
                      every --health-every seconds (default 1.0): alert
                      states + burn rates, quality rollup, drift
                      summary, harvest depth, full metrics registry —
                      the one document an operator (or the CI drill
                      gate) polls
  --harvest-out F     write the bounded per-query harvest ring (trace
                      id, route, probed clusters, shed/degrade
                      decision, latency, rerank rounds, recall proxy)
                      as a compressed npz shard at exit — the replay
                      substrate for offline policy training

  drills:
    # quality-observed kill drill: watch the victim's proxy dip and
    # the partial burn-rate alert fire, then clear
    serve --shards 8 --replicas 1 --kill-shard-at 4 --duration 8 \\
          --health-out /tmp/health.json --harvest-out /tmp/harvest.npz
    # calibrate the proxy: 10 pct shadow audits, then read
    # quality.calibration_err out of the final health snapshot
    serve --indexes 1 --duration 8 --shadow-rate 0.1 \\
          --health-out /tmp/health.json

operator runbook — concurrency & determinism invariants:

  The serving path is one poller thread crossing several locks; the
  rules that keep it deadlock-free, bounded-memory, and replayable are
  enforced by the static analysis gate and its runtime lock-order
  checker:

    PYTHONPATH=src python -m repro.analysis.lint src tests

  Rule catalog, motivating incidents (including the PR 9
  callback-under-lock deadlock), and the waiver syntax are documented
  in docs/invariants.md.
"""


def main() -> None:
    ap = argparse.ArgumentParser(
        epilog=FABRIC_RUNBOOK,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--indexes", type=int, default=2)
    ap.add_argument("--duration", type=float, default=8.0,
                    help="seconds of traffic")
    ap.add_argument("--rate", type=float, default=30.0,
                    help="total offered qps across tenants")
    ap.add_argument("--batch", type=int, default=32,
                    help="batcher max micro-batch")
    ap.add_argument("--n", type=int, default=10_000)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline (0 = best-effort)")
    ap.add_argument("--depth", type=int, default=2,
                    help="in-flight batch window (1 = PR 2 double buffer)")
    ap.add_argument("--grouping", choices=("locality", "fifo"),
                    default="locality",
                    help="micro-batch formation: probe-overlap grouping "
                         "or arrival order")
    ap.add_argument("--rebuild", action="store_true",
                    help="rebuild + swap index 0 mid-run (freshness flow)")
    ap.add_argument("--fail-shard", type=int, default=-1,
                    help="simulate this shard failing mid-run")
    ap.add_argument("--no-kernel", action="store_true",
                    help="packed-domain jnp oracle instead of the Pallas "
                         "kernel (interpret-mode on CPU)")
    ap.add_argument("--tier", choices=("q8", "f32"), default=None,
                    help="first-pass posting payload: int8-residual hot "
                         "tier + flash f32 re-rank (single-node default) "
                         "or the all-f32-resident baseline (see runbook). "
                         "Fabric mode (--shards > 0) serves f32 and "
                         "REJECTS an explicit q8")
    ap.add_argument("--no-rerank", action="store_true",
                    help="q8 tier only: skip the flash-tier exact re-rank "
                         "and serve raw quantized distances")
    ap.add_argument("--rerank-round", type=int, default=64,
                    help="candidates exact-scored per re-rank round")
    ap.add_argument("--rerank-stable", type=int, default=1,
                    help="stop re-ranking after this many consecutive "
                         "rounds leave the top-k unchanged")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve through the sharded fabric with this many "
                         "shards (0 = single-node pipeline; see runbook "
                         "below)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="fabric mode: replicas per cluster (R>=2 for "
                         "zero-loss failover)")
    ap.add_argument("--kill-shard-at", type=float, default=0.0,
                    help="fabric mode: kill a seeded-random live shard at "
                         "this many seconds into the trace (0 = no drill)")
    ap.add_argument("--trace-out", type=str, default="",
                    help="write a Chrome/Perfetto trace_event JSON here at "
                         "exit (enables tracing; see observability runbook)")
    ap.add_argument("--sample-rate", type=float, default=1.0,
                    help="fraction of requests traced when --trace-out is "
                         "set (deterministic per-id sampling)")
    ap.add_argument("--metrics-every", type=float, default=0.0,
                    help="print the metrics registry every N seconds "
                         "(0 = only the end-of-run summary lines)")
    ap.add_argument("--health-out", type=str, default="",
                    help="atomically (re)write the health snapshot JSON "
                         "here — alert states + burn rates, quality "
                         "rollup, full metrics (see quality runbook)")
    ap.add_argument("--health-every", type=float, default=0.0,
                    help="SLO tick + health snapshot cadence in seconds "
                         "(defaults to 1.0 when --health-out is set)")
    ap.add_argument("--shadow-rate", type=float, default=0.01,
                    help="fraction of queries shadow-audited against the "
                         "live corpus (deterministic per-id sampling; "
                         "0 disables the audit lane)")
    ap.add_argument("--no-quality", action="store_true",
                    help="disable the quality-observability layer "
                         "entirely: no recall proxies, shadow audits, "
                         "burn-rate alerts, or harvest records (the "
                         "overhead A/B baseline)")
    ap.add_argument("--harvest-out", type=str, default="",
                    help="write the per-query harvest ring as a "
                         "compressed npz shard here at exit")
    args = ap.parse_args()
    if args.health_out and args.health_every <= 0:
        args.health_every = 1.0
    enable_compile_cache()

    if args.shards > 0:
        if args.rebuild:
            ap.error("--rebuild needs the single-node pipeline; the fabric "
                     "has no epoch-swap path yet (drop --shards)")
        if args.fail_shard >= 0:
            ap.error("--fail-shard is the single-node heartbeat simulation; "
                     "in fabric mode use --kill-shard-at for a live kill")
        run_fabric(args)
        return

    if args.tier is None:
        args.tier = "q8"               # quantized single-node default
    n_shards = 8
    arena = ChunkArena(n_devices=12, device_bytes=1 << 30, chunk_bytes=1 << 20)
    hb = HeartbeatMonitor(n_shards)
    scfg = SearchConfig(k=10, nprobe_max=16, pruning="llsp", n_ratio=8,
                        use_kernel=not args.no_kernel, fused_topk=True)
    names = list(PAPER_DATASETS)[: args.indexes]
    deadline_s = args.deadline_ms * 1e-3 or None
    rerank = RerankConfig(round_size=args.rerank_round,
                          stable_rounds=args.rerank_stable)
    deps: dict[str, Deployment] = {}
    tiers_seen: list = []          # every deployed tier, incl. swapped-out
    with tempfile.TemporaryDirectory() as root:
        for name in names:
            spec = dataclasses.replace(PAPER_DATASETS[name], n=args.n)
            deps[name] = deploy(arena, name, spec,
                                os.path.join(root, name), n_shards, scfg,
                                tier=args.tier, rerank=rerank,
                                with_rerank=not args.no_rerank)
            tiers_seen.append(deps[name].pipeline.tier)

        policy = BatchPolicy(max_batch=args.batch, max_wait_s=0.05,
                             shed="degrade", degrade_nprobe=8,
                             grouping=args.grouping)
        batcher = DynamicBatcher(policy, names)
        obs = make_obs(args)
        # shadow audits need one ground-truth corpus: with co-resident
        # indexes the proxy/SLO streams stay on but the audit lane is off
        audit_vecs = (_vectors_from_postings(deps[names[0]].index)
                      if len(names) == 1 else None)
        quality, harvest, slo = make_quality_stack(args, obs,
                                                   vectors=audit_vecs)
        engine = ServeEngine({n: d.pipeline for n, d in deps.items()},
                             batcher, depth=args.depth, obs=obs,
                             quality=quality)
        # epoch-tagged versions (lifecycle runtime): every batch routes to
        # the current epoch at formation and carries it to harvest, so the
        # mid-run rebuild below swaps atomically — in-flight batches finish
        # on the old epoch, which retires only after its last harvest
        vm = VersionManager()
        for name in names:
            vm.deploy(name, deps[name].pipeline)
        vm.bind(engine)
        # compile off-clock: the batcher can release any partial size up to
        # max_batch, and the pipeline pads each to its own pad_batch
        # multiple — warm exactly that padded-shape set
        pb = deps[names[0]].pipeline.pad_batch
        top = -(-policy.max_batch // pb) * pb
        warm_sizes = tuple(range(pb, top + 1, pb))
        for d in deps.values():
            d.pipeline.warmup(batch_sizes=warm_sizes)
        engine.start()

        trace = multi_tenant_trace(
            [TenantSpec(n, args.rate / len(names), topk_lo=10, topk_hi=50,
                        deadline_s=deadline_s, n_queries=256)
             for n in names],
            args.duration)
        print(f"[serve] replaying {len(trace)} arrivals over "
              f"{args.duration:.0f}s ({args.rate:.0f} qps offered, "
              f"kernel={'pallas' if scfg.use_kernel else 'oracle'})")
        t0 = time.monotonic()
        next_report = 1.0
        next_metrics = args.metrics_every or float("inf")
        next_health = args.health_every or float("inf")
        n_ticks = 0
        # bounded recent window (heartbeat means only); percentiles come
        # from the engine's streaming latency histogram, not a raw list
        lat: collections.deque = collections.deque(maxlen=64)
        lat_hist = obs.metrics.histogram("engine.latency_s")
        failed: list[int] = []
        did_fail = did_rebuild = False
        for arr in trace:
            lag = t0 + arr.t - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            dep = deps[arr.index]
            engine.submit(dep.queries[arr.qrow], arr.topk, index=arr.index,
                          deadline_s=arr.deadline_s)
            el = time.monotonic() - t0
            if el >= next_report:
                # heartbeat ticks every 1s (the monitor needs a few ticks
                # after a failure to cross its miss threshold); stats print
                # every other tick
                while next_report <= el:
                    next_report += 1.0
                n_ticks += 1
                comps = engine.qp.poll()
                lat += [c.latency for c in comps if c.status != "shed"]
                hb.tick()
                mean_lat = float(np.mean(lat)) if lat else 0.0
                for s in range(n_shards):
                    if s not in failed:
                        hb.beat(s, latency=mean_lat)
                st = engine.stats
                if n_ticks % 2 == 0:
                    print(f"[serve] t={el:4.1f}s completed={st.completed} "
                          f"batches={st.batches} shed={st.shed} "
                          f"degraded={st.degraded} "
                          f"p50={lat_hist.summary_ms()['p50_ms']:.0f}ms")
            if el >= next_metrics:
                next_metrics += args.metrics_every
                for line in obs.metrics.render():
                    print(f"[metrics] {line}")
            if el >= next_health:
                next_health += args.health_every
                emit_health(args, quality, harvest, slo, obs.metrics)
            if (not did_fail and args.fail_shard >= 0
                    and el > args.duration / 2):
                did_fail = True
                dep0 = deps[names[0]]
                owners = set(dep0.replica_map.replicas[:, 0].tolist())
                shard = (args.fail_shard if args.fail_shard in owners
                         else int(dep0.replica_map.replicas[0, 0]))
                failed.append(shard)
                plan = plan_failover(dep0.replica_map, failed)
                print(f"[fault] shard {shard} down: "
                      f"{len(plan.moved)} clusters on replicas, "
                      f"{plan.n_lost} lost pending re-replication; "
                      f"heartbeat reports failed={hb.failed().tolist()}")
            if not did_rebuild and args.rebuild and el > 2 * args.duration / 3:
                did_rebuild = True
                name_r = names[0]
                old = deps[name_r]
                spec = dataclasses.replace(old.spec, seed=old.spec.seed + 1)
                # the rebuild inherits the serving tier: a q8 deployment
                # re-quantizes the fresh epoch's shards before the swap
                fresh = deploy(arena, name_r + "_r1", spec,
                               os.path.join(root, f"{name_r}_r1"),
                               n_shards, scfg, tier=args.tier, rerank=rerank,
                               with_rerank=not args.no_rerank)
                tiers_seen.append(fresh.pipeline.tier)
                fresh.pipeline.warmup(batch_sizes=warm_sizes)
                old_ep, new_ep = vm.swap(name_r, fresh.pipeline)
                # reclaim the old extents ONLY after the old epoch's last
                # in-flight batch harvests — freeing early is exactly the
                # use-after-free the epoch protocol exists to prevent
                retired = old_ep.finalized.wait(timeout=30.0)
                if retired:
                    undeploy(arena, old)
                else:
                    print(f"[swap] WARNING: epoch {old_ep.eid} still has "
                          f"{old_ep.inflight} batch(es) in flight; leaking "
                          f"its extents instead of freeing under a live scan")
                deps[name_r] = fresh
                print(f"[swap] {name_r} epoch {old_ep.eid} -> {new_ep.eid}: "
                      f"{old_ep.record.batches} batches finished on the old "
                      f"epoch, retired={retired} (engine kept serving)")

        for name, dep in deps.items():
            r = probe_recall(engine, dep, lat, name)
            print(f"[health] {name}: recall@10={r:.3f} (through the engine)")
        engine.stop(drain=True)
        engine.qp.poll()
        st = engine.stats
        pct = lat_hist.summary_ms()
        wall = time.monotonic() - t0
        print(f"[done] {st.completed} completions in {wall:.1f}s "
              f"({(st.completed - st.shed) / wall:.0f} q/s), "
              f"p50={pct['p50_ms']:.0f}ms p99={pct['p99_ms']:.0f}ms, "
              f"shed={st.shed} degraded={st.degraded} "
              f"rejected={st.rejected}")
        bs = batcher.stats
        # released tiers keep their stats (release drops only the payload),
        # so a retired epoch's pre-swap gather traffic still counts here
        union_mib = sum(t.stats.union_bytes_streamed
                        for t in tiers_seen if t is not None) / 2**20
        print(f"[batcher] grouping={args.grouping} depth={args.depth}: "
              f"{bs.batches} batches ({bs.locality_batches} locality-"
              f"formed, {bs.aged_seeds} aged seeds), "
              f"max queue wait {bs.max_queue_wait_s * 1e3:.1f}ms "
              f"(bound {policy.max_wait_s * 1e3:.0f}ms), "
              f"gather union {union_mib:.1f} MiB")
        if failed:
            # live shards keep beating through shutdown so the monitor can
            # cross its miss threshold on the silent one
            for _ in range(3):
                hb.tick()
                for s in range(n_shards):
                    if s not in failed:
                        hb.beat(s, latency=1e-3)
            print(f"[health] heartbeat-detected failures at shutdown: "
                  f"{hb.failed().tolist()} (injected: {failed})")
        finish_quality(args, quality, harvest, slo, obs.metrics)
        finish_obs(obs, args)
        for dep in deps.values():
            undeploy(arena, dep)
        arena.validate()
    fail_on_errors(engine)


if __name__ == "__main__":
    main()
