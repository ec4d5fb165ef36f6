#!/usr/bin/env python3
"""Benchmark of the serving path: one cell, one seed, one measured window.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  The cell's parts
are found by name (``bench/registry.py``).  One run:

1. Looks for the chip: exits non-zero, printing no result, unless JAX's
   devices are TPUs and there are as many as the cell asks for.
2. Set-up (``setup_s``, from process start to the first due request): the
   configuration's corpus (from its ``corpus_seed``) and a query pool from
   ``--seed``; the index deployed through the program's
   ``launch.serve.deploy``, resuming build stages 1-2 from
   ``bench/buildcache.py`` (only a checkout's first run of a configuration
   builds them); the scan shapes warmed; the served scan checked to be the
   Mosaic kernel; a short burst of requests through the engine.
3. The window: requests offered open loop at the traffic file's times
   through ``ServeEngine.submit`` with no deadline, for ``--seconds``.
   Latency runs from the time a request was due to its completion.  With
   ``--trace 1`` the window is traced by the JAX profiler.
4. After the window: every request due in it waited for (up to a minute
   past the close), the device's peak memory read, the program's state
   freed, then the answers compared with the exact reference
   (``bench/reference.py``).
5. Prints the numbers compared with their limits as the last lines of
   standard error, and one JSON result as the last line of standard
   output: the end-to-end metrics with ``--trace 0``, the per-layer metrics
   with ``--trace 1``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import (  # noqa: E402
    buildcache, corpus, reference, registry, roofline, trace_reduce,
    traffic,
)

CACHE = os.path.join(ROOT, "bench", "cache")
WAIT_AFTER_CLOSE_S = 60.0        # the longest a due answer is waited for
WARM_REQUESTS = 64               # burst through the engine during set-up
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def look_for_chip(chips: int):
    """The devices, or exit non-zero with no result when they are not TPUs
    or fewer than the cell asks for.  There is no CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU found: jax.devices()[0].platform is "
                 f"{devs[0].platform!r}; the benchmark runs only on the chip")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU devices, found "
                 f"{len(devs)}")
    return devs[:chips]


def log_memory(where: str) -> None:
    """Device memory on the fullest chip: in use now, and the peak so far."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    use = max(int(s.get("bytes_in_use", 0)) for s in stats)
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    log(f"device memory {where}: in use {use} B, peak {peak} B")


def disk_writes() -> int | None:
    """Bytes this process has caused to be written to storage (Linux)."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        return None
    return None


def check_mosaic(pipe, batch: int) -> None:
    """The served scan program must hold a Mosaic kernel."""
    text = pipe.lower_scan(batch, pipe.row_bucket).as_text()
    if "tpu_custom_call" not in text:
        raise SystemExit("bench: the served scan has no tpu_custom_call: it "
                         "is not the Mosaic kernel")


class Recorded:
    """The deployed pipeline, with the stage stamps (``StageTimes``) of
    every batch it harvests kept for the per-layer metrics.  Everything
    else is the pipeline's own."""

    def __init__(self, pipe):
        self._pipe = pipe
        self.times: list = []

    def __getattr__(self, name):
        return getattr(self._pipe, name)

    def harvest(self, infl):
        result = self._pipe.harvest(infl)
        self.times.append(result.times)
        return result


@dataclasses.dataclass
class Served:
    """What set-up leaves for the window."""
    name: str
    engine: object
    pipe: Recorded
    arena: object
    dep: object
    workdir: str
    x: np.ndarray                # the corpus, for the reference
    pool: np.ndarray             # the query pool, in the mix's order


def deploy_cell(cell, seed: int) -> Served:
    """Set-up of one run, up to an idle engine whose shapes are warm."""
    from repro.core.search import SearchConfig
    from repro.data import PAPER_DATASETS
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import deploy
    from repro.runtime import (BatchPolicy, DynamicBatcher, RerankConfig,
                               ServeEngine)
    from repro.storage import ChunkArena
    import jax

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg = cell.config
    spec = dataclasses.replace(
        PAPER_DATASETS[cfg["dataset"]], n=cfg["n"], dim=cfg["dim"],
        n_modes=cfg["n_modes"], spread=cfg["spread"],
        seed=cfg["corpus_seed"])
    x = corpus.make_vectors(cfg["n"], cfg["dim"], cfg["n_modes"],
                            cfg["spread"], cfg["corpus_seed"])
    pool, modes = corpus.make_pool(cfg["pool"], cfg["dim"], cfg["n_modes"],
                                   cfg["spread"], cfg["corpus_seed"], seed,
                                   cfg["query_temp"])
    pool = corpus.order_pool(pool, modes, cell.traffic["pool_order"])
    scfg = SearchConfig(k=cfg["k"], nprobe_max=cfg["nprobe"],
                        pruning=cfg["pruning"], use_kernel=cfg["kernel"],
                        fused_topk=True)
    arena = ChunkArena(n_devices=12, device_bytes=1 << 30,
                       chunk_bytes=1 << 20)
    workdir = tempfile.mkdtemp(prefix="bench-")
    key = buildcache.key(cfg, os.path.join(cell.root, "src"))
    kept = os.path.join(CACHE, "build", key)
    restored = buildcache.restore(kept, workdir)
    t = time.perf_counter()
    dep = deploy(arena, cfg["name"], spec, workdir, 8, scfg,
                 tier=cfg["tier"],
                 rerank=RerankConfig() if cfg["rerank"] else None,
                 with_rerank=cfg["rerank"])
    deploy_s = time.perf_counter() - t
    buildcache.save(workdir, kept)
    log_memory("after deploy")
    rep = dep.report
    pipe = Recorded(dep.pipeline)
    pb = pipe.pad_batch
    batch = cfg["max_batch"]
    top = -(-batch // pb) * pb
    t = time.perf_counter()
    n_prog = pipe.warmup(batch_sizes=tuple(range(pb, top + 1, pb)))
    warm_s = time.perf_counter() - t
    check_mosaic(pipe, top)
    policy = BatchPolicy(max_batch=batch, max_wait_s=cfg["max_wait_s"],
                         pad=pb)
    engine = ServeEngine({cfg["name"]: pipe},
                         DynamicBatcher(policy, [cfg["name"]]),
                         clock=time.perf_counter, depth=cfg["depth"])
    t = time.perf_counter()
    n_rows = warm_row_buckets(pipe, pool, cfg["k"], top)
    engine.start()
    rids = [engine.submit(pool[i % len(pool)], cfg["k"], index=cfg["name"],
                          block=True) for i in range(WARM_REQUESTS)]
    got = _collect(engine, set(rids), time.perf_counter() + 120.0)
    burst_s = time.perf_counter() - t
    if len(got) < len(rids) or any(c.status != "ok" for c in got.values()):
        raise SystemExit(f"bench: the warm-up burst did not complete ok: "
                         f"{engine.last_error}")
    pipe.times.clear()
    log_memory("after warm-up")
    stages = {k: round(v, 3) for k, v in rep.stage_seconds.items()}
    log(f"deploy {deploy_s:.1f} s (stages {stages}, resumed "
        f"{rep.resumed_stages}, {restored} checkpoint files restored), "
        f"{rep.n_clusters} clusters, warmup {n_prog} programs {warm_s:.1f} s,"
        f" served warm-up over {n_rows} row buckets and a burst {burst_s:.1f} s")
    return Served(cfg["name"], engine, pipe, arena, dep, workdir, x, pool)


def warm_row_buckets(pipe, pool: np.ndarray, k: int, top: int) -> int:
    """Serve one batch in every (padded batch, streamed row bucket) shape
    the window can meet, through the pipeline's own stages, so that every
    scan program it calls is compiled in set-up.  ``PrefetchPipeline.warmup``
    compiles the scan with its static configuration passed by keyword,
    which JAX caches apart from the dispatch's positional call.  Each batch
    is planned from a probe plan given as ``routed`` whose union of
    distinct clusters falls inside the bucket.  Returns the shapes served."""
    c = pipe.index.n_clusters
    p = min(pipe.cfg.nprobe_max, c)
    rb = pipe.row_bucket
    shapes = set()
    for bp in range(pipe.pad_batch, top + 1, pipe.pad_batch):
        most = min(c, bp * p)          # the largest union bp queries reach
        for edge in range(rb, most + 1 + rb, rb):
            union = min(max(edge - rb // 2, bp), most)
            cap = -(-union // bp)
            cids = (np.arange(bp)[:, None] * cap + np.arange(p)[None, :]) % c
            plan = pipe.plan(pool[:bp], k, routed=(
                cids.astype(np.int32), np.full(bp, cap, np.int32)))
            pipe.harvest(pipe.dispatch(pipe.prefetch(plan)))
            shapes.add((bp, pipe.times[-1].rows))
    return len(shapes)


def _collect(engine, want: set, deadline: float) -> dict:
    """Completions of ``want`` until all are in or ``deadline`` passes."""
    got: dict = {}
    while len(got) < len(want) and time.perf_counter() < deadline:
        engine.qp.wait_completions(1, timeout=0.05)
        for c in engine.qp.poll():
            if c.req_id in want:
                got[c.req_id] = c
    return got


@dataclasses.dataclass
class Window:
    t0: float
    seconds: float
    due: np.ndarray              # (n,) perf_counter time each request was due
    rids: np.ndarray             # (n,) request id, -1 = refused at submit
    late: np.ndarray             # (n,) submit time - due
    comps: dict                  # req_id -> Completion
    gave_up: float               # when the wait for answers ended
    backlog: list                # submitted, not completed, at each
                                 # quarter of the window
    compiles: int                # backend compiles inside the window
    gc_pauses: list = dataclasses.field(default_factory=list)
                                 # (generation, seconds) of the window's
                                 # garbage collections
    trace_mark: float = 0.0      # perf_counter time of the trace's mark
    trace: object = None         # trace_reduce.Summary of the window


def run_window(served: Served, sched: traffic.Schedule, seconds: float,
               trace_dir: str | None = None) -> Window:
    """Offer ``sched`` open loop; wait for every answer due in it."""
    import jax

    engine = served.engine
    compiles = [0]
    counting = [False]

    def on_event(event, duration, **_):
        if counting[0] and event == COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    pauses: list = []
    gc_start = [0.0]

    def on_gc(phase, info):
        if not counting[0]:
            return
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            pauses.append((info["generation"],
                           time.perf_counter() - gc_start[0]))

    gc.callbacks.append(on_gc)
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    n = len(sched)
    due = np.empty(n)
    rids = np.full(n, -1, np.int64)
    late = np.empty(n)
    base = engine.stats.completed
    counting[0] = True
    t0 = time.perf_counter() + 0.01
    mark = t0
    if trace_dir is not None:
        with jax.profiler.TraceAnnotation("bench.window"):
            mark = time.perf_counter()
    backlog = []
    quarter = 1
    for i in range(n):
        due[i] = t0 + sched.t[i]
        while quarter < 4 and sched.t[i] >= quarter * seconds / 4:
            backlog.append(int((rids[:i] >= 0).sum())
                           - (engine.stats.completed - base))
            quarter += 1
        dt = due[i] - time.perf_counter()
        if dt > 0:
            time.sleep(dt)
        rids[i] = engine.submit(served.pool[sched.qrow[i]], sched.k,
                                index=served.name)
        late[i] = time.perf_counter() - due[i]
    close = t0 + seconds
    dt = close - time.perf_counter()
    if dt > 0:
        time.sleep(dt)
    counting[0] = False
    gc.callbacks.remove(on_gc)
    backlog += [int((rids >= 0).sum()) - (engine.stats.completed - base)]
    summary = None
    if trace_dir is not None:
        jax.profiler.stop_trace()
    want = set(int(r) for r in rids if r >= 0)
    comps = _collect(engine, want, close + WAIT_AFTER_CLOSE_S)
    gave_up = time.perf_counter()
    if trace_dir is not None:
        path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        tr = trace_reduce.load(path)
        lo = tr.marks.get("bench.window", 0.0)
        summary = trace_reduce.summarize(tr, lo, lo + seconds * 1e9)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return Window(t0, seconds, due, rids, late, comps, gave_up, backlog,
                  compiles[0], pauses, mark, summary)


def latency_ms(win: Window) -> np.ndarray:
    """From due time to completion, for every request due in the window; a
    request not completed ok counts as waited for until the wait ended."""
    out = np.empty(len(win.due))
    for i, (d, r) in enumerate(zip(win.due, win.rids)):
        c = win.comps.get(int(r))
        done = c.completed if c is not None and c.status == "ok" \
            else win.gave_up
        out[i] = (done - d) * 1e3
    return out


def ok_in_window(win: Window) -> int:
    close = win.t0 + win.seconds
    return sum(1 for c in win.comps.values()
               if c.status == "ok" and win.t0 <= c.completed <= close)


@dataclasses.dataclass
class RunData:
    """What a per-layer metric's reader reads."""
    cell: object                 # registry.Cell
    batches: list                # StageTimes of the window's batches
    trace: object                # trace_reduce.Summary, or None
    peak: dict                   # the chip's peaks (bench/peaks.json)

    def scan_work(self, t) -> roofline.ScanWork:
        cfg = self.cell.config
        pb = -(-t.size // 16) * 16
        return roofline.scan_work(
            cfg["tier"], probes=t.clusters_requested,
            union_clusters=t.union_clusters, batch_pad=pb,
            cluster_len=cfg["cluster_len"], dim=cfg["dim"],
            n_cand=cfg["n_cand"])


def gap_labeler(win: Window, times: list):
    """Names an idle gap of the device by the batch stages whose stamps
    cover its middle.  The trace's clock is tied to the host's by the
    ``bench.window`` mark, which starts at ``win.trace_mark``."""
    from repro.runtime.pipeline import stage_spans

    spans = [s for t in times for s in stage_spans(t)]

    def label(start_ns: float, length_ns: float) -> str:
        mid = win.trace_mark + (start_ns + length_ns / 2) * 1e-9
        names = sorted({n for n, a, b in spans if a <= mid <= b})
        return "+".join(names) if names else "no batch stage"

    return label


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    cell = registry.resolve(ROOT, args.workload)
    devs = start_jax(cell)
    peak = roofline.peaks(devs[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      peak)
    print(json.dumps(result), flush=True)
    return 0


def start_jax(cell):
    """The compile cache at its fixed path in the checkout, and the chip."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    devs = look_for_chip(int(cell.workload["chips"]))
    src = os.path.join(cell.root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return devs


@dataclasses.dataclass
class Measured:
    """One run's window and answers, after the program's state is freed."""
    win: Window
    times: list                  # StageTimes of the window's batches
    setup_s: float
    memory_peak_bytes: int
    label: object                # idle-gap labeler (traced runs)
    x: np.ndarray                # the corpus
    q: np.ndarray                # (m, D) queries answered ok
    ids: np.ndarray              # (m, k) their served ids
    dists: np.ndarray            # (m, k) and distances
    served: list                 # (m,) (pool row, Completion) of each
    missing: int                 # due, never completed
    failed: int                  # due, not completed ok

    def numbers(self, true_ids: np.ndarray) -> dict:
        return reference.compare(self.x, self.q, self.ids, self.dists,
                                 true_ids, self.missing)


def measure(cell, seed: int, seconds: float, trace: bool, devs) -> Measured:
    """Set-up, the window, and the answers due in it."""
    served = deploy_cell(cell, seed)
    try:
        sched = traffic.schedule(cell.traffic, seed, seconds, len(served.pool))
        tdir = os.path.join(served.workdir, "trace") if trace else None
        setup_s = time.perf_counter() + 0.01 - T_START
        win = run_window(served, sched, seconds, tdir)
        log_memory("after the window")
        served.engine.stop(drain=True)
        times = [t for t in served.pipe.times
                 if win.t0 <= t.scan_dispatch < win.t0 + seconds]
        mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devs)
        label = gap_labeler(win, served.pipe.times) if trace else None
        rows = served.pool[sched.qrow]
        from repro.launch.serve import undeploy
        undeploy(served.arena, served.dep)
    finally:
        shutil.rmtree(served.workdir, ignore_errors=True)
    x = served.x
    served.dep = served.pipe = served.engine = None
    gc.collect()
    k = cell.config["k"]
    comps = [win.comps.get(int(r)) for r in win.rids]
    ok = [i for i, c in enumerate(comps) if c is not None and c.status == "ok"]
    missing = sum(1 for r, c in zip(win.rids, comps) if r >= 0 and c is None)
    ids = np.stack([comps[i].ids[:k] for i in ok]) if ok \
        else np.zeros((0, k), np.int64)
    dists = np.stack([comps[i].dists[:k] for i in ok]) if ok \
        else np.zeros((0, k), np.float32)
    served_rows = [(int(sched.qrow[i]), comps[i]) for i in ok]
    return Measured(win, times, setup_s, mem, label, x, rows[ok], ids, dists,
                    served_rows, missing, len(win.rids) - len(ok))


def log_bad_answers(m: Measured, win: Window, most: int = 12) -> None:
    """Each bad answer (``reference.answer_faults``), up to ``most``: its
    faults, pool row, completion and ids and distances as served."""
    faults = reference.answer_faults(m.ids, m.dists, len(m.x))
    bad = np.flatnonzero(np.any(list(faults.values()), axis=0)) \
        if len(m.ids) else []
    for j in bad[:most]:
        row, c = m.served[j]
        why = [name for name, f in faults.items() if f[j]]
        log(f"bad answer: {why}, pool row {row}, request {c.req_id}, "
            f"nprobe {c.nprobe}, quality {c.quality:.3f}, done "
            f"{c.completed - win.t0:.4f} s into the window, ids "
            f"{m.ids[j].tolist()}, dists {m.dists[j].tolist()}")


def run_cell(cell, seed: int, seconds: float, trace: bool, devs,
             peak: dict) -> dict:
    from repro.kernels import ops

    m = measure(cell, seed, seconds, trace, devs)
    win = m.win
    t = time.perf_counter()
    k = cell.config["k"]
    true_ids = reference.exact_topk(m.x, m.q, k)[1] if len(m.q) else m.ids
    numbers = m.numbers(true_ids)
    ref_s = time.perf_counter() - t
    log_bad_answers(m, win)
    log(f"short answers (a no-neighbour mark at the tail, counted as "
        f"misses): {reference.short_answers(m.ids, m.dists)}")
    correct, checks = reference.judge(numbers, cell.config["correct"])
    lat = latency_ms(win)
    log(f"window {seconds} s: {len(win.rids)} due, {len(m.q)} ok, "
        f"{m.failed} not ok (missing {m.missing}), backlog at quarters "
        f"{win.backlog}, compiles in window {win.compiles}, generator late "
        f"p50 {np.median(win.late) * 1e3:.3f} ms max "
        f"{win.late.max() * 1e3:.3f} ms, gen-2 collections "
        f"{[round(p * 1e3, 1) for g, p in win.gc_pauses if g == 2]} ms, "
        f"batches {len(m.times)}, "
        f"kernel fallbacks {dict(ops.FALLBACKS) or 'none'}, "
        f"reference {ref_s:.1f} s, latency p95 "
        f"{np.percentile(lat, 95):.1f} ms p99 {np.percentile(lat, 99):.1f} ms")
    if trace:
        metrics = {}
        data = RunData(cell, m.times, win.trace, peak)
        for metric in cell.per_layer:
            v = registry.metric_reader(cell.root, metric["name"])(data)
            if v is not None:
                metrics[metric["name"]] = {"value": float(v),
                                           "unit": metric["unit"]}
    else:
        e2e = {
            "qps": ok_in_window(win) / seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "recall_at_10": 1.0 - numbers["recall_miss"],
            "setup_s": m.setup_s,
        }
        metrics = {e["name"]: {"value": e2e[e["name"]], "unit": e["unit"]}
                   for e in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": m.memory_peak_bytes}
    out = {"correct": bool(correct), "attempted": len(win.rids),
           "failed": m.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = win.trace.busy_s
        device["window_s"] = win.trace.window_s
        out["breakdown"] = trace_reduce.breakdown(win.trace, m.label)
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    log(f"disk writes of this run: {disk_writes()} B")
    for name, v, lim in checks:
        log(f"check {name} = {v!r} limit {lim!r}")
    return out


if __name__ == "__main__":
    sys.exit(main())
