#!/usr/bin/env python3
"""Find a cell's knee: one deployment, a window at each offered rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 80,100,120 [--check-cache]

Run on the chip, from the root of a checkout.  The traffic is the cell's
own mix with ``rate_qps`` replaced by each rate in turn, each window with
its own seed.  Prints one JSON line per rate: the rate offered, the ok
completions per second inside the window, p50/p95/p99 latency from due
time, and the backlog (submitted, not completed) at each quarter of the
window.
The knee is the highest rate whose completions keep pace and whose backlog
does not grow through the window.  The benchmark's runs do not run this.

``--check-cache`` first builds the configuration's index afresh (the build
cache must not hold it), then builds it again resumed from the checkpoints
the first build left, and prints whether the two hash alike
(``index_content_hash``).
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from bench import buildcache, registry, run, traffic  # noqa: E402


def check_cache(cell, served) -> dict:
    from repro.build.pipeline import build_index, index_content_hash
    from repro.launch.serve import build_config

    cfg = cell.config
    key = buildcache.key(cfg, os.path.join(cell.root, "src"))
    work = tempfile.mkdtemp(prefix="bench-check-")
    try:
        restored = buildcache.restore(
            os.path.join(run.CACHE, "build", key), work)
        index, _, rep = build_index(served.x, build_config(cfg["nprobe"]),
                                    work)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    fresh = index_content_hash(served.dep.index)
    return {"restored_files": restored, "resumed": rep.resumed_stages,
            "fresh_hash": fresh, "resumed_hash": index_content_hash(index),
            "same": fresh == index_content_hash(index)}


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--check-cache", action="store_true")
    args = ap.parse_args()
    cell = registry.resolve(run.ROOT, args.workload)
    run.start_jax(cell)
    if args.check_cache:
        key = buildcache.key(cell.config, os.path.join(cell.root, "src"))
        if os.path.isdir(os.path.join(run.CACHE, "build", key)):
            sys.exit("sweep: --check-cache needs a cache without this "
                     "configuration's build")
    served = run.deploy_cell(cell, args.seed)
    try:
        if args.check_cache:
            print(json.dumps({"cache_check": check_cache(cell, served)}), flush=True)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(cell.traffic,
                       params=dict(cell.traffic["params"], rate_qps=rate))
            sched = traffic.schedule(mix, args.seed + 1 + i, args.seconds,
                                     len(served.pool))
            t = time.perf_counter()
            win = run.run_window(served, sched, args.seconds)
            lat = run.latency_ms(win)
            print(json.dumps({
                "rate": rate, "offered": len(sched),
                "ok_per_s": run.ok_in_window(win) / args.seconds,
                "not_ok": int(sum(1 for c in win.comps.values()
                                  if c.status != "ok")
                              + len(sched) - len(win.comps)),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "backlog": win.backlog, "compiles": win.compiles,
                "late_max_ms": float(win.late.max() * 1e3),
                "gc_max_ms": max([p * 1e3 for _, p in win.gc_pauses],
                                 default=0.0),
                "drain_s": time.perf_counter() - t - args.seconds,
            }), flush=True)
        served.engine.stop(drain=True)
        from repro.launch.serve import undeploy
        undeploy(served.arena, served.dep)
    finally:
        shutil.rmtree(served.workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
