#!/usr/bin/env python3
"""Chip smoke test: drive the single-node serving path once on a TPU.

    python3 chip_smoke.py              # one chip: phases (a)-(e)
    python3 chip_smoke.py --chips 4    # four chips: the sharded phase only

One process holds the chip for the whole run and starts no other.

(a) Device check: exits non-zero unless JAX's first device is a TPU.  There
    is no CPU fallback.
(b) Build: a SIFT-shaped corpus (``PAPER_DATASETS["sift"]`` at its
    published 128-d, posting lists of L=128) made from ``--seed``, deployed
    through ``launch.serve.deploy`` -> ``build_index`` once per tier; the
    second deploy resumes the first one's stage-1/2 checkpoints.
(c) Serve: the q8 tier with the flash f32 re-rank, then the f32 tier, each
    through ``ServeEngine`` -> ``PrefetchPipeline`` with the Pallas kernels.
    ``--requests`` queries with per-request k in [10, 50]; every completion
    must be "ok" and recall@10 against exact brute force at least 0.95.
(d) Kernel check: the scan program that served, lowered at the served
    shapes, must hold a Mosaic kernel (``tpu_custom_call``).
(e) Last line: ``{"ok": true, "device": {...}}`` and nothing else.

``--chips 4`` builds one index, stripes its postings over a
(data=1, model=4) mesh, and checks ``make_sharded_serve`` against
``serve_step`` on one chip over the same index.

The q/s printed here is a smoke figure from a short closed loop, not a
benchmark.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

RECALL_FLOOR = 0.95      # recall@10 against brute force, both tiers
REQUESTS = 512           # per tier, each with its own k in [10, 50]
BATCH = 32               # batcher max micro-batch (launch/serve.py default)
SERVE_TIMEOUT_S = 300.0  # for all of one tier's completions
SIFT1M = 1_000_000       # ann-benchmarks' SIFT size
# (n, nprobe) per run.  One chip: SIFT1M cut so that the build takes a few
# minutes on the chip host; 128 probes hold recall@10 >= 0.95 there.  Four
# chips: half that corpus, since the phase checks agreement, not scale;
# 64 probes hold the floor at 100k.  PERF.md section 4 has the measurements.
ONE_CHIP = (200_000, 128)
FOUR_CHIPS = (100_000, 64)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def device_check(chips: int):
    """Phase (a): the devices, or exit non-zero when they are not TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found: jax.devices()[0].platform is "
                 f"{devs[0].platform!r}; this script runs only on the chip")
    if len(devs) < chips:
        fail(f"--chips {chips} needs {chips} TPU devices, found {len(devs)}")
    log(f"device: {devs[0].device_kind} x{len(devs)}")
    return devs


def sift_spec(n: int, seed: int):
    from repro.data import PAPER_DATASETS

    spec = dataclasses.replace(PAPER_DATASETS["sift"], n=n, seed=seed)
    if spec.dim != 128:
        fail(f"SIFT spec is {spec.dim}-d, expected its published 128-d")
    return spec


def search_config(nprobe: int):
    from repro.core.search import SearchConfig

    return SearchConfig(k=10, nprobe_max=nprobe, pruning="none",
                        use_kernel=True, fused_topk=True)


def serve_tier(dep, seed: int) -> dict:
    """Phase (c) for one deployed tier: warm up, serve, check."""
    import numpy as np

    from repro.core.distance import recall_at_k
    from repro.runtime import BatchPolicy, DynamicBatcher, ServeEngine

    pipe = dep.pipeline
    pb = pipe.pad_batch
    top = -(-BATCH // pb) * pb
    t = time.perf_counter()
    n_prog = pipe.warmup(batch_sizes=tuple(range(pb, top + 1, pb)))
    warm_s = time.perf_counter() - t
    log(f"{dep.name}: warmup compiled {n_prog} programs in {warm_s:.1f} s "
        f"(compile seconds)")

    policy = BatchPolicy(max_batch=BATCH, max_wait_s=0.02, pad=pb)
    engine = ServeEngine({dep.name: pipe},
                         DynamicBatcher(policy, [dep.name]), depth=2)
    rng = np.random.default_rng(seed)
    rows = np.arange(REQUESTS) % len(dep.queries)
    ks = rng.integers(10, 51, size=REQUESTS)
    want: dict[int, int] = {}
    got: dict = {}
    engine.start()
    try:
        t0 = time.perf_counter()
        for r, k in zip(rows, ks):
            rid = engine.submit(dep.queries[r], int(k), index=dep.name,
                                block=True)
            if rid < 0:
                fail(f"{dep.name}: the submission queue refused a request")
            want[rid] = int(r)
        deadline = time.monotonic() + SERVE_TIMEOUT_S
        while len(got) < len(want):
            if time.monotonic() > deadline:
                fail(f"{dep.name}: {len(want) - len(got)} of {len(want)} "
                     f"requests not completed in {SERVE_TIMEOUT_S} s")
            engine.qp.wait_completions(1, timeout=1.0)
            for c in engine.qp.poll():
                got[c.req_id] = c
        wall = time.perf_counter() - t0
    finally:
        engine.stop(drain=True)
    bad = collections.Counter(
        f"{c.status}:{c.reason}" for c in got.values() if c.status != "ok")
    if bad:
        fail(f"{dep.name}: completions not ok: {dict(bad)}\n"
             f"{engine.last_error}")
    order = list(want)
    ids = np.stack([got[rid].ids[:10] for rid in order])
    recall = recall_at_k(ids, dep.true10[[want[rid] for rid in order]])
    log(f"{dep.name}: {len(got)} completions, all ok, "
        f"{engine.stats.batches} batches, recall@10={recall:.4f}, "
        f"smoke q/s={len(got) / wall:.1f} (closed loop, not a benchmark)")
    if recall < RECALL_FLOOR:
        fail(f"{dep.name}: recall@10 {recall:.4f} < {RECALL_FLOOR}")
    return {"recall": recall, "warm_s": warm_s}


def kernel_check(dep) -> None:
    """Phase (d): the served scan program holds a Mosaic kernel."""
    pipe = dep.pipeline
    rows = pipe.tier.stats.events[-1].rows        # last served batch
    bp = -(-BATCH // pipe.pad_batch) * pipe.pad_batch
    if "tpu_custom_call" not in pipe.lower_scan(bp, rows).as_text():
        fail(f"{dep.name}: the served scan at (batch {bp}, rows {rows}) has "
             f"no tpu_custom_call: it is not the Mosaic kernel")
    log(f"{dep.name}: served scan at (batch {bp}, rows {rows}) lowers to "
        f"tpu_custom_call")


def single_chip(seed: int) -> None:
    """Phases (b)-(d) on one chip."""
    from repro.kernels import ops
    from repro.launch.serve import deploy, undeploy
    from repro.runtime import RerankConfig
    from repro.storage import ChunkArena

    n, nprobe = ONE_CHIP
    spec = sift_spec(n, seed)
    log(f"scale cut: n={n:,} of SIFT1M's {SIFT1M:,}; see PERF.md section 4")
    scfg = search_config(nprobe)
    arena = ChunkArena(n_devices=12, device_bytes=1 << 30,
                       chunk_bytes=1 << 20)
    with tempfile.TemporaryDirectory() as work:
        deps = []
        for tier in ("q8", "f32"):
            t = time.perf_counter()
            dep = deploy(arena, f"sift_{tier}", spec, work, 8, scfg,
                         tier=tier, rerank=RerankConfig())
            took = time.perf_counter() - t
            st = dep.pipeline.tier
            host = (st.nbytes() if dep.pipeline.quantized
                    else st.postings.nbytes + st.posting_ids.nbytes)
            rep = dep.report
            log(f"build {dep.name}: n={spec.n} d={spec.dim} "
                f"L={dep.index.cluster_len} clusters={rep.n_clusters} "
                f"replication={rep.replication:.3f} "
                f"host tier {host / 2**20:.1f} MiB, deploy {took:.1f} s, "
                f"stages {({k: round(v, 1) for k, v in rep.stage_seconds.items()})}"
                f" resumed={rep.resumed_stages}")
            deps.append(dep)
        results = {}
        for dep in deps:
            results[dep.name] = serve_tier(dep, seed)
            kernel_check(dep)
        for dep in deps:
            undeploy(arena, dep)
    fallbacks = dict(ops.FALLBACKS)
    log(f"kernel-to-oracle fallbacks (VMEM budget): {fallbacks or 'none'}")
    log(f"summary: n={spec.n} nprobe={nprobe} " + ", ".join(
        f"{k} recall@10={v['recall']:.4f} warmup {v['warm_s']:.1f} s"
        for k, v in results.items()))


def sharded(seed: int, devs) -> None:
    """The four-chip phase: make_sharded_serve vs serve_step on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.build.pipeline import build_index
    from repro.core.ivf import IVFIndex
    from repro.core.search import make_sharded_serve, serve_step
    from repro.data import make_queries, make_vectors
    from repro.launch.serve import build_config

    n_shards = 4
    n, nprobe = FOUR_CHIPS
    spec = sift_spec(n, seed)
    x = make_vectors(spec)
    q, topk = make_queries(spec, 256)
    topk = np.minimum(topk, 50).astype(np.int32)
    t = time.perf_counter()
    with tempfile.TemporaryDirectory() as work:
        index, _, rep = build_index(x, build_config(nprobe), work,
                                    queries=q, query_topk=topk)
    log(f"build: n={spec.n} d={spec.dim} L={index.cluster_len} "
        f"clusters={rep.n_clusters} in {time.perf_counter() - t:.1f} s")
    # pad the cluster dim to the model axis with dead clusters
    c = index.n_clusters
    pad = -c % n_shards
    cents = np.concatenate([np.asarray(index.centroids),
                            np.full((pad, spec.dim), 1e6, np.float32)])
    post = np.concatenate([np.asarray(index.postings), np.zeros(
        (pad, index.cluster_len, spec.dim), np.float32)])
    pids = np.concatenate([np.asarray(index.posting_ids), np.full(
        (pad, index.cluster_len), -1, np.int32)])
    scfg = search_config(nprobe)
    mesh = Mesh(np.asarray(devs[:n_shards]).reshape(1, n_shards),
                ("data", "model"))
    striped = NamedSharding(mesh, P("model"))
    post_d = jax.device_put(post, striped)
    pids_d = jax.device_put(pids, striped)
    cents_d = jax.device_put(cents, NamedSharding(mesh, P()))
    homes = {s.device for s in post_d.addressable_shards}
    rows = {s.data.shape[0] for s in post_d.addressable_shards}
    if post_d.sharding.device_set != set(devs[:n_shards]) or \
            len(homes) != n_shards or rows != {(c + pad) // n_shards}:
        fail(f"postings not striped over {n_shards} devices: "
             f"{len(homes)} homes, shard rows {rows}")
    log(f"postings striped: {len(homes)} devices x "
        f"{(c + pad) // n_shards} clusters")
    qd, tkd = jnp.asarray(q), jnp.asarray(topk)
    t = time.perf_counter()
    d_sh, i_sh, _ = jax.jit(make_sharded_serve(mesh, scfg))(
        cents_d, post_d, pids_d, None, qd, tkd)
    d_sh, i_sh = np.asarray(d_sh), np.asarray(i_sh)
    log(f"sharded serve (compile + run) {time.perf_counter() - t:.1f} s")
    one = IVFIndex(jnp.asarray(cents), jnp.asarray(post), jnp.asarray(pids))
    out = jax.jit(serve_step, static_argnames=("cfg",))(
        one, None, qd, tkd, cfg=scfg)
    d_one, i_one = np.asarray(out["dists"]), np.asarray(out["ids"])
    if "tpu_custom_call" not in jax.jit(make_sharded_serve(mesh, scfg)).lower(
            cents_d, post_d, pids_d, None, qd, tkd).as_text():
        fail("the sharded serve holds no tpu_custom_call")
    # the same tolerance as tests/test_multidevice.py
    np.testing.assert_allclose(d_sh, d_one, rtol=1e-4, atol=1e-4)
    worst = max(len(set(a.tolist()) ^ set(b.tolist()))
                for a, b in zip(i_sh, i_one))
    if worst > 2:
        fail(f"sharded ids differ from one chip by up to {worst} per query")
    log(f"sharded == one chip over {len(q)} queries: dists within 1e-4, "
        f"id sets differ by <= {worst}; the sharded scan is tpu_custom_call")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=1,
                    help="corpus and traffic seed")
    args = ap.parse_args()

    devs = device_check(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        sharded(args.seed, devs)
    else:
        single_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
