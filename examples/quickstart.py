"""Quickstart: build a Helmsman index and search it, in ~30 lines of API.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.build.pipeline import BuildConfig, build_index
from repro.core.distance import recall_at_k
from repro.core.ivf import brute_force_topk
from repro.core.llsp import LLSPConfig
from repro.core.search import SearchConfig, serve_step
from repro.data import PAPER_DATASETS, make_queries, make_vectors
import dataclasses
import tempfile

# 1. a clustered corpus + production-like queries (per-query top-k)
spec = dataclasses.replace(PAPER_DATASETS["sift"], n=20_000)   # 128-d
x = make_vectors(spec)
queries, topk = make_queries(spec, 256)
topk = np.minimum(topk, 50).astype(np.int32)

# 2. three-stage build: GPU-analogue coarse k-means -> elastic fine split +
#    closure assignment -> merge + LLSP training
cfg = BuildConfig(
    max_cluster_size=96, cluster_len=128, coarse_per_task=5_000, n_workers=2,
    llsp=LLSPConfig(levels=(8, 16, 32, 64), recall_target=0.9),
)
with tempfile.TemporaryDirectory() as workdir:
    index, llsp, report = build_index(x, cfg, workdir,
                                      queries=queries, query_topk=topk)
print(f"built {report.n_clusters} clusters "
      f"(replication {report.replication:.2f}x) "
      f"in {sum(report.stage_seconds.values()):.1f}s")

# 3. serve a batch: router -> centroid scan -> leveling pruning -> one
#    batched posting scan -> dedup top-k
out = serve_step(
    index, llsp, jnp.asarray(queries), jnp.asarray(topk),
    SearchConfig(k=10, nprobe_max=64, pruning="llsp", n_ratio=16),
)

_, true10 = brute_force_topk(jnp.asarray(x), jnp.asarray(queries), 10)
print(f"recall@10 = {recall_at_k(np.asarray(out['ids']), np.asarray(true10)):.3f}  "
      f"mean nprobe = {float(np.asarray(out['nprobe']).mean()):.1f} / 64")
