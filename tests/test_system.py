"""End-to-end behaviour of the paper's system (replaces the scaffold stub).

Covers the full Helmsman story at container scale: build a clustered index
over a realistic (clustered) corpus, train LLSP from logged queries, serve
with all three pruning modes, and check the paper's qualitative claims:

  * clustering-based search reaches the recall target with small nprobe
    (the premise of §3.3);
  * LLSP spends fewer probes than no-pruning at comparable recall (§5.4);
  * per-query recall is more stable than fixed-eps (§5.4, Fig. 20);
  * serving survives a posting-shard failure via replicas (§6.2).
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.distance import recall_at_k
from repro.core.ivf import brute_force_topk
from repro.core.search import SearchConfig, serve_step


@pytest.fixture(scope="module")
def system(tmp_path_factory, small_corpus):
    from repro.build.pipeline import BuildConfig, build_index
    from repro.core.llsp import LLSPConfig
    x, q, topk = small_corpus
    wd = str(tmp_path_factory.mktemp("sys"))
    cfg = BuildConfig(max_cluster_size=48, cluster_len=64,
                      coarse_per_task=1000, n_workers=2,
                      llsp=LLSPConfig(levels=(4, 8, 16, 32), n_trees=25,
                                      max_depth=4, n_ratio_features=8))
    idx, llsp, _ = build_index(x, cfg, wd, queries=q,
                               query_topk=np.minimum(topk, 20).astype(np.int32))
    qj = jnp.asarray(q)
    _, ti = brute_force_topk(jnp.asarray(x), qj, 10)
    return idx, llsp, qj, np.asarray(ti)


def _run(idx, llsp, qj, mode, **kw):
    cfg = SearchConfig(k=10, nprobe_max=32, pruning=mode, use_kernel=False,
                       n_ratio=8, **kw)
    return serve_step(idx, llsp, qj, jnp.full((qj.shape[0],), 10, jnp.int32), cfg)


def test_clustering_premise(system):
    idx, llsp, qj, ti = system
    out = _run(idx, None, qj, "none")
    r = recall_at_k(out["ids"], ti)
    assert r >= 0.9, f"non-pruned recall {r}"


def test_llsp_probe_savings(system):
    idx, llsp, qj, ti = system
    out_all = _run(idx, None, qj, "none")
    out_llsp = _run(idx, llsp, qj, "llsp")
    r_all = recall_at_k(out_all["ids"], ti)
    r_llsp = recall_at_k(out_llsp["ids"], ti)
    mean_probe = float(np.asarray(out_llsp["nprobe"]).mean())
    assert mean_probe < 32
    assert r_llsp >= r_all - 0.08, (r_llsp, r_all, mean_probe)


def test_llsp_stability_vs_fixed(system):
    idx, llsp, qj, ti = system
    out_llsp = _run(idx, llsp, qj, "llsp")
    probes_llsp = float(np.asarray(out_llsp["nprobe"]).mean())

    def frac_ok(out):
        ids = np.asarray(out["ids"])
        per = [(len(set(ids[i].tolist()) & set(ti[i].tolist())) / 10)
               for i in range(ids.shape[0])]
        return float(np.mean(np.asarray(per) >= 0.9))

    best_fixed = 0.0
    for eps in (0.05, 0.1, 0.2, 0.4):
        out_f = _run(idx, None, qj, "fixed", eps=eps)
        if float(np.asarray(out_f["nprobe"]).mean()) <= probes_llsp + 1:
            best_fixed = max(best_fixed, frac_ok(out_f))
    assert frac_ok(out_llsp) >= best_fixed - 0.05


def test_shard_failure_failover(system):
    """Losing one posting shard only loses that shard's un-replicated
    clusters; replicated (hot) clusters keep serving."""
    import numpy as np
    from repro.storage import make_replica_map, plan_striping
    from repro.distributed import ownership_mask, plan_failover

    idx = system[0]
    C = idx.n_clusters
    n_shards = 8
    st = plan_striping(C, n_shards)
    hot = np.arange(C)[::2]          # replicate every other cluster
    rm = make_replica_map(C, n_shards, st, hot_clusters=hot, n_replicas=2)
    plan = plan_failover(rm, [2])
    mask = ownership_mask(plan.owner, n_shards)
    # every non-lost cluster has exactly one live owner, none on shard 2
    assert mask[2].sum() == 0
    alive = np.setdiff1d(np.arange(C), plan.lost)
    assert (mask[:, alive].sum(axis=0) == 1).all()
    # hot clusters all survive
    assert not set(hot.tolist()) & set(plan.lost.tolist())
    # coverage loss is bounded by the failed shard's cold share
    assert plan.n_lost <= C // n_shards + 1


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """The persistent compile cache sits at a fixed path: the operator's
    JAX_COMPILATION_CACHE_DIR when set (left to JAX, nothing else set),
    otherwise .jax_cache/ at the root of the checkout."""
    import os

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            want = os.path.join(root, ".jax_cache")
            assert enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
