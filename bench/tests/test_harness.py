"""The benchmark harness: lookup by name, generators, roofline, trace
reduction, and the refusal to run without a chip.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO, make_root

from bench import corpus, loadgen, registry, roofline, run, trace_reduce
from bench import traffic

TESTDATA = os.path.join(REPO, "bench", "testdata")


def test_every_cell_resolves_from_its_files():
    bench = registry.load_benchmark(REPO)
    used = set()
    for wl in bench["workloads"]:
        cell = registry.resolve(REPO, wl["name"])
        used.add(wl["config"])
        assert cell.config["name"] == wl["config"]
        assert os.path.exists(registry.traffic_path(REPO, wl["traffic"]))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            assert callable(registry.metric_reader(REPO, m["name"]))
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) <= set(cfg["reduced"])


def test_added_cell_config_traffic_and_metric_are_found(tmp_path):
    root = make_root(str(tmp_path), tiers=("q8",))
    # a later change adds files and entries; it edits no harness code
    with open(os.path.join(root, "bench", "traffic", "hot.json"), "w") as f:
        json.dump({"generator": "hot_cluster_trace",
                   "params": {"rate_qps": 40.0, "hot_frac": 0.1,
                              "hot_weight": 0.8},
                   "pool_order": "mode", "k": 10}, f)
    with open(os.path.join(root, "bench", "metrics", "batch.count.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run.batches) or None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-q8.hot", "config": "tiny-q8",
                               "traffic": "hot", "chips": 1, "why": "t"})
    bench["per_layer"].append({
        "name": "batch.count", "unit": "batches", "better": "higher",
        "source": "program_counter", "layer": "admission and batching",
        "moves": "p50_ms", "workloads": ["tiny-q8.hot"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = registry.resolve(root, "tiny-q8.hot")
    assert cell.traffic["generator"] == "hot_cluster_trace"
    assert "batch.count" in {m["name"] for m in cell.per_layer}
    assert "batch.count" not in {
        m["name"] for m in registry.resolve(root, "tiny-q8.steady").per_layer}
    read = registry.metric_reader(root, "batch.count")
    assert read(run.RunData(cell, [object()] * 3, None, {})) == 3
    sched = traffic.schedule(cell.traffic, 7, 3.0, cell.config["pool"])
    assert len(sched) > 0 and np.all(np.diff(sched.t) >= 0)


@pytest.mark.parametrize("name", ["poisson_trace", "bursty_trace",
                                  "hot_cluster_trace"])
def test_copied_generators_repeat_by_seed_and_match_the_program(name):
    from repro.runtime import loadgen as program

    kw = {"poisson_trace": dict(rate_qps=80.0),
          "bursty_trace": dict(base_qps=20.0, burst_qps=90.0, period_s=1.0,
                               duty=0.3),
          "hot_cluster_trace": dict(rate_qps=80.0, n_queries=512)}[name]
    kw = dict(kw, duration_s=4.0, seed=2**31 + 11)
    ours = getattr(loadgen, name)(**kw)
    assert ours == getattr(loadgen, name)(**kw)
    assert [(a.t, a.qrow, a.topk) for a in ours] == [
        (a.t, a.qrow, a.topk) for a in getattr(program, name)(**kw)]
    assert ours != getattr(loadgen, name)(**dict(kw, seed=kw["seed"] + 1))


def test_schedule_repeats_by_seed_with_a_fixed_count(tmp_path):
    mix = {"generator": "hot_cluster_trace",
           "params": {"rate_qps": 110.0, "hot_frac": 0.05, "hot_weight": 0.9},
           "pool_order": "mode", "k": 10}
    a = traffic.schedule(mix, 2**31 + 5, 20.0, 4096)
    b = traffic.schedule(mix, 2**31 + 5, 20.0, 4096)
    c = traffic.schedule(mix, 2**31 + 6, 20.0, 4096)
    assert len(a) == len(c) == 2200
    np.testing.assert_array_equal(a.t, b.t)
    np.testing.assert_array_equal(a.qrow, b.qrow)
    assert not np.array_equal(a.qrow, c.qrow)
    assert a.t.min() >= 0.0 and a.t.max() < 20.0
    assert np.mean(a.qrow < 204) > 0.85          # the hot slice
    # arrivals are always a fixed-count Poisson process at rate_qps
    path = tmp_path / "no-rate.json"
    path.write_text(json.dumps({"generator": "bursty_trace",
                                "params": {"base_qps": 5.0}}))
    with pytest.raises(ValueError, match="rate_qps"):
        traffic.load(str(path))


def test_corpus_copy_matches_the_program():
    from repro.data import PAPER_DATASETS, make_vectors

    spec = PAPER_DATASETS["sift"]
    spec = type(spec)(**{**spec.__dict__, "n": 500, "seed": 2**31 + 3})
    np.testing.assert_array_equal(
        corpus.make_vectors(500, spec.dim, spec.n_modes, spec.spread,
                            spec.seed),
        make_vectors(spec))
    pool, modes = corpus.make_pool(64, 128, 64, 0.25, 2**31 + 3, 9, 1.2)
    again, _ = corpus.make_pool(64, 128, 64, 0.25, 2**31 + 3, 9, 1.2)
    np.testing.assert_array_equal(pool, again)
    other, _ = corpus.make_pool(64, 128, 64, 0.25, 2**31 + 3, 10, 1.2)
    assert not np.array_equal(pool, other)
    srt = corpus.order_pool(pool, modes, "mode")
    assert np.all(np.diff(modes[np.argsort(modes, kind="stable")]) >= 0)
    assert srt.shape == pool.shape


def test_roofline_counts_one_small_shape_by_hand():
    # 4 probes over a union of 3 clusters, L=8, D=4, batch padded to 16
    q8 = roofline.scan_work("q8", probes=4, union_clusters=3, batch_pad=16,
                            cluster_len=8, dim=4, n_cand=24)
    f32 = roofline.scan_work("f32", probes=4, union_clusters=3, batch_pad=16,
                             cluster_len=8, dim=4, n_cand=24)
    io = 16 * 4 * 4 + 16 * 24 * 8                     # queries in, cands out
    assert q8.flops == f32.flops == 2 * 4 * 8 * 4
    # q8 per cluster: codes 32 B, ids 32 B, scale 4 B, norms 32 B, centroid 16 B
    assert q8.bytes == 3 * (32 + 32 + 4 + 32 + 16) + io
    # f32 per cluster: floats 128 B, ids 32 B
    assert f32.bytes == 3 * (128 + 32) + io
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_time(f32, peak)
    assert bound == "memory" and t == pytest.approx(f32.bytes / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_trace_reduce_on_a_recorded_trace():
    # five calls of the f32 scan kernel (C=512, P=64, B=32) and a small
    # jitted reduction, traced on one TPU v5 lite
    tr = trace_reduce.load(os.path.join(TESTDATA, "scan_probe.xplane.pb"))
    assert list(tr.devices) == ["/device:TPU:0"]
    ops = tr.devices["/device:TPU:0"]
    s = trace_reduce.summarize(tr)
    assert s.window_s == pytest.approx(0.433535004)
    # one core runs one op at a time, so busy time is the sum of the ops
    assert s.busy_s == pytest.approx(sum(o.dur_ns for o in ops) * 1e-9)
    assert s.busy_s == pytest.approx(0.087209083)
    seconds, calls = s.kernel(r"^ivf_scan")
    assert calls == 5 and seconds == pytest.approx(0.087166203)
    assert s.busy_s + sum(g for _, g in s.gaps) * 1e-9 == \
        pytest.approx(s.window_s)
    lo = tr.marks["bench.mark"]
    w = trace_reduce.summarize(tr, lo, lo + 0.2e9)
    assert w.window_s == pytest.approx(0.2) and w.kernel(r"^ivf_scan")[1] == 4
    bd = trace_reduce.breakdown(s)
    assert bd["device_ops"][0][0] == "ivf_scan_topk"
    assert len(bd["idle_gaps"]) == 10


def test_command_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cwd in (REPO, _bench_only(tmp_path)):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload",
             "sift200k-q8.steady", "--seed", "1", "--seconds", "1",
             "--trace", "0"], cwd=cwd, env=env, capture_output=True,
            text=True, timeout=120)
        assert p.returncode != 0
        assert "no TPU found" in p.stderr
        assert not p.stdout.strip()


def _bench_only(tmp_path) -> str:
    """A directory that holds only BENCHMARK.json and the files under its
    paths."""
    import shutil

    d = tmp_path / "only"
    shutil.copytree(os.path.join(REPO, "bench"), d / "bench",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), d / "BENCHMARK.json")
    return str(d)
