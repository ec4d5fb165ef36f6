"""Shared helpers of the benchmark's own tests: a tiny checkout in a
temporary directory that holds one small cell of each tier, built from the
benchmark's files the way a later change would add a cell."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(n=3000, nprobe=8, pool=256, kernel=False)


def make_root(tmp: str, tiers=("q8", "f32"), rate: float = 60.0) -> str:
    """A checkout holding the benchmark's files, the program's sources and
    BENCHMARK.json with one tiny cell per tier."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(tmp, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"], bench["workloads"] = [], []
    with open(os.path.join(tmp, "bench", "traffic", "tiny.json"), "w") as f:
        json.dump({"generator": "poisson_trace",
                   "params": {"rate_qps": rate},
                   "pool_order": "drawn", "k": 10}, f)
    for tier in tiers:
        with open(os.path.join(REPO, "bench", "configs",
                               f"sift200k-{tier}.json")) as f:
            cfg = json.load(f)
        cfg.update(TINY, name=f"tiny-{tier}")
        path = f"bench/configs/tiny-{tier}.json"
        with open(os.path.join(tmp, path), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": f"tiny-{tier}", "source": "test",
                                 "file": path, "reduced": ["n"], "why": "t"})
        bench["workloads"].append({"name": f"tiny-{tier}.steady",
                                   "config": f"tiny-{tier}",
                                   "traffic": "tiny", "chips": 1, "why": "t"})
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    return make_root(str(tmp_path / "checkout"))
