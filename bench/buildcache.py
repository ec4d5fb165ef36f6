"""Stage 1-2 checkpoints of the index build, kept between runs.

``build_index`` resumes a stage whose checkpoint is in its work directory:
``stage1_centroids.npy`` (stage 1) and ``shards/assign_*.npz`` (stage 2).
Each run deploys into a fresh work directory; ``restore`` first copies in
the checkpoints a previous run of the same key left, and ``save`` keeps
them after the build.  Nothing else is kept: stage 3 and the flash file are
made anew by every run.

The key is the build's inputs: the corpus spec (with its ``corpus_seed``)
and build sizes of the configuration, and a hash of every file under
``src/``, so an index built by other code (a parent commit's, say) is never
resumed.  The run's seed is not in it: it draws the traffic, not the
corpus, so only a checkout's first run of a configuration builds.
"""
from __future__ import annotations

import glob
import hashlib
import json
import os
import shutil

STAGE_FILES = ("stage1_centroids.npy", "shards/assign_*.npz")
BUILD_KEYS = ("n", "dim", "n_modes", "spread", "cluster_len",
              "max_cluster_size", "nprobe")


def src_hash(src_dir: str) -> str:
    h = hashlib.sha256()
    for path in sorted(glob.glob(os.path.join(src_dir, "**", "*.py"),
                                 recursive=True)):
        h.update(os.path.relpath(path, src_dir).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def key(config: dict, src_dir: str) -> str:
    build = {k: config[k] for k in BUILD_KEYS}
    blob = json.dumps([build, int(config["corpus_seed"]), src_hash(src_dir)],
                      sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


def _files(root: str) -> list:
    return sorted(p for pat in STAGE_FILES
                  for p in glob.glob(os.path.join(root, pat)))


def restore(cache_dir: str, workdir: str) -> int:
    """Copy the cached checkpoints into ``workdir``; returns how many."""
    files = _files(cache_dir)
    for p in files:
        dst = os.path.join(workdir, os.path.relpath(p, cache_dir))
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        shutil.copyfile(p, dst)
    return len(files)


def save(workdir: str, cache_dir: str) -> int:
    """Keep ``workdir``'s checkpoints under ``cache_dir`` (once: a key's
    files never change).  Written to a temporary name and renamed, so a run
    cut short leaves no half-written cache."""
    if os.path.isdir(cache_dir):
        return 0
    files = _files(workdir)
    tmp = cache_dir + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    for p in files:
        dst = os.path.join(tmp, os.path.relpath(p, workdir))
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        shutil.copyfile(p, dst)
    os.makedirs(os.path.dirname(cache_dir), exist_ok=True)
    os.replace(tmp, cache_dir)
    return len(files)
