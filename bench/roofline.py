"""Operations and bytes of the IVF scan, from its shapes alone.

The least time the chip could take for one batch's scan is the larger of
its operations over the peak rate and its bytes over the HBM bandwidth.
Both counts come from the algorithm's shapes, not from the kernel, so any
implementation of the scan is measured against the same work:

* operations: ``2 * L * D`` multiply-adds for every probed (query,
  cluster) pair, ``sum(nprobe)`` of them in a batch;
* bytes: the probed union's posting payload read once (codes or floats,
  the ids, and for q8 the per-cluster scale, the per-slot norms and the
  owning centroid), plus the padded queries in and the candidates out.

The peak rate is the chip's bf16 rate for both tiers: the f32 scan runs on
the same MXU in several bf16 passes and the int8 codes are widened before
the product, so no faster published rate applies to either, and using the
fastest one can only understate the share.
"""
from __future__ import annotations

import dataclasses
import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class ScanWork:
    flops: float
    bytes: float


def scan_work(tier: str, *, probes: int, union_clusters: int,
              batch_pad: int, cluster_len: int, dim: int,
              n_cand: int) -> ScanWork:
    """Work of one batch's scan.  ``probes`` is the sum of nprobe over the
    batch's queries, ``union_clusters`` the distinct clusters among them."""
    ids = cluster_len * 4
    if tier == "q8":
        per_cluster = cluster_len * dim + ids + 4 + cluster_len * 4 + dim * 4
    elif tier == "f32":
        per_cluster = cluster_len * dim * 4 + ids
    else:
        raise ValueError(f"unknown tier {tier!r}")
    io = batch_pad * dim * 4 + batch_pad * n_cand * 8
    return ScanWork(flops=2.0 * probes * cluster_len * dim,
                    bytes=float(union_clusters * per_cluster + io))


def least_time(work: ScanWork, peak: dict) -> tuple[float, str]:
    """(seconds, which bound) of the least time the chip could take."""
    compute = work.flops / peak["bf16_flops"]
    memory = work.bytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
