"""The per-layer metrics of the wide top-k cell (``redsrch200k-q8.steady``)
that read the device trace.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import os
import types

from bench import registry, roofline, run, trace_reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PROBE = os.path.join(REPO, "bench", "testdata", "serve_probe.xplane.pb")


def test_wide_topk_roofline_is_the_scan_roofline():
    """``topk.scan_roofline`` reads what ``scan_roofline`` reads of the
    same window, and nothing from an untraced run."""
    tr = trace_reduce.load(PROBE)
    lo = tr.marks["bench.window"]
    s = trace_reduce.summarize(tr, lo, lo + 1e9)
    cell = registry.resolve(REPO, "redsrch200k-q8.steady")
    t = types.SimpleNamespace(size=16, clusters_requested=2048,
                              union_clusters=900)
    data = run.RunData(cell, [t, t], s, roofline.peaks("TPU v5 lite"))
    want = registry.metric_reader(REPO, "scan_roofline")(data)
    read = registry.metric_reader(REPO, "topk.scan_roofline")
    assert want > 0.0
    assert read(data) == want
    assert read(run.RunData(cell, [t], None, {})) is None
