"""The serving path's profiler spans as the chip records them, and the
per-layer metric that reads the batcher's queue wait.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import importlib.util
import os
import re
import types

import pytest

from bench import registry, run, trace_reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PROBE = os.path.join(REPO, "bench", "testdata", "serve_probe.xplane.pb")


def _reader(name):
    return registry.metric_reader(REPO, name)


def _events(path):
    """(plane, line, name, batch arg or None, start_ns, end_ns) of every
    event, read with nothing but the profiler's own reader."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            dict(ev.stats).get("batch"), ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def test_queue_wait_reads_waits_over_requests():
    t = [types.SimpleNamespace(size=3, queue_wait_s=0.030),
         types.SimpleNamespace(size=1, queue_wait_s=0.002)]
    cell = registry.resolve(REPO, "sift200k-q8.steady")
    read = _reader("queue.wait_ms_per_request")
    assert read(run.RunData(cell, t, None, {})) == pytest.approx(8.0)
    # a program that keeps no wait, and a window with no batch: nothing
    old = [types.SimpleNamespace(size=4)]
    assert read(run.RunData(cell, old, None, {})) is None
    assert read(run.RunData(cell, [], None, {})) is None


def test_pinned_kernel_names_are_what_the_scan_readers_match():
    from repro.kernels.ops import SCAN_KERNEL_NAMES

    assert SCAN_KERNEL_NAMES == ("ivf_scan_topk", "ivf_scan_q8_topk")
    for name in ("scan.kernel_ms_per_batch", "scan_roofline"):
        spec = importlib.util.spec_from_file_location(
            "reader", registry.metric_path(REPO, name))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert all(re.search(mod.KERNEL, k) for k in SCAN_KERNEL_NAMES)


def test_serving_spans_on_a_recorded_trace():
    # a tiny q8 cell (3000 vectors, nprobe 8, the Mosaic kernel with its
    # pinned name) served at 10 queries/s for a 1-s window on one TPU v5
    # lite, trimmed to the chip's "XLA Ops" line, the serving spans, the
    # window mark and the Task Environment plane
    from repro.kernels.ops import SCAN_KERNEL_NAMES
    from repro.obs.trace import SERVE_SPANS, SPAN_DISPATCH, SPAN_IDLE

    ev = _events(PROBE)
    spans = [e for e in ev if e[2] in SERVE_SPANS]
    assert {e[2] for e in spans} == set(SERVE_SPANS)
    for _, line, name, _, _, _ in spans:
        assert line.startswith(SERVE_SPANS[name]), (name, line)
    for name in SERVE_SPANS:
        got = {e[3] for e in spans if e[2] == name} - {None}
        assert got == (set() if name == SPAN_IDLE else set(range(2, 10)))
    calls = sorted((e for e in ev if e[1] == "XLA Ops"
                    and e[2].startswith("%ivf_scan")), key=lambda e: e[4])
    assert [c[2].split(" = ")[0] for c in calls] == \
        ["%" + SCAN_KERNEL_NAMES[1] + ".1"] * 8
    # the profile places the chip's clock early: each batch's scan seems to
    # start 0.48-0.68 ms before its dispatch span opens, yet ends after it
    disp = sorted((e for e in spans if e[2] == SPAN_DISPATCH),
                  key=lambda e: e[4])
    early = [d[4] - c[4] for d, c in zip(disp, calls)]
    assert [d[3] for d in disp] == list(range(2, 10))
    assert min(early) == pytest.approx(481449.0)
    assert max(early) == pytest.approx(677973.0)
    assert all(c[5] > d[4] for d, c in zip(disp, calls))


def test_existing_readers_read_the_serving_probe():
    tr = trace_reduce.load(PROBE)
    lo = tr.marks["bench.window"]
    s = trace_reduce.summarize(tr, lo, lo + 1e9)
    cell = registry.resolve(REPO, "sift200k-q8.steady")
    data = run.RunData(cell, [], s, {})
    assert _reader("scan.kernel_ms_per_batch")(data) == \
        pytest.approx(1.096926625)
    assert _reader("device.idle_share")(data) == pytest.approx(99.1062911)
