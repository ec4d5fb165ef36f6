"""From a profiler trace (``.xplane.pb``) to device busy time and op times.

What the JAX profiler writes for a TPU, and what is read here:

* planes named ``/device:TPU:<n>``, one per chip, each with a line
  ``XLA Ops`` whose events are the HLO operations that ran on the chip.
  An event's name is the HLO text, ``%<op> = <shape> <opcode>(...)``; the
  op name (``ivf_scan_topk.1``) is what precedes `` = ``, and a Pallas
  kernel is a ``custom-call`` whose op name is the kernel's;
* host planes, whose events include the benchmark's own
  ``jax.profiler.TraceAnnotation`` marks (names starting ``bench.``);
* the ``Task Environment`` plane, whose stats give the profile's start and
  stop in nanoseconds.

All event times are nanoseconds from the profile's start.  Busy time is the
union of a chip's op intervals inside a window, averaged over chips.
"""
from __future__ import annotations

import dataclasses
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MARK_PREFIX = "bench."
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str            # op name without its numeric suffix
    custom_call: bool    # a kernel (custom-call), not an XLA op
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Trace:
    window_ns: float
    devices: dict         # plane name -> list[Op], by start
    marks: dict           # annotation name -> start_ns (first occurrence)


def _op(ev) -> Op:
    head, _, rest = ev.name.partition(" = ")
    name = _SUFFIX.sub("", head.lstrip("%"))
    return Op(name, " custom-call(" in rest, float(ev.start_ns),
              float(ev.duration_ns))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = {}
    marks: dict = {}
    start = stop = None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [_op(ev) for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            devices[plane.name] = sorted(ops, key=lambda o: o.start_ns)
        elif plane.name == "Task Environment":
            stats = dict(plane.stats)
            start = stats.get("profile_start_time")
            stop = stats.get("profile_stop_time")
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARK_PREFIX):
                        marks.setdefault(ev.name, float(ev.start_ns))
    if start is None or stop is None:
        raise ValueError(f"{path}: no profile start/stop in the trace")
    return Trace(float(stop - start), devices, marks)


def busy_intervals(ops: list, lo: float, hi: float) -> list:
    """Union of the ops' intervals, clipped to [lo, hi), in order."""
    out: list = []
    for o in ops:
        a = max(o.start_ns, lo)
        b = min(o.start_ns + o.dur_ns, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over chips
    op_s: dict                    # op name -> [seconds, calls], all chips
    kernel_s: dict                # custom-call name -> [seconds, calls]
    gaps: list                    # (start, length) in ns from the window's
                                  # start: idle gaps of the first chip

    def kernel(self, pattern: str) -> tuple[float, int]:
        """Seconds and calls of the kernels whose name matches ``pattern``."""
        rx = re.compile(pattern)
        hits = [v for k, v in self.kernel_s.items() if rx.search(k)]
        return sum(v[0] for v in hits), sum(v[1] for v in hits)


def summarize(trace: Trace, lo_ns: float = 0.0,
              hi_ns: float | None = None) -> Summary:
    """Busy time, op and kernel times, and idle gaps inside the window
    [lo_ns, hi_ns) of the trace (the whole profile by default).  An op
    counts towards its name's time when it starts inside the window."""
    if not trace.devices:
        raise ValueError("the trace holds no TPU device plane")
    hi = trace.window_ns if hi_ns is None else hi_ns
    busy = []
    op_s: dict = {}
    kernel_s: dict = {}
    gaps: list = []
    for i, (_, ops) in enumerate(sorted(trace.devices.items())):
        spans = busy_intervals(ops, lo_ns, hi)
        busy.append(sum(b - a for a, b in spans))
        if i == 0:
            edges = [lo_ns] + [x for s in spans for x in s] + [hi]
            gaps = [(edges[j] - lo_ns, edges[j + 1] - edges[j])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
        for o in ops:
            if not lo_ns <= o.start_ns < hi:
                continue
            d = op_s.setdefault(o.name, [0.0, 0])
            d[0] += o.dur_ns * 1e-9
            d[1] += 1
            if o.custom_call:
                d = kernel_s.setdefault(o.name, [0.0, 0])
                d[0] += o.dur_ns * 1e-9
                d[1] += 1
    return Summary((hi - lo_ns) * 1e-9, sum(busy) / len(busy) * 1e-9,
                   op_s, kernel_s, gaps)


def breakdown(summary: Summary, label_gap=None, n: int = 10) -> dict:
    """The ``breakdown`` of a result line: the ``n`` device ops that took
    most time, and the ``n`` longest idle gaps, each named by
    ``label_gap(start_ns, length_ns)`` (``"unattributed"`` without one)."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1][0])[:n]
    gaps = sorted(summary.gaps, key=lambda g: -g[1])[:n]
    label = label_gap or (lambda a, n_: "unattributed")
    return {"device_ops": [[k, v[0]] for k, v in ops],
            "idle_gaps": [[label(a, g), g * 1e-9] for a, g in gaps]}
