"""The scan kernel's share of its roofline, in %: the least time the chip
could take for the window's batches (``bench/roofline.py``: the larger of
operations over the bf16 peak and bytes over HBM bandwidth, per batch)
over the device time of the ``ivf_scan*`` kernel calls in the traced
window.  Layer: scan kernel.  Nothing where the kernel is not in the
trace; never a share of zero time."""

KERNEL = r"^ivf_scan"


def read(run):
    from bench.roofline import least_time

    if run.trace is None:
        return None
    seconds, calls = run.trace.kernel(KERNEL)
    if not calls or seconds <= 0.0:
        return None
    least = sum(least_time(run.scan_work(t), run.peak)[0]
                for t in run.batches)
    # per call: the window's batches and the kernel calls it holds can
    # differ by one at either edge
    return 100.0 * (least / max(len(run.batches), 1)) / (seconds / calls)
