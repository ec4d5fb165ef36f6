"""Mean device milliseconds of one call of the served scan kernel: the
traced window's events of the kernels named ``ivf_scan*`` (custom calls),
summed, over their count.  Layer: scan kernel (``kernels/ivf_scan``,
``kernels/ivf_scan_q8``).  Nothing where the kernel is not in the trace."""

KERNEL = r"^ivf_scan"


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.trace.kernel(KERNEL)
    return 1e3 * seconds / calls if calls else None
