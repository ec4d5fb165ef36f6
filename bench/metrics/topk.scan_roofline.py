"""The scan kernel's share of its roofline, in %, in a cell whose kernel
runs the wide top-k: ``scan_roofline``'s own reading (``bench/roofline.py``'s
least time for the window's batches over the device time of the
``ivf_scan*`` kernel calls), under a name of its own since that metric
lists only the SIFT cells.  Layer: scan kernel.  Nothing where the kernel
is not in the trace."""


def read(run):
    from bench.registry import metric_reader

    return metric_reader(run.cell.root, "scan_roofline")(run)
