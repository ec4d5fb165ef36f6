"""Share of the fused scan kernel's grid steps, in %, that ran insertion
passes into the running top-k: the window's batches' summed
``StageTimes.scan_steps_merged`` over their summed ``scan_steps`` (query
tiles x probe slots).  Layer: scan kernel (``kernels/ivf_scan``,
``kernels/ivf_scan_q8``).  Nothing where the program does not count the
kernel's steps."""


def read(run):
    merged = [getattr(t, "scan_steps_merged", None) for t in run.batches]
    steps = [getattr(t, "scan_steps", None) for t in run.batches]
    if None in merged or None in steps or not sum(steps):
        return None
    return 100.0 * sum(merged) / sum(steps)
