"""Passes of the wide in-kernel top-k over its candidate buffer per live
grid step: the window's batches' summed ``StageTimes.scan_select_passes``
(the compactions' compare-exchange and filter passes) over their summed
``scan_steps_live``.  Layer: scan kernel (``kernels/ivf_scan``,
``kernels/ivf_scan_q8``).  Nothing where the program does not count
them."""


def read(run):
    passes = [getattr(t, "scan_select_passes", None) for t in run.batches]
    live = [getattr(t, "scan_steps_live", None) for t in run.batches]
    if None in passes or None in live or not sum(live):
        return None
    return sum(passes) / sum(live)
