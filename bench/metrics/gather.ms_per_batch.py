"""Mean milliseconds of a batch's host gather of its probed-cluster union
(``gather_end - gather_start``).  Layer: host gather and H2D
(``storage/host_tier``, ``PrefetchPipeline._gather``)."""


def read(run):
    d = [t.gather_end - t.gather_start for t in run.batches
         if t.gather_end > t.gather_start > 0.0]
    return 1e3 * sum(d) / len(d) if d else None
