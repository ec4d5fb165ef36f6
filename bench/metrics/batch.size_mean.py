"""Mean number of requests in a served batch (``StageTimes.size``), over
the batches whose scan was dispatched in the window.  Layer: admission and
batching (``runtime/engine``, ``runtime/batcher``)."""


def read(run):
    sizes = [t.size for t in run.batches]
    return sum(sizes) / len(sizes) if sizes else None
