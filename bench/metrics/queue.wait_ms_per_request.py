"""Mean milliseconds a request waited in the batcher, from its submit to
its batch's formation: the window's batches' summed waits
(``StageTimes.queue_wait_s``, the sum of ``MicroBatch.waits``) over their
requests (``StageTimes.size``).  Layer: admission and batching
(``runtime/batcher``, ``runtime/engine``).  Nothing where the program does
not keep the wait."""


def read(run):
    waits = [getattr(t, "queue_wait_s", None) for t in run.batches]
    n = sum(t.size for t in run.batches)
    if not n or None in waits:
        return None
    return 1e3 * sum(waits) / n
