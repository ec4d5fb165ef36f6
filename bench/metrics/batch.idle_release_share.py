"""Share of the window's batches, in %, that the batcher released early
because the poller had nothing prepared and nothing in flight
(``StageTimes.released_idle``, stamped at formation from
``MicroBatch.released_idle``).  Layer: admission and batching
(``runtime/batcher``, ``runtime/engine``).  Nothing where the program does
not stamp it."""


def read(run):
    flags = [getattr(t, "released_idle", None) for t in run.batches]
    if not flags or None in flags:
        return None
    return 100.0 * sum(flags) / len(flags)
