"""f32 rows the re-rank read from the flash tier per served query: the
window's batches' summed ``StageTimes.rerank_rows`` over their summed
``size``.  Layer: merge and re-rank (``PrefetchPipeline._rerank``,
``storage/flash_tier``).  Nothing where the program does not count the
rows or no batch re-ranked."""


def read(run):
    rows = [getattr(t, "rerank_rows", None) for t in run.batches]
    n = sum(t.size for t in run.batches)
    if None in rows or not n or not sum(rows):
        return None
    return sum(rows) / n
