"""Mean milliseconds of a batch's flash-tier f32 re-rank
(``rerank_end - rerank_start``); nothing where no batch re-ranked.
Layer: merge and re-rank (``PrefetchPipeline._rerank``,
``storage/flash_tier``)."""


def read(run):
    d = [t.rerank_end - t.rerank_start for t in run.batches
         if t.rerank_end > t.rerank_start > 0.0]
    return 1e3 * sum(d) / len(d) if d else None
