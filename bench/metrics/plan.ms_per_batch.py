"""Mean milliseconds of a batch's plan stage (``plan_end - plan_start``):
probe routing, or its reuse from admission, and the probe mask.  Layer:
plan and route (``PrefetchPipeline.plan``, ``_plan_jit``)."""


def read(run):
    d = [t.plan_end - t.plan_start for t in run.batches
         if t.plan_end > t.plan_start > 0.0]
    return 1e3 * sum(d) / len(d) if d else None
