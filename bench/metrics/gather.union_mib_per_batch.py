"""Mean MiB of posting payload in a batch's gathered union
(``StageTimes.union_bytes``).  Layer: host gather and H2D."""


def read(run):
    b = [t.union_bytes for t in run.batches if t.union_bytes > 0]
    return sum(b) / len(b) / 2**20 if b else None
