"""Mean valid rows (decode tokens + chunk tokens) of a sequential mixed
dispatch."""

from benchmarks.layer_metrics._counters import delta


def read(ctx):
    rows = delta(ctx, "mixed_batch_tokens_sum")
    n = delta(ctx, "mixed_batch_tokens_count")
    if rows is None or not n:
        return None
    return rows / n
