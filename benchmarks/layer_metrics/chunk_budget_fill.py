"""Share of the prefill-chunk budget the sequential mixed steps used, in
percent: prompt tokens taken over budget offered, counting only steps
issued while a prompt was prefilling or queued (the program's counter
pair; a program without the budget counter has nothing to read)."""

from benchmarks.layer_metrics._counters import delta


def read(ctx):
    offered = delta(ctx, "mixed_chunk_budget_tokens_total")
    if not offered:
        return None
    taken = delta(ctx, "mixed_chunk_tokens_total") or 0.0
    return 100.0 * taken / offered
