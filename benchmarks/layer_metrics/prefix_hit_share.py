"""Share of queried prompt tokens the prefix cache served, in percent."""

from benchmarks.layer_metrics._counters import delta


def read(ctx):
    asked = delta(ctx, "prefix_cache_query_tokens_total")
    if not asked:
        return None
    # A counter with labels has no series until its first increment: no
    # hit family at all means no hit.
    hit = delta(ctx, "prefix_cache_hit_tokens_total") or 0.0
    return 100.0 * hit / asked
