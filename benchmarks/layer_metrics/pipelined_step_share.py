"""Share of model dispatches that took the pipelined program, in percent."""

from benchmarks.layer_metrics._counters import delta


def read(ctx):
    piped = delta(ctx, "pipeline_depth_occupancy_count")
    seq = delta(ctx, "mixed_batch_tokens_count")
    if piped is None or seq is None or piped + seq <= 0:
        return None
    return 100.0 * piped / (piped + seq)
