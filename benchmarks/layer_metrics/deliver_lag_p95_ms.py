"""95th percentile, over the streams that ended in the untraced window, of
a stream's worst lag from the engine's put of a frame to its flush on the
wire, in ms: what the handler threads and the GIL cost a client.  Read off
the bucket deltas of ``stream_deliver_lag_seconds``, linear inside a
bucket; past the last finite bound it reads that bound."""

from benchmarks.layer_metrics._counters import delta

FAMILY = "stream_deliver_lag_seconds_bucket"


def quantile(bounds, q):
    """``bounds``: (upper bound, cumulative count) pairs, ascending, the
    last one ``inf``."""
    want = q * bounds[-1][1]
    lo = below = 0.0
    for hi, count in bounds:
        if count >= want and count > below:
            if hi == float("inf"):
                return lo
            return lo + (hi - lo) * (want - below) / (count - below)
        lo, below = hi, count
    return None


def read(ctx):
    if FAMILY not in ctx["metrics_close"]:
        return None
    les = {lab["le"] for lab, _ in ctx["metrics_close"][FAMILY]}
    bounds = sorted((float(le), delta(ctx, FAMILY, le=le)) for le in les)
    if not bounds or bounds[-1][1] <= 0:
        return None
    return 1e3 * quantile(bounds, 0.95)
