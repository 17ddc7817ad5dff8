"""Share of the routers' (token, expert) pairs that landed on an identity
(zero-compute) expert over the window, in percent: ``moe_zero_pairs_total``
over ``moe_routed_pairs_total``.  Such a pair reads no weight and enters no
batch; a seeded router over 512 real and 256 identity experts sends about a
third of its pairs there.  Nothing to read where the program has no
``moe_zero_pairs_total`` or routed no pair."""

from benchmarks.layer_metrics._counters import delta


def read(ctx):
    zero = delta(ctx, "moe_zero_pairs_total")
    routed = delta(ctx, "moe_routed_pairs_total")
    if zero is None or not routed:
        return None
    return 100.0 * zero / routed
