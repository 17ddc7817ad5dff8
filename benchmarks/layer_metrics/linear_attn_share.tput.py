"""Share of the device's busy time in the LINEAR-attention layers, in
percent: the scopes ``arks.linear_qkv`` (the q | k | v projections, the
short convolution over the slots' carry, the decay and the step size),
``arks.linear_state`` (the delta rule over the slots' state: the one-step
recurrence of the decode lanes and the chunked scan of the prefill lanes,
nothing else) and ``arks.linear_out`` (the per-head norm, the low-rank gate
and the output projection).  Nothing to read where the program has no
``arks.linear_*`` scope."""

from benchmarks.layer_metrics import _scopes

SCOPES = ("arks.linear_qkv", "arks.linear_state", "arks.linear_out")


def read(ctx):
    got = _scopes.by_scope(ctx)
    if not got or ctx["device"]["busy_s"] <= 0 \
            or "arks.linear_state" not in got:
        return None
    return 100.0 * sum(got.get(s, 0.0) for s in SCOPES) \
        / ctx["device"]["busy_s"]
