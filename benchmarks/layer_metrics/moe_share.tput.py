"""Share of the device's busy time in the routed FFN, in percent: the
scopes ``arks.moe_route`` (router, top-k, the sort into expert order, the
scatter back), ``arks.moe_dequant``, ``arks.moe_dot`` and
``arks.moe_shared`` (the shared expert).  Under a share of a layer the
held experts run as batched contractions inside ``arks.moe_dot`` and are
in this share; a layer held whole runs ``ragged-dot`` ops, which carry no
scope in a v5e trace (PERF.md section 7 row 4) and are NOT.  Nothing to
read where the program has no ``arks.moe_shared`` scope."""

from benchmarks.layer_metrics import _scopes

SCOPES = ("arks.moe_route", "arks.moe_dequant", "arks.moe_dot",
          "arks.moe_shared")


def read(ctx):
    got = _scopes.by_scope(ctx)
    if not got or ctx["device"]["busy_s"] <= 0 \
            or "arks.moe_shared" not in got:
        return None
    return 100.0 * sum(got.get(s, 0.0) for s in SCOPES) \
        / ctx["device"]["busy_s"]
