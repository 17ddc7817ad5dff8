"""Share of the device's busy time in a routed FFN that has identity
experts and no shared one, in percent: the scopes ``arks.moe_route``
(router, top-k, the sort into expert order, the combine),
``arks.moe_dequant``, ``arks.moe_dot`` (the held experts' contractions) and
``arks.moe_zero`` (the identity experts' part: their weights summed a token
and multiplied onto the layer's input).  In the block this was written for
the layer lies on a shortcut beside a dense FFN (``dense_ffn_share.tput``).
Nothing to read where the program has no ``arks.moe_zero`` scope
(``moe_share.tput`` reads the routed FFNs with a shared expert)."""

from benchmarks.layer_metrics import _scopes

SCOPES = ("arks.moe_route", "arks.moe_dequant", "arks.moe_dot",
          "arks.moe_zero")


def read(ctx):
    got = _scopes.by_scope(ctx)
    if not got or ctx["device"]["busy_s"] <= 0 \
            or "arks.moe_zero" not in got:
        return None
    return 100.0 * sum(got.get(s, 0.0) for s in SCOPES) \
        / ctx["device"]["busy_s"]
