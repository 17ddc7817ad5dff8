"""Share of the device's busy time under ``arks.ffn``, in percent: a dense
SwiGLU FFN whole with the norm ahead of it (a routed layer's parts carry
scopes of their own and are not in it).  In the shortcut block these are
the two dense FFNs a layer that the routed layer's shortcut runs beside.
Nothing to read where no op carries the scope."""

from benchmarks.layer_metrics import _scopes


def read(ctx):
    got = _scopes.by_scope(ctx)
    if not got or "arks.ffn" not in got:
        return None
    return _scopes.share(ctx, "arks.ffn")
