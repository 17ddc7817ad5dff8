"""Share of the device's busy time under ``arks.attn_layout``, in percent:
everything between the projections and the attention kernel and back (the
gather of the flat queries into one dense block per lane, the pad to the
kernel's query blocks, the scatter back)."""

from benchmarks.layer_metrics import _scopes


def read(ctx):
    return _scopes.share(ctx, "arks.attn_layout")
