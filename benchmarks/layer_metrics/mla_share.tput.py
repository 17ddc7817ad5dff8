"""Share of the device's busy time in the latent attention block, in
percent: the scopes ``arks.mla_q`` (query down, norm, up, absorb),
``arks.mla_kv`` (latent down, norm, RoPE, the page write),
``arks.attn_kernel`` with ``arks.attn_layout`` (the latent kernel and its
block layout) and ``arks.mla_out`` (un-absorb, output projection).  Nothing
to read where the program has no ``arks.mla_*`` scope."""

from benchmarks.layer_metrics import _scopes

SCOPES = ("arks.mla_q", "arks.mla_kv", "arks.attn_kernel",
          "arks.attn_layout", "arks.mla_out")


def read(ctx):
    got = _scopes.by_scope(ctx)
    if not got or ctx["device"]["busy_s"] <= 0 \
            or not any(s.startswith("arks.mla_") for s in got if s):
        return None
    return 100.0 * sum(got.get(s, 0.0) for s in SCOPES) \
        / ctx["device"]["busy_s"]
