"""Share of the device's busy time in the sandwich norms and the output
gates, in percent: the scopes ``arks.norm_post`` (the two post-norms of a
layer with sandwich norms, each an RMS pass over the sublayer's output on
its way to the residual add), ``arks.mla_gate`` (the latent block's
elementwise gate: its projection of the sublayer's normed input, the
sigmoid and the product over the H x v outputs) and ``arks.attn_gate`` (a
GQA layer's gate, where the model has one).  What a configuration with four
norms a layer and a gate on every mixer pays for them in a step.  Nothing
to read where the program has no ``arks.norm_post`` scope."""

from benchmarks.layer_metrics import _scopes

SCOPES = ("arks.norm_post", "arks.mla_gate", "arks.attn_gate")


def read(ctx):
    got = _scopes.by_scope(ctx)
    if not got or ctx["device"]["busy_s"] <= 0 \
            or "arks.norm_post" not in got:
        return None
    return 100.0 * sum(got.get(s, 0.0) for s in SCOPES) \
        / ctx["device"]["busy_s"]
