"""Share of the device's busy time in the softmax GQA layers of a model
that also has LINEAR-attention layers, in percent: the scopes
``arks.attn_qkv`` (the projections; no rotation in such a model),
``arks.attn_kernel`` and ``arks.attn_layout`` (the KV row write, the ragged
launch and its block layout), ``arks.attn_gate`` (the elementwise output
gate: here it belongs to this kind alone, unlike the per-head gate of a
model with window layers, which ``full_attn_share.tput`` leaves out) and
``arks.attn_out``.  With ``linear_attn_share.tput`` and ``moe_share.tput``
it accounts for a step's layers; the head, the sampler and the embedding
are the rest.  Nothing to read where the program has no ``arks.linear_*``
scope: a model of one kind of layer has one attention share, which
``attn_layout_share`` and the roofline readers already split."""

from benchmarks.layer_metrics import _scopes

SCOPES = ("arks.attn_qkv", "arks.attn_kernel", "arks.attn_layout",
          "arks.attn_gate", "arks.attn_out")


def read(ctx):
    got = _scopes.by_scope(ctx)
    if not got or ctx["device"]["busy_s"] <= 0 \
            or "arks.linear_state" not in got:
        return None
    return 100.0 * sum(got.get(s, 0.0) for s in SCOPES) \
        / ctx["device"]["busy_s"]
