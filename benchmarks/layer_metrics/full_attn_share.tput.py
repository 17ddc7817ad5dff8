"""Share of the device's busy time in the FULL attention layers, in
percent: the scopes ``arks.attn_qkv``, ``arks.attn_kernel``, ``arks.attn_layout``
and ``arks.attn_out`` (the projections and RoPE of the kind, the KV
row write and the ragged launch, its block layout, the output projection).
The per-head gate (``arks.attn_gate``) is one scope for both kinds and in
neither share.  Nothing to read where the program has no
``arks.attn_win_*`` scope: a model of one kind of layer has one attention
share, which ``attn_layout_share`` and the roofline readers already
split."""

from benchmarks.layer_metrics import _scopes

SCOPES = ('arks.attn_qkv', 'arks.attn_kernel', 'arks.attn_layout', 'arks.attn_out')


def read(ctx):
    got = _scopes.by_scope(ctx)
    if not got or ctx["device"]["busy_s"] <= 0 \
            or not any(s.startswith("arks.attn_win_") for s in got if s):
        return None
    return 100.0 * sum(got.get(s, 0.0) for s in SCOPES) \
        / ctx["device"]["busy_s"]
