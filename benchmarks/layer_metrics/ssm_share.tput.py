"""Share of the device's busy time in the Mamba-2 (state-space) layers, in
percent: the scopes ``arks.ssm_in`` (the input projection, the short
convolution over the slots' carry, the activations, the step size and the
decay), ``arks.ssm_state`` (the selective scan over the slots' state: the
one-step recurrence of the lanes of one row and the chunked scan of the
prefill lanes, nothing else) and ``arks.ssm_out`` (the skip, the gate, the
grouped norm and the output projection).  With ``moe_share.tput`` it
accounts for most of such a model's step: its attention layers are six of
52.  Nothing to read where the program has no ``arks.ssm_state`` scope."""

from benchmarks.layer_metrics import _scopes

SCOPES = ("arks.ssm_in", "arks.ssm_state", "arks.ssm_out")


def read(ctx):
    got = _scopes.by_scope(ctx)
    if not got or ctx["device"]["busy_s"] <= 0 \
            or "arks.ssm_state" not in got:
        return None
    return 100.0 * sum(got.get(s, 0.0) for s in SCOPES) \
        / ctx["device"]["busy_s"]
