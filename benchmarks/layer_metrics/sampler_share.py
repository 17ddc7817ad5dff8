"""Share of the device's busy time under ``arks.sampler``, in percent:
token counting, logit shaping, the sample itself and the logprob top-k,
inside the step programs."""

from benchmarks.layer_metrics import _scopes


def read(ctx):
    return _scopes.share(ctx, "arks.sampler")
