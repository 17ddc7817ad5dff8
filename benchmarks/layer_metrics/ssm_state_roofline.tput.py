"""The selective scan's state update's share of its roofline, in percent.

Time: the device self time of the ops under the scope ``arks.ssm_state``
inside the traced slice (the one-step recurrence over the slots' state and
the chunked scan, nothing else; whatever implements them keeps the scope).
Needed work (``benchmarks/kernels/ssm_state_update.py``): a sequence's state
read once and written once a dispatch a layer at its stored width, the rows
that drive it, five operations a state element a token; the same count
whether XLA or a kernel does the work.  The calls come from the load
generator's records as ``linear_state_roofline.tput`` takes them (every
content character a client received inside the slice is one decode token; a
prompt's tokens are spread evenly between the request's sending and its
first token, cut at the step's prefill budget, ``ARKS_MIXED_CHUNK_TOKENS``
of ``deploy.json``).  The shapes come from the cell's reference family
(``ssm_kernel_shapes``); a family without them, or a program without the
scope, leaves nothing to read."""

from benchmarks import manifest, peaks
from benchmarks.kernels import ssm_state_update as k
from benchmarks.layer_metrics import _scopes

SCOPE = "arks.ssm_state"


def read(ctx):
    dev = ctx["device"]
    got = _scopes.by_scope(ctx)
    if not dev or not got or got.get(SCOPE, 0.0) <= 0:
        return None
    ref = ctx["cell"]["reference"]
    if not hasattr(ref, "ssm_kernel_shapes"):
        return None
    shapes = ref.ssm_kernel_shapes(ref.arch(ctx["cell"]["config"]))
    chunk = int((ctx["cell"]["deploy"].get("env") or {}).get(
        "ARKS_MIXED_CHUNK_TOKENS", 256))
    calls_in_slice = manifest._load(
        "benchmarks.layer_metrics._",
        manifest.metric_paths("latent_attn_roofline.tput")[1]).calls_in_slice
    t0, t1 = dev["slice_monotonic"]
    w = k.work(**shapes, calls=calls_in_slice(ctx["run"], t0, t1, chunk))
    least, bound = k.least_seconds(w, peaks.peaks(ctx["kind"]))
    dev["ssm_state_roofline_detail"] = {
        "scope_s": got[SCOPE], "least_s": least, "bound": bound, **w}
    return 100.0 * least / got[SCOPE]
