"""A window layer's paged-attention launch's share of its roofline, in
percent.

Kernel time: the summed device time of the ops named
``paged_window_attention`` inside the traced slice (the ragged mixed kernel
told a window; the full layers' launch keeps the name ``attn_roofline``
reads).  Needed work (``benchmarks/kernels/paged_window_attention.py``):
only the keys inside the window count as bytes and only the unmasked pairs
as operations, so the share reads the same work whatever implements it.
The calls come from the load generator's records as
``latent_attn_roofline.tput`` takes them: every content character a client
received inside the slice is one decode token at (prompt + characters so
far); a prompt's tokens are spread evenly between the request's sending and
its first token, cut at the step's prefill budget
(``ARKS_MIXED_CHUNK_TOKENS`` of ``deploy.json``).  The shapes come from the
cell's reference family (``window_kernel_shapes``); a family without them,
or a program without the launch, leaves nothing to read."""

from benchmarks import manifest, peaks, trace_reduce
from benchmarks.kernels import paged_window_attention as k

NEEDLE = "paged_window_attention"


def read(ctx):
    dev = ctx["device"]
    if not dev:
        return None
    kernel_s, _ = trace_reduce.sum_by_name(dev["ops"], NEEDLE)
    if kernel_s <= 0:
        return None
    ref = ctx["cell"]["reference"]
    if not hasattr(ref, "window_kernel_shapes"):
        return None
    shapes = ref.window_kernel_shapes(ref.arch(ctx["cell"]["config"]))
    kv = ctx["engine"].resolved_config.get("kv_dtype")
    width = {"int8": (1.0, 4.0), "int4": (0.5, 4.0)}.get(kv, (2.0, 0.0))
    chunk = int((ctx["cell"]["deploy"].get("env") or {}).get(
        "ARKS_MIXED_CHUNK_TOKENS", 256))
    calls_in_slice = manifest._load(
        "benchmarks.layer_metrics._",
        manifest.metric_paths("latent_attn_roofline.tput")[1]).calls_in_slice
    t0, t1 = dev["slice_monotonic"]
    w = k.work(**shapes, kv_bytes=width[0], kv_scale_bytes=width[1],
               calls=calls_in_slice(ctx["run"], t0, t1, chunk))
    least, bound = k.least_seconds(w, peaks.peaks(ctx["kind"]))
    dev["window_attn_roofline_detail"] = {
        "kernel_s": kernel_s, "least_s": least, "bound": bound, **w}
    return 100.0 * least / kernel_s
