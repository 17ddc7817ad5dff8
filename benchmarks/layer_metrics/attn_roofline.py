"""The mixed paged-attention kernel's share of its roofline, in percent.

Kernel time: the summed device time of the ops named
``paged_mixed_attention`` inside the traced slice.  Needed work: every
content character a client received inside the slice is one decode token
attending over (prompt + characters so far) cached tokens; every request
whose prefill fell (partly) inside the slice adds that part of its chunks.
Both come from the load generator's records, not from the program.  The
kernel's shapes come from the cell's reference family (``kernel_shapes``);
a family without them leaves nothing to read."""

from benchmarks import peaks, trace_reduce
from benchmarks.kernels import paged_mixed_attention as k

NEEDLE = "paged_mixed_attention"
CHUNK = 256


def calls_in_slice(run, t0, t1):
    calls = []
    for r in run["records"]:
        seen = 0
        for t, n in r["frames"]:
            if t0 <= t < t1:
                calls += [(1, r["prompt_tokens"] + seen + i)
                          for i in range(n)]
            seen += n
        if r["first"] is None or r["first"] <= r["sent"]:
            continue
        part = (min(r["first"], t1) - max(r["sent"], t0)) \
            / (r["first"] - r["sent"])
        if part <= 0:
            continue
        n = r["prompt_tokens"]
        chunks = [(min(CHUNK, n - c), min(c + CHUNK, n))
                  for c in range(0, n, CHUNK)]
        calls += chunks[:max(int(round(part * len(chunks))), 0)]
    return calls


def read(ctx):
    dev = ctx["device"]
    if not dev:
        return None
    kernel_s, _ = trace_reduce.sum_by_name(dev["ops"], NEEDLE)
    if kernel_s <= 0:
        return None
    ref = ctx["cell"]["reference"]
    if not hasattr(ref, "kernel_shapes"):
        return None
    shapes = ref.kernel_shapes(ref.arch(ctx["cell"]["config"]))
    kv = ctx["engine"].resolved_config.get("kv_dtype")
    width = {"int8": (1.0, 4.0), "int4": (0.5, 4.0)}.get(kv, (2.0, 0.0))
    t0, t1 = dev["slice_monotonic"]
    w = k.work(**shapes, kv_bytes=width[0], kv_scale_bytes=width[1],
               calls=calls_in_slice(ctx["run"], t0, t1))
    least, bound = k.least_seconds(w, peaks.peaks(ctx["kind"]))
    dev["attn_roofline_detail"] = {"kernel_s": kernel_s, "least_s": least,
                                   "bound": bound, **w}
    return 100.0 * least / kernel_s
