"""The latent paged-attention kernel's share of its roofline, in percent.

Kernel time: the summed device time of the ops named
``paged_latent_attention`` inside the traced slice.  Needed work: every
content character a client received inside the slice is one decode token
attending over (prompt + characters so far) cached rows; every request
whose prefill fell (partly) inside the slice adds that part of its prompt,
cut at the step's prefill budget (``ARKS_MIXED_CHUNK_TOKENS`` of the
configuration's ``deploy.json``).  A prompt's tokens are taken as spread
evenly between the request's sending and its first token, and the slice
gets the tokens that fall inside it WITH the context they have by then:
a long prompt's later chunks carry most of its (query, key) pairs, and a
request that queues behind others waits for its first token longer than
the slice lasts (8 s at the median of 32 callers, against 3 s), so taking
the prompt's leading chunks for the share, as ``attn_roofline.py`` does
for short chat prompts, counted a fifth of the pairs the kernel saw there.  Both come from the load generator's
records, not from the program.  The kernel's shapes come from the cell's
reference family (``kernel_shapes``), the stored width of a row from the
engine's pool; a program without a latent kernel leaves nothing to read."""

from benchmarks import peaks, trace_reduce
from benchmarks.kernels import paged_latent_attention as k

NEEDLE = "paged_latent_attention"


def calls_in_slice(run, t0, t1, chunk):
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
        per_s = r["prompt_tokens"] / (r["first"] - r["sent"])
        at = int(round((max(r["sent"], t0) - r["sent"]) * per_s))
        end = int(round((min(r["first"], t1) - r["sent"]) * per_s))
        while at < end:
            # One call a step: up to the next multiple of the budget.
            upto = min((at // chunk + 1) * chunk, end)
            calls.append((upto - at, upto))
            at = upto
    return calls


def read(ctx):
    dev = ctx["device"]
    if not dev:
        return None
    kernel_s, _ = trace_reduce.sum_by_name(dev["ops"], NEEDLE)
    if kernel_s <= 0:
        return None
    ref = ctx["cell"]["reference"]
    pool = getattr(getattr(ctx["engine"], "_cache", None), "k", None)
    if not hasattr(ref, "kernel_shapes") or pool is None:
        return None
    shapes = ref.kernel_shapes(ref.arch(ctx["cell"]["config"]))
    if "row" not in shapes:
        return None
    chunk = int((ctx["cell"]["deploy"].get("env") or {}).get(
        "ARKS_MIXED_CHUNK_TOKENS", 256))
    t0, t1 = dev["slice_monotonic"]
    w = k.work(**shapes, row_bytes=pool.shape[-1] * pool.dtype.itemsize,
               calls=calls_in_slice(ctx["run"], t0, t1, chunk))
    least, bound = k.least_seconds(w, peaks.peaks(ctx["kind"]))
    dev["latent_attn_roofline_detail"] = {
        "kernel_s": kernel_s, "least_s": least, "bound": bound, **w}
    return 100.0 * least / kernel_s
