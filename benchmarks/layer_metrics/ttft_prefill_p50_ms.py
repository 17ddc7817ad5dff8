"""Median prefill leg of the requests that arrived in the window: the
summed duration of each request's closed ``prefill`` spans (admission to
first token: the leg of the time to first token after the ``queue`` leg
that ``queue_wait_p50_ms`` reads, same arithmetic)."""

from benchmarks.client_metrics import percentile


def read(ctx):
    legs = []
    for t in ctx["traces"]:
        spans = [s for s in t["spans"]
                 if s.get("component") in (None, "engine")
                 and s["name"] == "prefill" and s.get("end") is not None]
        if spans:
            legs.append(sum(s["end"] - s["start"] for s in spans))
    p50 = percentile(legs, 50)
    return None if p50 is None else p50 * 1e3
