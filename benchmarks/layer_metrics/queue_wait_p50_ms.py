"""Median admission-queue wait of the requests that arrived in the window.

The leg arithmetic is a copy of ``bench_serving.py::_ttft_decomposition``:
a request's ``queue`` leg is the summed duration of its closed engine spans
of that name (a request can queue more than once)."""

from benchmarks.client_metrics import percentile


def read(ctx):
    legs = []
    for t in ctx["traces"]:
        spans = [s for s in t["spans"]
                 if s.get("component") in (None, "engine")
                 and s["name"] == "queue" and s.get("end") is not None]
        if spans:
            legs.append(sum(s["end"] - s["start"] for s in spans))
    p50 = percentile(legs, 50)
    return None if p50 is None else p50 * 1e3
