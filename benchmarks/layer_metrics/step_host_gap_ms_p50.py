"""Median host time between two sequential mixed steps, in ms.

Per sequential step: from the end of ``phase.mixed.wait`` (the step's
results are on the host, the device has nothing queued) to the end of the
next ``phase.mixed.dispatch`` (the next step is handed to the device):
fan-out, promotion, the rest of ``step()``, then retire, pack, count and
put of the next step.  A pair with a pipelined decode phase in between is
not two sequential steps in a row and is left out."""

from benchmarks.client_metrics import percentile
from benchmarks.layer_metrics._spans import window_spans

WAIT, DISPATCH, PIPELINED = ("phase.mixed.wait", "phase.mixed.dispatch",
                             "phase.decode")


def gaps(spans):
    out, waited = [], None
    for s in spans:
        if s["name"] == WAIT:
            waited = s["end"]
        elif s["name"].startswith(PIPELINED):
            waited = None
        elif s["name"] == DISPATCH and waited is not None:
            out.append(s["end"] - waited)
            waited = None
    return out


def read(ctx):
    spans = window_spans(ctx)
    if not spans:
        return None
    p50 = percentile(gaps(spans), 50)
    return None if p50 is None else p50 * 1e3
