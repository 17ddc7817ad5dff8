"""Deltas of /metrics families over the window (shared by the readers that
take a program counter)."""


def delta(ctx, name, **labels):
    def total(metrics):
        return sum(v for lab, v in metrics.get(name, ())
                   if all(lab.get(k) == w for k, w in labels.items()))
    if name not in ctx["metrics_close"]:
        return None
    return total(ctx["metrics_close"]) - total(ctx["metrics_open"])
