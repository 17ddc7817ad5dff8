"""How late the load generator ran: sent - due, 95th percentile."""


def read(ctx):
    if ctx["run"]["loop"] != "open":
        return None
    return ctx["client"]["gen_late_p95_ms"]
