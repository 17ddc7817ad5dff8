"""Share of the traced slice in which no operation ran on the device."""


def read(ctx):
    dev = ctx["device"]
    if not dev or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
