"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of the `XLA Ops` intervals / window), averaged over the
chips used.  Source: device_trace.  Layer: device."""


def read(ctx):
    red = ctx.get("reduced")
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
