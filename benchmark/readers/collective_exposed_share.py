"""Collective time during which no compute ran on the device, over the
traced window, in percent (mean over the chips)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
