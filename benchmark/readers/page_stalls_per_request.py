"""Page stalls in the window per request that finished in it."""

from ..counting import finished_in_window


def read(ctx):
    c0, c1 = ctx.get("counters0"), ctx.get("counters1")
    finished = len(finished_in_window(ctx.get("records", ()), ctx["t0"], ctx["t1"]))
    if not c0 or not c1 or not finished:
        return None
    return (c1["page_stalls"] - c0["page_stalls"]) / finished
