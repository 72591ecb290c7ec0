"""Peak of the KV pool's pages in use, sampled every 100 ms inside the
window, over the pool's size, in percent."""


def read(ctx):
    inside = [n for t, n in ctx.get("pages", ()) if ctx["t0"] <= t < ctx["t1"]]
    if not inside or not ctx.get("pool_total"):
        return None
    return 100.0 * max(inside) / ctx["pool_total"]
