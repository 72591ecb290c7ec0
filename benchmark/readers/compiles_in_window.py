"""Programs compiled, or fetched from the persistent cache, inside the
window: must read 0 (a run where it does not is not `correct`)."""


def read(ctx):
    return ctx.get("compiles_in_window")
