"""The trainer's input wait over the window, in percent."""


def read(ctx):
    window = ctx.get("window")
    if not window:
        return None
    return 100.0 * window["input_wait_s"] / (window["t1"] - window["t0"])
