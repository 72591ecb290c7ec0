"""Share of decode lanes that carried a token: decode_tokens /
(decode_steps * max_slots) over the window, in percent."""


def read(ctx):
    c0, c1 = ctx.get("counters0"), ctx.get("counters1")
    if not c0 or not c1:
        return None
    steps = c1["decode_steps"] - c0["decode_steps"]
    if steps <= 0:
        return None
    tokens = c1["decode_tokens"] - c0["decode_tokens"]
    return 100.0 * tokens / (steps * ctx["conf"]["engine"]["max_slots"])
