"""Peak bytes (in use plus reserved for programs' temporaries) on the
fullest chip over its limit, in percent."""


def read(ctx):
    memory = ctx.get("memory") or {}
    if not memory.get("peak_bytes") or not memory.get("bytes_limit"):
        return None
    return 100.0 * memory["peak_bytes"] / memory["bytes_limit"]
