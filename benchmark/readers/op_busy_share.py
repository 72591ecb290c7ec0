"""Device time of the operations whose names start with one of
`prefixes`, over the device's busy time in the trace, in percent."""

from ..trace_reduce import seconds_of


def read(ctx, *, prefixes):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    return 100.0 * seconds_of(trace, prefixes) / trace["busy_s"]
