"""Percentile of the time between two request-log phase marks, per request,
for requests whose `end` mark fell inside the window (milliseconds)."""

from ..counting import percentile


def read(ctx, *, start: str, end: str, percentile_q: float):
    first = {}
    for mark in ctx.get("marks", ()):
        first.setdefault((mark["rid"], mark["phase"]), mark["mono"])
    spans = [
        (t_end - first[(rid, start)]) * 1e3
        for (rid, phase), t_end in first.items()
        if phase == end and ctx["t0"] <= t_end < ctx["t1"] and (rid, start) in first
    ]
    return percentile(spans, percentile_q)
