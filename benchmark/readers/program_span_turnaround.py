"""Median time, in milliseconds, from the end of one named program span to
the end of the next span of another name, over the pairs that lie inside
the window; with `within`, only the part of it that spans of that name
cover. With `train.report.read`, `train.step.dispatch` and `train.loop`
it is the program's part of the device's idle gap at a segment boundary:
from the moment the host has the finished step's metrics to the moment
the next step is handed to the device, less whatever the caller does
between its two `train()` calls (in a traced run the benchmark starts
and stops the profiler there, which takes milliseconds to seconds)."""

from ..counting import percentile
from ..trace_reduce import length, union
from .program_spans import program_spans


def read(ctx, *, start, end, within=None):
    spans = program_spans()
    if spans is None or "t0" not in ctx:
        return None
    t0, t1 = ctx["t0"], ctx["t1"]
    ends = sorted(s["end_mono"] for s in spans if s["name"] == end)
    covers = [(s["start_mono"], s["end_mono"]) for s in spans if s["name"] == within]
    turnarounds = []
    for s in spans:
        if s["name"] != start or not t0 <= s["end_mono"] < t1:
            continue
        began = s["end_mono"]
        done = next((t for t in ends if t > began), None)
        if done is None or done >= t1:
            continue
        if within is None:
            turnarounds.append((done - began) * 1e3)
        else:
            turnarounds.append(1e3 * length(union(
                (max(a, began), min(b, done)) for a, b in covers if b > began and a < done)))
    return percentile(turnarounds, 50.0)
