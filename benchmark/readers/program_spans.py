"""Seconds, a count or a percentile of the program's own spans
(`ray_tpu.util.tracing`) of the given names, among those that ended in
set-up (before the window opened) or inside the window.

The spans carry the clock the window is measured on (`start_mono`,
`end_mono`: `time.perf_counter`, which is `harness.now`). A program
without that span record (the parent of the PR that brought it) has
nothing to read, and the metric is left out.
"""

from ..counting import percentile
from ..trace_reduce import length, union

SCALE = {"s": 1.0, "ms": 1e3}


def program_spans():
    """Every recorded span that carries the monotonic stamps, or None
    where the program keeps no such record."""
    try:
        from ray_tpu.util import tracing

        spans = tracing.tracer().spans(limit=10**9)
    except Exception:  # noqa: BLE001 - a program without the record: nothing to read
        return None
    spans = [s for s in spans if s.get("end_mono") and "start_mono" in s]
    return spans or None


def read(ctx, *, names, phase, stat, unit="s"):
    """`phase`: "setup" or "window". `stat`: "sum" (seconds, summed),
    "covered" (seconds some span of these names covers: a span nested in
    another counts once), "count", or "p50"."""
    spans = program_spans()
    if spans is None or "t0" not in ctx:
        return None
    t0, t1 = ctx["t0"], ctx["t1"]
    chosen = [
        s for s in spans if s["name"] in names
        and (s["end_mono"] <= t0 if phase == "setup" else t0 <= s["end_mono"] < t1)
    ]
    if stat == "count":
        return len(chosen)
    if stat == "covered":
        return SCALE[unit] * length(union((s["start_mono"], s["end_mono"]) for s in chosen))
    durations = [SCALE[unit] * (s["end_mono"] - s["start_mono"]) for s in chosen]
    if stat == "sum":
        return sum(durations)
    return percentile(durations, 50.0)
