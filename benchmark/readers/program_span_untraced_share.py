"""The share of set-up, in percent, that no program span covers: `setup_s`
less the union of all of the program's spans between process start
(`t0 - setup_s` on the window's clock) and window open, over `setup_s`.
What is left is imports, the device runtime's start and the benchmark's
own work."""

from ..trace_reduce import length, union
from .program_spans import program_spans


def read(ctx):
    spans = program_spans()
    setup_s = ctx.get("setup_s")
    if spans is None or not setup_s or "t0" not in ctx:
        return None
    t0 = ctx["t0"]
    start = t0 - setup_s
    covered = length(union(
        (max(s["start_mono"], start), min(s["end_mono"], t0))
        for s in spans if s["end_mono"] > start and s["start_mono"] < t0))
    return 100.0 * (setup_s - covered) / setup_s
