"""An attribute of the last `train.report` span that ended inside the
window: what the program's step reported of itself (a counter the host
read with the step's metrics). A program without the span record or
without the attribute has nothing to read."""

from .program_spans import program_spans


def read(ctx, *, attribute, span="train.report"):
    spans = program_spans()
    if spans is None or "t0" not in ctx:
        return None
    values = [
        (s["end_mono"], s["attrs"][attribute]) for s in spans
        if s["name"] == span and ctx["t0"] <= s["end_mono"] < ctx["t1"]
        and attribute in s.get("attrs", {})
    ]
    return float(max(values)[1]) if values else None
