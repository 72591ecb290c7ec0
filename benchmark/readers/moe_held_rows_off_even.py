"""How far the rows routed to the experts held here lie from an even
router's: |`moe_rows_held_share` - 100 x held / published|, in points of the
T k routed rows, the share being the mean over the `train.report` spans that
ended inside the window (each carries the step's own count: rows routed to
held experts over T k, mean over the expert layers). The share itself has no
better direction (a router that sends nothing here, or everything, is at
fault either way, and the rate follows the rows); the distance has: 0 is a
router that loads the published experts evenly. Held and published are the
family's to say (`model_config.expert_layer`). A program without the span
record or the counter, a family without an expert layer, or a configuration
that holds every expert, has nothing to read."""

from .. import model_config
from .program_spans import program_spans

ATTRIBUTE = "moe_rows_held_share"


def window_reports(ctx, attribute, span="train.report"):
    """The attribute's values on the spans of that name that ended inside the
    window, in order; None without a span record."""
    spans = program_spans()
    if spans is None or "t0" not in ctx:
        return None
    return [float(s["attrs"][attribute]) for s in sorted(spans, key=lambda s: s["end_mono"])
            if s["name"] == span and ctx["t0"] <= s["end_mono"] < ctx["t1"]
            and attribute in s.get("attrs", {})]


def read(ctx):
    experts = model_config.expert_layer(ctx["conf"])
    shares = window_reports(ctx, ATTRIBUTE)
    if not shares or not experts or experts["published"] == experts["held"]:
        return None
    return abs(sum(shares) / len(shares) - 100.0 * experts["held"] / experts["published"])
