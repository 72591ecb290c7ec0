"""The grouped-matmul kernels' share of their roofline where the chip holds a
part of the experts: `moe_gmm_*` calls in the trace x the required work of
one call (benchmark/moe_cost.gmm_cost) at the rows the program COUNTED as
routed to its held experts, over the experts held, over the kernels' device
time. Rows by the counter, never by expectation, so the share cannot pass
100% by a routing that sends fewer: `moe_rows_held` (the mean over the expert
layers) of every `train.report` span that ended inside the window, each over
that report's `moe_passes` (where a layer took more than one pass through
its buffer a call is priced at that share of the rows: exact for one pass,
the usual case, and an under-count otherwise), and of those the mean: the
trace covers a segment of the window, not the last report's step. Under one
row tile a group (`moe_gmm_tile_rows` of the `train.init.step_fn` span)
there is nothing to measure: the kernels' time is then the held experts'
weights alone, whatever the rows, and no number is given. A program without
the counters or without the kernels, or a family without an expert layer
(`model_config.expert_layer`), has nothing to read."""

from .. import model_config, moe_cost, roofline
from ..trace_reduce import count_of, seconds_of
from .moe_held_rows_off_even import window_reports
from .program_spans import program_spans

PREFIXES = ("moe_gmm_",)


def _tile_rows():
    plans = [s["attrs"]["moe_gmm_tile_rows"] for s in program_spans() or ()
             if s["name"] == "train.init.step_fn" and "moe_gmm_tile_rows" in s.get("attrs", {})]
    return float(plans[-1]) if plans else 1.0


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel_s, calls = seconds_of(trace, PREFIXES), count_of(trace, PREFIXES)
    rows, passes = window_reports(ctx, "moe_rows_held"), window_reports(ctx, "moe_passes")
    experts = model_config.expert_layer(ctx["conf"])
    if (kernel_s <= 0 or not calls or not rows or not passes or len(rows) != len(passes)
            or not experts):
        return None
    rows = sum(r / max(p, 1.0) for r, p in zip(rows, passes)) / len(rows)
    if rows < experts["held"] * _tile_rows():
        return None
    cost = moe_cost.gmm_cost(rows=rows, k=experts["hidden"], n=experts["width"],
                             groups=experts["held"])
    least = roofline.roofline_seconds(cost, ctx["device"]["kind"])["seconds"]
    return 100.0 * calls * least / kernel_s
