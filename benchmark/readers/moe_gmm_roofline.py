"""The grouped-matmul kernels' share of their roofline: calls in the trace
x the required work of one call at the cell's shapes (benchmark/moe_cost.py:
the same for the forward and both backward products of all three
projections), over the kernels' device time: every routed row of the step
over the experts held, one expert's width (`model_config.expert_layer`; a
cell that holds a share of the experts reads `moe_held_gmm_roofline`
instead). A program that runs no `moe_gmm_*` kernel, or a family without an
expert layer, has nothing to read."""

from .. import model_config, moe_cost, roofline
from ..trace_reduce import count_of, seconds_of

PREFIXES = ("moe_gmm_",)


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel_s, calls = seconds_of(trace, PREFIXES), count_of(trace, PREFIXES)
    experts = model_config.expert_layer(ctx["conf"])
    if kernel_s <= 0 or not calls or not experts:
        return None
    cost = moe_cost.gmm_cost(
        rows=ctx["traffic"]["batch"] * ctx["traffic"]["seq"] * experts["per_token"],
        k=experts["hidden"], n=experts["width"], groups=experts["held"])
    least = roofline.roofline_seconds(cost, ctx["device"]["kind"])["seconds"]
    return 100.0 * calls * least / kernel_s
