"""The grouped-matmul kernels' share of their roofline: calls in the trace
x the required work of one call at the cell's shapes (benchmark/moe_cost.py:
the same for the forward and both backward products of all three
projections), over the kernels' device time. A program that runs no
`moe_gmm_*` kernel has nothing to read."""

from .. import moe_cost, roofline
from ..trace_reduce import count_of, seconds_of

PREFIXES = ("moe_gmm_",)


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel_s, calls = seconds_of(trace, PREFIXES), count_of(trace, PREFIXES)
    if kernel_s <= 0 or not calls:
        return None
    conf, traffic = ctx["conf"], ctx["traffic"]
    cost = moe_cost.gmm_cost(
        rows=traffic["batch"] * traffic["seq"] * conf["num_experts_per_tok"],
        k=conf["hidden_size"], n=conf["intermediate_size"], groups=conf["num_experts"])
    least = roofline.roofline_seconds(cost, ctx["device"]["kind"])["seconds"]
    return 100.0 * calls * least / kernel_s
