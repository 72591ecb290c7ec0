"""The windowed training forward attention kernel's share of its roofline:
`flash_win_fwd`'s calls in the trace x the required work of one windowed call
(benchmark/window_cost.py: the live pairs sum_i min(i + 1, W), every q, k, v,
output and lse row once), over the kernel's device time. A program that runs
no such kernel (the parent of the PR that brought it), or a configuration
whose family states no window (`model_config.attention_window`), has
nothing to read."""

from .. import model_config, roofline, window_cost
from ..trace_reduce import count_of, seconds_of

PREFIXES = ("flash_win_fwd",)


def read(ctx):
    trace, window = ctx.get("trace"), model_config.attention_window(ctx["conf"])
    if not trace or not window:
        return None
    kernel_s, calls = seconds_of(trace, PREFIXES), count_of(trace, PREFIXES)
    if kernel_s <= 0 or not calls:
        return None
    shape = model_config.shape_numbers(ctx["conf"])
    cost = window_cost.flash_win_fwd_cost(
        batch=ctx["traffic"]["batch"], seq=ctx["traffic"]["seq"], window=window,
        n_q_heads=shape["n_q_heads"], n_kv_heads=shape["n_kv_heads"], head_dim=shape["head_dim"])
    least = roofline.roofline_seconds(cost, ctx["device"]["kind"])["seconds"]
    return 100.0 * calls * least / kernel_s
