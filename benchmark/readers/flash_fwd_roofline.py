"""The training forward attention kernel's share of its roofline: calls in
the trace x the required work of one call at this device's shard of the
batch and heads, over the kernel's device time."""

from .. import model_config, roofline
from ..trace_reduce import count_of, seconds_of


def read(ctx, *, prefixes=("flash_fwd",)):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel_s, calls = seconds_of(trace, prefixes), count_of(trace, prefixes)
    if kernel_s <= 0 or not calls:
        return None
    shape = model_config.shape_numbers(ctx["conf"])
    mesh = ctx["conf"].get("trainer", {}).get("mesh") or {}
    data_ways = mesh.get("dp", 1) * mesh.get("fsdp", 1)
    tp = mesh.get("tp", 1)
    cost = roofline.flash_fwd_cost(
        batch=ctx["traffic"]["batch"] // data_ways, seq=ctx["traffic"]["seq"],
        n_q_heads=shape["n_q_heads"] // tp, n_kv_heads=shape["n_kv_heads"] // tp,
        head_dim=shape["head_dim"])
    least = roofline.roofline_seconds(cost, ctx["device"]["kind"])["seconds"]
    return 100.0 * calls * least / kernel_s
