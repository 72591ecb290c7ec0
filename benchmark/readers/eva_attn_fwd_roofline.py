"""The forward EVA attention's share of its roofline: the least time the
chip's published peaks allow ONE forward call (benchmark/eva_cost.eva_fwd_cost
at the configuration's sizes and this device's share of the traffic's batch:
the pooling, the causally visible local pairs, the visible summaries; nothing
the program chooses), times the forward calls in the traced window
(executions of the traced step x the layers that are run), over the device
time the program's operation table places under the scope `attn.eva` in the
pass `fwd`. It prices the same work whatever implements it. With `kernel`:
that kernel alone (by its name's prefix, every call of it in the trace,
recomputed ones too) against `eva_cost.eva_far_fwd_cost`, the far part as the
kernel is given it. A family without such a layer (its adapter has no
`eva_layer`), a run without a trace, or a program without the table, the scope
or the kernel (the parent of the PR that brought them) has nothing to read."""

from .. import eva_cost, model_config, roofline
from ..trace_reduce import count_of, seconds_of

SCOPE, PASS = "attn.eva", "fwd"


def read(ctx, *, kernel=None):
    trace = ctx.get("trace")
    sizes = getattr(model_config.adapter(ctx["conf"]), "eva_layer", None)
    if not trace or sizes is None:
        return None
    sizes = sizes(ctx["conf"])
    mesh = ctx["conf"].get("trainer", {}).get("mesh") or {}
    shard = dict(batch=ctx["traffic"]["batch"] // (mesh.get("dp", 1) * mesh.get("fsdp", 1)),
                 seq=ctx["traffic"]["seq"], heads=sizes["heads"] // mesh.get("tp", 1),
                 head_dim=sizes["head_dim"], window=sizes["window"], chunk=sizes["chunk"])
    if kernel:
        calls, seconds = count_of(trace, (kernel,)), seconds_of(trace, (kernel,))
        cost = eva_cost.eva_far_fwd_cost(**shard)
    else:
        try:
            from ray_tpu.util import profiling

            tables = profiling.program_ops()
        except Exception:  # noqa: BLE001 - a program without the table: nothing to read
            return None
        runs = {name: found for name, found in trace.get("program_seconds", {}).items() if name in tables}
        if not runs:
            return None
        program = max(runs, key=lambda name: sum(runs[name]))
        split = profiling.scope_seconds(trace.get("op_seconds", {}), trace.get("op_counts", {}),
                                        tables[program])
        seconds = split["by_scope_pass"].get((SCOPE, PASS), 0.0)
        calls, cost = len(runs[program]) * sizes["layers"], eva_cost.eva_fwd_cost(**shard)
    if seconds <= 0 or not calls:
        return None
    return 100.0 * calls * roofline.roofline_seconds(cost, ctx["device"]["kind"])["seconds"] / seconds
