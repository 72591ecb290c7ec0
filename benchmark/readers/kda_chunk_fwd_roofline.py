"""The forward delta rule's share of its roofline: the least time the chip's
published peaks allow one forward recurrence (benchmark/kda_cost.recurrence_cost
at the configuration's sizes and the traffic's batch and sequence: nothing the
program chooses), times the forward recurrences in the traced window
(executions of the traced step x the family's count of delta-rule layers), over
the device time the program's operation table places under the scope
`kda.chunk` in the pass `fwd`. It prices the same work whatever implements the
rule. A family without such a layer (its adapter has no `delta_rule_layer`), a
run without a trace, or a program without the table or the scope (the parent
of the PR that brought them) has nothing to read."""

from .. import kda_cost, model_config, roofline

SCOPE, PASS = "kda.chunk", "fwd"


def read(ctx):
    trace = ctx.get("trace")
    sizes = getattr(model_config.adapter(ctx["conf"]), "delta_rule_layer", None)
    if not trace or sizes is None:
        return None
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
    rule_s = split["by_scope_pass"].get((SCOPE, PASS), 0.0)
    sizes = sizes(ctx["conf"])
    if rule_s <= 0 or not sizes["layers"]:
        return None
    mesh = ctx["conf"].get("trainer", {}).get("mesh") or {}
    cost = kda_cost.recurrence_cost(
        batch=ctx["traffic"]["batch"] // (mesh.get("dp", 1) * mesh.get("fsdp", 1)),
        seq=ctx["traffic"]["seq"], heads=sizes["heads"], head_dim=sizes["head_dim"])
    least = roofline.roofline_seconds(cost, ctx["device"]["kind"])["seconds"]
    return 100.0 * len(runs[program]) * sizes["layers"] * least / rule_s
