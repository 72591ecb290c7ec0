"""The forward selective scan's share of its roofline: the least time the
chip's published peaks allow one forward scan (benchmark/ssd_cost.scan_cost
at the configuration's sizes and the traffic's batch and sequence: nothing
the program chooses), times the forward scans in the traced window
(executions of the traced step x the family's count of state-space layers),
over the device time the program's operation table places under the scope
`ssm.scan` in the pass `fwd`. It prices the same work whatever implements
the scan. A family without such a layer (its adapter has no
`state_space_layer`), a run without a trace, or a program without the table
or the scope (the parent of the PR that brought them) has nothing to read."""

from .. import model_config, roofline, ssd_cost

SCOPE, PASS = "ssm.scan", "fwd"


def read(ctx):
    trace = ctx.get("trace")
    sizes = getattr(model_config.adapter(ctx["conf"]), "state_space_layer", None)
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
    scan_s = split["by_scope_pass"].get((SCOPE, PASS), 0.0)
    sizes = sizes(ctx["conf"])
    if scan_s <= 0 or not sizes["layers"]:
        return None
    mesh = ctx["conf"].get("trainer", {}).get("mesh") or {}
    cost = ssd_cost.scan_cost(
        batch=ctx["traffic"]["batch"] // (mesh.get("dp", 1) * mesh.get("fsdp", 1)),
        seq=ctx["traffic"]["seq"], heads=sizes["heads"], head_dim=sizes["head_dim"],
        state=sizes["state"], groups=sizes["groups"])
    least = roofline.roofline_seconds(cost, ctx["device"]["kind"])["seconds"]
    return 100.0 * len(runs[program]) * sizes["layers"] * least / scan_s
