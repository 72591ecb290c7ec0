"""The forward gated short convolution's share of its roofline: the least time
the chip's published peaks allow one forward gated convolution
(benchmark/sconv_cost.gated_conv_cost at the configuration's sizes and the
traffic's batch and sequence: nothing the program chooses), times the forward
convolutions in the traced window (executions of the traced step x the
family's count of conv layers), over the device time the program's operation
table places under the scope `sconv.conv` in the pass `fwd`. It prices the
same work whatever implements the op. A family without such a layer (its
adapter has no `short_conv_layer`), a run without a trace, or a program
without the table or the scope (the parent of the PR that brought them) has
nothing to read."""

from .. import model_config, roofline, sconv_cost

SCOPE, PASS = "sconv.conv", "fwd"


def read(ctx):
    trace = ctx.get("trace")
    sizes = getattr(model_config.adapter(ctx["conf"]), "short_conv_layer", None)
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
    conv_s = split["by_scope_pass"].get((SCOPE, PASS), 0.0)
    sizes = sizes(ctx["conf"])
    if conv_s <= 0 or not sizes["layers"]:
        return None
    mesh = ctx["conf"].get("trainer", {}).get("mesh") or {}
    cost = sconv_cost.gated_conv_cost(
        batch=ctx["traffic"]["batch"] // (mesh.get("dp", 1) * mesh.get("fsdp", 1)),
        seq=ctx["traffic"]["seq"], channels=sizes["channels"], taps=sizes["taps"])
    least = roofline.roofline_seconds(cost, ctx["device"]["kind"])["seconds"]
    return 100.0 * len(runs[program]) * sizes["layers"] * least / conv_s
