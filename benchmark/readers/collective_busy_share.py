"""Device time of the traced step's collectives, as the profile's "XLA Ops"
line has it, over the device's busy time in the trace, in percent: of those
that run along exactly the mesh axes `axes` (`["tp"]`; a collective over
`fsdp` and `tp` together counts under `["fsdp", "tp"]` alone; empty = any,
the unplaced ones too), of one of `kinds` (`all-reduce`, `all-gather`,
`reduce-scatter`, `all-to-all`, `collective-permute`, `collective-broadcast`;
empty = any), under one of `scopes` (any scope on the operation's path;
empty = any) in one of `passes` (`fwd`, `recompute`, `bwd`, `optimizer`,
`other`; empty = any).

The program keeps, for its compiled step, what the optimised module says of
every operation that is a collective (`ray_tpu.util.profiling
.program_collectives`: kind, device groups, mesh axes, bytes; built in set-up
by `LMTrainer` under the span `train.report.ops`) and joins a reduced
trace's `op_seconds` and `op_counts` to it and to the operation table's
scopes and passes (`profiling.collective_seconds`, the function `ray_tpu
profile`'s record uses). What the seconds hold: a synchronous collective
whole; of an asynchronous one its start and the wait in its done. What they
leave out: the part of an asynchronous collective that runs under compute
(the "Async XLA Ops" line, which the reduced trace keeps no names of), and
the compute fusion such a collective is carried through. A program without
the registry (the parent of the PR that brought it), a run without a trace,
or a trace of a program with no table has nothing to read, and the metric is
left out.
"""


def traced_rows(trace, *, axes=(), kinds=(), scopes=(), passes=()):
    """The join's rows of the traced program that has a table and a registry
    (of several, the one with most device time) that the arguments select;
    None where there is nothing to join."""
    try:
        from ray_tpu.util import profiling

        tables, registry = profiling.program_ops(), profiling.program_collectives()
        join = profiling.collective_seconds
    except Exception:  # noqa: BLE001 - a program without the registry: nothing to read
        return None
    seconds = {name: sum(runs) for name, runs in trace.get("program_seconds", {}).items()
               if name in tables and name in registry}
    if not seconds:
        return None
    program = max(seconds, key=seconds.get)
    rows = join(trace.get("op_seconds", {}), trace.get("op_counts", {}), tables[program], registry[program])
    return [row for row in rows
            if (not axes or set(row["axes"]) == set(axes))
            and (not kinds or row["kind"] in kinds)
            and (not scopes or set(row["scopes"]) & set(scopes))
            and (not passes or row["pass"] in passes)]


def read(ctx, **select):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    rows = traced_rows(trace, **select)
    if rows is None:
        return None
    return 100.0 * sum(row["seconds"] for row in rows) / trace["busy_s"]
