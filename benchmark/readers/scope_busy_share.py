"""Device time of the traced step's operations that the program places
under one of `scopes` (its `jax.named_scope`s: sublayers, the head, the
optimizer; empty = any) in one of `passes` (`fwd`, `recompute`, `bwd`,
`optimizer`, `other`; empty = any), over the device's busy time in the
trace, in percent. Operations whose names start with one of
`exclude_prefixes` are left out before the join. With `unscoped`: the time
of the operations the program's table does not hold, plus that of those no
scope places in a sublayer or the optimizer.

The program keeps, for each compiled step, which scopes and which pass
every operation belongs to (`ray_tpu.util.profiling.program_ops`, built
in set-up by `LMTrainer` under the span `train.report.ops`) and joins a
reduced trace's `op_seconds` to it (`profiling.scope_seconds`, the
function `ray_tpu profile`'s record uses). A program without either (the
parent of the PR that brought them), a run without a trace, or a trace of
a program with no table has nothing to read, and the metric is left out.
"""


def traced_table(trace):
    """(the join, the operation table of the traced program that has one:
    of several, the one with most device time), or None."""
    try:
        from ray_tpu.util import profiling

        tables, join = profiling.program_ops(), profiling.scope_seconds
    except Exception:  # noqa: BLE001 - a program without the table: nothing to read
        return None
    seconds = {name: sum(runs) for name, runs in trace.get("program_seconds", {}).items()
               if name in tables}
    return (join, tables[max(seconds, key=seconds.get)]) if seconds else None


def read(ctx, *, scopes=(), passes=(), exclude_prefixes=(), unscoped=False):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    found = traced_table(trace)
    if found is None:
        return None
    join, table = found
    split = join({name: s for name, s in trace.get("op_seconds", {}).items()
                  if not name.startswith(tuple(exclude_prefixes))},
                 trace.get("op_counts", {}), table)
    if unscoped:
        seconds = sum(split["unmatched_ops"].values()) + sum(split["unscoped_ops"].values())
    elif scopes:
        # an operation under two of the scopes asked for counts under both: the
        # scopes one metric names together are disjoint ones
        seconds = sum(s for (scope, pass_), s in split["by_scope_pass"].items()
                      if scope in scopes and (not passes or pass_ in passes))
    else:
        seconds = sum(s for pass_, s in split["by_pass"].items() if not passes or pass_ in passes)
    return 100.0 * seconds / trace["busy_s"]
