"""Mean device time of one jitted program's runs in the trace
(milliseconds), optionally per inner step: `per` names a number in the
configuration, such as engine.decode_block_steps."""


def read(ctx, *, program: str, per: str = ""):
    trace = ctx.get("trace")
    if not trace:
        return None
    runs = [s for name, all_runs in trace["program_seconds"].items()
            if name.startswith(program) for s in all_runs]
    if not runs:
        return None
    divide = 1.0
    if per:
        node = ctx["conf"]
        for key in per.split("."):
            node = node[key]
        divide = float(node)
    return 1e3 * sum(runs) / len(runs) / divide
