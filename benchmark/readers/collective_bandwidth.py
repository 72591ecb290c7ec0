"""Bytes the traced step's collectives move over the device seconds they
take on the profile's "XLA Ops" line, in GB/s: sum of bytes x calls over sum
of seconds of the collectives that `axes`, `kinds`, `scopes` and `passes`
select (`collective_busy_share`'s arguments and join). A collective's bytes
are the full array's on one chip, the larger of its operand and its result
(an all-reduce's array, an all-gather's gathered result), not what crosses a
link: a ring all-reduce over n chips sends 2 (n - 1) / n of them. The
seconds leave out what an asynchronous collective runs under compute, so a
hidden collective reads a rate above the links'. None where
`collective_busy_share` has nothing to read, or the selected collectives took
no time.
"""

from .collective_busy_share import traced_rows


def read(ctx, **select):
    trace = ctx.get("trace")
    if not trace:
        return None
    rows = traced_rows(trace, **select)
    seconds = sum(row["seconds"] for row in rows or ())
    if not seconds:
        return None
    return sum(row["bytes"] for row in rows) / seconds / 1e9
