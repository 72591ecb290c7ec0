"""How long after an answer the client's next submit really left (mean,
milliseconds), over submits inside the window: a starved generator must
not be read as a fast server."""

from ..counting import mean


def read(ctx):
    late = [(r.t_submit - r.t_due) * 1e3 for r in ctx.get("records", ())
            if r.ordinal > 0 and ctx["t0"] <= r.t_submit < ctx["t1"]]
    return mean(late)
