"""Mean time between one client's successive answers (seconds), over
answers that arrived inside the window: in a closed loop with no think
time this is clients / (requests per second), and under the engine's
lockstep generations it is the length of one generation. It moves
smoothly where the windowed token count moves in steps (PERF.md)."""

from ..counting import mean


def read(ctx):
    done = {}
    for r in ctx.get("records", ()):
        if r.t_done is not None and r.error is None:
            done.setdefault(r.client, []).append(r.t_done)
    gaps = [b - a for times in done.values() for a, b in zip(sorted(times), sorted(times)[1:])
            if ctx["t0"] <= b < ctx["t1"]]
    return mean(gaps)
