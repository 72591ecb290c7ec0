"""Percentile of submit-to-first-token at the client, over first tokens
that arrived inside the window (milliseconds)."""

from ..counting import percentile, ttfts_in_window


def read(ctx, *, percentile_q: float):
    value = percentile(ttfts_in_window(ctx.get("records", ()), ctx["t0"], ctx["t1"]), percentile_q)
    return None if value is None else value * 1e3
