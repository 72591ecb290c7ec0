"""Per request (t_last - t_first) / (n_out - 1) at the client, mean over
requests that finished inside the window (milliseconds; tokens arrive in
blocks of 16, so per-token gaps are not used). Computed with the
end-to-end numbers (counting.serving_end_to_end); it is a per-layer metric
because its runs spread too widely to carry a bound (PERF.md)."""


def read(ctx):
    return ctx.get("end_to_end", {}).get("tpot_mean_ms")
