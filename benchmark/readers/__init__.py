"""Per-layer metric readers: one function `read(ctx, **args)` per module,
from what a run gathered (client records, counters, request-log marks,
the reduced trace) to a number, or None where there is nothing to read.
benchmark/metrics/<metric>.json names the reader and its arguments."""
