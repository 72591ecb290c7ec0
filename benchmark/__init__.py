"""The repo's benchmark: one command, cells found by name in BENCHMARK.json.

Everything the driver's check depends on lives here (traffic generation,
counting, trace reduction, peaks, kernel cost functions, references, the
comparison that decides `correct`); from `ray_tpu` it takes only the system
under test and its spans, counters and kernel names.
"""
