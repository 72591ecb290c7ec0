"""What every kind of cell needs from the harness: the clock, the log, the
set-up clock, the compile counter, the profiler around part of a window,
the device's record and peak memory, and the wrapper that turns a
comparison that cannot be made into a problem of the run."""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
now = time.perf_counter


def seconds_since_process_start() -> float:
    """Set-up is counted from the start of the process, not of main()."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


def log(*parts: Any) -> None:
    print("[benchmark]", *parts, file=sys.stderr, flush=True)


def load_json(path: str) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(spare: bool = False) -> Dict[str, Any]:
    """BENCHMARK.json; with `spare`, the entries of benchmark/spare.json
    (cells measured and not shipped) appended to its lists. The command
    never asks for them: the window study and the tests do."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if spare:
        extra = load_json(os.path.join(BENCH_DIR, "spare.json"))
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[group] = bench[group] + extra[group]
    return bench


class CompileCounter:
    """Every executable JAX builds or fetches from its persistent cache,
    with the time it happened: rule 6 wants none inside the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.times.append(now())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


class Tracer:
    """The profiler around part of a window. Python-level tracing is off:
    it multiplies the trace's size and slows the host; the host planes
    still carry JAX's own spans, which name the idle gaps."""

    def __init__(self, tree: str) -> None:
        # one directory per process: two runs in one tree (the tests run in
        # parallel) must not clear each other's trace
        self.logdir = os.path.join(tree, "_out", "trace", str(os.getpid()))
        self.t0 = self.t1 = None

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        self.t0 = now()

    def stop(self) -> None:
        import jax

        self.t1 = now()
        jax.profiler.stop_trace()

    def __enter__(self) -> "Tracer":
        self.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()

    def reduce(self) -> Optional[Dict[str, Any]]:
        import shutil

        from . import trace_reduce

        if self.t1 is None:
            return None
        reduced = trace_reduce.reduce_trace(
            trace_reduce.load_xplane(trace_reduce.find_xplane(self.logdir)))
        shutil.rmtree(self.logdir, ignore_errors=True)
        if not reduced["devices"]:
            return None     # no chip in the trace (the tests' CPU rehearsal)
        reduced["window_s"] = self.t1 - self.t0
        return reduced


def device_record() -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> Dict[str, Optional[int]]:
    import jax

    def peak(stats: Dict[str, int]) -> int:
        # the TPU runtime keeps a program's temporaries in memory it
        # RESERVES, outside bytes_in_use: the peak is the sum of the two
        return stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    fullest = max(stats, key=peak)
    return {"peak_bytes": peak(fullest) or None, "bytes_limit": fullest.get("bytes_limit")}


def checked(check_fn, *args: Any) -> List[str]:
    """The comparison's problems; a comparison that cannot be made is one
    more problem (the run is then not `correct`), not a lost run."""
    import traceback

    try:
        return check_fn(*args)
    except Exception as exc:  # noqa: BLE001 - reported in the result, never dropped
        traceback.print_exc()
        return [f"{check_fn.__name__} raised {type(exc).__name__}: {exc}"[:300]]
