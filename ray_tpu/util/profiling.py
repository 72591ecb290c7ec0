"""Profiling plane: device/host capture + compiled-graph cost accounting.

Reference parity: the tracing/profiling aux subsystem (SURVEY.md §5 —
the reference ships `ray timeline` and per-worker profiling as a
first-class subsystem). TPU inversion: the interesting timeline is on
the DEVICE, and XLA already has a first-class profiler *and* a
first-class cost model — so this module is three things:

1. The **jax.profiler bridge** (`device_trace`, `start_profiler_server`)
   with typed errors (`ProfilingError`) instead of raw jax
   exceptions, an idempotent profiler server whose port rides the node
   stats snapshot, and `capture_local_profile` — a time-boxed device
   trace plus a host-side sampling profile, collected as bounded
   artifact bytes the cluster capture RPC ships back to the head. (Host
   regions in a profile are `util/tracing.span`s: each is a
   `jax.profiler.TraceAnnotation` of its name.)
2. The **cost-model layer**: `step_cost` reads
   ``compiled.cost_analysis()`` FLOPs/bytes off any jitted/compiled
   step, `device_peaks` prices them against the detected chip's peak
   FLOPs/HBM bandwidth, and `roofline` turns (cost, step time) into
   MFU + roofline fractions — the currency every TPU perf claim is
   quoted in. The train/serve MFU gauges all go through
   here instead of hand-maintained constants.
3. The **ProfileStore**: captured artifacts registered on the driver so
   `state.list_profiles()/get_profile()`, `ray_tpu profile`, and the
   dashboard download route can reach them, and `trace_dump` can merge
   a capture's device events into the Perfetto export.

Import discipline: jax imports stay FUNCTION-LOCAL so this module (and
core/stats.py, which reads `node_snapshot()`) imports on jax-less
observer hosts — enforced by scripts/check_lazy_jax.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gzip
import json
import math
import os
import re
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterator, List, NamedTuple, Optional, Tuple

from ..core.exceptions import ProfilingError

# ----------------------------------------------------- device trace (typed)

# Module-level latch: jax.profiler allows one trace per process, and its
# double-start/orphan-stop failures are raw RuntimeErrors with
# backend-specific strings. The latch lets us raise typed errors BEFORE
# touching jax, and lets captures report "busy" instead of colliding.
_trace_lock = threading.Lock()
_trace_logdir: Optional[str] = None


def start_device_trace(logdir: str, *, perfetto: bool = True) -> None:
    """Begin capturing an XLA device trace into `logdir` (view with
    TensorBoard's profile plugin or ui.perfetto.dev). Raises
    `ProfilingError` when a trace is already active or jax is missing."""
    global _trace_logdir
    with _trace_lock:
        if _trace_logdir is not None:
            raise ProfilingError(
                f"a device trace into {_trace_logdir!r} is already active; "
                f"stop it before starting another"
            )
        try:
            import jax
        except ImportError as exc:
            raise ProfilingError(f"device tracing requires jax: {exc!r}") from exc
        try:
            jax.profiler.start_trace(logdir, create_perfetto_trace=perfetto)
        except Exception as exc:  # noqa: BLE001 - typed boundary
            raise ProfilingError(f"start_trace failed: {exc!r}") from exc
        _trace_logdir = logdir


def stop_device_trace() -> None:
    """Stop the active device trace. Raises `ProfilingError` (not a raw
    jax RuntimeError) when no trace is active."""
    global _trace_logdir
    with _trace_lock:
        if _trace_logdir is None:
            raise ProfilingError("no active device trace to stop")
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001 - typed boundary
            raise ProfilingError(f"stop_trace failed: {exc!r}") from exc
        finally:
            _trace_logdir = None


def device_trace_active() -> bool:
    return _trace_logdir is not None


@contextlib.contextmanager
def device_trace(logdir: str) -> Iterator[None]:
    """Context manager form: everything dispatched inside is captured.
    Remember to block_until_ready/fetch inside the block — work still in
    flight when the trace stops is cut off."""
    start_device_trace(logdir)
    try:
        yield
    finally:
        stop_device_trace()


# --------------------------------------------------- profiler server (xprof)

_server_lock = threading.Lock()
_profiler_server: Any = None
_profiler_server_port: Optional[int] = None


def start_profiler_server(port: int = 9999):
    """Serve the live profiling endpoint (attach with TensorBoard/xprof:
    capture profile -> 'localhost:<port>'). Idempotent: repeat calls
    return the existing server (jax allows one per process); the bound
    port is advertised in the node stats snapshot (`node_snapshot`) so
    operators can attach on demand."""
    global _profiler_server, _profiler_server_port
    with _server_lock:
        if _profiler_server is not None:
            return _profiler_server
        try:
            import jax
        except ImportError as exc:
            raise ProfilingError(
                f"the profiler server requires jax: {exc!r}"
            ) from exc
        try:
            _profiler_server = jax.profiler.start_server(port)
        except Exception as exc:  # noqa: BLE001 - typed boundary
            raise ProfilingError(
                f"profiler server failed to start on port {port}: {exc!r}"
            ) from exc
        _profiler_server_port = port
        return _profiler_server


def profiler_server_port() -> Optional[int]:
    """Port of the live profiler server, or None when not started."""
    return _profiler_server_port


# ------------------------------------------------------ host-side profiling


class HostProfiler:
    """Time-boxed sampling profiler over EVERY thread of this process
    (``sys._current_frames()`` at a fixed interval). cProfile instruments
    only the installing thread, which is useless for profiling an agent
    whose work happens on RPC/worker/engine threads — sampling sees them
    all, stdlib-only, at bounded overhead."""

    def __init__(self, interval_s: float = 0.005):
        self.interval_s = interval_s
        self._counts: Dict[Tuple[str, str], int] = {}
        self._samples = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="ray_tpu-host-profiler"
        )
        self._thread.start()

    def _loop(self) -> None:
        names = {}
        while not self._stop.wait(self.interval_s):
            if not names:
                names = {t.ident: t.name for t in threading.enumerate()}
            self._samples += 1
            for tid, frame in list(sys._current_frames().items()):
                if frame is None:
                    continue
                stack: List[str] = []
                depth = 0
                while frame is not None and depth < 24:
                    code = frame.f_code
                    stack.append(
                        f"{os.path.basename(code.co_filename)}:"
                        f"{frame.f_lineno}:{code.co_name}"
                    )
                    frame = frame.f_back
                    depth += 1
                key = (names.get(tid, str(tid)), ";".join(reversed(stack)))
                self._counts[key] = self._counts.get(key, 0) + 1

    def stop(self) -> str:
        """Stop sampling; returns a text report: per-thread top stacks by
        sample count (a flamegraph collapses from the same lines)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        lines = [
            f"# host sampling profile: {self._samples} samples @ "
            f"{self.interval_s * 1e3:.1f}ms"
        ]
        ranked = sorted(self._counts.items(), key=lambda kv: -kv[1])[:200]
        for (tname, stack), count in ranked:
            lines.append(f"{count}\t{tname}\t{stack}")
        return "\n".join(lines) + "\n"


# --------------------------------------------------------- local capture

# latch naming the capture currently running in this process (None = idle)
_capture_lock = threading.Lock()
_active_capture: Optional[str] = None
# summary of the most recent finished capture: shown by `ray_tpu status
# --verbose` via the node stats snapshot
_last_capture: Optional[Dict[str, Any]] = None


def capture_local_profile(duration_s: Optional[float] = None, *,
                          device: bool = True, host: bool = True,
                          profile_id: str = "",
                          workload: Optional[Callable[[], Any]] = None,
                          ) -> Dict[str, Any]:
    """One time-boxed capture of THIS process: a jax device trace and/or
    a host sampling profile, returned as bounded artifact bytes. This is
    the agent side of the cluster `profile_capture` RPC and the whole of
    the in-process path.

    Returns {"meta": {...}, "artifacts": {name: bytes}}. Never raises
    for a degraded capture (no jax, trace busy): the meta records what
    was skipped and why, so a fan-out over mixed nodes still returns.
    Where the device trace holds runs of a program whose operation table
    this process keeps (`program_ops`: a train step's), the meta's
    `scope_seconds` has that program's device time by scope and by pass
    (`scope_seconds`, the join the benchmark's reader calls) and, where the
    program runs collectives, its `collective_seconds` their calls, bytes
    and device time by kind, mesh axis, scope and pass (`collective_seconds`)."""
    import shutil
    import tempfile

    from ..core.config import cfg

    global _active_capture, _last_capture
    if duration_s is None:
        duration_s = cfg.profile_default_duration_s
    duration_s = max(0.05, float(duration_s))
    meta: Dict[str, Any] = {
        "profile_id": profile_id,
        "started_at": time.time(),
        "duration_s": duration_s,
        "pid": os.getpid(),
        "profiler_port": profiler_server_port(),
        "device": "skipped",
        "host": "skipped",
    }
    artifacts: Dict[str, bytes] = {}
    with _capture_lock:
        if _active_capture is not None:
            meta["device"] = meta["host"] = f"busy: capture {_active_capture}"
            return {"meta": meta, "artifacts": artifacts}
        _active_capture = profile_id or "local"
    logdir = None
    sampler = None
    try:
        if device:
            if sys.modules.get("jax") is None:
                # an observer/agent that never imported jax must not pay
                # the import (nor fail the host half of the capture)
                meta["device"] = "skipped: jax not imported in this process"
            else:
                logdir = tempfile.mkdtemp(prefix="ray_tpu_prof_")
                try:
                    start_device_trace(logdir)
                    meta["device"] = "ok"
                except ProfilingError as exc:
                    meta["device"] = f"error: {exc}"
                    logdir = None
        if host:
            sampler = HostProfiler(interval_s=cfg.profile_host_sample_s)
            sampler.start()
            meta["host"] = "ok"
        if workload is not None:
            deadline = time.time() + duration_s
            while time.time() < deadline:
                workload()
        else:
            time.sleep(duration_s)
    finally:
        if logdir is not None:
            try:
                stop_device_trace()
                artifacts.update(_collect_trace_artifacts(
                    logdir, cfg.profile_max_artifact_bytes
                ))
                split, moved = _captured_splits(logdir)
                if split:
                    meta["scope_seconds"] = split
                if moved:
                    meta["collective_seconds"] = moved
            except ProfilingError as exc:
                meta["device"] = f"error: {exc}"
            shutil.rmtree(logdir, ignore_errors=True)
        if sampler is not None:
            artifacts["host_profile.txt"] = sampler.stop().encode()
        with _capture_lock:
            _active_capture = None
    meta["bytes"] = sum(len(b) for b in artifacts.values())
    meta["artifact_names"] = sorted(artifacts)
    _last_capture = {
        "profile_id": profile_id, "ts": meta["started_at"],
        "duration_s": duration_s, "bytes": meta["bytes"],
        "device": meta["device"], "host": meta["host"],
    }
    return {"meta": meta, "artifacts": artifacts}


def _collect_trace_artifacts(logdir: str, max_bytes: int) -> Dict[str, bytes]:
    """Gather the profiler's output files (xplane, trace.json.gz,
    perfetto) as {relative_name: bytes}, bounded: the chrome-trace and
    perfetto files (the mergeable/viewable ones) are collected first,
    xplane blobs only with remaining budget."""
    files: List[Tuple[str, str]] = []
    for root, _dirs, names in os.walk(logdir):
        for name in names:
            full = os.path.join(root, name)
            files.append((os.path.relpath(full, logdir), full))
    # mergeable JSON traces first, then everything else by size ascending
    files.sort(key=lambda t: (
        0 if t[0].endswith(".trace.json.gz") else
        1 if t[0].endswith("perfetto_trace.json.gz") else 2,
        os.path.getsize(t[1]),
    ))
    out: Dict[str, bytes] = {}
    budget = max_bytes
    for rel, full in files:
        size = os.path.getsize(full)
        if size > budget:
            continue
        with open(full, "rb") as f:
            out[rel.replace(os.sep, "/")] = f.read()
        budget -= size
    return out


def node_snapshot() -> Dict[str, Any]:
    """This process's profiling status for the node stats snapshot
    (core/stats.py): profiler-server port, whether a capture is running,
    and the last finished capture's summary."""
    with _capture_lock:
        active = _active_capture
    return {
        "server_port": _profiler_server_port,
        "active_capture": active,
        "last_capture": dict(_last_capture) if _last_capture else None,
    }


# ------------------------------------------------- device trace -> Perfetto


def load_device_trace_events(artifacts: Dict[str, bytes], *,
                             started_at: float, lane_prefix: str = "device",
                             max_events: Optional[int] = None,
                             ) -> List[Dict[str, Any]]:
    """Parse a capture's chrome-trace artifact (`*.trace.json.gz`) into
    trace events aligned to wall-clock time, ready to merge into the
    span export: the profiler's timestamps are microseconds relative to
    trace start, so each event is offset by the capture's `started_at`.
    Lanes become "<lane_prefix>:<process name>" (e.g. `device:/device:
    TPU:0`), so runtime spans and chip activity sit side by side in one
    Perfetto view. Events are capped (largest durations win) to keep the
    export loadable."""
    from ..core.config import cfg

    if max_events is None:
        max_events = cfg.profile_merge_max_events
    raw = None
    for name in sorted(artifacts):
        if name.endswith(".trace.json.gz"):
            raw = artifacts[name]
            break
    if raw is None:
        return []
    try:
        data = json.loads(gzip.decompress(raw))
    except Exception as exc:  # noqa: BLE001 - corrupt artifact boundary
        raise ProfilingError(f"undecodable device trace artifact: {exc!r}")
    events = data.get("traceEvents", []) if isinstance(data, dict) else []
    proc_names: Dict[Any, str] = {}
    thread_names: Dict[Tuple[Any, Any], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            proc_names[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            thread_names[(e.get("pid"), e.get("tid"))] = (
                e.get("args", {}).get("name", "")
            )
    xs = [e for e in events if e.get("ph") == "X"]
    # device tracks are the point; host-python tracks only ride along
    # when there is budget left after them
    xs.sort(key=lambda e: (
        0 if "/device:" in proc_names.get(e.get("pid"), "") else 1,
        -float(e.get("dur", 0.0)),
    ))
    xs = xs[:max_events]
    offset_us = started_at * 1e6
    out: List[Dict[str, Any]] = []
    for e in xs:
        pid = e.get("pid")
        proc = proc_names.get(pid) or str(pid)
        out.append({
            "name": e.get("name", "?"),
            "cat": "device",
            "ph": "X",
            "ts": offset_us + float(e.get("ts", 0.0)),
            "dur": float(e.get("dur", 0.0)),
            "pid": f"{lane_prefix}:{proc}",
            "tid": thread_names.get((pid, e.get("tid")), str(e.get("tid"))),
            "args": e.get("args", {}),
        })
    out.sort(key=lambda e: e["ts"])
    return out


# ------------------------------------------------------------ profile store


class ProfileStore:
    """Driver-side registry of captures: bounded LRU of records (meta +
    per-node artifact bytes). The state API (`list_profiles`,
    `get_profile`, `profile_artifact`), the CLI, and the dashboard
    download route all read from here; capture metas are additionally
    mirrored into the GCS `_profiles` table for cluster visibility."""

    def __init__(self, capacity: Optional[int] = None):
        from ..core.config import cfg

        self._capacity = capacity or cfg.profile_store_capacity
        self._records: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._artifacts: Dict[str, Dict[Tuple[str, str], bytes]] = {}
        self._lock = threading.Lock()

    def add(self, record: Dict[str, Any],
            artifacts: Dict[Tuple[str, str], bytes]) -> None:
        with self._lock:
            pid = record["profile_id"]
            self._records[pid] = record
            self._artifacts[pid] = dict(artifacts)
            while len(self._records) > self._capacity:
                old, _ = self._records.popitem(last=False)
                self._artifacts.pop(old, None)

    def list(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(r) for r in self._records.values()]

    def get(self, profile_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            rec = self._records.get(profile_id)
            return dict(rec) if rec is not None else None

    def artifact(self, profile_id: str, node_hex: str,
                 name: str) -> Optional[bytes]:
        with self._lock:
            return self._artifacts.get(profile_id, {}).get((node_hex, name))

    def artifacts_for(self, profile_id: str,
                      node_hex: Optional[str] = None) -> Dict[str, bytes]:
        """All of one capture's artifacts (optionally one node's), keyed
        `node_hex/name` — what the Perfetto merge and `--output` read."""
        with self._lock:
            blobs = self._artifacts.get(profile_id, {})
            return {
                f"{nh}/{name}": data
                for (nh, name), data in blobs.items()
                if node_hex is None or nh == node_hex
            }


# ----------------------------------------------------- cost model / roofline

# Published peak dense bf16 FLOP/s and HBM bytes/s of one chip, keyed by the
# `device_kind` JAX reports (Google Cloud TPU documentation, the "System
# architecture" page of each generation; v5e: 197 TFLOP/s, 819 GB/s). This
# is the ONE table every MFU/roofline number in the repo prices against. A
# device that is not in it is an error, not a default: a utilization priced
# against an invented peak is not a measurement. CPU tests that want the
# arithmetic exercised add a nominal "cpu" row themselves (monkeypatch).
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),   # v5e
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),  # v6e
    "TPU v6e": (918e12, 1640e9),
}


def device_labels() -> Dict[str, Any]:
    """What every benchmark result line carries: the device as JAX
    reports it. A number without these is not attributable to a chip."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def device_peaks(device: Any = None) -> Dict[str, Any]:
    """Peak FLOPs/s and HBM bandwidth of the attached (or given) device.
    Raises ProfilingError for a device kind without published peaks."""
    if device is None:
        jax = sys.modules.get("jax")
        if jax is None:
            raise ProfilingError("device_peaks needs jax imported or a device")
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "unknown")
    if kind not in DEVICE_PEAKS:
        raise ProfilingError(
            f"no published peaks for device kind {kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); MFU and roofline shares are device "
            "metrics and are not estimated for other backends"
        )
    peak_flops, peak_hbm_bps = DEVICE_PEAKS[kind]
    return {
        "device_kind": kind,
        "peak_flops": peak_flops,
        "peak_hbm_bps": peak_hbm_bps,
    }


@dataclasses.dataclass
class StepCost:
    """cost_analysis() of one compiled program, normalized. XLA reports
    PER-DEVICE numbers for a sharded program (verified against an 8-way
    sharded matmul: per-device flops = total/8), so `flops`/`bytes
    _accessed` here are per device per invocation and MFU divides by the
    per-device peak — `total_flops` is the whole-program count."""

    flops: float
    bytes_accessed: float
    device_kind: str
    n_devices: int
    peak_flops: float           # per device
    peak_hbm_bps: float         # per device

    @property
    def total_flops(self) -> float:
        return self.flops * self.n_devices

    @property
    def total_bytes(self) -> float:
        return self.bytes_accessed * self.n_devices


def compiled_cost(compiled: Any) -> Tuple[float, float]:
    """`compiled.cost_analysis()` (a dict) -> (flops, bytes_accessed)."""
    try:
        analysis = compiled.cost_analysis()
    except Exception as exc:  # noqa: BLE001 - typed boundary
        raise ProfilingError(f"cost_analysis failed: {exc!r}") from exc
    if not isinstance(analysis, dict):
        raise ProfilingError(
            f"cost_analysis returned {type(analysis).__name__}, not a dict"
        )
    return (float(analysis.get("flops", 0.0)),
            float(analysis.get("bytes accessed", 0.0)))


def compile_step(fn: Any, *args: Any, **kwargs: Any) -> Any:
    """The executable of a jitted `fn` at the given example arguments, by
    the AOT path (one extra XLA compile or a fetch from the persistent
    cache, so callers keep what they read from it and drop it)."""
    if not hasattr(fn, "lower"):
        raise ProfilingError(
            f"step_cost needs a jitted or compiled callable, got "
            f"{type(fn).__name__}"
        )
    device_peaks()  # no peaks for this backend: fail before the compile
    try:
        return fn.lower(*args, **kwargs).compile()
    except Exception as exc:  # noqa: BLE001 - typed boundary
        raise ProfilingError(f"lower/compile failed: {exc!r}") from exc


def step_cost(fn: Any, *args: Any, **kwargs: Any) -> StepCost:
    """FLOPs/bytes of one invocation of a jitted function at the given
    example arguments, priced against the attached chip. `fn` may be a
    jitted callable (`compile_step`s it here, so callers cache the
    result) or an already-compiled object exposing `cost_analysis()`."""
    compiled = fn if hasattr(fn, "cost_analysis") else compile_step(fn, *args, **kwargs)
    flops, nbytes = compiled_cost(compiled)
    if flops <= 0 and nbytes <= 0:
        raise ProfilingError(
            "cost_analysis reported no flops/bytes for this program"
        )
    # devices the program actually spans (pjit over a mesh): the first
    # input sharding's device set, else the default device
    import jax

    device = None
    n_devices = 1
    leaves = jax.tree_util.tree_leaves(compiled.input_shardings)
    device_set = getattr(leaves[0], "device_set", None) if leaves else None
    if device_set:
        n_devices = len(device_set)
        device = next(iter(device_set))
    peaks = device_peaks(device)
    return StepCost(
        flops=flops,
        bytes_accessed=nbytes,
        device_kind=peaks["device_kind"],
        n_devices=n_devices,
        peak_flops=peaks["peak_flops"],
        peak_hbm_bps=peaks["peak_hbm_bps"],
    )


def roofline(cost: StepCost, step_time_s: float) -> Dict[str, Any]:
    """Price one step against the chip roofline. `mfu` is the model-
    FLOPs-utilization (achieved / peak matmul throughput), `hbm_fraction`
    the share of peak HBM bandwidth the program's byte traffic implies;
    whichever fraction is higher names the binding resource. Per-device
    cost over per-device peak: the step time is wall time, every device
    runs its shard concurrently."""
    if step_time_s <= 0:
        raise ProfilingError(f"step_time_s must be positive, got {step_time_s}")
    mfu = cost.flops / (step_time_s * cost.peak_flops)
    hbm = cost.bytes_accessed / (step_time_s * cost.peak_hbm_bps)
    return {
        "mfu": mfu,
        "hbm_fraction": hbm,
        "bound": "memory" if hbm > mfu else "compute",
        "flops_per_device": cost.flops,
        "total_flops": cost.total_flops,
        "bytes_per_device": cost.bytes_accessed,
        "step_time_s": step_time_s,
        "n_devices": cost.n_devices,
        "device_kind": cost.device_kind,
    }


# ------------------------------------------- the step's operations, by scope
#
# A compiled train step says of every operation which sublayer and which
# pass it belongs to: each HLO instruction carries the `jax.named_scope`s
# and the transforms it was traced under (`metadata={op_name="jit(step)/
# steplog.fwd_bwd_compute/transpose(jvp(...))/while/body/checkpoint/
# rematted_computation/moe/moe.combine/..."}`), and a device profile names
# each operation by that instruction. `program_ops_table` reads the one,
# `scope_seconds` joins it to the other.

# The ONE closed set of scope names a train step may use (train/lm.py and
# the models' sublayers; tests/test_step_scopes.py holds every
# `jax.named_scope` literal there to it). Scopes nest, and an operation
# belongs to every name on its path: `attn.proj` (norm, q/k/v/gate
# projections, QK-norm, rotary), `attn.kernel` (the flash call and the
# layout work around it) and `attn.out` (gate, output projection, norm,
# residual) lie inside `attn.full` or `attn.window`, the layer's kind
# (`attn.latent`, inside `attn.proj`: a latent-attention layer's down- and
# up-projections, its latents' norms, the rotary slice and the build of q and
# k); the
# seven `moe.*` inside `moe`, which also holds the expert layer's norms, its
# weights' casts and its residual (`moe.select`: top-k, the gates'
# renormalisation, the load count, the auxiliary loss; `moe.passes`: the
# control and the sums of a held layer's passes after the first, around
# those passes' own `moe.dispatch`, `moe.experts` and `moe.combine`);
# `head` is the final norm, the logits and the loss; `mtp` a multi-token
# prediction module whole (its block's own scopes nest in it, its pass of the
# head is `mtp` and `head`); `ssm` a state-space mixer whole, and inside it
# `ssm.in_proj` (norm and the one projection), `ssm.conv` (the causal
# depthwise convolution and its silu), `ssm.scan` (the step's softplus and
# the chunked selective scan), `ssm.gate_norm` (the gate and the norm a
# group) and `ssm.out_proj` (the projection and the residual); `kda` a
# delta-rule mixer whole, and inside it `kda.in_proj` (norm and the two
# projections), `kda.conv` (the convolutions of q, k and v and their silu),
# `kda.chunk` (ops/kda.kda_rule: the chunked delta rule from q, k, v, the
# gate's input and the two logits a head as the mixer has them, through the
# norm a head under the head's gate; on a TPU its two kernels, which make the
# L2 norms, the log-decay and beta in VMEM and norm o where they have it, the
# copy of what a checkpoint keeps of them, and the few small operations that
# prepare their rows and finish the three parameters' gradients; in the XLA
# form all of that as operations), `kda.gate_norm` (INSIDE `kda.chunk`, in the
# XLA form alone: the norm a head and its gate as operations; a program whose
# kernels do the norm holds nothing under it) and `kda.out_proj`.
STEP_SCOPES: Tuple[str, ...] = (
    "steplog.fwd_bwd_compute", "steplog.optimizer_update",
    "embed",
    "attn.full", "attn.window", "attn.proj", "attn.latent", "attn.kernel", "attn.out",
    "attn.eva", "attn.eva.pool", "attn.eva.local", "attn.eva.far", "attn.eva.merge",
    "ssm", "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj",
    "kda", "kda.in_proj", "kda.conv", "kda.chunk", "kda.gate_norm", "kda.out_proj",
    "sconv", "sconv.in_proj", "sconv.conv", "sconv.out_proj",
    "mlp",
    "moe", "moe.route", "moe.select", "moe.dispatch", "moe.experts", "moe.combine", "moe.passes",
    "moe.shared",
    "head", "head.multibyte",
    "mtp",
)
# the forward and backward pass as a whole: a phase, which places an
# operation in no sublayer (one that carries nothing else is "unscoped")
_PHASE_SCOPE = "steplog.fwd_bwd_compute"
STEP_PASSES: Tuple[str, ...] = ("fwd", "recompute", "bwd", "optimizer", "other")

# (scopes of STEP_SCOPES on the path, pass, index of the computation that
# holds the instruction: all of a computation's instructions run equally often)
OpInstance = Tuple[Tuple[str, ...], str, int]

_SCOPE_ON_PATH = re.compile(
    r"(?<!jit\()(?<![\w.])(" + "|".join(
        re.escape(name) for name in sorted(STEP_SCOPES, key=len, reverse=True))
    + r")(?![\w.])")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) [({]")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
# a traced operation's path; the partitioner's own names (`op_name="convert.9"`) have no "/"
_OP_NAME = re.compile(r'op_name="([^"]*/[^"]*)"')
_INLINED = re.compile(r"(calls|to_apply)=%?([\w.\-]+)")
_INSTANCE_SUFFIX = re.compile(r"(\.\d+)+$")
# instructions that are no operation of the device's: a profile never shows one
_NO_DEVICE_OP = frozenset({"parameter", "constant", "get-tuple-element", "tuple", "bitcast"})

_ops_lock = threading.Lock()
_program_ops: Dict[str, Dict[str, Tuple[OpInstance, ...]]] = {}


def _profile_key(name: str, rest: str) -> Tuple[str, str]:
    """(the name a reduced profile keys an operation by, its opcode), of an
    instruction `name = rest`: a kernel (`custom-call`) loses its instance
    suffix (`flash_fwd.3` -> `flash_fwd`), so that a layer's copies add up."""
    opcode = _OPCODE.search(" " + rest)
    opcode = opcode.group(1) if opcode else ""
    return (_INSTANCE_SUFFIX.sub("", name) if opcode == "custom-call" else name), opcode


def op_pass(op_name: str) -> str:
    """Which pass of the step an operation traced under `op_name` runs in."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    if "steplog.optimizer_update" in op_name:
        return "optimizer"
    return "other"


def program_ops_table(text: str) -> Tuple[str, Dict[str, Tuple[OpInstance, ...]]]:
    """The text of an optimised HLO module -> (the module's name, which is
    how a profile names the program: `jit_step_under_mesh`; {operation name
    as a device profile gives it: its instances}). Instructions of every
    computation that runs as control flow (the entry, while bodies,
    branches), not the insides of fusions or of reducers: what a profile's
    "XLA Ops" line shows. A kernel (`custom-call`) is keyed without its
    instance suffix, as the reduced trace of the benchmark keys it
    (`_profile_key`), and so may have several instances; every other name
    has one."""
    return _walk_module(text)[:2]


def _walk_module(text: str) -> Tuple[str, Dict[str, Tuple[OpInstance, ...]], List["_FoundCollective"]]:
    """`program_ops_table`'s walk -> (the module's name, the table, the
    collectives among the table's operations as the text spells them)."""
    module = re.match(r"HloModule\s+([^\s,]+)", text)
    if module is None:
        raise ProfilingError("not the text of an HLO module")
    computations: List[str] = []
    inlined = set()
    fused_paths: Dict[str, str] = {}    # a fusion's computation -> its last instruction's op_name
    rows: List[Tuple[str, OpInstance]] = []
    # a one-chip step's text (up to 11 MB) spells no group: nothing below looks for a collective
    may_hold = "replica_groups=" in text or "source_target_pairs=" in text
    held: Dict[str, _FoundCollective] = {}      # a computation -> the collective inside it
    found: List[Tuple[int, _FoundCollective]] = []
    in_fusion = False
    for line in text.splitlines():
        if not line.startswith(" "):
            header = _COMPUTATION.match(line)
            if header:
                computations.append(header.group(1))
                # most of the text, and dropped below through its caller's
                # `calls=` whatever it is named: the name only spares the parse
                in_fusion = header.group(1).startswith("fused_computation")
            continue
        if in_fusion:
            if "to_apply=" in line:     # a reducer called from inside a fusion
                inlined.add(_INLINED.search(line).group(2))
            path = _OP_NAME.search(line)
            if path:
                fused_paths[computations[-1]] = path.group(1)
            elif "calls=" in line:      # a fusion inside the fusion
                inner = fused_paths.get(_INLINED.search(line).group(2))
                if inner:
                    fused_paths[computations[-1]] = inner
            if may_hold:
                _collective_on(line, computations[-1], held)
            continue
        instruction = _INSTRUCTION.match(line)
        if instruction is None or not computations:
            continue
        name, opcode = _profile_key(*instruction.groups())
        rest = instruction.group(2)
        called = _INLINED.search(rest)
        if called and (called.group(1) == "calls" or opcode != "call"):
            inlined.add(called.group(2))
        if opcode in _NO_DEVICE_OP:
            continue
        if may_hold:
            inside = _collective_on(line, computations[-1], held)
            if inside:
                found.append((len(computations) - 1, inside))
        path = _OP_NAME.search(rest)
        # a fusion the compiler left without metadata (a multi-output one) is
        # placed by what it fuses
        path = path.group(1) if path else fused_paths.get(called.group(2), "") if called else ""
        on_path = set(_SCOPE_ON_PATH.findall(path))
        rows.append((name, (tuple(s for s in STEP_SCOPES if s in on_path),
                            op_pass(path), len(computations) - 1)))
    table: Dict[str, Tuple[OpInstance, ...]] = {}
    for name, instance in rows:
        if computations[instance[2]] not in inlined:
            table[name] = table.get(name, ()) + (instance,)
    return module.group(1), table, [c for at, c in found if computations[at] not in inlined]


def _print_module(compiled: Any, leave_out: Tuple[str, ...]) -> str:
    from jaxlib._jax import HloPrintOptions

    options = HloPrintOptions()
    for option in ("print_backend_config", "print_large_constants", "print_operand_shape",
                   "print_program_shape", "include_layout_in_shapes", "print_control_dependencies") + leave_out:
        setattr(options, option, False)
    return compiled.runtime_executable().hlo_modules()[0].to_string(options)


def _module_text(compiled: Any) -> str:
    """The optimised module of a compiled program as text, without the
    kernels' payloads and the constants (most of `as_text()`'s bytes)."""
    return _print_module(compiled, ("print_result_shape",))


def _module_shapes_text(compiled: Any) -> str:
    """The same module with every instruction's result shape and nothing
    of where it came from: what a collective's bytes are read off. Printed
    only for a module that holds a collective (Mistral's four-chip step:
    0.34 MB beside `_module_text`'s 0.52)."""
    return _print_module(compiled, ("print_metadata", "print_operand_names"))


def sublayer_scoped(scopes: Tuple[str, ...]) -> bool:
    """Whether the scopes place an operation: in a sublayer or the optimizer."""
    return any(s != _PHASE_SCOPE for s in scopes)


def register_program_ops(compiled: Any, mesh: Any = None) -> Dict[str, Any]:
    """Build the operation table of a compiled program and keep it under
    the program's name (`program_ops`), and beside it what the module says
    of its collectives (`program_collectives`; their axes are those of
    `mesh`, the `jax.sharding.Mesh` the program runs under). The compiled
    object is not kept. -> what the caller's span says of it: `program`,
    `ops` (instructions in the table), `ops_scoped` (those a scope places),
    `text_bytes`, `collectives` (a start / done pair counted once; 0 on a
    one-chip step), `collective_axes` (the mesh axes they run along,
    `fsdp,tp`) and `collectives_unplaced` (those whose groups match no set
    of axes)."""
    mesh_axes = tuple(mesh.shape.items()) if mesh is not None else ()
    try:
        text = _module_text(compiled)
        program, table, spelled = _walk_module(text)
        collectives = _collective_records(
            spelled, _collective_shapes(_module_shapes_text(compiled)), mesh_axes) if spelled else {}
    except ProfilingError:
        raise
    except Exception as exc:  # noqa: BLE001 - typed boundary
        raise ProfilingError(f"no operation table for this program: {exc!r}") from exc
    with _ops_lock:
        _program_ops[program] = table
    with _collectives_lock:
        _program_collectives[program] = collectives
    instances = [i for found in table.values() for i in found]
    whole = [record for record in collectives.values() if not record.completes]
    along = {axis for record in whole for axis in record.axes}
    return {"program": program, "ops": len(instances),
            "ops_scoped": sum(sublayer_scoped(i[0]) for i in instances),
            "text_bytes": len(text), "collectives": len(whole),
            "collective_axes": ",".join(name for name, _ in mesh_axes if name in along),
            "collectives_unplaced": sum(not record.axes for record in whole)}


def program_ops() -> Dict[str, Dict[str, Tuple[OpInstance, ...]]]:
    """{program name as a profile prints it: its operation table}, for
    every program registered in this process."""
    with _ops_lock:
        return dict(_program_ops)


def scope_seconds(op_seconds: Dict[str, float], op_counts: Dict[str, float],
                  table: Dict[str, Tuple[OpInstance, ...]]) -> Dict[str, Any]:
    """Device seconds by operation name (`op_seconds`, with how often each
    ran, `op_counts`: a reduced profile's) joined to a program's operation
    table -> {"by_scope_pass": {(scope, pass): seconds}, an operation under
    several scopes counted under each; "by_pass": {pass: seconds};
    "unscoped_ops": {name: seconds} of the operations that no scope places
    in a sublayer or the optimizer; "unmatched_ops": {name: seconds} of
    those the table does not hold; "total": all of `op_seconds`}. A name
    with several instances (a kernel) has its seconds split by how often
    each instance's computation ran, which the counts of that
    computation's other operations say (a branch never taken ran none);
    evenly where they say nothing."""
    runs: Dict[int, float] = {}
    for name, count in op_counts.items():
        found = table.get(name, ())
        if len(found) == 1:
            runs[found[0][2]] = max(runs.get(found[0][2], 0.0), count)
    by_scope_pass: Dict[Tuple[str, str], float] = {}
    by_pass: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    unmatched: Dict[str, float] = {}
    for name, seconds in op_seconds.items():
        found = table.get(name)
        if not found:
            unmatched[name] = seconds
            continue
        weights = [runs.get(computation, 0.0) for _, _, computation in found]
        if not sum(weights):
            weights = [1.0] * len(found)
        total = sum(weights)
        for (scopes, pass_, _), weight in zip(found, weights):
            share = seconds * weight / total
            by_pass[pass_] = by_pass.get(pass_, 0.0) + share
            for scope in scopes:
                by_scope_pass[scope, pass_] = by_scope_pass.get((scope, pass_), 0.0) + share
            if not sublayer_scoped(scopes):
                unscoped[name] = unscoped.get(name, 0.0) + share
    return {"by_scope_pass": by_scope_pass, "by_pass": by_pass, "unscoped_ops": unscoped,
            "unmatched_ops": unmatched, "total": sum(op_seconds.values())}


# ------------------------------------------ the step's collectives, by axis
#
# A name like `all-reduce.102` hides what decides a collective's cost: its
# kind, between which devices it runs (which MESH AXIS) and how many bytes
# it moves. The optimised module says all three (`replica_groups=...`, the
# opcode, the result's shape); `_walk_module` finds the instructions,
# `program_collectives_table` reads them, `collective_seconds` joins a
# profile's seconds to them and to the operation table's scopes and passes.

COLLECTIVE_KINDS: Tuple[str, ...] = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                                     "collective-permute", "collective-broadcast")


class Collective(NamedTuple):
    """What the module says of one operation that is, wraps or fuses a
    collective. An asynchronous one is ONE collective in several operations
    (`-start` / `-done`, `async-start` / `async-done` around a computation
    that holds one, or the TPU compiler's fusions `async-collective-start` /
    `async-collective-done`): the `done` names its `start` in `completes`,
    and so does a compute fusion the collective is carried through between
    the two (`under`: its device time is the compute's, not the
    collective's); all carry the collective's groups, axes and bytes."""

    kind: str                               # one of COLLECTIVE_KINDS
    half: str                               # "" (synchronous), "start", "done" or "under"
    completes: str                          # a done's or an under's start (its name in the registry), else ""
    groups: Tuple[Tuple[int, ...], ...]     # device groups by partition id; a permute's (source, target)s
    axes: Tuple[str, ...]                   # the mesh axes the groups run along; () = unplaced
    bytes: int                              # the full array on one chip: the larger of operand and result


class _FoundCollective(NamedTuple):
    name: str       # the operation as a profile names it (the wrapper's or the fusion's, where one holds it)
    kind: str
    half: str
    inner: str      # the collective instruction itself, whose result's shape says the bytes
    spelled: str    # the text behind its opcode: `replica_groups=` or `source_target_pairs=`
    operand: str    # the operation's first operand: a done's start


_KINDS = "|".join(COLLECTIVE_KINDS)
_MAY_BE_COLLECTIVE = re.compile(r" (?:all-|collective-|reduce-scatter)[a-z\-]*\(")     # spares most lines the next
_COLLECTIVE_OP = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = (?:\S.*? )?(" + _KINDS + r")(?:-(start|done))?\(%?([\w.\-]*)(.*)$")
_HOLDER = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = (?:\S.*? )?(fusion|async-start|async-update|async-done)"
    r"\(%?([\w.\-]*).*?calls=%?([\w.\-]+)")
_ASYNC_HALF = {"async-start": "start", "async-update": "done", "async-done": "done"}
_COLLECTIVE_CALL = re.compile(r" (?:" + _KINDS + r")(?:-start|-done)?\(")
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_GROUP_LIST = re.compile(r"(?:replica_groups|source_target_pairs)=\{((?:\{[\d,]*\},?)*)\}")
_CHANNEL = re.compile(r"channel_id=(\d+)")
_GROUP_IOTA = re.compile(r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")

_collectives_lock = threading.Lock()
_program_collectives: Dict[str, Dict[str, Collective]] = {}


def _collective_on(line: str, computation: str,
                   held: Dict[str, _FoundCollective]) -> Optional[_FoundCollective]:
    """The collective an instruction's line is, or holds through the
    computation it `calls=` (a fusion, an async wrapper), or None; `held`
    keeps the first one of each computation for its callers. The TPU
    compiler's own asynchronous form is a fusion whose computation holds the
    collective and a custom call `AsyncCollectiveStart` / `AsyncCollectiveDone`,
    which makes the computation's collective that half."""
    inside = None
    own = _COLLECTIVE_OP.match(line) if _MAY_BE_COLLECTIVE.search(line) else None
    if own:
        name, kind, half, operand, spelled = own.groups()
        inside = _FoundCollective(name, kind, half or "", name, spelled, operand)
    elif "AsyncCollectiveStart" in line and computation in held:
        held[computation] = held[computation]._replace(half="start")
    elif "AsyncCollectiveDone" in line and computation in held:
        held[computation] = held[computation]._replace(half="done")
    elif "calls=" in line:
        holder = _HOLDER.match(line)
        if holder and holder.group(4) in held:
            inner = held[holder.group(4)]
            inside = inner._replace(name=holder.group(1), operand=holder.group(3),
                                    half=_ASYNC_HALF.get(holder.group(2), inner.half))
    if inside:
        held.setdefault(computation, inside)
    return inside


def _numbers(text: Optional[str]) -> List[int]:
    return [int(n) for n in (text or "").split(",") if n]


def collective_groups(spelled: str, devices: int) -> Tuple[Tuple[int, ...], ...]:
    """The device groups of a collective as its instruction spells them:
    `replica_groups={{0,1},{2,3}}`, `={}` (all `devices` in one), the iota
    forms `[2,2]<=[4]` and `[2,2]<=[2,2]T(1,0)` (the ids 0..n-1 reshaped,
    transposed, and cut into rows), or a permute's
    `source_target_pairs={{0,1},{1,0}}`. () where it spells none."""
    import itertools

    iota = _GROUP_IOTA.search(spelled)
    if iota:
        dims, reshape, perm = (_numbers(part) for part in iota.groups())
        perm = perm or list(range(len(reshape)))
        strides = [1] * len(reshape)
        for k in range(len(reshape) - 2, -1, -1):
            strides[k] = strides[k + 1] * reshape[k + 1]
        flat = [sum(i * strides[p] for i, p in zip(index, perm))
                for index in itertools.product(*(range(reshape[p]) for p in perm))]
        return tuple(tuple(flat[at:at + dims[-1]]) for at in range(0, len(flat), dims[-1]))
    listed = _GROUP_LIST.search(spelled)
    if listed is None:
        return ()
    groups = tuple(tuple(_numbers(group)) for group in re.findall(r"\{([\d,]*)\}", listed.group(1)))
    return groups or ((tuple(range(devices)),) if devices else ())


def group_axes(groups: Tuple[Tuple[int, ...], ...], mesh_axes: Tuple[Tuple[str, int], ...],
               pairs: bool = False) -> Tuple[str, ...]:
    """The axes of a mesh (`tuple(mesh.shape.items())`) that device groups
    run along. A partition id is a position in the executable's device
    assignment, which for a step jitted under a mesh is the mesh's devices
    row-major, so id i has the coordinates of position i. Groups run along
    the axes whose coordinate varies inside a group, if the groups are
    exactly the mesh cut along the other axes; () where they are not (or
    name a device the mesh has not). A permute's `pairs` run along every
    axis on which some source and its target differ."""
    sizes = [size for _, size in mesh_axes]
    devices = math.prod(sizes)

    def coordinates(device: int) -> Tuple[int, ...]:
        out = []
        for size in reversed(sizes):
            out.append(device % size)
            device //= size
        return tuple(reversed(out))

    if not groups or any(not 0 <= d < devices for group in groups for d in group):
        return ()
    if pairs:
        varying = {k for source, target in groups
                   for k, (a, b) in enumerate(zip(coordinates(source), coordinates(target))) if a != b}
    else:
        varying = {k for k in range(len(sizes)) if len({coordinates(d)[k] for d in groups[0]}) > 1}
        cut: Dict[Tuple[int, ...], set] = {}
        for device in range(devices):
            at = coordinates(device)
            cut.setdefault(tuple(c for k, c in enumerate(at) if k not in varying), set()).add(device)
        if {frozenset(group) for group in groups} != {frozenset(group) for group in cut.values()}:
            return ()
    return tuple(name for k, (name, _) in enumerate(mesh_axes) if k in varying)


def _array_bytes(shape: str) -> List[int]:
    """Bytes of each array a printed shape holds (`bf16[12,1024,4096]`, a
    tuple's `(f32[4096], f32[])`; a width in bits is the first number of the
    element type's name, `pred` a byte, `token[]` none)."""
    out = []
    for dtype, dims in _ARRAY.findall(shape):
        bits = re.search(r"\d+", dtype)
        if bits is None and dtype != "pred":
            continue
        out.append(math.prod(_numbers(dims)) * (int(bits.group()) if bits else 8) // 8)
    return out


def _collective_records(found: List[_FoundCollective], shapes: Dict[str, str],
                        mesh_axes: Tuple[Tuple[str, int], ...]) -> Dict[str, Collective]:
    """{operation name: record} of the collectives the walk found; `shapes`
    is {collective instruction: its printed result shape}. A done finds its
    start by its operand, or, like a fusion that carries an asynchronous
    collective under its compute, by the channel the pieces share."""
    devices = math.prod(size for _, size in mesh_axes)
    records: Dict[str, Collective] = {}
    started: Dict[str, str] = {}        # channel id -> the start on it
    placed: Dict[Tuple[Any, ...], Tuple[str, ...]] = {}     # a step spells a handful of groups, each many times
    for c in sorted(found, key=lambda c: c.half != "start"):     # starts first, else as the text has them
        channel = _CHANNEL.search(c.spelled)
        channel = channel.group(1) if channel else ""
        arrays = _array_bytes(shapes.get(c.inner, ""))
        named = c.half == "done" and c.operand in records and records[c.operand].half == "start"
        start = c.operand if named else started.get(channel, "")
        if start and c.half != "start":
            half = "done" if c.half == "done" else "under"
            size = records[start].bytes
            if half == "done":     # the pair's bytes are its result's, which the done says plainly
                size = sum(arrays) or size
                records[start] = records[start]._replace(bytes=size)
            records[c.name] = records[start]._replace(half=half, completes=start, bytes=size)
            continue
        pairs = c.kind == "collective-permute"
        groups = collective_groups(c.spelled, devices)
        # a start's own shape is (operand, result, ...): the larger of the two
        size = max(arrays, default=0) if c.half == "start" and c.inner == c.name else sum(arrays)
        if c.kind == "reduce-scatter" and groups:
            size *= len(groups[0])  # its operand is the full array
        if (groups, pairs) not in placed:
            placed[groups, pairs] = group_axes(groups, mesh_axes, pairs)
        records[c.name] = Collective(c.kind, c.half, "", groups, placed[groups, pairs], size)
        if c.half == "start" and channel:
            started[channel] = c.name
    return records


def program_collectives_table(text: str, mesh_axes: Tuple[Tuple[str, int], ...] = (),
                              shapes_text: Optional[str] = None) -> Dict[str, Collective]:
    """The text of an optimised HLO module -> {operation name as a device
    profile gives it: `Collective`} for every operation of
    `program_ops_table`'s that is a collective, one half of an asynchronous
    one, or a fusion or async wrapper that holds one. `mesh_axes` is
    `tuple(mesh.shape.items())` of the mesh the step runs under; the bytes
    need result shapes, which `shapes_text` (the same module printed with
    them; default: `text`) has."""
    found = _walk_module(text)[2]
    return _collective_records(found, _collective_shapes(text if shapes_text is None else shapes_text),
                               tuple(mesh_axes))


def _collective_shapes(text: str) -> Dict[str, str]:
    """{collective instruction: its result shape as printed} of a module's
    text that has shapes (`name = shape opcode(`)."""
    shapes = {}
    for call in _COLLECTIVE_CALL.finditer(text):
        name, _, shape = text[text.rfind("\n", 0, call.start()) + 1:call.start()].partition(" = ")
        shapes[name.split()[-1].lstrip("%")] = shape
    return shapes


def program_collectives() -> Dict[str, Dict[str, Collective]]:
    """{program name as a profile prints it: {operation name: `Collective`}}
    for every program registered in this process ({} for a one-chip step)."""
    with _collectives_lock:
        return dict(_program_collectives)


def collective_seconds(op_seconds: Dict[str, float], op_counts: Dict[str, float],
                       table: Dict[str, Tuple[OpInstance, ...]],
                       collectives: Dict[str, Collective]) -> List[Dict[str, Any]]:
    """A reduced profile's device seconds and runs by operation name joined
    to a program's collectives and, for the same names, its operation
    table's scopes and pass -> one row a (kind, axes, scopes, pass), most
    seconds first: `calls`, `bytes` (all calls'), `bytes_per_call`,
    `seconds` (on the profile's "XLA Ops" line: a synchronous collective
    whole, an asynchronous one's start and its wait in the done),
    `gbytes_per_s` (bytes over seconds) and `under_seconds` (the compute
    fusions an asynchronous collective is carried through: no part of
    `seconds`). The operations of an asynchronous collective are one
    collective: seconds summed, calls counted once. `scopes` are the
    sublayer scopes on the operation's path (() for the partitioner's own
    or an operation the table does not hold, whose pass is `other`).
    Collectives that never ran in the profile give no row."""
    parts: Dict[str, List[str]] = {}
    for name, record in collectives.items():
        parts.setdefault(record.completes or name, []).append(name)
    rows: Dict[Tuple[Any, ...], Dict[str, Any]] = {}
    for first, names in parts.items():
        own = [name for name in names if collectives[name].half != "under"]
        seconds = sum(op_seconds.get(name, 0.0) for name in own)
        calls = max((op_counts.get(name, 0.0) for name in own), default=0.0)
        if not seconds and not calls:
            continue
        record = collectives[first]
        scopes, pass_, _ = (table.get(first) or (((), "other", 0),))[0]
        scopes = tuple(s for s in scopes if s != _PHASE_SCOPE)
        row = rows.setdefault((record.kind, record.axes, scopes, pass_), {
            "kind": record.kind, "axes": record.axes, "scopes": scopes, "pass": pass_,
            "calls": 0.0, "bytes": 0.0, "seconds": 0.0, "under_seconds": 0.0})
        row["calls"] += calls
        row["bytes"] += record.bytes * calls
        row["seconds"] += seconds
        row["under_seconds"] += sum(op_seconds.get(name, 0.0) for name in names if name not in own)
    for row in rows.values():
        row["bytes_per_call"] = row["bytes"] / row["calls"] if row["calls"] else 0.0
        row["gbytes_per_s"] = row["bytes"] / row["seconds"] / 1e9 if row["seconds"] else 0.0
    return sorted(rows.values(), key=lambda row: -row["seconds"])


# what a profile lists as one operation though it only contains others
_CONTAINERS = frozenset({"while", "conditional", "call"})


def profiled_op_seconds(planes: Any, program: str) -> Tuple[Dict[str, float], Dict[str, float]]:
    """({operation: device seconds}, {operation: times it ran}) inside the
    runs of `program`, summed over the chips of a profile
    (`jax.profiler.ProfileData.planes`: a chip is a plane `/device:TPU:<n>`
    whose line "XLA Modules" has an event a program run, `<program>(<hash>)`,
    and whose line "XLA Ops" one an operation, named by its HLO text),
    keyed as `program_ops_table` keys them."""
    import bisect

    seconds: Dict[str, float] = {}
    counts: Dict[str, float] = {}
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Modules" not in lines or "XLA Ops" not in lines:
            continue
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in lines["XLA Modules"].events
                      if e.name.split("(")[0] == program)
        starts = [start for start, _ in runs]
        for event in lines["XLA Ops"].events:
            at = bisect.bisect_right(starts, event.start_ns) - 1
            if at < 0 or event.start_ns >= runs[at][1]:
                continue
            name, _, rest = event.name.partition(" = ")
            name, opcode = _profile_key(name.lstrip("%").strip(), rest)
            if opcode in _CONTAINERS:
                continue
            seconds[name] = seconds.get(name, 0.0) + event.duration_ns / 1e9
            counts[name] = counts.get(name, 0.0) + 1
    return seconds, counts


def _captured_splits(logdir: str) -> Tuple[Dict[str, Dict[str, Any]], Dict[str, List[Dict[str, Any]]]]:
    """({program: its device seconds by "scope|pass", by pass, unscoped,
    unmatched and in all}, {program: `collective_seconds`' rows, axes and
    scopes as `fsdp,tp` and `attn.full/attn.proj`, where it ran any}) for
    every registered program that ran in the device trace under `logdir`;
    ({}, {}) where none did (no chip, no table)."""
    tables, collectives = program_ops(), program_collectives()
    paths = [os.path.join(root, name) for root, _dirs, names in os.walk(logdir)
             for name in names if name.endswith(".xplane.pb")]
    if not tables or not paths:
        return {}, {}
    import jax

    planes = list(jax.profiler.ProfileData.from_file(sorted(paths)[-1]).planes)
    scopes: Dict[str, Dict[str, Any]] = {}
    moved: Dict[str, List[Dict[str, Any]]] = {}
    for program, table in tables.items():
        seconds, counts = profiled_op_seconds(planes, program)
        if not seconds:
            continue
        split = scope_seconds(seconds, counts, table)
        scopes[program] = {
            "by_scope_pass": {f"{scope}|{pass_}": s
                              for (scope, pass_), s in sorted(split["by_scope_pass"].items())},
            "by_pass": split["by_pass"],
            "unscoped_s": sum(split["unscoped_ops"].values()),
            "unmatched_s": sum(split["unmatched_ops"].values()),
            "total_s": split["total"],
        }
        rows = collective_seconds(seconds, counts, table, collectives.get(program, {}))
        if rows:
            moved[program] = [dict(row, axes=",".join(row["axes"]), scopes="/".join(row["scopes"]))
                              for row in rows]
    return scopes, moved
