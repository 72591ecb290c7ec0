"""End-to-end distributed tracing: spans, context propagation, export.

Reference parity: the reference wires OpenTelemetry spans through its
workers (python/ray/util/tracing/tracing_helper.py — every task/actor
submission and execution gets a span whose context rides the TaskSpec)
and ships `ray timeline` for post-hoc chrome traces. TPU inversion: no
OpenTelemetry dependency in this image, so this is a lock-cheap
in-process tracer with the same wire semantics — 64-bit hex
trace_id/span_id/parent_id, a context-local "current span", and a
`_trace_ctx` dict that crosses the cluster RPC boundary (core/rpc.py
injects it into call frames; the serving agent extracts it and parents
its execution spans back to the driver's submit span, so one trace_id
spans processes).

Spans land in a per-process ring buffer (capacity
``cfg.trace_buffer_spans``) and are sampled per TRACE at the root
(``cfg.trace_sample_ratio``): an unsampled root hands every descendant —
local or remote — an unsampled context, so a whole request is either
fully recorded or free. Ending a span derives latency histograms
(raytpu_task_queue_seconds, raytpu_task_exec_seconds,
raytpu_serve_ttft_seconds, raytpu_serve_tpot_seconds,
raytpu_transfer_seconds) so the /metrics scrape and the trace waterfall
always agree. Export is chrome-trace/Perfetto JSON — spans nest, one
process lane per node, one thread lane per actor/engine slot/thread —
superseding the completed-task-only `chrome_tracing_dump`.

Clocks. Every span carries two stamps of each end: wall time
(``start_ts``/``end_ts``, ``time.time()``), which places spans of
different nodes on one axis, and the monotonic clock
(``start_mono``/``end_mono``, ``time.perf_counter()``), which is what
durations, train/steplog buckets and the benchmark's window are
measured on. A span that follows another may take that span's end as
its start (``start=prev.ended``), so a boundary is read once.

The profile. ``span()`` also enters ``jax.profiler.TraceAnnotation`` of
the same name once JAX has been imported by someone else (this module
never imports it): a TraceMe costs a flag check when no profile is being
taken, and while one is, every program span sits on the host lane of the
profile (the benchmark's, ``ray_tpu profile``'s, TensorBoard's) on the
profiler's own clock, beside the device's operations. Spans opened with
``start_span`` may end on another thread, which a TraceMe cannot, and are
not mirrored.

Compiles. The first span after JAX is imported registers one
``jax.monitoring`` listener: every jaxpr trace, lowering, backend
compile and persistent-cache fetch becomes a ``compile.*`` span, child of
the span current on the compiling thread, and feeds
``raytpu_compile_total{kind}`` / ``raytpu_compile_seconds_total{kind}``.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

__all__ = [
    "Span",
    "Tracer",
    "tracer",
    "span",
    "start_span",
    "current_context",
    "use_context",
    "inject_context",
    "extract_context",
    "export_chrome_trace",
    "compile_seconds",
]


def _new_id() -> str:
    return os.urandom(8).hex()


# ids of a span that is not recorded: nothing reads them (inject_context
# passes no unsampled context on), so none are drawn
_UNSAMPLED_ID = "0" * 16

Stamp = Tuple[float, float]     # one instant on both clocks: (wall, mono)

# The context-local current span context: {"trace_id", "span_id",
# "sampled"}. contextvars follow the thread that set them; hops across
# threads/processes are EXPLICIT — carry `current_context()` with the
# work item and re-enter it with `use_context`/`start_span(parent=...)`.
_current: "contextvars.ContextVar[Optional[Dict[str, Any]]]" = contextvars.ContextVar(
    "ray_tpu_trace_ctx", default=None
)


class Span:
    """One timed operation. Not thread-safe for concurrent mutation, but
    start/end may happen on different threads (engine submit thread vs.
    loop thread) — `end()` is idempotent. An unsampled span still keeps
    its stamps (callers read durations off it) and is never recorded."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "start_ts", "end_ts",
        "start_mono", "end_mono", "attrs", "status", "lane", "sampled",
        "_tracer", "_ended",
    )

    def __init__(self, trace_id: str, span_id: str, parent_id: Optional[str],
                 name: str, *, attrs: Optional[Dict[str, Any]] = None,
                 lane: str = "", sampled: bool = True,
                 start_ts: Optional[float] = None,
                 start: Optional[Stamp] = None,
                 tracer_: "Optional[Tracer]" = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        if start is not None:
            self.start_ts, self.start_mono = start
        else:
            now, self.start_mono = time.time(), time.perf_counter()
            if start_ts is None:
                self.start_ts = now
            else:   # a wall stamp taken earlier: the same instant on the mono clock
                self.start_ts = start_ts
                self.start_mono -= now - start_ts
        self.end_ts = self.end_mono = 0.0
        self.attrs = dict(attrs) if attrs else {}
        self.status = "OK"
        self.lane = lane
        self.sampled = sampled
        self._tracer = tracer_
        self._ended = False

    @property
    def context(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "sampled": self.sampled,
        }

    @property
    def started(self) -> Stamp:
        return self.start_ts, self.start_mono

    @property
    def ended(self) -> Stamp:
        """The end's stamp, for the `start=` of the span that follows."""
        return self.end_ts, self.end_mono

    @property
    def duration_s(self) -> float:
        """Seconds on the monotonic clock (0 until the span has ended)."""
        return max(0.0, self.end_mono - self.start_mono)

    def set_attribute(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def end(self, status: str = "OK",
            end_ts: Optional[float] = None, **attrs: Any) -> None:
        if self._ended:
            return
        self._ended = True
        now, self.end_mono = time.time(), time.perf_counter()
        if end_ts is None:
            self.end_ts = now
        else:
            self.end_ts = end_ts
            self.end_mono -= now - end_ts
        self.status = status
        if attrs:
            self.attrs.update(attrs)
        if self.sampled and self._tracer is not None:
            self._tracer._record(self)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_ts": self.start_ts,
            "end_ts": self.end_ts,
            "start_mono": self.start_mono,
            "end_mono": self.end_mono,
            "duration_s": self.duration_s,
            "status": self.status,
            "lane": self.lane,
            "attrs": dict(self.attrs),
        }

    def __repr__(self):
        return (f"Span({self.name!r}, trace={self.trace_id[:8]}, "
                f"span={self.span_id[:8]}, parent={str(self.parent_id)[:8]})")


# ------------------------------------------------------- span-derived metrics

# span name -> (histogram name, description, bucket boundaries). Observed
# at end() so the waterfall and the /metrics scrape tell the same story.
_DURATION_METRICS: Dict[str, tuple] = {
    "task.queue": (
        "raytpu_task_queue_seconds",
        "Submit-to-dispatch queue latency of tasks, from spans.",
        (0.001, 0.01, 0.1, 1.0, 10.0),
    ),
    "task.execute": (
        "raytpu_task_exec_seconds",
        "Wall-clock execution time of tasks, from spans.",
        (0.001, 0.01, 0.1, 1.0, 10.0, 60.0),
    ),
    "transfer.pull": (
        "raytpu_transfer_seconds",
        "Node-to-node object transfer latency, from spans.",
        (0.001, 0.01, 0.1, 1.0, 10.0),
    ),
    "transfer.push": (
        "raytpu_transfer_seconds",
        "Node-to-node object transfer latency, from spans.",
        (0.001, 0.01, 0.1, 1.0, 10.0),
    ),
}

# attribute of an ending "serve.request"/"engine.request" span ->
# histogram. TTFT/TPOT/queue-time fall out of the request span instead of
# ad-hoc timers (the Gemma-on-TPU comparison reports exactly these).
_SERVE_ATTR_METRICS: Dict[str, tuple] = {
    "ttft_s": (
        "raytpu_serve_ttft_seconds",
        "Time to first generated token, from engine request spans.",
        (0.005, 0.025, 0.1, 0.5, 2.0, 10.0),
    ),
    "tpot_s": (
        "raytpu_serve_tpot_seconds",
        "Time per output token after the first, from engine request spans.",
        (0.001, 0.005, 0.025, 0.1, 0.5),
    ),
    "queue_s": (
        "raytpu_serve_queue_seconds",
        "Engine admission queue wait, from engine request spans.",
        (0.001, 0.01, 0.1, 1.0, 10.0),
    ),
}


_DERIVED_NAMES = frozenset(_DURATION_METRICS) | {"engine.request", "serve.request"}


def _observe_derived(span_: Span) -> None:
    from .metrics import get_or_create_histogram

    spec = _DURATION_METRICS.get(span_.name)
    if spec is not None:
        name, desc, bounds = spec
        tags = None
        if span_.name.startswith("transfer."):
            tags = {"direction": span_.name.split(".", 1)[1]}
        get_or_create_histogram(name, desc, boundaries=bounds,
                                tag_keys=("direction",) if tags else ()).observe(
            span_.duration_s, tags=tags
        )
    if span_.name in ("engine.request", "serve.request"):
        for attr, (name, desc, bounds) in _SERVE_ATTR_METRICS.items():
            value = span_.attrs.get(attr)
            if isinstance(value, (int, float)) and value >= 0:
                get_or_create_histogram(name, desc, boundaries=bounds).observe(
                    float(value)
                )


# ------------------------------------------------------------------- tracer


class Tracer:
    """Per-process span sink: a ring buffer plus the sampling decision.

    Lock discipline: one mutex guards only the deque in `_record` and the
    queries; span creation takes no lock at all (ids are os.urandom, the
    sampling roll is thread-local random), so tracing stays off the hot
    path's contention profile. The ring holds the ended Span objects;
    the queries hand out dictionaries."""

    def __init__(self, capacity: Optional[int] = None,
                 sample_ratio: Optional[float] = None):
        from ..core.config import cfg

        self._capacity = capacity or cfg.trace_buffer_spans
        self._sample_ratio = sample_ratio
        self._buf: "deque[Span]" = deque(maxlen=self._capacity)
        self._lock = threading.Lock()

    # -------------------------------------------------------------- creation

    def _sampled(self) -> bool:
        ratio = self._sample_ratio
        if ratio is None:
            from ..core.config import cfg

            ratio = cfg.trace_sample_ratio
        if ratio >= 1.0:
            return True
        if ratio <= 0.0:
            return False
        return random.random() < ratio

    def start_span(self, name: str, *, parent: Optional[Dict[str, Any]] = None,
                   attrs: Optional[Dict[str, Any]] = None, lane: str = "",
                   start_ts: Optional[float] = None,
                   start: Optional[Stamp] = None) -> Span:
        """Open a span. `parent` is a context dict (wire-shaped); when
        None the context-local current span is the parent; when there is
        no current span either, this span roots a new trace and rolls
        the sampling decision for the whole trace. `start` is a stamp of
        both clocks taken already (`prev.ended`, `parent.started`);
        `start_ts` a wall time alone."""
        if parent is None:
            parent = _current.get()
        if parent is None:
            sampled, trace_id, parent_id = self._sampled(), None, None
        else:
            sampled = bool(parent.get("sampled", True))
            trace_id, parent_id = parent["trace_id"], parent["span_id"]
        if not sampled:
            return Span(trace_id or _UNSAMPLED_ID, _UNSAMPLED_ID, parent_id,
                        name, lane=lane, sampled=False, start_ts=start_ts,
                        start=start)
        return Span(trace_id or _new_id(), _new_id(), parent_id, name,
                    attrs=attrs, lane=lane, start_ts=start_ts, start=start,
                    tracer_=self)

    def record_span(self, name: str, start_ts: float, end_ts: float, *,
                    parent: Optional[Dict[str, Any]] = None,
                    attrs: Optional[Dict[str, Any]] = None,
                    lane: str = "", status: str = "OK") -> Span:
        """Record an already-finished interval (e.g. queue time measured
        after the fact) as one span."""
        span_ = self.start_span(name, parent=parent, attrs=attrs, lane=lane,
                                start_ts=start_ts)
        span_.end(status=status, end_ts=end_ts)
        return span_

    def _record(self, span_: Span) -> None:
        with self._lock:
            self._buf.append(span_)
        if span_.name in _DERIVED_NAMES:
            try:
                _observe_derived(span_)
            except Exception:  # noqa: BLE001 - metrics must not break tracing
                pass

    # --------------------------------------------------------------- queries

    def spans(self, trace_id: Optional[str] = None,
              limit: int = 10_000) -> List[Dict[str, Any]]:
        with self._lock:
            out = [
                s for s in self._buf
                if trace_id is None or s.trace_id == trace_id
            ]
        return [s.to_dict() for s in out[-limit:]]

    def list_traces(self, limit: int = 100) -> List[Dict[str, Any]]:
        """Newest-last trace summaries: root name, span count, duration."""
        with self._lock:
            snapshot = list(self._buf)
        traces: Dict[str, Dict[str, Any]] = {}
        for s in snapshot:
            t = traces.setdefault(s.trace_id, {
                "trace_id": s.trace_id,
                "root": s.name,
                "start_ts": s.start_ts,
                "end_ts": s.end_ts,
                "spans": 0,
                "errors": 0,
            })
            t["spans"] += 1
            t["start_ts"] = min(t["start_ts"], s.start_ts)
            t["end_ts"] = max(t["end_ts"], s.end_ts)
            if s.status != "OK":
                t["errors"] += 1
            if s.parent_id is None:
                t["root"] = s.name
        out = sorted(traces.values(), key=lambda t: t["start_ts"])
        for t in out:
            t["duration_s"] = max(0.0, t["end_ts"] - t["start_ts"])
        return out[-limit:]

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()


_tracer: Optional[Tracer] = None
_tracer_lock = threading.Lock()


def tracer() -> Tracer:
    global _tracer
    if _tracer is None:  # double-checked: creation is rare, reads are hot
        with _tracer_lock:
            if _tracer is None:
                _tracer = Tracer()
    return _tracer


# --------------------------------------------------------- context plumbing


def current_context() -> Optional[Dict[str, Any]]:
    """The active span's wire context, or None outside any span."""
    return _current.get()


@contextlib.contextmanager
def use_context(ctx: Optional[Dict[str, Any]]) -> Iterator[None]:
    """Adopt a propagated context (thread hop / RPC extract) for the
    duration of the block; no-op when ctx is None."""
    if ctx is None:
        yield
        return
    token = _current.set(ctx)
    try:
        yield
    finally:
        _current.reset(token)


def start_span(name: str, *, parent: Optional[Dict[str, Any]] = None,
               attrs: Optional[Dict[str, Any]] = None, lane: str = "") -> Span:
    """Module-level convenience over tracer().start_span (does NOT make
    the span current — use `span()` for that)."""
    return tracer().start_span(name, parent=parent, attrs=attrs, lane=lane)


class _SpanScope:
    """`with span(...)`: the span is current inside the block, mirrored
    into the profile, and ended on the way out."""

    __slots__ = ("span", "_token", "_annotation")

    def __init__(self, span_: Span):
        self.span = span_

    def __enter__(self) -> Span:
        sp = self.span
        self._token = _current.set(sp.context)
        # looked up for every span, recorded or not: the first look after
        # JAX is imported is what registers the compile listener
        annotation = _profile_annotation()
        self._annotation = (
            annotation(sp.name) if annotation is not None and sp.sampled else None)
        if self._annotation is not None:
            self._annotation.__enter__()
        return sp

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        _current.reset(self._token)
        if exc is None:
            self.span.end()
        else:
            self.span.end(status="ERROR", error=repr(exc))


def span(name: str, *, parent: Optional[Dict[str, Any]] = None,
         lane: str = "", start: Optional[Stamp] = None,
         **attrs: Any) -> _SpanScope:
    """Open a span, make it the context-local current span, end it on
    exit (status=ERROR with the exception repr on the error path). While
    a JAX profile is being taken the block is also a host event of that
    name in it."""
    return _SpanScope(
        tracer().start_span(name, parent=parent, attrs=attrs, lane=lane,
                            start=start))


# ------------------------------------------------------ the profile, compiles

_trace_annotation = None    # jax.profiler.TraceAnnotation, once JAX is there


def _profile_annotation():
    """jax.profiler.TraceAnnotation if somebody has imported JAX, else
    None: tracing never imports the accelerator stack. The first time it
    is found, the compile listener is registered with it."""
    global _trace_annotation
    if _trace_annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:    # not imported, or still importing
            return None
        with _tracer_lock:
            if _trace_annotation is None:
                import jax.monitoring

                jax.monitoring.register_event_duration_secs_listener(
                    _on_jax_duration)
                _trace_annotation = profiler.TraceAnnotation
    return _trace_annotation


# jax.monitoring duration event -> span kind
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
# fires, before backend_compile_duration does, only when the executable
# came from the persistent cache
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class _CompileLog(threading.local):
    """What one thread's compile events have added up to. Events of a
    thread arrive by end time, an inner trace before the one that
    contains it, so `open` keeps (start, seconds) of the spans no later
    one has swallowed yet."""

    def __init__(self) -> None:
        self.cache_hit = False
        self.open: List[Tuple[float, float]] = []


_compile_log = _CompileLog()
_compile_seconds = 0.0      # process-wide, nested spans counted once


def compile_seconds() -> float:
    """Seconds this process has spent tracing, lowering, compiling and
    fetching programs so far; a trace inside a trace counts once."""
    return _compile_seconds


def _on_jax_duration(event: str, duration: float, **kwargs: Any) -> None:
    """One compile-path event of JAX, as it ends on the compiling thread.
    JAX calls this from inside the user's jit call: it must not raise."""
    if event == _CACHE_HIT_EVENT:
        _compile_log.cache_hit = True
        return
    kind = _COMPILE_EVENTS.get(event)
    if kind is None:
        return
    if kind == "backend" and _compile_log.cache_hit:
        kind, _compile_log.cache_hit = "cache_load", False
    try:
        _record_compile(kind, duration, str(kwargs.get("fun_name", "")))
    except Exception:  # noqa: BLE001 - tracing must not break a compile
        pass


def _record_compile(kind: str, duration: float, fun_name: str) -> None:
    """A `compile.<kind>` span of that duration ending now, under the span
    current here (so the parent says who compiled), and the counters."""
    global _compile_seconds
    wall, mono = time.time(), time.perf_counter()
    sp = tracer().start_span(
        "compile." + kind, attrs={"fun_name": fun_name},
        start=(wall - duration, mono - duration))
    sp.end()
    inner, open_ = 0.0, _compile_log.open
    while open_ and open_[-1][0] >= sp.start_mono:
        inner += open_.pop()[1]
    open_.append((sp.start_mono, duration))
    del open_[:-4096]
    with _tracer_lock:
        _compile_seconds += duration - inner
    from .metrics import get_or_create_counter

    tags = {"kind": kind}
    get_or_create_counter(
        "raytpu_compile_total",
        "Programs traced, lowered, built (backend) or fetched from the "
        "persistent cache (cache_load), from jax.monitoring.",
        tag_keys=("kind",)).inc(tags=tags)
    get_or_create_counter(
        "raytpu_compile_seconds_total",
        "Seconds in jaxpr tracing, lowering, backend compiles and "
        "persistent-cache fetches, by kind (a nested trace counts in its "
        "own and in the enclosing one).",
        tag_keys=("kind",)).inc(duration, tags=tags)


# --------------------------------------------------------------- wire format

# RPC methods that never carry trace context: chunk windows fire dozens
# of times per transfer (the enclosing transfer.* span already times the
# whole thing) and heartbeats/polls are pure noise.
_RPC_SKIP = frozenset({
    "pull_chunk", "push_chunk", "heartbeat", "ping", "poll_task_done",
})


def inject_context(kwargs: Dict[str, Any], method: str = "") -> Dict[str, Any]:
    """Client half of the RPC boundary: attach the current span context
    as a `_trace_ctx` kwarg (only when a sampled span is active — idle
    control traffic stays zero-overhead)."""
    ctx = _current.get()
    if ctx is None or not ctx.get("sampled", True) or method in _RPC_SKIP:
        return kwargs
    out = dict(kwargs)
    out["_trace_ctx"] = ctx
    return out


def extract_context(kwargs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Server half: pop the propagated context out of the call kwargs
    (mutates kwargs so handlers never see the private field)."""
    ctx = kwargs.pop("_trace_ctx", None)
    return ctx if isinstance(ctx, dict) and "trace_id" in ctx else None


# ------------------------------------------------------------------- export


def export_chrome_trace(spans: List[Dict[str, Any]],
                        path: Optional[str] = None,
                        extra_events: Optional[List[Dict[str, Any]]] = None,
                        ) -> str:
    """Chrome trace-event / Perfetto JSON for a span set. Spans nest by
    time on their lane: pid = the span's lane (node/actor/engine slot,
    falling back to the trace id), tid = the span name's subsystem. Load
    in https://ui.perfetto.dev or chrome://tracing.

    `extra_events` are pre-built trace events appended verbatim — the
    hook `state.trace_dump(profile_id=...)` uses to merge a captured
    device trace's per-device tracks (util/profiling
    load_device_trace_events, already wall-clock aligned) into the same
    file, so one timeline shows what the runtime asked for AND what the
    chip did.

    Parent→child links that CROSS a lane (a remote task's execute span
    parenting back to the driver's submit span, a router hop landing on
    a replica) additionally emit chrome flow events (ph "s"/"f") so the
    cross-node causality renders as arrows between tracks, not just
    vertically stacked slices."""
    events: List[Dict[str, Any]] = list(extra_events or [])
    by_id = {s["span_id"]: s for s in spans}

    def _pid(s: Dict[str, Any]) -> str:
        return s.get("lane") or s["trace_id"][:8]

    def _tid(s: Dict[str, Any]) -> str:
        return s["name"].split(".", 1)[0]

    for s in spans:
        parent = by_id.get(s["parent_id"]) if s.get("parent_id") else None
        if parent is None or _pid(parent) == _pid(s):
            continue
        # flow id from the child span id: unique per edge, stable across
        # re-exports of the same span set
        flow_id = int(s["span_id"][:12], 16)
        events.append({
            "name": "span-link", "cat": "flow", "ph": "s", "id": flow_id,
            "ts": parent["start_ts"] * 1e6,
            "pid": _pid(parent), "tid": _tid(parent),
            "args": {"trace_id": s["trace_id"], "child": s["name"]},
        })
        events.append({
            "name": "span-link", "cat": "flow", "ph": "f", "bp": "e",
            "id": flow_id,
            "ts": max(s["start_ts"], parent["start_ts"]) * 1e6,
            "pid": _pid(s), "tid": _tid(s),
            "args": {"trace_id": s["trace_id"], "parent": parent["name"]},
        })
    for s in spans:
        end = s["end_ts"] or s["start_ts"]
        pid = s.get("lane") or s["trace_id"][:8]
        events.append({
            "name": s["name"],
            "cat": s["name"].split(".", 1)[0],
            "ph": "X",
            "ts": s["start_ts"] * 1e6,
            "dur": max(0.0, end - s["start_ts"]) * 1e6,
            "pid": pid,
            "tid": s["name"].split(".", 1)[0],
            "args": {
                "trace_id": s["trace_id"],
                "span_id": s["span_id"],
                "parent_id": s["parent_id"],
                "status": s["status"],
                "start_mono": s.get("start_mono"),
                "end_mono": s.get("end_mono"),
                **{k: v for k, v in s.get("attrs", {}).items()
                   if isinstance(v, (str, int, float, bool, type(None)))},
            },
        })
    payload = json.dumps({"traceEvents": events})
    if path:
        with open(path, "w") as f:
            f.write(payload)
    return payload
