"""Request forensics plane: per-request lifecycle ledger.

The serve path's aggregate observability (histograms, SLO burn) answers
"how slow is the fleet" but not "why was THIS request slow". The
RequestLog records typed PHASE MARKS with both clocks (wall for
cross-node placement, mono for intra-process interval math) along the
whole request path: router receive → fair-queue park/grant → replica
dispatch (incl. failover hops) → engine admit (prefix-cache hit pages)
→ prefill chunks → first token → decode blocks → spec rounds → COW
copies → lane preempt/resume → finish/shed/timeout.

Marks live in a bounded per-node ring plus a bounded per-request
summary index; the cluster heartbeat federates each node's tail into
the GCS ``_requests`` table (core/cluster.py, same piggyback as the
flight recorder), so the head answers ``state.request_timeline(id)`` /
``state.list_requests()`` / ``ray_tpu request <id>`` cluster-wide. The
shared request id also lands on the trace spans, joining the two views.

Phases are TYPED: every ``mark`` names a phase registered in ``PHASES``
(the raylint ``request-phase`` rule holds call sites to the registry,
mirroring ``event-kinds``), so the waterfall renderer and the TTFT
decomposition can rely on phase names instead of parsing messages.
"""

from __future__ import annotations

import threading
import uuid
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from ..core.gcs import REQLOG_NS
from ..util.markring import (
    INDEX_ENTRIES,
    RING_MARKS,
    MarkRing,
    register_federated,
)

# ----------------------------------------------------------- phase registry
#
# phase -> one-line doc. Components may register additional phases at
# import time with register_phase (raylint's request-phase rule reads
# both this literal and register_phase("...") call sites).

PHASES: Dict[str, str] = {
    # HTTP frontends (openai.py, serve/api.py)
    "http.received": "an HTTP frontend accepted the request",
    # router (serve/router.py)
    "route.received": "the request entered the router via a handle",
    "route.shed": "the router shed the request (parked-queue bound)",
    "route.parked": "no replica had capacity; parked in the fair queue",
    "route.granted": "the fair queue granted the parked request a slot",
    "route.dispatched": "the router dispatched the call to a replica",
    "route.failover": "the router re-dispatched after a replica failure",
    "route.timeout": "the request deadline expired inside the router",
    "route.failed": "the router sealed a non-retryable failure",
    # engine admission (llm/engine.py, llm/paged_engine.py)
    "engine.submitted": "the engine accepted the request into its queue",
    "engine.shed": "engine admission control shed the request",
    "engine.timeout": "the request deadline expired inside the engine",
    "engine.admitted": "the request was seated in an engine lane",
    "engine.page_stall": "admission stalled waiting for KV pages",
    # engine execution (llm/paged_engine.py)
    "engine.prefill_chunk": "one prompt chunk was ingested",
    "engine.first_token": "the first token was emitted (TTFT point)",
    "engine.decode_block": "a fused decode block completed",
    "engine.spec_round": "a speculative verify round completed",
    "engine.cow": "a copy-on-write page copy before divergence",
    "engine.preempted": "the lane was parked for a higher-priority lane",
    "engine.resumed": "a parked lane was re-admitted",
    "engine.finished": "the request finished and emitted its last token",
}

# Phases that END a request: once one is recorded, the request is no
# longer pending (the satellite fix — shed/expired requests must never
# appear forever-pending in list_requests()).
TERMINAL_PHASES = frozenset({
    "route.shed", "route.timeout", "route.failed",
    "engine.shed", "engine.timeout", "engine.finished",
})


def register_phase(phase: str, doc: str = "") -> None:
    """Register an additional typed request phase (idempotent)."""
    PHASES.setdefault(phase, doc)


def request_phases() -> Dict[str, str]:
    """The registered phase catalog (copy)."""
    return dict(PHASES)


def new_request_id() -> str:
    """A fresh end-to-end request id (the public key threaded
    frontend→router→replica→engine and echoed in responses)."""
    return "req-" + uuid.uuid4().hex[:16]


class RequestLog:
    """Per-process request recorder: a bounded mark ring plus a bounded
    per-request summary index (OrderedDict, oldest-evicted-first)."""

    def __init__(self, mark_capacity: int = RING_MARKS,
                 request_capacity: int = INDEX_ENTRIES):
        self.ring = MarkRing(mark_capacity, on_append=self._index_locked)
        self._lock = self.ring.lock  # the index shares it
        self._requests: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._request_capacity = request_capacity

    def mark(self, request_id: str, phase: str,
             node: Optional[str] = None,
             tenant: Optional[str] = None,
             **attrs: Any) -> Dict[str, Any]:
        """Record one typed phase mark. `phase` is a registered PHASES
        name (the raylint request-phase rule enforces this statically —
        at runtime unknown phases are still recorded). The ring stamps
        seq, both clocks and the default node."""
        rec: Dict[str, Any] = {
            "seq": 0,
            "rid": request_id,
            "phase": phase,
            "ts": None,
            "mono": None,
            "node": node,
        }
        if tenant is not None:
            rec["tenant"] = tenant
        if attrs:
            rec["attrs"] = attrs
        return self.ring.append(rec)

    def _index_locked(self, rec: Dict[str, Any]) -> None:
        rid = rec["rid"]
        summary = self._requests.get(rid)
        if summary is None:
            summary = {
                "request_id": rid,
                "tenant": rec.get("tenant"),
                "node": rec.get("node"),
                "first_ts": rec["ts"],
                "last_ts": rec["ts"],
                "first_phase": rec["phase"],
                "last_phase": rec["phase"],
                "marks": 0,
                "terminal": None,
                "ttft_s": None,
            }
            self._requests[rid] = summary
            while len(self._requests) > self._request_capacity:
                self._requests.popitem(last=False)
        summary["marks"] += 1
        summary["last_ts"] = rec["ts"]
        summary["last_phase"] = rec["phase"]
        if rec.get("tenant") is not None:
            summary["tenant"] = rec["tenant"]
        # first terminal wins: a late straggler mark must not resurrect
        # a shed/timed-out request into a different outcome
        if rec["phase"] in TERMINAL_PHASES and summary["terminal"] is None:
            summary["terminal"] = rec["phase"]
        if rec["phase"] == "engine.first_token":
            attrs = rec.get("attrs") or {}
            summary["ttft_s"] = attrs.get("ttft_s")
            summary["buckets"] = {
                k: attrs[k]
                for k in ("queue_wait_s", "preempt_wait_s",
                          "prefill_compute_s", "cache_saved_s")
                if k in attrs
            }

    # --------------------------------------------------------------- queries

    def timeline(self, request_id: str) -> List[Dict[str, Any]]:
        """Every buffered mark of one request, oldest first."""
        return [m for m in self.ring.since() if m["rid"] == request_id]

    def requests(self, tenant: Optional[str] = None,
                 slow_only: bool = False,
                 limit: int = 200) -> List[Dict[str, Any]]:
        """Request summaries, newest last. `slow_only` keeps requests
        whose TTFT exceeded the serve SLO objective or that timed out."""
        from ..core.config import cfg

        slo = cfg.serve_slo_ttft_p99_s
        with self._lock:
            out = [dict(s) for s in self._requests.values()]
        if tenant is not None:
            out = [s for s in out if s.get("tenant") == tenant]
        if slow_only:
            out = [
                s for s in out
                if (s.get("ttft_s") is not None and s["ttft_s"] > slo)
                or s.get("terminal") in ("route.timeout", "engine.timeout")
            ]
        return out[-limit:]

    def since(self, seq: int, max_n: int = 1000) -> List[Dict[str, Any]]:
        """The OLDEST max_n marks with seq greater than `seq`
        (MarkRing.since: the federation cursor's walk)."""
        return self.ring.since(seq, max_n)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            ring = self.ring.stats()
            return {
                "seq": ring["seq"],
                "buffered_marks": ring["buffered"],
                "indexed_requests": len(self._requests),
            }

    def clear(self) -> None:
        with self._lock:
            self.ring.clear()
            self._requests.clear()


# ------------------------------------------------------- module singleton

_reqlog: Optional[RequestLog] = None
_reqlog_lock = threading.Lock()


def log() -> RequestLog:
    global _reqlog
    with _reqlog_lock:
        if _reqlog is None:
            _reqlog = RequestLog()
        return _reqlog


def enabled() -> bool:
    from ..core.config import cfg

    return bool(cfg.serve_request_log)


def mark(request_id: Optional[str], phase: str,
         tenant: Optional[str] = None, **attrs: Any) -> None:
    """Fast-path module-level mark: no-op when the request has no id
    (recorder off at ingress) or the recorder is disabled."""
    if request_id is None or not enabled():
        return
    log().mark(request_id, phase, tenant=tenant, **attrs)


register_federated("requests", REQLOG_NS, lambda: log().ring, enabled)


# ------------------------------------------------------- derived views


def summarize_marks(marks: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Build request summaries from a flat mark list (the federated
    path: other nodes' marks arrive via the GCS table without their
    summary index)."""
    scratch = RequestLog(mark_capacity=len(marks) + 1,
                         request_capacity=len(marks) + 1)
    with scratch._lock:
        for m in sorted(marks, key=lambda m: (m.get("ts", 0.0),
                                              m.get("seq", 0))):
            scratch._index_locked(m)
        return [dict(s) for s in scratch._requests.values()]


def decompose(marks: List[Dict[str, Any]]) -> Dict[str, Any]:
    """TTFT decomposition of one request's timeline: the bucket attrs
    the engine attached at the first-token mark (queue_wait +
    preempt_wait + prefill_compute sum to the measured TTFT by
    construction; cache_saved is the informational estimate of what the
    prefix cache skipped, NOT part of the sum)."""
    for m in marks:
        if m.get("phase") == "engine.first_token":
            attrs = dict(m.get("attrs") or {})
            return attrs
    return {}


def render_waterfall(marks: List[Dict[str, Any]]) -> str:
    """Causally-ordered text waterfall of one request's marks: relative
    wall-clock offsets, per-mark attrs, and the TTFT decomposition
    footer. Marks from several nodes interleave on wall time (the same
    ordering the postmortem timeline uses for cross-node placement)."""
    if not marks:
        return "(no marks)"
    marks = sorted(marks, key=lambda m: (m.get("ts", 0.0), m.get("seq", 0)))
    rid = marks[0].get("rid", "?")
    tenant = next((m["tenant"] for m in marks if m.get("tenant")), None)
    t0 = marks[0].get("ts", 0.0)
    span = max(m.get("ts", t0) for m in marks) - t0
    lines = [
        f"request {rid}"
        + (f" · tenant {tenant}" if tenant else "")
        + f" · {len(marks)} mark(s) · {span:.3f}s"
    ]
    width = 28
    for m in marks:
        off = m.get("ts", t0) - t0
        bar_at = 0 if span <= 0 else int((off / span) * (width - 1))
        bar = " " * bar_at + "|"
        node = str(m.get("node") or "")[:8]
        attrs = m.get("attrs") or {}
        attr_txt = " ".join(
            f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in attrs.items()
        )
        lines.append(
            f"  +{off:9.4f}s {bar:<{width}} {m['phase']:<21}"
            f" {node:<8} {attr_txt}".rstrip()
        )
    d = decompose(marks)
    if d.get("ttft_s") is not None:
        parts = " + ".join(
            f"{k[:-2]} {d.get(k, 0.0):.4f}"
            for k in ("queue_wait_s", "preempt_wait_s", "prefill_compute_s")
        )
        cache = (
            f" (cache_saved ~{d['cache_saved_s']:.4f}s,"
            f" cached_tokens {d.get('cached_tokens', 0)})"
            if d.get("cache_saved_s") else ""
        )
        lines.append(f"  TTFT {d['ttft_s']:.4f}s = {parts}{cache}")
    terminal = next(
        (m["phase"] for m in marks if m["phase"] in TERMINAL_PHASES), None
    )
    if terminal:
        lines.append(f"  terminal: {terminal}")
    return "\n".join(lines)
