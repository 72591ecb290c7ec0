"""Request forensics plane (serve/reqlog.py): per-request token-level
timelines, TTFT attribution, and live engine introspection.

The load-bearing drills:
- every exit path leaves a TERMINAL phase — shed/expired requests never
  read as forever-pending;
- the TTFT decomposition is exact by construction: queue_wait +
  preempt_wait + prefill_compute == TTFT (within the 5% acceptance
  band), with cache_saved as an informational side channel;
- the flagship waterfall: one request whose timeline shows a
  prefix-cache-hit admission, speculative verify rounds with rollback,
  and a lane preemption + resume — causally ordered across phases;
- marks federate into the GCS ``_requests`` table and the state
  queries join them cluster-wide on the shared request id.
"""

import json
import queue as queue_mod
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.config import cfg
from ray_tpu.core.exceptions import BackPressureError, RequestTimeoutError
from ray_tpu.models import get_config, init_params
from ray_tpu.serve import reqlog, tenancy
from ray_tpu.serve.llm.engine import _Request, _observe_tenant_ttft
from ray_tpu.serve.llm.paged import PagedConfig
from ray_tpu.serve.llm.paged_engine import PagedEngineConfig, PagedLLMEngine


@pytest.fixture(autouse=True)
def _clean_reqlog():
    reqlog.log().clear()
    tenancy.reset()
    yield
    reqlog.log().clear()
    tenancy.reset()
    cfg.reset()


def _tiny_engine(model="llama-tiny", seed=0, **over):
    config = get_config(model)
    params = init_params(config, jax.random.PRNGKey(seed))
    paged = dict(
        page_size=8, num_pages=64, max_pages_per_slot=8, chunk_pages=2,
        prefix_cache=True,
    )
    paged.update(over.pop("paged", {}))
    defaults = dict(max_slots=4, paged=PagedConfig(**paged))
    defaults.update(over)
    return config, params, PagedLLMEngine(
        config, params, PagedEngineConfig(**defaults)
    )


def _phases(marks):
    return [m["phase"] for m in marks]


# ------------------------------------------------------------ recorder core


def test_mark_records_both_clocks_and_indexes():
    rl = reqlog.RequestLog()
    rec = rl.mark("req-a", "engine.submitted", tenant="t1", prompt_tokens=3)
    assert rec["rid"] == "req-a" and rec["phase"] == "engine.submitted"
    assert rec["ts"] > 0 and rec["mono"] > 0 and rec["seq"] == 1
    assert rec["attrs"] == {"prompt_tokens": 3}
    rl.mark("req-a", "engine.finished", tenant="t1")
    tl = rl.timeline("req-a")
    assert _phases(tl) == ["engine.submitted", "engine.finished"]
    (summary,) = rl.requests()
    assert summary["request_id"] == "req-a"
    assert summary["tenant"] == "t1"
    assert summary["marks"] == 2
    assert summary["terminal"] == "engine.finished"


def test_terminal_phase_first_wins():
    rl = reqlog.RequestLog()
    rl.mark("req-b", "engine.shed", reason="queue_full")
    rl.mark("req-b", "engine.finished")  # late straggler must not flip it
    (summary,) = rl.requests()
    assert summary["terminal"] == "engine.shed"
    assert reqlog.TERMINAL_PHASES <= set(reqlog.PHASES)


def test_ring_and_index_eviction():
    rl = reqlog.RequestLog(mark_capacity=8, request_capacity=4)
    for i in range(20):
        rl.mark(f"req-{i}", "engine.submitted")
    stats = rl.stats()
    assert stats["buffered_marks"] == 8
    assert stats["indexed_requests"] == 4
    assert stats["seq"] == 20
    # oldest evicted from both views, newest retained
    assert rl.timeline("req-0") == []
    assert rl.timeline("req-19")
    ids = {s["request_id"] for s in rl.requests()}
    assert ids == {f"req-{i}" for i in range(16, 20)}


def test_since_cursor_walks_oldest_first():
    rl = reqlog.RequestLog()
    for i in range(5):
        rl.mark("req-c", "engine.decode_block", steps=i)
    batch = rl.since(0, max_n=3)
    assert [m["seq"] for m in batch] == [1, 2, 3]
    rest = rl.since(batch[-1]["seq"], max_n=10)
    assert [m["seq"] for m in rest] == [4, 5]
    assert rl.since(5) == []


def test_summarize_marks_rebuilds_federated_summaries():
    rl = reqlog.RequestLog()
    rl.mark("req-d", "route.received", tenant="t9")
    rl.mark("req-d", "engine.first_token", ttft_s=0.5, queue_wait_s=0.1,
            preempt_wait_s=0.0, prefill_compute_s=0.4, cache_saved_s=0.0)
    rl.mark("req-d", "engine.finished")
    rl.mark("req-e", "route.shed", reason="parked_queue_full")
    summaries = {s["request_id"]: s
                 for s in reqlog.summarize_marks(rl.since(0))}
    assert summaries["req-d"]["terminal"] == "engine.finished"
    assert summaries["req-d"]["ttft_s"] == 0.5
    assert summaries["req-d"]["buckets"]["queue_wait_s"] == 0.1
    assert summaries["req-e"]["terminal"] == "route.shed"


def test_render_waterfall_orders_and_decomposes():
    rl = reqlog.RequestLog()
    rl.mark("req-w", "route.received", tenant="gold")
    rl.mark("req-w", "route.dispatched", replica="abc123", attempt=1)
    rl.mark("req-w", "engine.admitted", hit_pages=2, cached_tokens=16)
    rl.mark("req-w", "engine.first_token", ttft_s=0.8, queue_wait_s=0.2,
            preempt_wait_s=0.1, prefill_compute_s=0.5, cache_saved_s=0.3,
            cached_tokens=16)
    rl.mark("req-w", "engine.finished")
    text = reqlog.render_waterfall(rl.timeline("req-w"))
    lines = text.splitlines()
    assert "req-w" in lines[0] and "gold" in lines[0]
    positions = [text.index(p) for p in (
        "route.received", "route.dispatched", "engine.admitted",
        "engine.first_token", "engine.finished")]
    assert positions == sorted(positions)  # causal order preserved
    assert any("TTFT 0.8000s = queue_wait 0.2000 + preempt_wait 0.1000 "
               "+ prefill_compute 0.5000" in line for line in lines)
    assert any("cache_saved ~0.3000s" in line for line in lines)
    assert lines[-1].strip() == "terminal: engine.finished"
    assert reqlog.render_waterfall([]) == "(no marks)"


def test_module_mark_is_noop_without_id_or_when_disabled():
    before = reqlog.log().stats()["seq"]
    reqlog.mark(None, "engine.submitted")
    assert reqlog.log().stats()["seq"] == before
    cfg.set(serve_request_log=False)
    try:
        assert not reqlog.enabled()
        reqlog.mark("req-off", "engine.submitted")
        assert reqlog.log().stats()["seq"] == before
    finally:
        cfg.reset()
    rid = reqlog.new_request_id()
    assert rid.startswith("req-") and len(rid) == 20
    assert rid != reqlog.new_request_id()


def test_register_phase_is_idempotent_and_additive():
    reqlog.register_phase("test.custom", "a drill phase")
    reqlog.register_phase("test.custom", "overwrite attempt ignored")
    assert reqlog.request_phases()["test.custom"] == "a drill phase"
    del reqlog.PHASES["test.custom"]


# -------------------------------------------------------- engine timelines


def test_prefix_hit_admit_timeline():
    """Second request over a warmed prefix records the hit at admission
    (hit_pages/cached_tokens) and a cache_saved estimate at first token."""
    config, params, engine = _tiny_engine()
    try:
        shared = [11, 22, 33, 44, 55, 66, 77, 88,
                  12, 23, 34, 45, 56, 67, 78, 89]  # 2 full pages
        warm = engine.submit(list(shared), max_tokens=2, tenant="warm",
                             request_id="req-warm")
        warm.result(timeout=60)
        hit = engine.submit(list(shared) + [7, 14, 21], max_tokens=2,
                            tenant="hit", request_id="req-hit")
        assert hit.request_id == "req-hit"
        hit.result(timeout=60)
        tl = reqlog.log().timeline("req-hit")
        phases = _phases(tl)
        assert phases[0] == "engine.submitted"
        assert phases[-1] == "engine.finished"
        admitted = next(m for m in tl if m["phase"] == "engine.admitted")
        assert admitted["attrs"]["hit_pages"] == 2
        assert admitted["attrs"]["cached_tokens"] == 16
        first = next(m for m in tl if m["phase"] == "engine.first_token")
        assert first["attrs"]["cache_saved_s"] > 0
        assert first["attrs"]["cached_tokens"] == 16
        assert "engine.prefill_chunk" in phases
    finally:
        engine.shutdown()


def test_shed_and_expiry_record_terminal_phases():
    """The satellite fix: EVERY shed/expiry exit leaves a terminal mark
    — with the honest Retry-After on quota sheds."""
    tenancy.set_tenant("free", quota_rps=0.05, quota_burst=1.0)
    config, params, engine = _tiny_engine(max_slots=1)
    try:
        ok = engine.submit([3, 1, 4], max_tokens=16, tenant="free",
                           request_id="req-ok")
        with pytest.raises(BackPressureError):
            engine.submit([3, 1, 4], max_tokens=2, tenant="free",
                          request_id="req-quota")
        tl = reqlog.log().timeline("req-quota")
        assert _phases(tl) == ["engine.shed"]
        assert tl[0]["attrs"]["reason"] == "quota"
        assert tl[0]["attrs"]["retry_after_s"] > 0

        # expiry while queued behind the busy lane → engine.timeout
        doomed = engine.submit([4, 5, 6], max_tokens=4, tenant="other",
                               deadline_ts=time.time() + 0.15,
                               request_id="req-doomed")
        time.sleep(0.25)
        with pytest.raises(RequestTimeoutError):
            doomed.result(timeout=60)
        ok.result(timeout=120)
        doomed_tl = reqlog.log().timeline("req-doomed")
        assert doomed_tl[-1]["phase"] == "engine.timeout"
        summaries = {s["request_id"]: s for s in reqlog.log().requests()}
        assert summaries["req-quota"]["terminal"] == "engine.shed"
        assert summaries["req-doomed"]["terminal"] == "engine.timeout"
        # terminal requests surface on the slow_only worklist
        slow = reqlog.log().requests(slow_only=True)
        assert any(s["request_id"] == "req-doomed" for s in slow)
        assert not any(s["request_id"] == "req-quota" for s in slow)
    finally:
        engine.shutdown()


def test_observe_tenant_ttft_never_fires_for_tokenless_requests():
    """A request that died before its first token must not contribute a
    TTFT sample (the pre-fix bug polluted tenant windows with zeros)."""
    r = _Request(rid=1, prompt=[1, 2], max_tokens=2, temperature=0.0,
                 out=queue_mod.Queue(), tenant="t-ghost")
    assert r.first_token_at is None
    assert _observe_tenant_ttft(r) == {}
    assert tenancy.drain_ttft_window() == {}
    assert tenancy.drain_ttft_breakdown() == {}


# --------------------------------------------- tenancy breakdown + watchdog


def test_ttft_breakdown_windows_and_queue_wait_p99_ledger():
    from ray_tpu.util.watchdog import ServeSLOMonitor, _dominant_ttft_bucket

    for _ in range(10):
        tenancy.observe_ttft("t-slow", 5.0)
        tenancy.observe_ttft_breakdown("t-slow", {
            "ttft_s": 5.0, "queue_wait_s": 4.0, "preempt_wait_s": 0.5,
            "prefill_compute_s": 0.5,
        })
    assert _dominant_ttft_bucket(
        [{"queue_wait_s": 4.0, "preempt_wait_s": 0.5,
          "prefill_compute_s": 0.5}]
    ) == ("queue_wait", pytest.approx(0.8))
    assert _dominant_ttft_bucket([]) is None

    cfg.set(serve_slo_ttft_p99_s=0.1, serve_slo_queue_p99_s=0.2)
    mon = ServeSLOMonitor()
    out = mon.check()
    assert out["ttft_p99:t-slow"] == 5.0
    assert out["queue_wait_p99:t-slow"] == 4.0
    report = mon.attainment_report()
    led = report["queue_wait_p99:t-slow"]
    assert led["last_p99_s"] == 4.0
    assert led["violated"] == 1 and led["attainment"] == 0.0
    # the burn warning names the dominant bucket
    from ray_tpu.util.events import events
    burns = [e for e in events().list(limit=100)
             if e.get("kind") == "watchdog.slo_burn"
             and "t-slow" in e.get("message", "")]
    assert burns, "no tenant burn event"
    assert "dominant bucket: queue_wait (80% of TTFT)" in burns[-1]["message"]
    # windows drained: a second check has nothing tenant-scoped
    assert "ttft_p99:t-slow" not in mon.check()


# ----------------------------------------------------------- engine snapshot


def test_engine_snapshot_lanes_pages_and_fair_depths():
    from ray_tpu.util import state

    config, params, engine = _tiny_engine(max_slots=2, decode_block_steps=1)
    try:
        stream = engine.submit([1, 2, 3, 4, 5, 6, 7, 8, 9], max_tokens=48,
                               tenant="snap", request_id="req-snap")
        next(iter(stream))  # engine is mid-request
        # single-step decode blocks keep the lane busy for ~63 more
        # dispatches; poll a few point-in-time snapshots to catch one
        busy, snap = [], {}
        for _ in range(200):
            snap = engine.snapshot()
            busy = [l for l in snap["lanes"] if not l["free"]]
            if busy:
                break
        assert snap["kind"] == "paged"
        assert len(snap["lanes"]) == 2
        assert busy and busy[0]["request_id"] == "req-snap"
        assert busy[0]["tenant"] == "snap"
        assert snap["pages"]["in_use"] >= 1
        assert snap["pages"]["total"] == 63  # page 0 reserved
        assert isinstance(snap["fair_depths"], list)
        assert "prefix_cache" in snap and "chains" in snap["prefix_cache"]
        # the state view finds it through the weak engine registry
        all_snaps = state.engine_snapshot()
        assert any(s.get("kind") == "paged" and any(
            l.get("request_id") == "req-snap" for l in s.get("lanes", []))
            for s in all_snaps.values())
        stream.result(timeout=120)
        assert engine.prefix_cache is not None
        heads = engine.prefix_cache.chain_heads()
        assert all({"digest", "page", "refcount"} <= set(h) for h in heads)
    finally:
        engine.shutdown()


# ----------------------------------------------------- router + HTTP drills


@pytest.fixture()
def rt():
    runtime = ray_tpu.init(num_cpus=8, detect_accelerators=False)
    yield runtime
    serve.shutdown()
    ray_tpu.shutdown()


def test_request_id_threads_handle_to_replica_context(rt):
    @serve.deployment
    class Echo:
        def __call__(self, payload):
            from ray_tpu.serve.context import get_request_id

            return get_request_id()

    handle = serve.run(Echo.options(name="rid-echo").bind())
    got = ray_tpu.get(handle.options(request_id="req-explicit").remote(None),
                      timeout=30)
    assert got == "req-explicit"
    # recorder on: an id is minted for the caller when none was passed
    auto = ray_tpu.get(handle.remote(None), timeout=30)
    assert auto and auto.startswith("req-")
    tl = reqlog.log().timeline("req-explicit")
    phases = _phases(tl)
    assert "route.received" in phases and "route.dispatched" in phases


def test_router_failover_marks_both_hops(rt):
    @serve.deployment(num_replicas=2, max_ongoing_requests=4)
    class Slow:
        def __call__(self, payload):
            time.sleep(0.3)
            return f"ok-{payload}"

    handle = serve.run(Slow.options(name="ff").bind())
    rids = [f"req-ff-{i}" for i in range(8)]
    refs = [handle.options(timeout_s=30, request_id=rid).remote(i)
            for i, rid in enumerate(rids)]
    from ray_tpu.serve import api as serve_api

    state = serve_api._controller._states["ff"]
    time.sleep(0.05)
    ray_tpu.kill(state.replicas[0])
    assert ray_tpu.get(refs, timeout=60) == [f"ok-{i}" for i in range(8)]
    # at least one request failed over: its timeline records BOTH hops
    # (dispatch to the dead replica, failover, re-dispatch to a survivor)
    failed_over = [
        rid for rid in rids
        if "route.failover" in _phases(reqlog.log().timeline(rid))
    ]
    assert failed_over, "no request recorded a failover hop"
    tl = reqlog.log().timeline(failed_over[0])
    dispatches = [m for m in tl if m["phase"] == "route.dispatched"]
    assert len(dispatches) >= 2
    assert dispatches[0]["attrs"]["attempt"] < dispatches[-1]["attrs"]["attempt"]
    fo = next(m for m in tl if m["phase"] == "route.failover")
    assert fo["attrs"]["attempt"] >= 1


def test_http_429_body_carries_request_id_next_to_retry_after(rt):
    gate = threading.Event()

    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      max_queued_requests=0)
    class Busy:
        def __call__(self, payload):
            gate.wait(timeout=30)
            return "ok"

    serve.run(Busy.options(name="busy-rid").bind())
    port = serve.start_http()
    blocked = serve.get_handle("busy-rid").options(timeout_s=30).remote("x")
    time.sleep(0.1)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/busy-rid", data=b'"y"',
        headers={"Content-Type": "application/json",
                 "x-request-id": "req-shed-drill"},
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 429
    assert e.value.headers.get("Retry-After")
    assert e.value.headers.get("x-request-id") == "req-shed-drill"
    body = json.loads(e.value.read())
    assert body["request_id"] == "req-shed-drill"
    tl = reqlog.log().timeline("req-shed-drill")
    phases = _phases(tl)
    assert phases[0] == "http.received"
    terminal = [p for p in phases if p in reqlog.TERMINAL_PHASES]
    assert terminal, phases
    gate.set()
    assert ray_tpu.get(blocked, timeout=30) == "ok"
    # a successful proxy call echoes the id in the 200 body too
    ok = urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/busy-rid", data=b'"z"',
        headers={"Content-Type": "application/json",
                 "x-request-id": "req-ok-drill"},
    ), timeout=30)
    payload = json.loads(ok.read())
    assert payload["request_id"] == "req-ok-drill"
    assert ok.headers.get("x-request-id") == "req-ok-drill"


# ---------------------------------------------------------------- federation


def test_request_marks_federate_and_state_queries():
    from ray_tpu.core.gcs import REQLOG_NS
    from ray_tpu.util import state

    rt = ray_tpu.init(num_cpus=1, head=True, detect_accelerators=False)
    try:
        ctx = rt.cluster
        my_hex = ctx.node_id.hex()
        reqlog.mark("req-fed", "route.received", tenant="fed")
        reqlog.mark("req-fed", "engine.first_token", tenant="fed",
                    ttft_s=9.0, queue_wait_s=8.0, preempt_wait_s=0.0,
                    prefill_compute_s=1.0)
        reqlog.mark("req-fed", "engine.finished", tenant="fed")
        reqlog.mark("req-other", "route.shed", reason="parked_queue_full")
        prev, tail = -1, []
        while len(tail) != prev:
            prev = len(tail)
            ctx._last_stats_ts = 0.0
            ctx._report_stats()
            tail = ctx.gcs.kv_get(my_hex, namespace=REQLOG_NS) or []
        assert tail, "no marks federated into the _requests table"
        assert all(m.get("node") for m in tail)
        # cursor advanced: another pass without new marks is a no-op
        before = len(tail)
        ctx._last_stats_ts = 0.0
        ctx._report_stats()
        assert len(ctx.gcs.kv_get(my_hex, namespace=REQLOG_NS)) == before
        # the state queries join + dedup (local ring ∪ federated table)
        tl = state.request_timeline("req-fed")
        assert _phases(tl) == ["route.received", "engine.first_token",
                               "engine.finished"]
        keys = [(m.get("node"), m.get("seq")) for m in tl]
        assert len(keys) == len(set(keys)), "duplicate (node, seq)"
        rows = {s["request_id"]: s for s in state.list_requests()}
        assert rows["req-fed"]["terminal"] == "engine.finished"
        assert rows["req-other"]["terminal"] == "route.shed"
        assert [s["request_id"] for s in state.list_requests(tenant="fed")] \
            == ["req-fed"]
        slow = state.list_requests(slow_only=True)
        assert any(s["request_id"] == "req-fed" for s in slow)  # 9s TTFT
        # a federated recorder off-switch: no new marks ship
        cfg.set(serve_request_log=False)
        reqlog.log().mark("req-dark", "route.received")
        ctx._last_stats_ts = 0.0
        ctx._report_stats()
        assert not any(m["rid"] == "req-dark" for m in
                       ctx.gcs.kv_get(my_hex, namespace=REQLOG_NS))
    finally:
        cfg.reset()
        ray_tpu.shutdown()
