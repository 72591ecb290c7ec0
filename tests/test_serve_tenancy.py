"""Multi-tenant overload protection drills.

Coverage for the tenancy tentpole: weighted-fair queueing at both
admission choke points (starvation-freedom, weight-proportional share,
priority tiers), token-bucket quotas with honest computed Retry-After,
preemptible decode lanes (trim-to-frontier park + token-exact resume,
prefix-shared pages never corrupted), tenant context propagation through
the handle path, and the noisy-tenant + replica-kill chaos capstone with
zero untyped errors.
"""

import pickle
import threading
import time

import jax
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core import chaos
from ray_tpu.core.chaos import ChaosInjectedError
from ray_tpu.core.config import cfg
from ray_tpu.core.exceptions import (
    BackPressureError,
    RequestTimeoutError,
    unwrap_error,
)
from ray_tpu.models import get_config, init_params
from ray_tpu.serve import tenancy
from ray_tpu.serve.llm.paged import PagedConfig
from ray_tpu.serve.llm.paged_engine import PagedEngineConfig, PagedLLMEngine
from ray_tpu.serve.tenancy import FairQueue, _TokenBucket


@pytest.fixture(autouse=True)
def _clean_tenancy():
    tenancy.reset()
    yield
    tenancy.reset()
    cfg.reset()


def _tiny_engine(model="llama-tiny", seed=0, **over):
    config = get_config(model)
    params = init_params(config, jax.random.PRNGKey(seed))
    defaults = dict(
        max_slots=4,
        paged=PagedConfig(
            page_size=8, num_pages=64, max_pages_per_slot=8, chunk_pages=2
        ),
    )
    defaults.update(over)
    return config, params, PagedLLMEngine(
        config, params, PagedEngineConfig(**defaults)
    )


# ------------------------------------------------------------- fair queue


def test_fairqueue_weight_proportional_share():
    """A weight-4 tenant drains ~4x faster than a weight-1 tenant under
    sustained backlog (SCFQ virtual finish tags)."""
    fq = FairQueue()
    for i in range(40):
        fq.push(("heavy", i), "heavy", weight=4.0)
    for i in range(40):
        fq.push(("light", i), "light", weight=1.0)
    first = [fq.pop()[0] for _ in range(25)]
    heavy = first.count("heavy")
    # exact SCFQ share is 20/5; allow slack for tie-breaks
    assert 18 <= heavy <= 22, first


def test_fairqueue_starvation_free():
    """A single item from a light tenant lands near the front even when
    a flooding tenant queued hundreds of items first."""
    fq = FairQueue()
    for i in range(200):
        fq.push(("flood", i), "flood")
    # flood's lane has raced ahead in virtual time; a newcomer starts at
    # the tier clock and its first finish tag is immediately competitive
    for _ in range(5):
        fq.pop()
    fq.push(("light", 0), "light")
    drained = [fq.pop()[0] for _ in range(5)]
    assert "light" in drained, drained
    assert len(fq) == 200 - 5 + 1 - 5


def test_fairqueue_priority_tiers_strict():
    """Higher priority tiers always pop first, regardless of how much
    virtual time the lower tier has accumulated."""
    fq = FairQueue()
    for i in range(10):
        fq.push(("low", i), "bulk", priority=0)
    fq.push(("high", 0), "paid", priority=1)
    fq.push(("high", 1), "paid", priority=1)
    assert fq.pop() == ("high", 0)
    assert fq.pop() == ("high", 1)
    assert fq.pop() == ("low", 0)


def test_fairqueue_requeue_keeps_place():
    """requeue() returns an item to the front of its lane with no fresh
    virtual-time charge (deferred admissions never pay twice)."""
    fq = FairQueue()
    fq.push("a1", "a")
    fq.push("a2", "a")
    head = fq.pop()
    assert head == "a1"
    fq.requeue(head, "a")
    assert fq.peek() == "a1"
    assert fq.pop() == "a1" and fq.pop() == "a2"


def test_fairqueue_pop_if_head_and_remove():
    fq = FairQueue()
    fq.push("x", "t")
    fq.push("y", "t")
    assert not fq.pop_if_head("y")
    assert fq.pop_if_head("x")
    assert fq.remove("y")
    assert not fq.remove("y")
    assert len(fq) == 0 and fq.pop() is None


def test_fairqueue_work_conserving_drain():
    fq = FairQueue()
    for t in ("a", "b", "c"):
        for i in range(3):
            fq.push((t, i), t)
    assert len(fq.drain()) == 9
    assert len(fq) == 0


# ------------------------------------------------------------ token bucket


def test_token_bucket_computes_honest_retry_after():
    bucket = _TokenBucket(rate=1.0, burst=2.0)
    assert bucket.acquire() is None
    assert bucket.acquire() is None
    retry = bucket.acquire()
    assert retry is not None and 0.5 < retry <= 1.01


def test_quota_check_registry_and_defaults():
    tenancy.set_tenant("metered", quota_rps=1.0, quota_burst=1.0)
    assert tenancy.quota_check("metered") is None
    retry = tenancy.quota_check("metered")
    assert retry is not None and retry > 0
    # undeclared tenants ride the config default (0 = unlimited)
    for _ in range(50):
        assert tenancy.quota_check("anyone") is None


def test_backpressure_error_pickles_retry_after():
    err = BackPressureError("over quota", retry_after_s=2.5)
    clone = pickle.loads(pickle.dumps(err))
    assert isinstance(clone, BackPressureError)
    assert clone.retry_after_s == 2.5
    assert "over quota" in str(clone)


def test_http_status_maps_computed_retry_after():
    from ray_tpu.serve.llm.openai import _http_status_for

    code, _etype, retry = _http_status_for(
        BackPressureError("x", retry_after_s=3.2)
    )
    assert (code, retry) == (429, 4)
    # no estimate → the historical 1-second default
    code, _etype, retry = _http_status_for(BackPressureError("x"))
    assert (code, retry) == (429, 1)


def test_resolve_http_tenant_header_and_api_key():
    tenancy.set_tenant("acme", priority=2, api_key="sk-acme-1")
    assert tenancy.resolve_http_tenant(
        {"x-tenant": "acme"}) == ("acme", 2)
    assert tenancy.resolve_http_tenant(
        {"Authorization": "Bearer sk-acme-1"}) == ("acme", 2)
    assert tenancy.resolve_http_tenant(
        {"x-tenant": "acme", "x-priority": "5"}) == ("acme", 5)
    assert tenancy.resolve_http_tenant({}) == (None, None)


# --------------------------------------------------------- engine admission


def test_engine_quota_shed_is_typed_with_retry_after():
    """Over-quota submits shed with BackPressureError carrying the
    bucket's actual refill time; admitted traffic is unaffected."""
    tenancy.set_tenant("free", quota_rps=0.1, quota_burst=1.0)
    _config, _params, engine = _tiny_engine()
    try:
        ok = engine.submit([3, 1, 4], max_tokens=2, tenant="free")
        with pytest.raises(BackPressureError) as e:
            engine.submit([3, 1, 4], max_tokens=2, tenant="free")
        assert e.value.retry_after_s is not None
        assert e.value.retry_after_s > 0
        assert engine.metrics["shed"] >= 1
        # other tenants are not collateral damage
        other = engine.submit([2, 7, 1], max_tokens=2, tenant="other")
        assert len(ok.result()) == 2
        assert len(other.result()) == 2
    finally:
        engine.shutdown()


def test_engine_priority_queue_order():
    """With the only slot busy and preemption off, a later high-priority
    submit is admitted ahead of earlier low-priority backlog (strict
    tiers at the engine admit queue)."""
    cfg.set(serve_lane_preemption=False)
    _config, _params, engine = _tiny_engine(max_slots=1)
    try:
        blocker = engine.submit([9, 9, 9], max_tokens=24, tenant="blk")
        lows = [
            engine.submit([5, 5, i], max_tokens=2, tenant="bulk", priority=0)
            for i in range(3)
        ]
        high = engine.submit([8, 8, 8], max_tokens=2, tenant="paid",
                             priority=1)
        done = []
        lock = threading.Lock()

        def drain(name, stream):
            stream.result()
            with lock:
                done.append(name)

        threads = [
            threading.Thread(target=drain, args=(f"low{i}", s))
            for i, s in enumerate(lows)
        ] + [threading.Thread(target=drain, args=("high", high))]
        for t in threads:
            t.start()
        blocker.result()
        for t in threads:
            t.join(timeout=60)
        assert done[0] == "high", done
    finally:
        engine.shutdown()


def test_engine_sheds_expired_request_at_admit_pop():
    """A request whose deadline expired while queued is failed at the
    admit-queue pop — it never consumes a slot ahead of live traffic."""
    _config, _params, engine = _tiny_engine(max_slots=1)
    try:
        blocker = engine.submit([1, 2, 3], max_tokens=24)
        doomed = engine.submit([4, 5, 6], max_tokens=4,
                               deadline_ts=time.time() + 0.15)
        live = engine.submit([6, 5, 4], max_tokens=2)
        time.sleep(0.2)  # doomed expires while still queued
        with pytest.raises(RequestTimeoutError):
            doomed.result(timeout=60)
        assert len(live.result(timeout=60)) == 2
        blocker.result(timeout=60)
        assert engine.metrics["timeouts"] >= 1
    finally:
        engine.shutdown()


# ------------------------------------------------------- lane preemption


def test_lane_preemption_restores_allocator_refcounts():
    """After a preemption round fully drains, every page is back in the
    free pool except the prefix cache's own pins (no leaked refs)."""
    _config, _params, engine = _tiny_engine(max_slots=1,
                                            decode_block_steps=2)
    try:
        # the victim has 19 tokens left when `high` is submitted: on a loaded
        # host the engine's thread can decode them all before this one is
        # scheduled again, and nothing is preempted. The round is run until
        # one was (a first round is enough on an idle host).
        rounds_until = time.time() + 120
        while engine.metrics["lane_preemptions"] < 1 and time.time() < rounds_until:
            victim = engine.submit([4] * 12, max_tokens=20,
                                   tenant="bulk", priority=0)
            it = iter(victim)
            next(it)
            high = engine.submit([9] * 12, max_tokens=4,
                                 tenant="paid", priority=1)
            high.result(timeout=60)
            list(it)
        assert engine.metrics["lane_preemptions"] >= 1
        deadline = time.time() + 10
        while time.time() < deadline:
            stats = engine.stats()
            # total allocatable = num_pages - 1 (page 0 reserved)
            if stats["pages_free"] + stats["prefix_cache_pages"] == 63:
                break
            time.sleep(0.05)
        stats = engine.stats()
        assert stats["pages_free"] + stats["prefix_cache_pages"] == 63, stats
    finally:
        engine.shutdown()


def test_lane_preemption_config_gate():
    """serve_lane_preemption=False disables parking entirely: the
    high-priority request waits instead (strict queue order only)."""
    cfg.set(serve_lane_preemption=False)
    _config, _params, engine = _tiny_engine(max_slots=1,
                                            decode_block_steps=2)
    try:
        victim = engine.submit([4] * 8, max_tokens=12, tenant="bulk")
        high = engine.submit([9] * 8, max_tokens=2,
                             tenant="paid", priority=1)
        victim.result(timeout=60)
        high.result(timeout=60)
        assert engine.metrics["lane_preemptions"] == 0
    finally:
        engine.shutdown()


# ---------------------------------------------------- tenant SLO accounting


def test_per_tenant_ttft_windows_feed_slo_monitor():
    from ray_tpu.util.watchdog import ServeSLOMonitor

    tenancy.set_tenant("gold", ttft_slo_s=0.000001)  # everything violates
    tenancy.observe_ttft("gold", 0.5)
    tenancy.observe_ttft("gold", 0.7)
    tenancy.observe_ttft("casual", 0.5)  # no objective → never violates
    monitor = ServeSLOMonitor()
    out = monitor.check()
    assert out["ttft_p99:gold"] >= 0.5
    report = monitor.attainment_report()
    assert report["ttft_p99:gold"]["violated"] == 1
    assert report["ttft_p99:gold"]["attainment"] == 0.0
    assert report["ttft_p99:casual"]["violated"] == 0
    # window drained: a second check sees no new samples
    assert "ttft_p99:gold" not in monitor.check()
    assert tenancy.any_tenant_slo()


def test_engine_reports_tenant_ttft():
    _config, _params, engine = _tiny_engine()
    try:
        engine.submit([5, 6, 7], max_tokens=2, tenant="acme").result()
        window = tenancy.drain_ttft_window()
        assert "acme" in window and len(window["acme"]) == 1
        assert window["acme"][0] > 0
    finally:
        engine.shutdown()


# --------------------------------------------------------------- serve plane


@pytest.fixture()
def rt():
    runtime = ray_tpu.init(num_cpus=8, detect_accelerators=False)
    yield runtime
    chaos.clear_chaos()
    serve.shutdown()
    ray_tpu.shutdown()


def test_tenant_context_rides_the_handle_path(rt):
    """handle.options(tenant=, priority=) surfaces in the replica's
    ambient serve context, exactly like deadlines do."""
    @serve.deployment
    class WhoAmI:
        def __call__(self, _payload):
            return (serve.get_request_tenant(), serve.get_request_priority())

    handle = serve.run(WhoAmI.options(name="whoami").bind())
    assert ray_tpu.get(handle.remote(None), timeout=30) == (None, None)
    caller = handle.options(tenant="acme", priority=3)
    assert ray_tpu.get(caller.remote(None), timeout=30) == ("acme", 3)
    # options() must not leak across calls
    assert ray_tpu.get(handle.remote(None), timeout=30) == (None, None)


def test_router_parks_dispatch_in_priority_order(rt):
    """When a replica is saturated, parked resilient dispatches are
    granted strictly by priority tier: the high-priority call runs
    before a low-priority call parked earlier."""
    gate = threading.Event()
    order = []

    @serve.deployment(max_ongoing_requests=1)
    class Gated:
        def __call__(self, tag):
            if tag == "blocker":
                gate.wait(timeout=30)
            order.append(tag)
            return tag

    handle = serve.run(Gated.options(name="gated").bind())
    caller = handle.options(timeout_s=30)
    blocker = caller.remote("blocker")
    time.sleep(0.3)  # blocker occupies the only ongoing slot
    low = caller.options(tenant="bulk", priority=0).remote("low")
    time.sleep(0.2)  # low parks first
    high = caller.options(tenant="paid", priority=1).remote("high")
    time.sleep(0.2)
    gate.set()
    assert ray_tpu.get(blocker, timeout=30) == "blocker"
    assert ray_tpu.get(high, timeout=30) == "high"
    assert ray_tpu.get(low, timeout=30) == "low"
    assert order.index("high") < order.index("low"), order


def test_router_park_overflow_sheds_typed_with_drain_estimate(rt):
    """Past max_queued_requests the router sheds synchronously with the
    typed error; Retry-After rides the exception when the drain-rate
    estimator has samples (never a bogus value when it doesn't)."""
    gate = threading.Event()

    @serve.deployment(max_ongoing_requests=1, max_queued_requests=1)
    class Tight:
        def __call__(self, tag):
            gate.wait(timeout=30)
            return tag

    handle = serve.run(Tight.options(name="tight").bind())
    caller = handle.options(timeout_s=30)
    first = caller.remote(0)
    time.sleep(0.3)
    second = caller.remote(1)  # parks (the 1 queued slot)
    time.sleep(0.2)
    with pytest.raises(BackPressureError) as e:
        caller.remote(2)
    retry = e.value.retry_after_s
    assert retry is None or retry >= 1
    gate.set()
    assert sorted(
        ray_tpu.get([first, second], timeout=30)) == [0, 1]


def test_chaos_capstone_noisy_tenant_replica_kill_zero_untyped(rt):
    """Capstone: a flooding low-priority tenant plus a mid-run replica
    kill. Every request either succeeds or fails with a TYPED error —
    overload and failure recovery compose, nothing hangs."""
    @serve.deployment(num_replicas=2, max_ongoing_requests=4,
                      max_queued_requests=32)
    class Drill:
        def __call__(self, payload):
            time.sleep(0.01)
            return payload * 2

    handle = serve.run(Drill.options(name="tdrill").bind())
    deadline = time.time() + 15
    while time.time() < deadline:
        if serve.status()["tdrill"]["live_replicas"] == 2:
            break
        time.sleep(0.05)
    noisy = handle.options(timeout_s=30, max_retries=4,
                           tenant="noisy", priority=0)
    paid = handle.options(timeout_s=30, max_retries=4,
                          tenant="paid", priority=1)
    refs = []
    shed_at_submit = 0

    def submit(caller, i):
        nonlocal shed_at_submit
        try:
            refs.append((i, caller.remote(i)))
        except BackPressureError as e:
            # synchronous shed past the parked-dispatch bound: typed,
            # tenant-attributed, with a sane (or absent) Retry-After
            assert e.retry_after_s is None or e.retry_after_s >= 1
            shed_at_submit += 1

    for i in range(80):
        submit(noisy, i)
    for i in range(80, 100):
        submit(paid, i)
    from ray_tpu.serve import api as serve_api

    state = serve_api._controller._states["tdrill"]
    ray_tpu.kill(state.replicas[0])
    for i in range(100, 140):
        submit(noisy, i)
    ok, typed, hung = 0, 0, []
    for i, ref in refs:
        try:
            assert ray_tpu.get(ref, timeout=60) == i * 2
            ok += 1
        except ray_tpu.GetTimeoutError:
            hung.append(i)
        except Exception as e:  # noqa: BLE001 - drill classification
            cause = unwrap_error(e)
            assert isinstance(
                cause, (RequestTimeoutError, BackPressureError,
                        ChaosInjectedError)
            ), f"request {i} failed with untyped {cause!r}"
            typed += 1
    assert not hung, f"hung requests: {hung}"
    # burst submission overruns the parked-dispatch bound by design: the
    # acceptance bar is full accounting — every request either succeeded
    # or shed/failed TYPED, and overload protection actually engaged
    assert ok >= 30, (ok, typed, shed_at_submit)
    assert shed_at_submit > 0
    assert ok + typed + shed_at_submit == 140
    # the killed replica is replaced and the deployment still serves
    assert ray_tpu.get(handle.remote(7), timeout=30) == 14
