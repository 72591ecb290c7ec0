"""Flight recorder & goodput plane (ISSUE 9).

Covers: typed event emission (kind registry, severity normalization,
dual timestamps), durable bounded segments, heartbeat federation into
the GCS `_events` table, the cluster-wide `state.events()` query,
Perfetto flow events across lanes, postmortem bundle construction, the
goodput accountant's wall-time invariant, and the chaos capstone: a
`preempt_node` episode during an in-process training run reconstructed
causally from one bundle, with the run's wall time fully attributed.
"""

import json
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core import chaos
from ray_tpu.util.events import (
    EVENT_KINDS, EventLog, events, normalize_severity, read_segments,
)


@pytest.fixture
def runtime():
    rt = ray_tpu.init(num_cpus=2, detect_accelerators=False)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def nodes4():
    rt = ray_tpu.init(num_cpus=1, num_nodes=4, detect_accelerators=False)
    yield rt
    chaos.clear_chaos()
    ray_tpu.shutdown()


# ------------------------------------------------------------- event typing


def test_emit_normalizes_severity_and_records_both_clocks():
    log = EventLog(capacity=16)
    assert normalize_severity("warn") == "WARNING"
    assert normalize_severity("FATAL") == "ERROR"
    assert normalize_severity("nonsense") == "INFO"
    e = log.emit("warning", "test", "lower-case severity",
                 kind="node.dead", node="abcd1234")
    assert e["severity"] == "WARNING"
    assert e["kind"] == "node.dead" and e["node"] == "abcd1234"
    assert isinstance(e["ts"], float) and isinstance(e["mono"], float)
    # monotonic and wall clocks are distinct domains
    assert abs(e["ts"] - e["mono"]) > 1.0
    log.emit("BOGUS-LEVEL", "test", "unknown level degrades")
    assert log.list()[-1]["severity"] == "INFO"
    # case-insensitive severity filter; kind/node filters
    assert log.list(severity="warning")[-1]["message"].startswith("lower")
    assert log.list(kind="node.dead") and log.list(node="abcd")
    assert log.list(node="ffff") == []


def test_event_kind_catalog_covers_runtime_call_sites():
    """The registered schema names the planes the issue demands."""
    for kind in ("node.discovered", "node.dead", "preempt.announced",
                 "preempt.drain", "pg.transition", "ckpt.saved",
                 "ckpt.quarantine", "train.gang_started",
                 "train.preempt_restart", "serve.scaled", "serve.drain",
                 "chaos.injected", "watchdog.stall", "watchdog.slo_burn"):
        assert kind in EVENT_KINDS, kind


def test_event_segments_rotate_bounded_and_tolerate_torn_tail(tmp_path):
    seg_dir = str(tmp_path / "seg")
    log = EventLog(capacity=4096)
    log.configure_segments(seg_dir, max_bytes=512, keep=3)
    for i in range(200):
        log.emit("INFO", "test", f"event {i}", kind="node.discovered", n=i)
    names = sorted(p.name for p in (tmp_path / "seg").iterdir())
    rotated = [n for n in names if n.startswith("events-")]
    assert rotated, "no rotation happened"
    assert len(rotated) <= 3, names  # retention bound holds
    assert "events.jsonl" in names
    replay = read_segments(seg_dir)
    assert replay and replay[-1]["extra"]["n"] == 199
    # events replay in order within the retained window
    ns = [e["extra"]["n"] for e in replay]
    assert ns == sorted(ns)
    # a torn tail line (crash mid-append) is skipped, not raised
    with open(tmp_path / "seg" / "events.jsonl", "a") as f:
        f.write('{"torn": ')
    replay2 = read_segments(seg_dir)
    assert [e["extra"]["n"] for e in replay2] == ns
    log.configure_segments(None)


# --------------------------------------------------------- raylint coverage


def test_event_kinds_rule_fixtures(tmp_path):
    """event-kinds: unregistered/missing/dynamic kinds are findings;
    registered literals and register_event_kind extensions pass."""
    from scripts.raylint import Project, run

    pkg = tmp_path / "ray_tpu"
    (pkg / "util").mkdir(parents=True)
    (pkg / "util" / "events.py").write_text(
        'EVENT_KINDS = {"good.kind": "doc"}\n'
        "def emit(*a, **k):\n    pass\n"
    )
    (pkg / "mod.py").write_text(
        "from .util.events import emit\n"
        "from .util.events import register_event_kind\n"
        'register_event_kind("extra.kind")\n'
        "def f(dyn):\n"
        '    emit("INFO", "m", "ok", kind="good.kind")\n'
        '    emit("INFO", "m", "ok2", kind="extra.kind")\n'
        '    emit("INFO", "m", "missing kind")\n'
        '    emit("INFO", "m", "bad", kind="not.registered")\n'
        '    emit("INFO", "m", "dynamic", kind=dyn)\n'
    )
    result = run(Project(tmp_path), rules=["event-kinds"])
    msgs = sorted(f.message for f in result.findings)
    assert len(msgs) == 3, msgs
    assert any("without kind=" in m for m in msgs)
    assert any("not registered" in m for m in msgs)
    assert any("string literal" in m for m in msgs)


def test_event_kinds_rule_clean_on_repo():
    """Every emit call site in the real tree passes the registry."""
    import pathlib

    from scripts.raylint import Project, run

    root = pathlib.Path(__file__).resolve().parents[1]
    result = run(Project(root), rules=["event-kinds"])
    assert result.counts["event-kinds"] == 0, [
        f"{f.location}: {f.message}" for f in result.findings
    ]


# ----------------------------------------------------- federation + queries


def test_events_federate_into_gcs_table_and_state_query():
    from ray_tpu.core.gcs import EVENT_NS
    from ray_tpu.util import state

    rt = ray_tpu.init(num_cpus=1, head=True, detect_accelerators=False)
    try:
        ctx = rt.cluster
        my_hex = ctx.node_id.hex()
        events().emit("WARNING", "test", "flight recorder drill",
                      kind="chaos.injected", mode="drill")
        # force federation passes (normally they ride the stats
        # piggyback) until the cursor has drained the whole ring — the
        # process-global event log may hold a backlog from earlier tests
        # larger than one bounded federate batch
        prev, tail = -1, []
        while len(tail) != prev:
            prev = len(tail)
            ctx._last_stats_ts = 0.0
            ctx._report_stats()
            tail = ctx.gcs.kv_get(my_hex, namespace=EVENT_NS) or []
        assert tail, "no events federated into the _events table"
        assert any(e.get("kind") == "chaos.injected" for e in tail)
        # every federated event carries node attribution
        assert all(e.get("node") for e in tail)
        # cursor advanced: another pass without new events is a no-op
        before = len(tail)
        ctx._last_stats_ts = 0.0
        ctx._report_stats()
        assert len(ctx.gcs.kv_get(my_hex, namespace=EVENT_NS)) == before
        # the state query merges + filters + dedupes
        drill = state.events(kind="chaos.injected")
        assert drill and drill[-1]["message"] == "flight recorder drill"
        keys = [(e.get("node"), e.get("seq")) for e in drill]
        assert len(keys) == len(set(keys)), "duplicate (node, seq) entries"
        assert state.events(kind="chaos.injected", node=my_hex[:8])
        assert state.events(kind="chaos.injected",
                            since=time.time() + 60) == []
        assert state.events(kind="chaos.injected", severity="warning")
    finally:
        ray_tpu.shutdown()


def _burst(name, i):
    from ray_tpu.serve import reqlog
    from ray_tpu.train import steplog

    if name == "events":
        events().emit("INFO", "test", f"burst {i}", kind="node.discovered")
    elif name == "requests":
        reqlog.mark(f"req-burst-{i}", "engine.submitted")
    else:
        steplog.mark("data_wait", 0.01, run="burst", rank=0, step=i)


@pytest.mark.parametrize("name, newest", [
    ("events", ("message", "burst 79")),
    ("requests", ("rid", "req-burst-79")),
    ("steps", ("step", 79)),
])
def test_federated_table_is_bounded(name, newest):
    """One shipper for every registered plane (core/cluster._federate):
    a node's table keeps its newest `cap` marks whatever the burst."""
    from ray_tpu.serve import reqlog
    from ray_tpu.train import steplog
    from ray_tpu.util.markring import federated_planes

    reqlog.log().clear()
    steplog.log().clear()
    plane = {p.name: p for p in federated_planes()}[name]
    rt = ray_tpu.init(num_cpus=1, head=True, detect_accelerators=False)
    try:
        ctx = rt.cluster
        for i in range(80):
            _burst(name, i)
        ctx._federate(plane, cap=20, batch=500)
        tail = ctx.gcs.kv_get(ctx.node_id.hex(), namespace=plane.namespace)
        assert len(tail) <= 20
        key, value = newest
        assert tail[-1][key] == value  # newest survive
        assert ctx._federation_lag()[name] == 0
    finally:
        ray_tpu.shutdown()
        reqlog.log().clear()
        steplog.log().clear()


def test_a_plane_a_node_never_loaded_reads_as_one_with_nothing_buffered():
    """`_federation_lag` has a key for each plane the node's process
    registered (an agent that never imported ray_tpu.serve reports no
    `requests`, one that never imported ray_tpu.train no `steps`); the
    head's summary and `ray_tpu status` say the same for a missing key
    as for a 0."""
    from ray_tpu.util import state

    rt = ray_tpu.init(num_cpus=1, head=True, detect_accelerators=False)
    try:
        ctx = rt.cluster
        assert "events" in ctx._federation_lag()

        def status_with(lag):
            with ctx._lock:
                ctx._info["federation_lag"] = lag
                info = dict(ctx._info)
            from ray_tpu.core.cluster import NODE_NS

            ctx.gcs.kv_put(ctx.node_id.hex(), info, namespace=NODE_NS)
            return (state.head_summary().get("federation_lag"),
                    [line for line in state.status_report().splitlines()
                     if "buffered federation" in line])

        assert status_with({"events": 0}) \
            == status_with({"events": 0, "requests": 0, "steps": 0}) \
            == (None, [])
        lag, lines = status_with({"events": 3, "requests": 0})
        assert lag == {ctx.node_id.hex(): {"events": 3}}
        assert len(lines) == 1 and lines[0].endswith("events=3")
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------- flow events export


def test_trace_dump_emits_cross_lane_flow_events():
    from ray_tpu.util.tracing import Tracer, export_chrome_trace

    tracer = Tracer(capacity=100, sample_ratio=1.0)
    t0 = time.time()
    parent = tracer.start_span("task.submit", lane="node:aaaa", start_ts=t0)
    child = tracer.start_span("task.execute", parent=parent.context,
                              lane="node:bbbb", start_ts=t0 + 0.01)
    sibling = tracer.start_span("task.queue", parent=parent.context,
                                lane="node:aaaa", start_ts=t0 + 0.001)
    sibling.end(end_ts=t0 + 0.005)
    child.end(end_ts=t0 + 0.02)
    parent.end(end_ts=t0 + 0.03)
    payload = json.loads(export_chrome_trace(tracer.spans()))
    flows = [e for e in payload["traceEvents"] if e.get("cat") == "flow"]
    # exactly one cross-lane edge (parent->child); same-lane nesting
    # renders as slices, not arrows
    starts = [e for e in flows if e["ph"] == "s"]
    finishes = [e for e in flows if e["ph"] == "f"]
    assert len(starts) == 1 and len(finishes) == 1
    assert starts[0]["id"] == finishes[0]["id"]
    assert starts[0]["pid"] == "node:aaaa"
    assert finishes[0]["pid"] == "node:bbbb"
    assert finishes[0]["bp"] == "e"
    assert finishes[0]["ts"] >= starts[0]["ts"]


# ------------------------------------------------------------ goodput plane


def test_goodput_accountant_partition_invariant():
    from ray_tpu.util.goodput import GoodputAccountant
    from ray_tpu.util.metrics import registry

    acct = GoodputAccountant("acct-drill")
    acct.begin("init")
    time.sleep(0.03)
    acct.begin("step_compute")
    time.sleep(0.05)
    acct.begin("ckpt_save")
    time.sleep(0.02)
    acct.begin("step_compute")
    time.sleep(0.03)
    acct.finish()
    report = acct.report()
    total = sum(report["buckets"].values())
    assert report["wall_time_s"] > 0
    assert abs(total - report["wall_time_s"]) < 1e-4
    assert report["buckets"]["step_compute"] >= 0.07
    assert report["goodput_s"] == report["buckets"]["step_compute"]
    assert 0.0 < report["goodput_fraction"] < 1.0
    # transfer preserves the partition and clamps to the source bucket
    acct.transfer("step_compute", "input_wait", 0.01)
    acct.transfer("init", "compile", 999.0)  # clamped to what init holds
    r2 = acct.report()
    assert abs(sum(r2["buckets"].values()) - r2["wall_time_s"]) < 1e-4
    assert r2["buckets"]["input_wait"] >= 0.01
    assert r2["buckets"]["init"] == 0.0
    # gauges published with run+bucket labels
    text = registry().prometheus_text()
    assert 'raytpu_train_goodput_seconds' in text
    assert 'run="acct-drill"' in text and 'bucket="step_compute"' in text
    assert "raytpu_train_goodput_fraction" in text


def test_serve_slo_attainment_ledger():
    from ray_tpu.core.config import cfg
    from ray_tpu.util.goodput import serve_slo_report
    from ray_tpu.util.metrics import get_or_create_histogram, registry
    from ray_tpu.util.watchdog import ServeSLOMonitor

    # a fresh monitor's first window is everything the process's serve
    # histograms ever saw, and every tenant's TTFT samples that an engine of
    # an earlier test file left undrained (one `ttft_p99:<tenant>` SLO each,
    # and the report below takes the minimum over all): start from none
    from ray_tpu.serve import tenancy

    registry().clear()
    tenancy.reset()
    cfg.set(serve_slo_ttft_p99_s=0.05)
    try:
        hist = get_or_create_histogram(
            "raytpu_serve_ttft_seconds",
            "Time to first generated token, from engine request spans.",
            boundaries=(0.005, 0.025, 0.1, 0.5, 2.0, 10.0),
        )
        monitor = ServeSLOMonitor()
        for _ in range(50):
            hist.observe(0.3)  # way over the 50ms objective
        monitor.check()
        for _ in range(50):
            hist.observe(0.01)  # healthy window
        monitor.check()
        ledger = monitor.attainment_report()
        assert ledger["ttft_p99"]["windows"] == 2
        assert ledger["ttft_p99"]["violated"] == 1
        assert ledger["ttft_p99"]["attainment"] == 0.5
        assert 'raytpu_serve_slo_attainment' in registry().prometheus_text()
        # module-level report (the serve goodput analogue)
        import ray_tpu.util.watchdog as wd

        prev = wd._slo_monitor
        wd._slo_monitor = monitor
        try:
            rep = serve_slo_report()
        finally:
            wd._slo_monitor = prev
        assert rep["attainment"] == 0.5
        assert rep["slos"]["ttft_p99"]["requests"] == 100
    finally:
        cfg.reset()


# ------------------------------------------------------- postmortem bundles


def test_postmortem_bundle_smoke(runtime, tmp_path):
    """Tier-1 smoke: the bundle builds from a live runtime and its
    timeline parses as valid Perfetto JSON."""
    from ray_tpu.util import state
    from ray_tpu.util.postmortem import load_bundle

    @ray_tpu.remote
    def work(x):
        return x * 2

    assert ray_tpu.get([work.remote(i) for i in range(4)], timeout=30) == [
        0, 2, 4, 6,
    ]
    events().emit("INFO", "test", "bundle smoke", kind="node.discovered")
    out = str(tmp_path / "bundle.tgz")
    manifest = state.postmortem(out, note="smoke drill")
    assert manifest["note"] == "smoke drill"
    assert manifest["counts"]["events"] > 0
    assert manifest["counts"]["spans"] > 0
    bundle = load_bundle(out)
    assert set(manifest["files"]) <= set(bundle) | {"manifest.json"}
    timeline = bundle["timeline.json"]
    assert isinstance(timeline["traceEvents"], list) and timeline["traceEvents"]
    phases = {e.get("ph") for e in timeline["traceEvents"]}
    assert "X" in phases and "i" in phases  # slices AND instant events
    assert any(e.get("cat") == "events" for e in timeline["traceEvents"])
    assert bundle["manifest.json"]["counts"] == manifest["counts"]
    # the exposition rode along
    assert "raytpu_" in bundle["metrics_cluster.prom"]


# ------------------------------------------------------------ capstone drill


def test_preempt_postmortem_capstone(nodes4, tmp_path):
    """A preempt_node episode during an in-process training run yields
    ONE postmortem bundle whose single timeline contains the preemption
    announcement, emergency checkpoint, gang restart, and resumed steps
    in causal order from >=2 logical nodes — and the goodput report
    attributes the run's whole wall time to buckets, with the same
    numbers in Result.goodput and the goodput gauges."""
    from ray_tpu.core.scheduler import NodeAffinitySchedulingStrategy
    from ray_tpu.train import (
        FailureConfig, RunConfig, RunStatus, ScalingConfig, TrainController,
    )
    from ray_tpu.util import state
    from ray_tpu.util.metrics import registry
    from ray_tpu.util.postmortem import load_bundle

    rt = nodes4
    events().clear()

    def train_fn(config):
        from ray_tpu import train

        ctx = train.get_context()
        ckpt = train.get_checkpoint()
        start = int(ckpt["step"]) + 1 if ckpt is not None else 0
        for step in range(start, 40):
            time.sleep(0.02)
            if ctx.world_rank != 0:
                if train.is_preempted():
                    return "preempted"
                continue
            if train.should_checkpoint():
                train.report({"step": step}, checkpoint={"step": step},
                             checkpoint_step=step)
            elif train.is_preempted():
                return "preempted"
            elif step % 10 == 9:
                train.report({"step": step}, checkpoint={"step": step},
                             checkpoint_step=step)
            else:
                train.report({"step": step})
        return "done"

    controller = TrainController(
        train_fn,
        ScalingConfig(num_workers=3),
        RunConfig(name="preempt-pm", storage_path=str(tmp_path / "trial"),
                  failure=FailureConfig(max_failures=0)),
        train_config={},
        restart_backoff_s=0.0,
    )
    box = {}
    thread = threading.Thread(
        target=lambda: box.update(result=controller.run()), daemon=True
    )
    thread.start()
    deadline = time.monotonic() + 60
    while not controller.metrics_history and time.monotonic() < deadline:
        time.sleep(0.02)
    assert controller.metrics_history, "gang never started reporting"

    chaos.set_chaos(preempt_node=True, preempt_warning_s=3.0,
                    name_filter="pm-trigger", max_injections=1)
    # a NON-head node hosting a gang worker: the announcement then comes
    # from a different logical node than the driver's train events, so
    # the bundle provably spans >=2 nodes
    victim = next(
        n for n in rt.scheduler.nodes()
        if not n.is_head and n.resources.available().get("CPU", 0.0) < 0.5
    )

    @ray_tpu.remote(name="pm-trigger", num_cpus=0)
    def trigger():
        return "sent"

    ref = trigger.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(victim.node_id)
    ).remote()
    assert ray_tpu.get(ref, timeout=30) == "sent"

    thread.join(timeout=120)
    assert not thread.is_alive(), "controller never finished"
    result = box["result"]
    assert result.status == RunStatus.FINISHED, result.error
    assert result.num_preempt_restarts == 1

    # ---- one bundle, one causally-ordered timeline
    out = str(tmp_path / "episode.tgz")
    state.postmortem(out, note="preempt capstone")
    bundle = load_bundle(out)
    evs = bundle["events.jsonl"]

    def first(kind, **match):
        for e in evs:
            if e.get("kind") != kind:
                continue
            extra = e.get("extra") or {}
            if all(extra.get(k) == v for k, v in match.items()):
                return e
        raise AssertionError(
            f"no {kind} event matching {match} in "
            f"{[(e.get('kind'), e.get('extra')) for e in evs]}"
        )

    announced = first("preempt.announced")
    emergency = first("ckpt.saved", emergency=True)
    restart = first("train.preempt_restart")
    resumed = first("train.gang_started", attempt=2)
    # causal order on the shared wall clock
    assert (announced["ts"] <= emergency["ts"] <= restart["ts"]
            <= resumed["ts"]), [announced, emergency, restart, resumed]
    # the resumed attempt picked up the emergency checkpoint
    assert resumed["extra"]["resume_from_step"] is not None
    # events span >=2 logical nodes (victim + driver/head)
    episode_nodes = {e.get("node") for e in
                     (announced, emergency, restart, resumed)}
    assert len(episode_nodes) >= 2, episode_nodes
    assert announced["node"] == victim.node_id.hex()

    # the SAME events appear as instant marks on the Perfetto timeline,
    # wall-clock aligned with the run's span slices
    timeline = bundle["timeline.json"]["traceEvents"]
    marks = {e["args"].get("kind"): e for e in timeline
             if e.get("ph") == "i" and e.get("cat") == "events"}
    for kind in ("preempt.announced", "ckpt.saved",
                 "train.preempt_restart", "train.gang_started"):
        assert kind in marks, sorted(marks)
    slices = [e for e in timeline if e.get("ph") == "X"
              and e.get("name") == "train.attempt"]
    assert len(slices) >= 2  # both gang attempts made it into the export
    lo = min(e["ts"] for e in slices)
    hi = max(e["ts"] + e.get("dur", 0) for e in slices)
    assert lo <= marks["preempt.announced"]["ts"] <= hi

    # ---- goodput: buckets partition the wall time (±5% demanded; the
    # accountant makes it exact) and surface identically everywhere
    goodput = result.goodput
    assert goodput is not None and goodput["wall_time_s"] > 0
    total = sum(goodput["buckets"].values())
    assert abs(total - goodput["wall_time_s"]) <= 0.05 * goodput["wall_time_s"]
    assert goodput["buckets"]["step_compute"] > 0
    assert goodput["buckets"]["ckpt_save"] > 0       # the emergency window
    assert goodput["buckets"]["preempt_restart"] > 0  # the re-mesh
    assert goodput["buckets"]["init"] > 0
    assert 0 < goodput["goodput_fraction"] < 1
    # gauges carry the same numbers
    gauge_total = 0.0
    for line in registry().prometheus_text().splitlines():
        if (line.startswith("raytpu_train_goodput_seconds")
                and 'run="preempt-pm"' in line):
            gauge_total += float(line.rsplit(" ", 1)[1])
    assert abs(gauge_total - total) < 1e-3, (gauge_total, total)


def test_autoscaler_events_and_gauges(runtime):
    """Capacity-plane actions land in the flight recorder as typed,
    demand-origin-tagged events, and the autoscaler gauges expose the
    same episode through /metrics."""
    from ray_tpu.core.capacity import (
        DEMAND_ORIGINS, CapacityAutoscaler, FakeNodeProvider, NodeType,
    )
    from ray_tpu.util.metrics import registry

    rt = runtime
    events().clear()
    for kind in ("autoscaler.scale_up", "autoscaler.scale_down",
                 "autoscaler.replace", "autoscaler.blocked",
                 "autoscaler.error"):
        assert kind in EVENT_KINDS, kind

    scaler = CapacityAutoscaler(
        rt.scheduler, FakeNodeProvider(rt.scheduler),
        [NodeType("cpu4", {"CPU": 4.0})],
        poll_interval_s=0.05, idle_timeout_s=0.3, drain_grace_s=5.0,
    )
    scaler.start()
    try:
        @ray_tpu.remote(num_cpus=4)
        def big():
            return "ran"

        assert ray_tpu.get(big.remote(), timeout=60) == "ran"
        deadline = time.monotonic() + 30
        while scaler.stats["scale_downs"] == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert scaler.stats["scale_downs"] >= 1
    finally:
        scaler.stop()

    ups = events().list(kind="autoscaler.scale_up")
    downs = events().list(kind="autoscaler.scale_down")
    assert ups and downs
    up, down = ups[0], downs[0]
    # demand-origin tagging on the way up, drain reason on the way down
    assert up["extra"]["origin"] in DEMAND_ORIGINS
    assert up["extra"]["node_type"] == "cpu4"
    assert up["extra"]["capacity_class"] == "on_demand"
    assert down["extra"]["reason"]
    assert down["extra"]["forced"] is False  # drain completed, not expired
    assert down["node"] == up["node"]  # the same launched node retired
    assert up["ts"] <= down["ts"]

    text = registry().prometheus_text()
    assert "raytpu_autoscaler_managed_nodes" in text
    assert "raytpu_autoscaler_pending_demands" in text
    up_n = down_n = None
    for line in text.splitlines():
        if line.startswith('raytpu_autoscaler_scale_total{direction="up"}'):
            up_n = float(line.rsplit(" ", 1)[1])
        if line.startswith('raytpu_autoscaler_scale_total{direction="down"}'):
            down_n = float(line.rsplit(" ", 1)[1])
    assert up_n is not None and up_n >= 1.0
    assert down_n is not None and down_n >= 1.0
