"""Metrics registry, /metrics endpoint, state API, distributed tracing."""

import json
import re
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import (
    Counter,
    Gauge,
    Histogram,
    chrome_tracing_dump,
    get_trace,
    list_nodes,
    list_objects,
    list_tasks,
    list_traces,
    registry,
    start_metrics_server,
    summary,
    trace_dump,
)


@pytest.fixture(autouse=True)
def rt():
    registry().clear()
    runtime = ray_tpu.init(num_cpus=4, detect_accelerators=False)
    yield runtime
    ray_tpu.shutdown()
    registry().clear()


def test_counter_gauge_histogram_collect():
    c = Counter("reqs_total", "requests", tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2, tags={"route": "/a"})
    c.inc(tags={"route": "/b"})
    collected = dict(
        (tuple(sorted(t.items())), v) for t, v in c.collect()
    )
    assert collected[(("route", "/a"),)] == 3.0

    g = Gauge("queue_depth", "depth")
    g.set(7)
    assert g.collect() == [({}, 7.0)]

    h = Histogram("latency_s", "latency", boundaries=[0.1, 1.0])
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    ((_, data),) = h.collect()
    assert data["count"] == 3
    assert data["sum"] == pytest.approx(5.55)
    assert data["buckets"] == [(0.1, 1), (1.0, 1)]


def test_prometheus_text_format():
    Counter("mycount", "a counter").inc(5)
    text = registry().prometheus_text()
    assert "# TYPE mycount counter" in text
    assert "mycount 5.0" in text


def test_metrics_http_endpoint():
    Gauge("live_gauge", "x").set(42)
    port = start_metrics_server()
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        body = r.read().decode()
    assert "live_gauge 42.0" in body


def test_callback_gauge_samples_at_scrape():
    state = {"v": 1.0}
    Gauge("cb_gauge", "callback", fn=lambda: state["v"])
    assert "cb_gauge 1.0" in registry().prometheus_text()
    state["v"] = 9.0
    assert "cb_gauge 9.0" in registry().prometheus_text()


def test_state_api_lists():
    @ray_tpu.remote
    def work(x):
        return x * 2

    refs = [work.remote(i) for i in range(5)]  # held: dropping them GC's the objects
    ray_tpu.get(refs)
    tasks = list_tasks()
    assert len(tasks) >= 5
    assert all(t["ok"] for t in tasks if t["name"] == "work")
    nodes = list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]
    assert any(o["state"] == "READY" for o in list_objects())
    s = summary()
    assert s["tasks_finished"] >= 5


def test_chrome_tracing_dump_deprecated_delegates(tmp_path):
    """chrome_tracing_dump is a thin wrapper over trace_dump now: same
    payload (the span export), one DeprecationWarning per process."""
    import warnings as _warnings

    from ray_tpu.util import state as _state

    @ray_tpu.remote
    def traced():
        import time

        time.sleep(0.01)
        return 1

    ray_tpu.get([traced.remote() for _ in range(3)])
    _state._chrome_dump_warned[0] = False  # reset the one-shot latch
    path = tmp_path / "trace.json"
    with pytest.warns(DeprecationWarning, match="trace_dump"):
        payload = chrome_tracing_dump(str(path))
    trace = json.loads(payload)
    execs = [
        e for e in trace["traceEvents"]
        if e["name"] == "task.execute" and e["args"].get("task") == "traced"
    ]
    assert len(execs) == 3
    for e in execs:
        assert e["ph"] == "X"
        assert e["dur"] >= 10_000  # ≥10ms in microseconds
    assert path.exists()
    # delegation means the two exports CANNOT drift
    assert json.loads(chrome_tracing_dump()) == json.loads(trace_dump())
    # ...and the warning is one-shot
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        chrome_tracing_dump()
    assert not [w for w in caught if w.category is DeprecationWarning]


# ---------------------------------------------------------- exposition format

# one exposition line: name{labels} value  (labels optional)
_EXPO_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{([a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*",?)*\})? '
    r"[0-9.eE+-]+(inf|nan)?$"
)


def test_metrics_scrape_parses_with_escaped_labels():
    """Fetch /metrics and validate the exposition format line by line:
    tagged histogram series stay distinct, and backslash/quote/newline in
    label values are escaped instead of corrupting the payload."""
    c = Counter("evil_labels_total", 'desc with "quotes"\nand newline',
                tag_keys=("path",))
    c.inc(tags={"path": 'C:\\tmp\n"quoted"'})
    h = Histogram("lat_seconds", "latency", boundaries=[0.1, 1.0],
                  tag_keys=("route",))
    h.observe(0.05, tags={"route": "a"})
    h.observe(5.0, tags={"route": 'b\\"x\n'})
    port = start_metrics_server()
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ) as r:
        body = r.read().decode()
    for line in body.strip().splitlines():
        if line.startswith("#"):
            continue
        assert _EXPO_LINE.match(line), f"unparseable exposition line: {line!r}"
    # escaped sequences present, raw ones absent
    assert '\\\\tmp' in body and '\\"quoted\\"' in body and "\\n" in body
    # tagged histogram series: labels + le on bucket lines, both routes
    assert re.search(r'lat_seconds_bucket\{route="a",le="0.1"\} 1', body)
    assert re.search(r'lat_seconds_count\{route="a"\} 1', body)
    assert 'route="b' in body


def test_callback_gauge_tagged_samples_and_sampler_warning():
    state = {"fail": False}

    def sample():
        if state["fail"]:
            raise RuntimeError("sampler broke")
        return [({"shard": "a"}, 1.0), ({"shard": "b"}, 2.0)]

    Gauge("cb_tagged", "tagged callback", tag_keys=("shard",), fn=sample)
    text = registry().prometheus_text()
    assert 'cb_tagged{shard="a"} 1.0' in text
    assert 'cb_tagged{shard="b"} 2.0' in text
    # a raising sampler suppresses the series AND emits one WARNING event
    from ray_tpu.util.events import events

    before = len(events().list(severity="WARNING", source="metrics",
                               limit=1000))
    state["fail"] = True
    assert registry().prometheus_text().count("cb_tagged") == 2  # HELP/TYPE only
    registry().prometheus_text()  # second failing scrape: no duplicate event
    warnings = events().list(severity="WARNING", source="metrics", limit=1000)
    mine = [w for w in warnings if "cb_tagged" in w["message"]]
    assert len(mine) == 1 and len(warnings) == before + 1


def test_event_sink_cached_handle(tmp_path):
    from ray_tpu.util.events import EventLog

    path = str(tmp_path / "ev.jsonl")
    log = EventLog()
    log.set_sink(path)
    log.emit("INFO", "test", "one")
    log.emit("INFO", "test", "two")
    lines = [json.loads(l) for l in open(path).read().splitlines()]
    assert [e["message"] for e in lines] == ["one", "two"]
    # the handle is cached (no reopen per event) and swapped on set_sink
    first_handle = log._sink_file
    assert first_handle is not None
    log.emit("INFO", "test", "three")
    assert log._sink_file is first_handle
    other = str(tmp_path / "ev2.jsonl")
    log.set_sink(other)
    assert log._sink_file is not first_handle
    log.emit("INFO", "test", "four")
    assert "four" in open(other).read()
    log.set_sink(None)
    log.emit("INFO", "test", "five")
    assert "five" not in open(other).read()


# ----------------------------------------------------------------- mark ring
#
# What the three planes (util/events, serve/reqlog, train/steplog) and
# the federation cursor of core/cluster.py rely on.


def test_mark_ring_since_is_oldest_first_and_never_skips():
    from ray_tpu.util.markring import MarkRing

    ring = MarkRing(capacity=64)
    for i in range(10):
        ring.append({"i": i})
    assert [m["seq"] for m in ring.since()] == list(range(1, 11))
    # a cursor walked in batches of three sees every mark exactly once
    cursor, seen = 0, []
    while batch := ring.since(cursor, 3):
        assert len(batch) <= 3
        seen.extend(m["i"] for m in batch)
        cursor = batch[-1]["seq"]
    assert seen == list(range(10))
    assert ring.since(cursor) == []


def test_mark_ring_stamps_what_a_record_lacks_and_tells_its_plane():
    import time

    from ray_tpu.util.markring import MarkRing

    indexed = []
    ring = MarkRing(on_append=indexed.append)
    before = time.time()
    rec = ring.append({"seq": 0, "ts": None, "mono": None, "node": None})
    assert rec["seq"] == 1 and before <= rec["ts"] <= time.time()
    assert 0 < rec["mono"] <= time.monotonic()
    assert list(rec) == ["seq", "ts", "mono", "node"]  # the plane's order
    given = ring.append({"ts": 12.5, "mono": 0.5, "node": "abcd"})
    assert (given["ts"], given["mono"], given["node"]) == (12.5, 0.5, "abcd")
    assert given["seq"] == 2
    assert indexed == [rec, given]  # on_append saw each, after its stamps


def test_mark_ring_seq_survives_clear_and_a_full_ring_evicts_the_oldest():
    from ray_tpu.util.markring import MarkRing

    ring = MarkRing(capacity=4)
    for i in range(6):
        ring.append({"i": i})
    assert ring.stats() == {"seq": 6, "buffered": 4}
    assert [m["i"] for m in ring.since()] == [2, 3, 4, 5]
    # a cursor behind the eviction resumes at the oldest mark still held
    assert ring.since(1, 2)[0]["seq"] == 3
    ring.clear()
    assert ring.stats() == {"seq": 6, "buffered": 0}
    assert ring.append({"i": 6})["seq"] == 7  # a cursor at 6 stays valid
    assert [m["i"] for m in ring.since(6)] == [6]


# ------------------------------------------------------------------- tracing


def test_local_task_trace_spans_and_metrics():
    """submit → queue → execute → result share one trace; queue/exec
    histograms derive from the spans."""

    @ray_tpu.remote
    def traced_work():
        import time

        time.sleep(0.01)
        return 1

    assert ray_tpu.get(traced_work.remote(), timeout=30) == 1
    trace = [t for t in list_traces() if t["root"] == "task.submit"][-1]
    spans = get_trace(trace["trace_id"])
    names = {s["name"] for s in spans}
    assert {"task.submit", "task.queue", "task.execute", "task.result"} <= names
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        assert s["trace_id"] == trace["trace_id"]
        if s["parent_id"] is not None:
            assert s["parent_id"] in by_id, f"orphan parent for {s['name']}"
    execute = next(s for s in spans if s["name"] == "task.execute")
    assert execute["duration_s"] >= 0.01
    text = registry().prometheus_text()
    assert "raytpu_task_queue_seconds_count" in text
    assert "raytpu_task_exec_seconds_count" in text


def test_trace_export_valid_chrome_json(tmp_path):
    @ray_tpu.remote
    def exported():
        return 2

    ray_tpu.get(exported.remote(), timeout=30)
    path = tmp_path / "spans.json"
    payload = trace_dump(str(path))
    trace = json.loads(payload)  # must load as valid chrome-trace JSON
    assert path.exists() and json.loads(path.read_text()) == trace
    events = trace["traceEvents"]
    assert events, "no span events exported"
    for e in events:
        # span slices are complete events; cross-lane parent->child
        # links additionally export as flow start/finish pairs (PR 9)
        assert e["ph"] in ("X", "s", "f")
        assert isinstance(e["ts"], float)
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        assert "trace_id" in e["args"]
    assert any(e["name"] == "task.execute" for e in events)
    # CLI path: ray_tpu timeline --trace
    from ray_tpu.cli import main as cli_main

    out = tmp_path / "cli_trace.json"
    assert cli_main(["timeline", "--trace", str(out)]) == 0
    assert json.loads(out.read_text())["traceEvents"]


def test_trace_sampling_knob():
    from ray_tpu.core.config import cfg
    from ray_tpu.util.tracing import tracer

    @ray_tpu.remote
    def unsampled():
        return 3

    cfg.set(trace_sample_ratio=0.0)
    try:
        before = len(tracer().spans())
        ray_tpu.get(unsampled.remote(), timeout=30)
        new = [
            s for s in tracer().spans()[before:]
            if s["attrs"].get("task") == "unsampled"
        ]
        assert new == [], f"unsampled trace still recorded: {new}"
    finally:
        cfg.reset("trace_sample_ratio")


def test_remote_task_span_parents_to_driver_submit_across_rpc():
    """Acceptance: a remote task yields ONE trace whose execute span (on
    the agent process) walks back to the driver's submit span, stitched
    through the state API across the RPC boundary."""
    import time as _time

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.scheduler import NodeAffinitySchedulingStrategy

    ray_tpu.shutdown()  # the autouse fixture runtime is not a cluster head
    from ray_tpu.core.config import cfg

    c = Cluster(head_node_args={
        "num_cpus": 2,
        "_system_config": {"node_stale_s": 5.0, "node_heartbeat_s": 0.2},
    })
    try:
        c.add_node(num_cpus=2, system_config={"node_heartbeat_s": 0.2})
        c.wait_for_nodes(2)
        remote_node = next(
            n for n in c.runtime.scheduler.nodes() if n.is_remote
        )

        @ray_tpu.remote
        def remote_probe():
            import os

            return os.getpid()

        pid = ray_tpu.get(
            remote_probe.options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    remote_node.node_id
                )
            ).remote(),
            timeout=60,
        )
        import os

        assert pid != os.getpid(), "task did not land on the agent"
        _time.sleep(0.3)  # let the agent finish recording result spans
        trace = next(
            t for t in reversed(list_traces())
            if t["root"] == "task.submit"
        )
        spans = get_trace(trace["trace_id"])
        names = {s["name"] for s in spans}
        assert {"task.submit", "task.queue", "task.dispatch",
                "task.execute", "task.result"} <= names, names
        by_id = {s["span_id"]: s for s in spans}
        execute = next(s for s in spans if s["name"] == "task.execute")
        assert execute["attrs"].get("remote") is True  # ran on the agent
        chain = []
        cur = execute
        while cur["parent_id"] is not None:
            cur = by_id[cur["parent_id"]]
            chain.append(cur["name"])
        assert chain[-1] == "task.submit", chain
        assert all(s["trace_id"] == trace["trace_id"] for s in spans)
        # exportable as valid chrome JSON through the state API
        exported = json.loads(trace_dump(trace_id=trace["trace_id"]))
        assert any(
            e["name"] == "task.execute" for e in exported["traceEvents"]
        )
        # span-derived histograms visible on the scrape
        text = registry().prometheus_text()
        assert "raytpu_task_queue_seconds_count" in text
    finally:
        c.shutdown()
        cfg.reset()


def test_serve_request_spans_yield_ttft_tpot():
    """An engine request span carries token counts and yields TTFT/TPOT
    observations into the serve histograms."""
    import jax

    from ray_tpu.models import get_config, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.util.tracing import tracer

    config = get_config("llama-tiny")
    params = init_params(config, jax.random.PRNGKey(0))
    engine = LLMEngine(config, params, EngineConfig(max_slots=2))
    try:
        tokens = engine.generate([5, 17, 42, 7], max_tokens=8)
        assert len(tokens) == 8
    finally:
        engine.shutdown()
    req = next(
        s for s in reversed(tracer().spans())
        if s["name"] == "engine.request"
    )
    assert req["attrs"]["generated_tokens"] == 8
    assert req["attrs"]["ttft_s"] > 0
    assert req["attrs"]["tpot_s"] > 0
    assert req["attrs"]["queue_s"] >= 0
    text = registry().prometheus_text()
    assert "raytpu_serve_ttft_seconds_count" in text
    assert "raytpu_serve_tpot_seconds_count" in text
    assert any(
        s["name"] == "engine.prefill" for s in tracer().spans()
    )


def test_metric_names_static_check():
    """scripts/check_metrics_names.py is now a shim over the raylint
    metrics-names rule; the repo-wide gate runs ONCE in
    tests/test_raylint.py. Here: the shim's compat API still flags a
    bad package, not just passes everything."""
    import pathlib

    repo = pathlib.Path(__file__).resolve().parent.parent
    script = repo / "scripts" / "check_metrics_names.py"
    import importlib.util

    spec = importlib.util.spec_from_file_location("cmn", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        bad = pathlib.Path(tmp) / "pkg"
        bad.mkdir()
        (bad / "m.py").write_text(
            'c = Counter("unprefixed_total", "x")\n'
            'd = Counter("raytpu_dup_total", "x")\n'
            'h = Histogram("raytpu_nobounds_seconds", "x")\n'
            'h2 = get_or_create_histogram(\n'
            '    "raytpu_bounded_seconds", "x",\n'
            '    boundaries=(0.1, 1.0),\n'
            ')\n'
            'value = some_gauge._fn()\n'
        )
        (bad / "n.py").write_text(
            'e = Counter("raytpu_dup_total", "x")\n'
            'class MyMetric:\n'
            '    def collect(self):\n'
            '        return []\n'
        )
        errors = mod.check(bad)
        assert any("unprefixed_total" in e for e in errors)
        assert any("raytpu_dup_total" in e and "2 sites" in e for e in errors)
        # new rules: histograms need explicit boundaries; sampler-guard
        # bypasses (direct ._fn() calls, collect() overrides) are flagged
        assert any("raytpu_nobounds_seconds" in e and "boundaries" in e
                   for e in errors)
        assert not any("raytpu_bounded_seconds" in e for e in errors)
        assert any("._fn()" in e for e in errors)
        assert any("collect() override" in e for e in errors)


# ------------------------------------------------------------ telemetry plane


def test_node_stats_snapshot_and_gauges(rt):
    """The per-node collector samples process/store/pool/queue stats and
    the node-local gauges ride the scrape."""
    snap = rt.node_stats.snapshot()
    for key in ("cpu_percent", "rss_bytes", "object_store", "worker_pool",
                "task_queues", "scheduler", "health", "pubsub", "tpu", "ts"):
        assert key in snap, key
    assert snap["rss_bytes"] > 0
    assert set(snap["task_queues"]) == {"pending", "blocked", "admission"}
    assert set(snap["worker_pool"]) >= {"busy", "idle"}
    text = registry().prometheus_text()
    for name in ("raytpu_node_cpu_percent", "raytpu_node_rss_bytes",
                 "raytpu_node_worker_pool", "raytpu_node_task_queue_depth"):
        assert f"# TYPE {name} gauge" in text, name
    assert re.search(r'raytpu_node_task_queue_depth\{queue="pending"\} ', text)


def test_status_report_renders():
    """Acceptance: `ray_tpu status` against an in-process runtime shows
    per-node resource usage, object-store bytes and worker-pool
    occupancy (state.status_report backs the CLI)."""
    from ray_tpu.util.state import status_report

    @ray_tpu.remote
    def work(x):
        return x + 1

    ray_tpu.get([work.remote(i) for i in range(3)])
    report = status_report()
    assert "Nodes: 1 (1 ALIVE)" in report
    assert "resources: CPU:" in report
    assert "object store:" in report
    assert "worker pool:" in report and "busy" in report
    assert "Scheduler: dispatched=" in report
    assert "Recent warnings" in report
    # --verbose appends per-node log tails
    assert "Logs (per node):" in status_report(verbose=True)


def test_metrics_cluster_endpoint_node_id_labels():
    """/metrics/cluster returns a parseable merged exposition where
    every sample carries a node_id label (single-node degenerate case)."""
    Counter("raytpu_probe_total", "probe").inc(3)
    port = start_metrics_server()
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics/cluster", timeout=10
    ) as r:
        body = r.read().decode()
    local_hex = ray_tpu.api._runtime().scheduler.head_node().node_id.hex()
    samples = [
        l for l in body.strip().splitlines() if not l.startswith("#")
    ]
    assert samples
    for line in samples:
        assert _EXPO_LINE.match(line), f"unparseable merged line: {line!r}"
        assert 'node_id="' in line, f"sample without node_id: {line!r}"
    assert f'node_id="{local_hex}"' in body
    assert re.search(
        rf'raytpu_probe_total\{{node_id="{local_hex}"\}} 3', body
    )


def test_cluster_telemetry_roundtrip_and_federation():
    """Capstone: stats snapshots round-trip through the GCS node table
    via the heartbeat piggyback, and the head federates both nodes'
    expositions with node_id labels over the metrics_snapshot RPC."""
    import time as _time

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.cluster import NODE_NS
    from ray_tpu.core.config import cfg
    from ray_tpu.util.metrics import cluster_prometheus_text
    from ray_tpu.util.state import node_stats, status_report, summary

    ray_tpu.shutdown()  # the autouse fixture runtime is not a cluster head
    c = Cluster(head_node_args={
        "num_cpus": 2,
        "_system_config": {"node_stale_s": 5.0, "node_heartbeat_s": 0.2,
                           "node_stats_period_s": 0.2},
    })
    try:
        c.add_node(num_cpus=2, system_config={"node_heartbeat_s": 0.2,
                                              "node_stats_period_s": 0.2})
        c.wait_for_nodes(2)
        ctx = c.runtime.cluster
        # (1) snapshot round-trip through the node table
        deadline = _time.monotonic() + 10
        table = {}
        while _time.monotonic() < deadline:
            table = {
                key: ctx.gcs.kv_get(key, namespace=NODE_NS)
                for key in ctx.gcs.kv_keys(namespace=NODE_NS)
            }
            if len(table) == 2 and all(
                (info or {}).get("stats") for info in table.values()
            ):
                break
            _time.sleep(0.1)
        assert len(table) == 2
        for info in table.values():
            stats = info.get("stats")
            assert stats, f"no stats piggybacked for {info.get('node_id')}"
            assert "object_store" in stats and "worker_pool" in stats
            assert "task_queues" in stats and stats["rss_bytes"] > 0
        # (2) state API carries both snapshots
        ns = node_stats()
        assert set(ns) == set(table)
        assert summary()["node_stats"].keys() == ns.keys()
        # (3) federated exposition: every sample labeled, both nodes in
        merged = cluster_prometheus_text()
        samples = [
            l for l in merged.strip().splitlines() if not l.startswith("#")
        ]
        assert samples
        for line in samples:
            assert _EXPO_LINE.match(line), f"unparseable: {line!r}"
            assert 'node_id="' in line, line
        for node_hex in table:
            assert f'node_id="{node_hex}"' in merged, node_hex[:12]
        # TYPE headers are deduplicated across nodes
        assert merged.count("# TYPE raytpu_node_rss_bytes gauge") == 1
        # (4) the status report sees the cluster
        report = status_report()
        assert "Nodes: 2" in report
    finally:
        c.shutdown()
        cfg.reset()


# ---------------------------------------------------------------- watchdogs


def test_stall_watchdog_unit_transitions():
    """Deterministic stall logic: EWMA regression names the straggler,
    the no-progress window catches a dead gang, recovery clears."""
    from ray_tpu.util.events import events
    from ray_tpu.util.watchdog import StallWatchdog

    wd = StallWatchdog("unit_run", 2, window_s=10.0, factor=3.0,
                       alpha=0.5, min_s=0.5)
    t0 = 1000.0
    # both ranks step every 0.2s for a while
    for i in range(6):
        wd.observe_report(0, t0 + 0.2 * i)
        wd.observe_report(1, t0 + 0.2 * i)
    now = t0 + 0.2 * 5
    assert wd.check(now + 0.1) is False
    # rank 1 goes silent: gap blows past factor x EWMA (and min_s)
    for i in range(6, 10):
        wd.observe_report(0, t0 + 0.2 * i)
    assert wd.check(t0 + 0.2 * 9 + 0.8) is True
    assert wd.straggler == 1
    g = registry().get("raytpu_train_stalled")
    assert dict((tuple(sorted(t.items())), v) for t, v in g.collect())[
        (("run", "unit_run"),)
    ] == 1.0
    warned = [
        e for e in events().list(severity="WARNING", source="watchdog",
                                 limit=100)
        if "unit_run" in e["message"]
    ]
    assert warned and "rank 1" in warned[-1]["message"]
    # rank 1 recovers
    wd.observe_report(1, t0 + 0.2 * 9 + 0.9)
    wd.observe_report(0, t0 + 0.2 * 9 + 0.9)
    assert wd.check(t0 + 0.2 * 9 + 1.0) is False
    assert dict((tuple(sorted(t.items())), v) for t, v in g.collect())[
        (("run", "unit_run"),)
    ] == 0.0
    # global no-progress window
    assert wd.check(t0 + 1000.0) is True
    wd.close()
    assert dict((tuple(sorted(t.items())), v) for t, v in g.collect())[
        (("run", "unit_run"),)
    ] == 0.0


def test_stall_watchdog_fires_on_injected_slow_gang_worker():
    """Acceptance: a chaos-injected slow gang worker flips
    raytpu_train_stalled to 1 and emits a WARNING naming the straggler
    rank; the gauge clears when the worker recovers."""
    import threading as _threading
    import time as _time

    from ray_tpu import train
    from ray_tpu.core.config import cfg
    from ray_tpu.train import (
        RunConfig,
        ScalingConfig,
        TrainController,
    )
    from ray_tpu.util.events import events

    cfg.set(train_stall_window_s=60.0,  # global window off the hot path
            train_stall_factor=4.0, train_stall_min_s=0.25,
            train_stall_ewma_alpha=0.3)
    run_name = "stall_drill"

    def train_fn(config):
        ctx = train.get_context()
        for step in range(25):
            train.report({"step": step})
            if ctx.world_rank == 1 and step == 10:
                _time.sleep(1.2)  # injected slow step: the straggler
            else:
                _time.sleep(0.03)

    controller = TrainController(
        train_fn,
        ScalingConfig(num_workers=2,
                      resources_per_worker={"CPU": 1.0}),
        RunConfig(name=run_name),
        train_config={},
        poll_interval=0.02,
    )
    result_box = {}

    def run():
        result_box["result"] = controller.run()

    t = _threading.Thread(target=run, daemon=True)
    t.start()

    def stalled_value():
        g = registry().get("raytpu_train_stalled")
        if g is None:
            return None
        vals = dict(
            (tuple(sorted(tags.items())), v) for tags, v in g.collect()
        )
        return vals.get((("run", run_name),))

    deadline = _time.monotonic() + 30
    fired = False
    while _time.monotonic() < deadline:
        if stalled_value() == 1.0:
            fired = True
            break
        _time.sleep(0.02)
    assert fired, "stall watchdog never fired on the injected slow worker"
    warned = [
        e for e in events().list(severity="WARNING", source="watchdog",
                                 limit=200)
        if run_name in e["message"] and "STALLED" in e["message"]
    ]
    assert warned, "no WARNING event from the stall watchdog"
    assert "rank 1" in warned[0]["message"], warned[0]["message"]
    assert warned[0].get("extra", {}).get("straggler_rank") == 1
    t.join(timeout=60)
    assert not t.is_alive()
    assert result_box["result"].status.value == "FINISHED", (
        result_box["result"].error
    )
    # run over (watchdog closed): the stalled gauge reads 0 again
    assert stalled_value() == 0.0
    cfg.reset("train_stall_window_s")
    cfg.reset("train_stall_factor")
    cfg.reset("train_stall_min_s")
    cfg.reset("train_stall_ewma_alpha")


def test_serve_slo_monitor_burns_on_p99_violation():
    """The SLO monitor diffs the PR-2 histograms per window and burns
    raytpu_serve_slo_burn_total{slo=ttft_p99} + a WARNING event when the
    window's p99 exceeds the objective."""
    from ray_tpu.core.config import cfg
    from ray_tpu.util.events import events
    from ray_tpu.util.watchdog import ServeSLOMonitor

    from ray_tpu.util.metrics import get_or_create_histogram

    hist = get_or_create_histogram(
        "raytpu_serve_ttft_seconds", "ttft",
        boundaries=(0.005, 0.025, 0.1, 0.5, 2.0, 10.0),
    )
    cfg.set(serve_slo_ttft_p99_s=0.1)
    try:
        monitor = ServeSLOMonitor()
        monitor.check()  # baseline the window cursor
        for _ in range(50):
            hist.observe(1.5)  # way over the 100ms objective
        verdict = monitor.check()
        assert verdict["ttft_p99"] > 0.1
        burn = registry().get("raytpu_serve_slo_burn_total")
        assert burn is not None
        burns = dict(
            (tuple(sorted(t.items())), v) for t, v in burn.collect()
        )
        assert burns[(("slo", "ttft_p99"),)] == 1.0
        warned = events().list(severity="WARNING", source="watchdog",
                               limit=50)
        assert any("serve SLO burn" in e["message"] and "ttft_p99"
                   in e["message"] for e in warned)
        # a healthy window does NOT burn again
        for _ in range(200):
            hist.observe(0.01)
        monitor.check()
        burns = dict(
            (tuple(sorted(t.items())), v) for t, v in burn.collect()
        )
        assert burns[(("slo", "ttft_p99"),)] == 1.0
    finally:
        cfg.reset("serve_slo_ttft_p99_s")


def test_log_lines_carry_node_and_task_attribution():
    """Captured log tails attribute lines with [node:...] and, inside a
    task, [task:...] — so aggregated tails keep their origin."""
    import logging as _logging

    from ray_tpu.util import logs

    _logging.getLogger("ray_tpu.test").warning("outside-any-task")

    @ray_tpu.remote
    def noisy():
        _logging.getLogger("ray_tpu.test").warning("inside-the-task")
        return 1

    assert ray_tpu.get(noisy.remote(), timeout=30) == 1
    tail = logs.tail(200)
    outside = next(l for l in tail if "outside-any-task" in l)
    inside = next(l for l in tail if "inside-the-task" in l)
    assert "[node:" in outside and "[task:" not in outside
    assert "[node:" in inside and "[task:" in inside


def test_device_trace_captures_xla_profile(tmp_path):
    """util.profiling.device_trace writes a TensorBoard-loadable XLA
    profile for work dispatched inside the block (SURVEY §5 tracing); the
    host regions in it are `tracing.span`s (each a TraceAnnotation)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.util import device_trace, tracing

    logdir = str(tmp_path / "trace")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    with device_trace(logdir):
        with tracing.span("warmup"):
            f(x).block_until_ready()
        for step in range(2):
            with tracing.span("measured.step", step=step):
                f(x).block_until_ready()
    import os

    found = []
    for root, _dirs, files in os.walk(logdir):
        found.extend(files)
    assert found, "device trace produced no profile files"
    assert any("trace" in name or name.endswith(".pb") for name in found), found
