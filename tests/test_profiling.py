"""Profiling plane: typed device-trace guards, coordinated capture,
cost-model MFU/roofline accounting, Perfetto device-track merge."""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu import ProfilingError
from ray_tpu.util import profiling, state
from ray_tpu.util.metrics import registry


@pytest.fixture
def rt():
    registry().clear()
    runtime = ray_tpu.init(num_cpus=4, detect_accelerators=False)
    yield runtime
    ray_tpu.shutdown()
    registry().clear()


@pytest.fixture
def rt3():
    """Three logical nodes: the in-process fan-out capture target."""
    registry().clear()
    runtime = ray_tpu.init(num_cpus=4, num_nodes=3, detect_accelerators=False)
    yield runtime
    ray_tpu.shutdown()
    registry().clear()


def _busy_jit():
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()  # compile outside any capture window
    return lambda: f(x).block_until_ready()


# ------------------------------------------------------- typed trace guards


def test_stop_without_active_trace_is_typed():
    with pytest.raises(ProfilingError, match="no active device trace"):
        profiling.stop_device_trace()


def test_double_start_is_typed(tmp_path):
    profiling.start_device_trace(str(tmp_path / "a"))
    try:
        with pytest.raises(ProfilingError, match="already active"):
            profiling.start_device_trace(str(tmp_path / "b"))
    finally:
        profiling.stop_device_trace()
    # the latch cleared: a fresh stop is typed again, not a jax error
    with pytest.raises(ProfilingError):
        profiling.stop_device_trace()


def test_device_trace_roundtrip_cpu(tmp_path):
    """CPU-backend capture round-trip: the context manager records a
    loadable chrome-trace artifact."""
    work = _busy_jit()
    logdir = tmp_path / "trace"
    with profiling.device_trace(str(logdir)):
        work()
    found = list(logdir.rglob("*.trace.json.gz"))
    assert found, "device trace produced no chrome-trace artifact"
    assert not profiling.device_trace_active()


def test_profiler_server_idempotent():
    try:
        first = profiling.start_profiler_server(9876)
    except ProfilingError as exc:
        pytest.skip(f"profiler server unavailable here: {exc}")
    second = profiling.start_profiler_server(9876)
    assert second is first
    assert profiling.profiler_server_port() == 9876
    assert profiling.node_snapshot()["server_port"] == 9876


# ----------------------------------------------------------- local capture


def test_capture_local_profile_roundtrip():
    work = _busy_jit()
    res = profiling.capture_local_profile(0.3, workload=work)
    meta, artifacts = res["meta"], res["artifacts"]
    assert meta["device"] == "ok" and meta["host"] == "ok"
    assert meta["bytes"] == sum(len(b) for b in artifacts.values()) > 0
    assert any(n.endswith(".trace.json.gz") for n in artifacts)
    report = artifacts["host_profile.txt"].decode()
    assert "host sampling profile" in report
    # the capture is reflected in the node snapshot for `status --verbose`
    snap = profiling.node_snapshot()
    assert snap["active_capture"] is None
    assert snap["last_capture"]["bytes"] == meta["bytes"]


def test_device_trace_events_align_to_wall_clock():
    work = _busy_jit()
    res = profiling.capture_local_profile(0.2, workload=work, host=False)
    events = profiling.load_device_trace_events(
        res["artifacts"], started_at=res["meta"]["started_at"],
        lane_prefix="device:test", max_events=500,
    )
    assert 0 < len(events) <= 500
    for e in events[:20]:
        assert e["pid"].startswith("device:test")
        # wall-clock aligned: inside ~a minute of the capture window
        assert abs(e["ts"] / 1e6 - res["meta"]["started_at"]) < 60.0


# -------------------------------------------------------- cost model / MFU


@pytest.fixture
def nominal_cpu_peaks(monkeypatch):
    """The peaks table holds published chips only; a CPU test that wants
    the MFU/roofline ARITHMETIC exercised asks for a nominal row here."""
    monkeypatch.setitem(profiling.DEVICE_PEAKS, "cpu", (1e12, 100e9))


def test_unknown_device_kind_is_an_error_not_a_default():
    f = jax.jit(lambda a, b: a @ b)
    with pytest.raises(ProfilingError, match="no published peaks"):
        profiling.step_cost(f, jnp.ones((8, 8)), jnp.ones((8, 8)))
    with pytest.raises(ProfilingError, match="no published peaks"):
        profiling.device_peaks()
    assert profiling.device_peaks(
        type("Dev", (), {"device_kind": "TPU v5 lite"})()
    )["peak_flops"] == 197e12


def test_step_cost_and_roofline(nominal_cpu_peaks):
    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((256, 128))
    w = jnp.ones((128, 64))
    cost = profiling.step_cost(f, x, w)
    assert cost.flops > 0 and cost.bytes_accessed > 0
    roof = profiling.roofline(cost, 0.001)
    assert roof["mfu"] > 0 and roof["hbm_fraction"] > 0
    assert roof["bound"] in ("compute", "memory")
    assert roof["device_kind"] == "cpu"
    with pytest.raises(ProfilingError):
        profiling.roofline(cost, 0.0)


def test_step_cost_rejects_plain_callable():
    with pytest.raises(ProfilingError, match="jitted or compiled"):
        profiling.step_cost(lambda: 1)


def test_sharded_step_cost_counts_devices(nominal_cpu_peaks):
    from jax.sharding import NamedSharding, PartitionSpec
    import numpy as np

    mesh = jax.sharding.Mesh(
        np.array(jax.devices()).reshape(len(jax.devices())), ("dp",)
    )
    x = jax.device_put(
        jnp.ones((256, 128)), NamedSharding(mesh, PartitionSpec("dp", None))
    )
    w = jax.device_put(jnp.ones((128, 64)), NamedSharding(mesh, PartitionSpec()))
    f = jax.jit(lambda a, b: a @ b)
    cost = profiling.step_cost(f, x, w)
    assert cost.n_devices == len(jax.devices())
    # cost_analysis is per-device: the whole program is N shards' worth
    assert cost.total_flops == pytest.approx(cost.flops * cost.n_devices)


# ------------------------------------------------- coordinated capture plane


def test_fanout_capture_in_process_runtime(rt3):
    """One state.profile() call covers >=2 logical nodes, registers the
    capture, and serves metas + artifact bytes through the state API."""
    work = _busy_jit()
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            work()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        record = state.profile(duration_s=0.4)
    finally:
        stop.set()
        t.join(timeout=5)
    assert len(record["nodes"]) >= 2
    assert record["total_bytes"] > 0
    listed = state.list_profiles()
    assert record["profile_id"] in [p["profile_id"] for p in listed]
    full = state.get_profile(record["profile_id"])
    holders = [
        (nh, m) for nh, m in full["nodes"].items() if not m.get("artifacts_at")
    ]
    assert holders, "no node holds the capture artifacts"
    node_hex, meta = holders[0]
    assert meta["device"] == "ok" and meta["host"] == "ok"
    name = meta["artifact_names"][0]
    assert len(state.profile_artifact(record["profile_id"], node_hex, name)) > 0
    # aliased logical nodes point at the holder instead of duplicating
    aliased = [m for m in full["nodes"].values() if m.get("artifacts_at")]
    assert all(m["artifacts_at"] == node_hex for m in aliased)
    with pytest.raises(ValueError):
        state.get_profile("no-such-profile")


def test_capture_selector_and_unknown_selector(rt3):
    head_hex = rt3.scheduler.head_node().node_id.hex()
    record = state.profile(nodes=[head_hex[:8]], duration_s=0.1, device=False)
    assert list(record["nodes"]) == [head_hex]
    with pytest.raises(ValueError, match="selector"):
        state.profile(nodes=["ffff-no-such-node"], duration_s=0.1)


def test_status_verbose_shows_profiler_and_capture(rt3):
    state.profile(duration_s=0.1, device=False)
    report = state.status_report(verbose=True)
    assert "profiler:" in report
    assert "last capture" in report


def test_trace_dump_merges_device_tracks(rt3):
    """trace_dump(profile_id=...) is valid Perfetto JSON holding BOTH
    runtime spans and per-device tracks from the capture."""
    from ray_tpu.core.config import cfg

    @ray_tpu.remote
    def traced():
        time.sleep(0.01)
        return 1

    ray_tpu.get([traced.remote() for _ in range(3)])
    work = _busy_jit()
    record = state.profile(duration_s=0.3)
    t = threading.Thread(target=work, daemon=True)
    t.start()
    t.join()
    cfg.set(profile_merge_max_events=500)
    try:
        payload = state.trace_dump(profile_id=record["profile_id"])
    finally:
        cfg.reset("profile_merge_max_events")
    trace = json.loads(payload)
    events = trace["traceEvents"]
    device = [e for e in events if str(e.get("pid", "")).startswith("device:")]
    spans = [e for e in events if not str(e.get("pid", "")).startswith("device:")]
    assert device, "no device tracks merged"
    assert spans, "runtime spans missing from the merged export"
    assert any(e["name"] == "task.execute" for e in spans)
    with pytest.raises(ValueError, match="no registered profile"):
        state.trace_dump(profile_id="bogus")


def test_check_lazy_jax_wired():
    """scripts/check_lazy_jax.py is now a shim over the raylint lazy-jax
    rule; the repo-wide gate runs ONCE in tests/test_raylint.py. Here:
    the shim's compat API still flags a module-level jax import and
    accepts a function-local one."""
    import ast
    import importlib.util

    repo = Path(__file__).resolve().parent.parent
    script = repo / "scripts" / "check_lazy_jax.py"
    spec = importlib.util.spec_from_file_location("clj", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = ast.parse("import jax\n")
    assert mod.module_level_jax_imports(bad) == [1]
    good = ast.parse("def f():\n    import jax\n")
    assert mod.module_level_jax_imports(good) == []


# --------------------------------------------------------- train MFU gauges


def test_train_run_publishes_mfu_from_cost_analysis(rt, nominal_cpu_peaks):
    """A short CPU-backend train run publishes a nonzero raytpu_train_mfu
    gauge derived from the compiled step's cost_analysis(), and the
    accounting lands in the Result."""
    from ray_tpu.train import RunConfig, ScalingConfig, Trainer

    def loop(config):
        from ray_tpu.models import get_config
        from ray_tpu.train.trainer import LMTrainer

        model = get_config("gpt2-tiny")
        trainer = LMTrainer(model, learning_rate=1e-3, total_steps=4)

        def batches():
            key = jax.random.PRNGKey(0)
            for _ in range(4):
                key, sub = jax.random.split(key)
                yield {"tokens": jax.random.randint(
                    sub, (8, 17), 0, model.vocab_size
                )}

        trainer.train(batches(), num_steps=4, report_every=2)

    result = Trainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="mfu_run"),
        train_loop_config={},
    ).fit()
    assert result.profiling is not None
    assert result.profiling["mfu"] > 0
    assert result.profiling["step_flops"] > 0
    assert result.metrics["mfu"] > 0  # rides the ordinary report metrics
    text = registry().prometheus_text()
    assert 'raytpu_train_mfu{run="mfu_run"}' in text
    mfu_line = [
        l for l in text.splitlines()
        if l.startswith('raytpu_train_mfu{run="mfu_run"}')
    ][0]
    assert float(mfu_line.split()[-1]) > 0
    assert 'raytpu_train_roofline_fraction{resource="hbm",run="mfu_run"}' in text


# ----------------------------------------------------- engine tick gauges


def test_engine_batch_occupancy_accounting(rt, nominal_cpu_peaks):
    from ray_tpu.models import get_config, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    config = get_config("llama-tiny")
    params = init_params(config, jax.random.PRNGKey(0))
    engine = LLMEngine(config, params, EngineConfig(max_slots=2))
    try:
        engine.generate([5, 17, 42], max_tokens=6)
        deadline = time.time() + 10
        while engine.metrics.get("tick_seconds", 0.0) == 0.0:
            assert time.time() < deadline, "engine never recorded a tick"
            time.sleep(0.01)
        assert engine.metrics["prefill_tokens"] >= 3
        assert engine.metrics["decode_tokens"] > 0
        # the compiled decode program priced itself via cost_analysis
        assert engine.metrics.get("decode_mfu", 0.0) > 0
        text = registry().prometheus_text()
        assert "raytpu_engine_batch_fill" in text
        assert 'raytpu_engine_token_mix{engine="%s",phase="prefill"}' % (
            engine.metrics_label
        ) in text
    finally:
        engine.shutdown()


def test_paged_engine_batch_occupancy_accounting(rt):
    from ray_tpu.models import get_config, init_params
    from ray_tpu.serve.llm.paged import PagedConfig
    from ray_tpu.serve.llm.paged_engine import PagedEngineConfig, PagedLLMEngine

    config = get_config("llama-tiny")
    params = init_params(config, jax.random.PRNGKey(0))
    engine = PagedLLMEngine(
        config, params,
        PagedEngineConfig(max_slots=2, paged=PagedConfig(
            page_size=8, num_pages=32, max_pages_per_slot=8, chunk_pages=2
        )),
    )
    try:
        engine.generate([5, 17, 42, 7], max_tokens=6)
        assert engine.metrics["prefill_tokens"] >= 4
        assert engine.metrics["decode_tokens"] > 0
        assert engine.metrics["tick_seconds"] > 0
        assert engine.metrics_label.startswith("paged-")
    finally:
        engine.shutdown()


# ------------------------------------------------- cluster RPC capture


def test_cluster_profile_capture_rpc():
    """Coordinated capture over a real subprocess agent: the RPC fans
    out, the remote answers with its host profile (device skipped — the
    agent process never imported jax), artifacts land in the head's
    store."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.config import cfg

    registry().clear()
    c = Cluster(head_node_args={
        "num_cpus": 2,
        "_system_config": {"node_heartbeat_s": 0.2},
    })
    try:
        c.add_node(num_cpus=2, system_config={"node_heartbeat_s": 0.2})
        c.wait_for_nodes(2)
        record = state.profile(duration_s=0.4, device=False)
        assert len(record["nodes"]) == 2
        for node_hex, meta in record["nodes"].items():
            assert meta.get("host") == "ok", meta
            data = state.profile_artifact(
                record["profile_id"], node_hex, "host_profile.txt"
            )
            assert b"host sampling profile" in data
    finally:
        c.shutdown()
        cfg.reset()
        registry().clear()
