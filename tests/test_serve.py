"""Serve layer: deployments, routing, autoscaling, recovery, LLM engine."""

import json
import threading
import time
import urllib.request

import jax
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import get_config, init_params
from ray_tpu.serve.llm import EngineConfig, LLMEngine, LLMServer, build_llm_app
from tests.test_paged_engine import _greedy_reference


@pytest.fixture(autouse=True)
def rt():
    runtime = ray_tpu.init(num_cpus=8, detect_accelerators=False)
    yield runtime
    serve.shutdown()
    ray_tpu.shutdown()


@serve.deployment
class Echo:
    def __init__(self, prefix: str = ""):
        self.prefix = prefix

    def __call__(self, payload):
        return f"{self.prefix}{payload}"

    def shout(self, payload):
        return f"{self.prefix}{payload}".upper()


def test_deploy_and_call():
    handle = serve.run(Echo.bind("pre-"))
    assert ray_tpu.get(handle.remote("x")) == "pre-x"
    assert ray_tpu.get(handle.shout.remote("x")) == "PRE-X"


def test_multiple_replicas_round():
    handle = serve.run(Echo.options(num_replicas=3, name="echo3").bind("r"))
    out = ray_tpu.get([handle.remote(i) for i in range(12)])
    assert out == [f"r{i}" for i in range(12)]
    assert serve.status()["echo3"]["live_replicas"] == 3


def test_get_handle_and_delete():
    serve.run(Echo.bind("a-"), name="named")
    handle = serve.get_handle("named")
    assert ray_tpu.get(handle.remote("z")) == "a-z"
    serve.delete("named")
    with pytest.raises(KeyError):
        serve.get_handle("named")


def test_replica_recovery_after_kill():
    handle = serve.run(Echo.options(name="frag").bind("ok-"))
    controller = serve._get_controller() if hasattr(serve, "_get_controller") else None
    from ray_tpu.serve import api as serve_api

    state = serve_api._controller._states["frag"]
    ray_tpu.kill(state.replicas[0])
    # reconcile loop should replace the dead replica
    deadline = time.time() + 10
    while time.time() < deadline:
        try:
            if ray_tpu.get(serve.get_handle("frag").remote("x"), timeout=5) == "ok-x":
                break
        except Exception:
            time.sleep(0.2)
    else:
        pytest.fail("replica not recovered")


def test_http_proxy():
    serve.run(Echo.bind("h-"), name="web")
    port = serve.start_http()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/web",
        data=json.dumps("ping").encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        body = json.loads(resp.read())
    assert body["result"] == "h-ping"
    # unknown deployment -> 404
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/nope", data=b"{}",
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 404


def test_autoscaling_up():
    @serve.deployment
    class Slow:
        def __call__(self, x):
            time.sleep(0.4)
            return x

    auto = serve.AutoscalingConfig(
        min_replicas=1, max_replicas=3, target_ongoing_requests=1.0, interval_s=0.1
    )
    handle = serve.run(
        Slow.options(name="slow", autoscaling=auto, num_replicas=1).bind()
    )
    refs = [handle.remote(i) for i in range(8)]
    deadline = time.time() + 15
    peaked = 1
    while time.time() < deadline:
        peaked = max(peaked, serve.status()["slow"]["live_replicas"])
        if peaked >= 2:
            break
        time.sleep(0.1)
    ray_tpu.get(refs, timeout=60)
    assert peaked >= 2, f"never scaled up: {serve.status()}"


# ------------------------------------------------------------------ LLM engine


def test_engine_more_requests_than_slots():
    config = get_config("gpt2-tiny")
    params = init_params(config, jax.random.PRNGKey(1))
    engine = LLMEngine(config, params, EngineConfig(max_slots=2))
    try:
        streams = [engine.submit([i + 1, i + 2], max_tokens=4) for i in range(6)]
        results = [s.result(timeout=120) for s in streams]
        for i, got in enumerate(results):
            expected = _greedy_reference(config, params, [i + 1, i + 2], 4)
            assert got == expected
    finally:
        engine.shutdown()


def test_engine_ttft_and_metrics():
    config = get_config("gpt2-tiny")
    params = init_params(config, jax.random.PRNGKey(1))
    engine = LLMEngine(config, params, EngineConfig(max_slots=2))
    try:
        s = engine.submit([1, 2, 3], max_tokens=5)
        s.result(timeout=60)
        assert s.ttft_s is not None and s.ttft_s > 0
        assert engine.metrics["generated_tokens"] == 5
    finally:
        engine.shutdown()


def test_llm_server_deployment_end_to_end():
    app = build_llm_app("gpt2-tiny", name="llm", max_slots=2)
    handle = serve.run(app)
    out = ray_tpu.get(
        handle.generate.remote({"prompt_tokens": [1, 2, 3], "max_tokens": 4}),
        timeout=120,
    )
    assert len(out["tokens"]) == 4
    assert out["usage"]["total_tokens"] == 7
    metrics = ray_tpu.get(handle.metrics.remote({}), timeout=30)
    assert metrics["generated_tokens"] >= 4
