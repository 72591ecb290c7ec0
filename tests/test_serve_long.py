"""The dense engine's two parity drills of tests/test_serve.py: 30-40 s each on a
loaded machine, so they live in a file of few tests (the rule in
tests/conftest.py). The runtime fixture is the origin's."""

import time

import jax

from ray_tpu.models import get_config, init_params
from ray_tpu.serve.llm import EngineConfig, LLMEngine
from tests.test_paged_engine import _greedy_reference
from tests.test_serve import rt  # noqa: F401 - the origin's autouse runtime


def test_engine_greedy_matches_full_forward():
    config = get_config("llama-tiny")
    params = init_params(config, jax.random.PRNGKey(0))
    engine = LLMEngine(config, params, EngineConfig(max_slots=4))
    try:
        prompt = [5, 17, 42, 7]
        got = engine.generate(prompt, max_tokens=8)
        expected = _greedy_reference(config, params, prompt, 8)
        assert got == expected, (got, expected)
    finally:
        engine.shutdown()


def test_engine_continuous_batching_staggered():
    """Requests arriving mid-flight batch with ongoing ones and all finish
    correctly (the continuous-batching property)."""
    config = get_config("gpt2-tiny")
    params = init_params(config, jax.random.PRNGKey(1))
    engine = LLMEngine(config, params, EngineConfig(max_slots=4))
    try:
        prompts = [[1, 2, 3], [9, 8], [30, 31, 32, 33], [4], [100, 101]]
        streams = []
        for i, p in enumerate(prompts):
            streams.append((p, engine.submit(p, max_tokens=6)))
            time.sleep(0.02)  # staggered arrivals
        for p, s in streams:
            got = s.result(timeout=60)
            expected = _greedy_reference(config, params, p, 6)
            assert got == expected, (p, got, expected)
        assert engine.metrics["prefills"] == 5
    finally:
        engine.shutdown()
