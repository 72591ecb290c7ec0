"""The paged engine's and the speculative engine's batch drills
(tests/test_paged_engine.py, tests/test_speculative.py): several requests a
test, each checked against the greedy reference, 20-60 s each on a loaded
machine, so they live in a file of few tests (the rule in tests/conftest.py).
Engines and references are the origins'."""

import time

import numpy as np

from ray_tpu.models import get_config
from ray_tpu.serve.llm.paged import PagedConfig
from tests.test_paged_engine import _greedy_reference, _tiny_engine
from tests.test_speculative import WrongProposer, _spec_engine


def test_paged_continuous_batching_staggered():
    config, params, engine = _tiny_engine(model="gpt2-tiny", seed=1)
    try:
        prompts = [[1, 2, 3], [9, 8], [30, 31, 32, 33], [4], [100, 101]]
        streams = []
        for p in prompts:
            streams.append((p, engine.submit(p, max_tokens=6)))
            time.sleep(0.02)
        for p, s in streams:
            got = s.result(timeout=60)
            expected = _greedy_reference(engine.model_config, params, p, 6)
            assert got == expected, (p, got, expected)
    finally:
        engine.shutdown()


def test_page_pool_backpressure_all_requests_complete():
    """More concurrent demand than pages: requests queue on the allocator
    and all finish correctly once pages recycle."""
    config, params, engine = _tiny_engine(
        max_slots=4,
        paged=PagedConfig(
            page_size=8, num_pages=9, max_pages_per_slot=4, chunk_pages=1
        ),
    )
    try:
        rng = np.random.default_rng(7)
        jobs = []
        for _ in range(6):
            p = [int(t) for t in rng.integers(1, 200, size=5)]
            jobs.append((p, engine.submit(p, max_tokens=10)))
        for p, s in jobs:
            got = s.result(timeout=120)
            expected = _greedy_reference(config, params, p, 10)
            assert got == expected, (p, got, expected)
        assert engine.allocator.available == 8  # all pages recycled
    finally:
        engine.shutdown()


def test_spec_ngram_greedy_parity_and_acceptance():
    """A repetitive prompt lets the n-gram proposer draft real spans:
    output stays exactly greedy and some drafts are accepted."""
    config, params, engine = _spec_engine()
    try:
        prompt = [5, 17, 42, 7, 5, 17, 42, 7, 5, 17, 42, 7]
        got = engine.generate(prompt, max_tokens=16)
        assert got == _greedy_reference(config, params, prompt, 16)
        m = engine.metrics
        assert m["spec_proposed"] > 0
        # one verify launch per round emits >= 1 token: launches/token <= 1
        assert m["decode_steps"] <= m["decode_tokens"]
    finally:
        engine.shutdown()


def test_spec_staggered_batch_parity():
    config, params, engine = _spec_engine(model="gpt2-tiny", seed=1)
    try:
        prompts = [[1, 2, 3, 1, 2, 3], [9, 8, 9, 8], [30, 31, 30, 31], [4, 4, 4]]
        streams = []
        for p in prompts:
            streams.append((p, engine.submit(p, max_tokens=6)))
            time.sleep(0.02)
        for p, s in streams:
            got = s.result(timeout=60)
            assert got == _greedy_reference(engine.model_config, params, p, 6)
    finally:
        engine.shutdown()


def test_spec_all_reject_parity_with_page_boundary_rollbacks():
    """Always-wrong drafts: every round rejects at draft 1, speculated
    pages roll back (across page boundaries), and the output is STILL
    exactly greedy. Afterwards every page returns to the pool."""
    config = get_config("llama-tiny")
    config2, params, engine = _spec_engine(
        proposer=WrongProposer(config.vocab_size)
    )
    try:
        prompt = [3, 1, 4, 1, 5]
        # 24 tokens from position 5: crosses pages at 8, 16, 24 (ps=8)
        got = engine.generate(prompt, max_tokens=24)
        assert got == _greedy_reference(config2, params, prompt, 24)
        m = engine.metrics
        assert m["spec_proposed"] > 0
        assert m["spec_acceptance_rate"] < 0.25
        assert m["spec_rollback_pages"] > 0
        deadline = time.time() + 10
        total = engine.paged.num_pages - 1  # page 0 reserved
        while engine.allocator.available < total:
            assert time.time() < deadline, "speculated pages leaked"
            time.sleep(0.01)
    finally:
        engine.shutdown()
