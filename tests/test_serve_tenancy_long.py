"""The two lane-preemption drills of tests/test_serve_tenancy.py that decode
whole requests against the greedy reference: 25-50 s each on a loaded machine,
so they live in a file of few tests (the rule in tests/conftest.py). Engines
and fixtures are the origin's."""

import time

from ray_tpu.serve.llm.paged import PagedConfig
from tests.test_paged_engine import _greedy_reference
from tests.test_serve_tenancy import _clean_tenancy, _tiny_engine  # noqa: F401 - the origin's autouse fixture


def test_lane_preemption_token_exact_resume_and_shared_pages_survive():
    """The acceptance drill: a high-priority admission preempts a
    low-priority decode lane. The victim is trimmed to its emitted
    frontier (never mid-flight), parked, re-admitted, and its stream
    resumes token-exact; pages it shared with the prefix cache are only
    un-refcounted, never corrupted — a later cache hit still reproduces
    the reference continuation."""
    # small decode blocks keep the victim mid-dispatch (preemptible) for
    # most of its decode, like a real long generation would be
    config, params, engine = _tiny_engine(max_slots=1,
                                          decode_block_steps=2)
    try:
        shared = [11, 22, 33, 44, 55, 66, 77, 88,
                  12, 23, 34, 45, 56, 67, 78, 89]  # 2 full pages
        # warm the prefix cache so the victim's first pages are shared
        warm = engine.submit(list(shared), max_tokens=4, tenant="warm")
        warm_tokens = warm.result(timeout=60)
        assert warm_tokens == _greedy_reference(config, params, shared, 4)

        victim_prompt = list(shared) + [7, 14, 21, 28, 35, 42, 49, 56]
        victim = engine.submit(victim_prompt, max_tokens=24,
                               tenant="bulk", priority=0)
        # wait until the victim is actually decoding before the preemptor
        victim_iter = iter(victim)
        first = next(victim_iter)

        high_prompt = [101, 102, 103, 104, 105, 106, 107, 108]
        high = engine.submit(high_prompt, max_tokens=6,
                             tenant="paid", priority=1)
        high_tokens = high.result(timeout=60)
        assert high_tokens == _greedy_reference(
            config, params, high_prompt, 6)

        rest = list(victim_iter)
        victim_tokens = [first] + rest
        assert victim_tokens == _greedy_reference(
            config, params, victim_prompt, 24)

        assert engine.metrics["lane_preemptions"] >= 1
        assert engine.metrics["lane_resumes"] >= 1
        assert engine.metrics["preempted_pages"] > 0

        # the shared prefix pages survived the victim's page release:
        # a fresh request over the warm prompt still matches reference
        again = engine.submit(list(shared), max_tokens=4, tenant="warm2")
        assert again.result(timeout=60) == warm_tokens
    finally:
        engine.shutdown()


def test_lane_preemption_under_page_pool_pressure():
    """The page-pressure trigger (`_reclaim_pages`), distinct from the
    all-slots-wedged trigger: a free slot exists, but the pool cannot
    cover the high-priority admission because a low-priority lane holds
    nearly every page. The victim is marked, drains, parks, and its
    pages fund the admission; both streams finish token-exact."""
    # 7 allocatable pages (page 0 reserved). The victim's prompt spans 5
    # and its decode grows the lane to all 7; inflight=1 paces dispatch
    # so the lane is still mid-decode when the preemptor arrives.
    config, params, engine = _tiny_engine(
        max_slots=2,
        decode_block_steps=2,
        max_inflight_blocks=1,
        paged=PagedConfig(
            page_size=8, num_pages=8, max_pages_per_slot=8, chunk_pages=2
        ),
    )
    try:
        victim_prompt = [(i * 7 + 3) % 97 for i in range(40)]  # 5 pages
        victim = engine.submit(victim_prompt, max_tokens=16,
                               tenant="bulk", priority=0)
        it = iter(victim)
        first = next(it)  # lane decoding: >=6 pages held, <2 free

        high_prompt = [201, 202, 203, 204, 205, 206, 207, 208]
        high = engine.submit(high_prompt, max_tokens=4,
                             tenant="paid", priority=1)
        high_tokens = high.result(timeout=60)
        assert high_tokens == _greedy_reference(
            config, params, high_prompt, 4)

        victim_tokens = [first] + list(it)
        assert victim_tokens == _greedy_reference(
            config, params, victim_prompt, 16)

        # preemption came from page pressure, not a slot wedge: a slot
        # was free the whole time, and the admission page-stalled first
        assert engine.metrics["lane_preemptions"] >= 1
        assert engine.metrics["lane_resumes"] >= 1
        assert engine.metrics["page_stalls"] >= 1

        deadline = time.time() + 10
        while time.time() < deadline:
            stats = engine.stats()
            if stats["pages_free"] + stats["prefix_cache_pages"] == 7:
                break
            time.sleep(0.05)
        stats = engine.stats()
        assert stats["pages_free"] + stats["prefix_cache_pages"] == 7, stats
    finally:
        engine.shutdown()
