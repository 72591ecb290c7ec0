"""The two whole-request timelines of tests/test_reqlog.py (speculative rollback;
the flagship waterfall): 30-50 s each on a loaded machine, so they live in a
file of few tests (the rule in tests/conftest.py). Engines and fixtures are
the origin's."""

from ray_tpu.models import get_config
from ray_tpu.serve import reqlog
from tests.test_paged_engine import _greedy_reference
from tests.test_reqlog import _clean_reqlog, _phases, _tiny_engine  # noqa: F401 - the origin's autouse fixture


def test_spec_rollback_timeline():
    """Speculative rounds with an adversarial proposer record
    engine.spec_round marks whose rollback trail is visible (accepted <
    proposed, rolled-back pages accounted)."""
    from tests.test_speculative import WrongProposer

    vocab = get_config("llama-tiny").vocab_size
    config, params, engine = _tiny_engine(
        speculative_tokens=3, speculative_proposer=WrongProposer(vocab)
    )
    try:
        prompt = [5, 17, 42, 7, 9, 2]
        stream = engine.submit(prompt, max_tokens=10, request_id="req-spec")
        got = stream.result(timeout=120)
        assert got == _greedy_reference(config, params, prompt, 10)
        tl = reqlog.log().timeline("req-spec")
        rounds = [m for m in tl if m["phase"] == "engine.spec_round"]
        assert rounds, _phases(tl)
        assert all(m["attrs"]["accepted"] <= m["attrs"]["proposed"]
                   for m in rounds)
        # the wrong proposer rejects nearly everything: rollback visible
        assert any(m["attrs"]["accepted"] < m["attrs"]["proposed"]
                   for m in rounds)
    finally:
        engine.shutdown()


def test_flagship_waterfall_prefix_spec_preempt_resume():
    """THE acceptance drill: one request's waterfall shows a prefix-hit
    admission, speculative rounds, a lane preemption AND the resume —
    causally ordered — and the TTFT buckets sum within 5%."""
    from tests.test_speculative import WrongProposer

    config, params, engine = _tiny_engine(
        max_slots=1, decode_block_steps=2,
        speculative_tokens=3,
        speculative_proposer=WrongProposer(
            get_config("llama-tiny").vocab_size),
    )
    try:
        shared = [11, 22, 33, 44, 55, 66, 77, 88,
                  12, 23, 34, 45, 56, 67, 78, 89]
        warm = engine.submit(list(shared), max_tokens=2, tenant="warm",
                             request_id="req-fw-warm")
        warm.result(timeout=120)

        victim_prompt = list(shared) + [7, 14, 21, 28, 35, 42, 49, 56]
        victim = engine.submit(victim_prompt, max_tokens=24, tenant="bulk",
                               priority=0, request_id="req-fw-victim")
        victim_iter = iter(victim)
        first = next(victim_iter)

        high = engine.submit([101, 102, 103, 104, 105, 106, 107, 108],
                             max_tokens=4, tenant="paid", priority=1,
                             request_id="req-fw-high")
        high.result(timeout=120)
        victim_tokens = [first] + list(victim_iter)
        assert victim_tokens == _greedy_reference(
            config, params, victim_prompt, 24)
        assert engine.metrics["lane_preemptions"] >= 1

        tl = reqlog.log().timeline("req-fw-victim")
        phases = _phases(tl)
        for needed in ("engine.submitted", "engine.admitted",
                       "engine.first_token", "engine.spec_round",
                       "engine.preempted", "engine.resumed",
                       "engine.finished"):
            assert needed in phases, phases
        # causal order along the mono clock
        def at(phase):
            return next(m["mono"] for m in tl if m["phase"] == phase)
        assert (at("engine.submitted") <= at("engine.admitted")
                <= at("engine.first_token"))
        assert at("engine.preempted") <= at("engine.resumed")
        assert at("engine.resumed") <= at("engine.finished")
        admitted = next(m for m in tl if m["phase"] == "engine.admitted")
        assert admitted["attrs"]["hit_pages"] >= 1  # prefix hit
        # park charged into the preempt bucket at resume
        resumed = next(m for m in tl if m["phase"] == "engine.resumed")
        assert resumed["attrs"]["wait_s"] >= 0

        # TTFT buckets sum within the 5% acceptance band (exact by
        # construction; the band covers float noise)
        d = reqlog.decompose(tl)
        total = (d["queue_wait_s"] + d["preempt_wait_s"]
                 + d["prefill_compute_s"])
        assert abs(total - d["ttft_s"]) <= max(0.05 * d["ttft_s"], 1e-6)

        text = reqlog.render_waterfall(tl)
        for needed in ("engine.spec_round", "engine.preempted",
                       "engine.resumed", "TTFT",
                       "terminal: engine.finished"):
            assert needed in text, text
    finally:
        engine.shutdown()
