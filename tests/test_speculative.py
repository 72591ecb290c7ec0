"""Speculative decoding: draft proposers, the exact accept/resample
step, and the engine's draft-and-verify rounds with KV/page rollback.

The load-bearing invariants:
- output EXACTNESS: at temperature 0 the speculative engine is
  token-for-token identical to the non-speculative engine (whatever the
  proposer does, including always-wrong drafts that reject every round);
  at temperature > 0 the per-step output DISTRIBUTION matches plain
  filtered sampling (standard speculative-sampling argument);
- rollback safety: pages a round speculates past the accepted frontier
  come back to the pool, never touching a prefix-cache-shared page, and
  a shared page the round must write gets COW-copied first.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import get_config, init_params
from ray_tpu.serve.llm.paged import PagedConfig
from ray_tpu.serve.llm.paged_engine import PagedEngineConfig, PagedLLMEngine
from ray_tpu.serve.llm.speculative import (
    NgramProposer,
    ReplayProposer,
    accept_speculative,
    filtered_scores,
)
from ray_tpu.util.metrics import registry


class WrongProposer:
    """Adversarial drill: drafts walk a +1 ring the greedy chain almost
    never follows, so nearly every round rejects at the first draft and
    rolls back its speculated pages."""

    def __init__(self, vocab: int, k: int = None):
        self.vocab = vocab
        self.k = k

    def propose(self, context, k):
        k = min(k, self.k) if self.k is not None else k
        return [(context[-1] + 1 + i) % self.vocab for i in range(k)]


def _spec_engine(model="llama-tiny", seed=0, spec=3, proposer=None, **over):
    config = get_config(model)
    params = init_params(config, jax.random.PRNGKey(seed))
    defaults = dict(
        max_slots=4,
        speculative_tokens=spec,
        speculative_proposer=proposer,
        paged=PagedConfig(
            page_size=8, num_pages=64, max_pages_per_slot=8, chunk_pages=2
        ),
    )
    defaults.update(over)
    return config, params, PagedLLMEngine(
        config, params, PagedEngineConfig(**defaults)
    )


# ------------------------------------------------------------------ proposers


def test_ngram_proposer_prefers_longest_and_newest_match():
    p = NgramProposer(max_ngram=3)
    # suffix [7, 8] occurs twice; the newest occurrence's continuation wins
    ctx = [7, 8, 1, 2, 7, 8, 9, 5, 7, 8]
    assert p.propose(ctx, 2) == [9, 5]
    # novel suffix: no proposal, the round degrades to plain decode
    assert p.propose([1, 2, 3, 4], 3) == []
    assert p.propose(ctx, 0) == []


def test_replay_proposer_stops_on_divergence():
    p = ReplayProposer({(1, 2): [10, 11, 12, 13]})
    assert p.propose([1, 2], 3) == [10, 11, 12]
    assert p.propose([1, 2, 10, 11], 3) == [12, 13]
    # context diverged from the recorded run: no more drafts
    assert p.propose([1, 2, 10, 99], 3) == []
    assert p.propose([5, 6], 3) == []


# ---------------------------------------------------------------- accept step


def test_accept_greedy_exact_prefix_and_bonus():
    """Greedy semantics: accept drafts while they match the argmax chain;
    first mismatch emits the argmax; a full match adds the bonus token."""
    b, kd, v = 3, 4, 11
    logits = np.full((b, kd, v), -10.0, np.float32)
    # lane 0: argmax chain 3, 4, 5, 6 — drafts [3, 4, 9]: accept 2, correct
    for j, t in enumerate([3, 4, 5, 6]):
        logits[0, j, t] = 10.0
    # lane 1: drafts all match -> all accepted plus the bonus from row 3
    for j, t in enumerate([1, 2, 3, 7]):
        logits[1, j, t] = 10.0
    tokens = np.zeros((b, kd), np.int32)
    tokens[0] = [0, 3, 4, 9]
    tokens[1] = [0, 1, 2, 3]
    counts = np.array([4, 4, 0], np.int32)  # lane 2 inactive
    out, n = accept_speculative(
        jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(counts),
        jax.random.PRNGKey(0),
        jnp.zeros((b,), jnp.float32),  # temperature 0 everywhere
        jnp.zeros((b,), jnp.int32), jnp.ones((b,), jnp.float32),
    )
    out, n = np.asarray(out), np.asarray(n)
    assert n.tolist() == [3, 4, 0]
    assert out[0, :3].tolist() == [3, 4, 5]   # 2 accepted + correction
    assert out[1, :4].tolist() == [1, 2, 3, 7]  # 3 accepted + bonus


def test_accept_rejection_sampling_marginal_is_exact():
    """temp > 0 with a point-mass draft: the FIRST emitted token's
    marginal must equal the filtered target distribution exactly
    (accept w.p. p(draft), else the renormalized residual)."""
    v, draft = 8, 2
    logits_row = jnp.asarray(
        np.linspace(-1.0, 1.0, v, dtype=np.float32)[None, :]
    )
    temps = jnp.asarray([0.7], jnp.float32)
    tks = jnp.asarray([5], jnp.int32)
    tps = jnp.asarray([0.9], jnp.float32)
    target = np.asarray(
        jax.nn.softmax(filtered_scores(logits_row, temps, tks, tps))
    )[0]
    logits = jnp.broadcast_to(logits_row[:, None, :], (1, 2, v))
    tokens = jnp.asarray([[0, draft]], jnp.int32)
    counts = jnp.asarray([2], jnp.int32)

    def first_token(key):
        out, _ = accept_speculative(
            logits, tokens, counts, key, temps, tks, tps
        )
        return out[0, 0]

    n = 20000
    toks = np.asarray(
        jax.jit(jax.vmap(first_token))(jax.random.split(jax.random.PRNGKey(7), n))
    )
    emp = np.bincount(toks, minlength=v) / n
    tv = 0.5 * np.abs(emp - target).sum()
    assert tv < 0.02, (tv, emp, target)


# --------------------------------------------------------- engine: exactness


def test_spec_replay_acceptance_reduces_launches():
    """Replaying a recorded greedy run makes every draft accept: the
    acceptance-rate gauge pins near 1 and verify launches per generated
    token drop well below 1 (the whole point of speculation)."""
    config, params, base = _spec_engine(spec=0)
    prompt = [11, 3, 11, 3, 7, 2]
    try:
        recorded = base.generate(prompt, max_tokens=16)
    finally:
        base.shutdown()
    _, _, engine = _spec_engine(
        proposer=ReplayProposer({tuple(prompt): recorded})
    )
    try:
        got = engine.generate(prompt, max_tokens=16)
        assert got == recorded
        m = engine.metrics
        assert m["spec_acceptance_rate"] >= 0.6
        assert m["decode_steps"] / m["decode_tokens"] <= 1 / 1.8
    finally:
        engine.shutdown()


# ----------------------------------------------- engine: rollback vs sharing


def _manual_spec_engine(monkeypatch, proposer, **over):
    monkeypatch.setattr(PagedLLMEngine, "_loop", lambda self: None)
    return _spec_engine(
        proposer=proposer,
        paged=PagedConfig(
            page_size=8, num_pages=64, max_pages_per_slot=8, chunk_pages=2,
            prefix_cache=True,
        ),
        **over,
    )


def _prefill_and_seed(engine):
    """Drive one request to the speculative steady state by hand:
    admit, prefill every chunk, then pump the 'first' fetch that seeds
    the host-side draft context."""
    engine._admit()
    slot = engine.slots[0]
    while slot.prefilling:
        assert engine._prefill_tick()
    deadline = time.time() + 30
    while slot.spec_ctx is None:
        engine._pump_completed(wait=True)
        assert time.time() < deadline, "first token never arrived"
    return slot


def _run_one_round(engine, slot):
    assert engine._dispatch_spec_verify()
    deadline = time.time() + 30
    while slot.spec_inflight:
        engine._pump_completed(wait=True)
        assert time.time() < deadline, "verify round never drained"


def test_spec_rollback_never_touches_prefix_shared_page(monkeypatch):
    """A fully-rejected round that grew a fresh page trims exactly that
    page; the prompt page pinned by the prefix cache (and shared with a
    manufactured second holder) keeps every ref."""
    config = get_config("llama-tiny")
    config, params, engine = _manual_spec_engine(
        monkeypatch, WrongProposer(config.vocab_size)
    )
    try:
        prompt = [int(t) for t in
                  np.random.default_rng(5).integers(1, 200, size=14)]
        engine.submit(prompt, max_tokens=8)
        slot = _prefill_and_seed(engine)
        assert slot.position == 14 and len(slot.pages) == 2
        shared = slot.pages[0]  # full prompt page, cache-pinned
        assert engine.allocator.refcount(shared) == 2
        engine.allocator.share([shared])  # simulate another slot's hold
        free_before = engine.allocator.available
        # round writes positions 14..17 -> grows page 2, rejects, trims it
        _run_one_round(engine, slot)
        assert engine.metrics["spec_rollback_pages"] == 1.0
        assert slot.position == 15 and len(slot.pages) == 2
        assert engine.allocator.available == free_before
        assert engine.allocator.refcount(shared) == 3  # untouched
        assert engine.block_tables[0, 2] == 0
        engine.allocator.free([shared])
    finally:
        engine.shutdown()


def test_spec_round_cow_copies_shared_write_page_then_rolls_back(monkeypatch):
    """The round's write range includes a SHARED partial page: the engine
    COW-copies it before dispatch (shared original keeps its other
    holder), then rollback frees only the round's fresh growth — the
    original is never double-freed."""
    config = get_config("llama-tiny")
    config, params, engine = _manual_spec_engine(
        monkeypatch, WrongProposer(config.vocab_size)
    )
    try:
        prompt = [int(t) for t in
                  np.random.default_rng(6).integers(1, 200, size=14)]
        engine.submit(prompt, max_tokens=8)
        slot = _prefill_and_seed(engine)
        victim = slot.pages[1]  # partial page the round writes first
        assert engine.allocator.refcount(victim) == 1
        engine.allocator.share([victim])
        _run_one_round(engine, slot)
        assert engine.metrics["prefix_cache_cow"] == 1.0
        assert slot.pages[1] != victim
        assert engine.allocator.refcount(victim) == 1  # slot's ref dropped
        assert engine.allocator.refcount(slot.pages[1]) == 1
        assert engine.metrics["spec_rollback_pages"] == 1.0
        assert engine.block_tables[0, 1] == slot.pages[1]
        engine.allocator.free([victim])  # last holder: recycles cleanly
        assert engine.allocator.refcount(victim) == 0
    finally:
        engine.shutdown()


# ------------------------------------------------------------------- gauges


@pytest.fixture
def clean_registry():
    registry().clear()
    yield
    registry().clear()


def test_spec_metrics_and_gauges_exported(clean_registry):
    config, params, engine = _spec_engine()
    try:
        prompt = [5, 17, 42, 7, 5, 17, 42, 7]
        engine.generate(prompt, max_tokens=12)
        stats = engine.stats()
        for key in ("spec_proposed", "spec_accepted",
                    "spec_acceptance_rate", "spec_rollback_pages"):
            assert key in stats, key
        assert stats["spec_proposed"] > 0
        assert 0.0 <= stats["spec_acceptance_rate"] <= 1.0
        text = registry().prometheus_text()
        for gauge in ("raytpu_engine_spec_proposed",
                      "raytpu_engine_spec_accepted",
                      "raytpu_engine_spec_acceptance_rate",
                      "raytpu_engine_spec_rollback_pages"):
            assert '%s{engine="%s"}' % (gauge, engine.metrics_label) in text
    finally:
        engine.shutdown()
