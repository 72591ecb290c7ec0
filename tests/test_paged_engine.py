"""Paged KV continuous batching (reference: vLLM paged attention +
chunked prefill behind vllm_engine.py:254; TPU recipe per PAPERS.md)."""

import jax
import numpy as np
import pytest

from ray_tpu.models import forward, get_config, init_params
from ray_tpu.serve.llm.paged import PagedConfig, PageAllocator
from ray_tpu.serve.llm.paged_engine import PagedEngineConfig, PagedLLMEngine


# one compile a length, shared by every test of a worker that asks for it,
# in place of one an operation
_forward = jax.jit(forward, static_argnums=2)


def _greedy_reference(config, params, prompt, n):
    """Greedy decode via repeated full forward: ground truth, here and in
    the other engine tests (serve, speculative, prefix cache, tenancy, reqlog)."""
    tokens = list(prompt)
    for _ in range(n):
        logits = _forward(params, np.asarray([tokens], dtype=np.int32), config)
        tokens.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return tokens[len(prompt):]


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


# ------------------------------------------------------------------ allocator


def test_allocator_exhaustion_and_reuse():
    a = PageAllocator(num_pages=5)  # 4 allocatable (page 0 reserved)
    p = a.alloc(4)
    assert sorted(p) == [1, 2, 3, 4]
    assert a.alloc(1) is None
    a.free(p[:2])
    assert a.available == 2
    q = a.alloc(2)
    assert set(q) <= {1, 2, 3, 4}


# -------------------------------------------------------------- correctness


def test_paged_greedy_matches_full_forward():
    config, params, engine = _tiny_engine()
    try:
        prompt = [5, 17, 42, 7]
        got = engine.generate(prompt, max_tokens=8)
        expected = _greedy_reference(config, params, prompt, 8)
        assert got == expected, (got, expected)
    finally:
        engine.shutdown()


def test_paged_multi_chunk_prompt_matches():
    """A prompt spanning several prefill chunks (chunk = 16 tokens here)
    must produce the same continuation as the unpaged full forward."""
    config, params, engine = _tiny_engine()
    try:
        prompt = list(np.random.default_rng(3).integers(1, 200, size=41))
        got = engine.generate([int(t) for t in prompt], max_tokens=6)
        expected = _greedy_reference(config, params, prompt, 6)
        assert got == expected, (got, expected)
    finally:
        engine.shutdown()


def test_long_prompt_does_not_block_running_stream():
    """Chunked prefill: while a long prompt ingests, an already-running
    stream must keep producing tokens (no head-of-line blocking)."""
    config, params, engine = _tiny_engine()
    try:
        fast = engine.submit([3, 1, 4], max_tokens=40)
        it = iter(fast)
        next(it)  # running
        # long prompt: 56 tokens = 4 chunks of prefill work
        long_prompt = [int(t) for t in
                       np.random.default_rng(0).integers(1, 200, size=56)]
        slow = engine.submit(long_prompt, max_tokens=4)
        fast_rest = [t for t in it]
        slow_out = slow.result(timeout=60)
        assert len(fast_rest) == 39
        assert slow_out == _greedy_reference(config, params, long_prompt, 4)
        # decode rounds ran interleaved with the 4+ prefill chunks
        assert engine.metrics["prefill_chunks"] >= 4
    finally:
        engine.shutdown()


def test_pages_scale_with_tokens_not_max_seq():
    """The paged pool must admit more concurrent sequences than a dense
    cache of the same byte budget: pages_in_use tracks actual tokens."""
    config, params, engine = _tiny_engine()
    try:
        s = engine.submit([1, 2, 3], max_tokens=4)
        s.result(timeout=60)
        # a 3+4 token sequence on page_size=8 peaks at exactly 1 page
        # (+chunk rounding), never the dense max_seq/page_size
        assert engine.metrics["pages_in_use"] <= 2
    finally:
        engine.shutdown()


def test_submit_validation():
    config, params, engine = _tiny_engine()
    try:
        with pytest.raises(ValueError, match="capacity"):
            engine.submit(list(range(60)), max_tokens=10)  # > 8 pages * 8
        with pytest.raises(ValueError, match="empty"):
            engine.submit([], max_tokens=1)
    finally:
        engine.shutdown()


def test_config_validation():
    config = get_config("llama-tiny")
    params = init_params(config, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="multiple"):
        PagedLLMEngine(config, params, PagedEngineConfig(
            paged=PagedConfig(max_pages_per_slot=5, chunk_pages=2)))


def test_llm_server_paged_path():
    from ray_tpu.serve.llm.server import LLMServer

    server = LLMServer(
        "llama-tiny",
        engine_config=PagedEngineConfig(
            max_slots=2,
            paged=PagedConfig(
                page_size=8, num_pages=32, max_pages_per_slot=8, chunk_pages=2
            ),
        ),
    )
    try:
        out = server.generate({"prompt_tokens": [5, 6, 7], "max_tokens": 4})
        assert len(out["tokens"]) == 4
        assert out["usage"]["total_tokens"] == 7
        assert isinstance(server.engine, PagedLLMEngine)
        server.check_health()
    finally:
        server.engine.shutdown()


def test_engine_death_fails_streams_not_hangs():
    """A crash in the engine loop must surface on every pending stream
    instead of hanging consumers forever."""
    config, params, engine = _tiny_engine()
    try:
        def boom(*a, **k):
            raise RuntimeError("injected engine crash")

        engine._decode_block_plain = boom
        engine._decode_block_filtered = boom
        engine._batched_chunk = boom
        s = engine.submit([1, 2, 3], max_tokens=4)
        with pytest.raises(RuntimeError, match="injected engine crash"):
            s.result(timeout=30)
    finally:
        engine.shutdown()


def test_engine_death_in_decode_loop_fails_streams():
    """A crash AFTER prefill (in the decode block dispatch) must also
    surface on pending streams — the decode-path death boundary."""
    config, params, engine = _tiny_engine()
    try:
        def boom(*a, **k):
            raise RuntimeError("injected decode crash")

        engine._decode_block_plain = boom
        engine._decode_block_filtered = boom
        s = engine.submit([1, 2, 3], max_tokens=4)
        with pytest.raises(RuntimeError, match="injected decode crash"):
            s.result(timeout=30)
    finally:
        engine.shutdown()


def test_stalled_lane_token_survives_other_lanes_dispatch(monkeypatch):
    """Regression: a lane page-stalled mid-decode keeps its pending input
    token while other lanes keep dispatching blocks. Before the per-lane
    merge fix, _dispatch_decode_block replaced the whole on-device token
    vector with the block's final samples — garbage for excluded lanes
    (they attend over the scratch page) — so an unstalling lane resumed
    from a corrupt token and silently produced wrong output.

    Driven without the engine loop so the stall-vs-dispatch interleaving
    is deterministic: A's next block fits its pages, B needs a page the
    starved allocator cannot grant."""
    monkeypatch.setattr(PagedLLMEngine, "_loop", lambda self: None)
    config, params, engine = _tiny_engine(
        max_slots=2,
        decode_block_steps=2,
        paged=PagedConfig(
            page_size=4, num_pages=9, max_pages_per_slot=8, chunk_pages=2
        ),
    )
    try:
        engine.submit([5, 17, 42, 7, 3, 11], max_tokens=2)      # A: slot 0
        engine.submit([3, 11, 2, 29, 8, 1, 19, 4], max_tokens=4)  # B: slot 1
        engine._admit()
        assert not engine.slots[0].free and not engine.slots[1].free
        while any(s.prefilling for s in engine.slots):
            assert engine._prefill_tick()
        # both lanes now hold their first sampled token on device
        token_b_before = int(engine._tokens_dev[1])
        # starve the pool so B's mid-decode growth stalls
        n_free = engine.allocator.available
        if n_free:
            assert engine.allocator.alloc(n_free) is not None
        assert engine._dispatch_decode_block()
        assert engine.slots[1].stalled, "B should be page-stalled"
        assert not engine.slots[0].stalled, "A should have dispatched"
        assert engine.slots[0].position == 7
        assert int(engine._tokens_dev[1]) == token_b_before, (
            "stalled lane's pending token was clobbered by the dispatch"
        )
    finally:
        engine.shutdown()


def test_sampling_params_topk_topp_and_stop():
    config, params, engine = _tiny_engine()
    try:
        prompt = [5, 17, 42, 7]
        greedy = _greedy_reference(config, params, prompt, 6)
        # top_k=1 forces greedy even at high temperature
        got = engine.submit(
            prompt, max_tokens=6, temperature=5.0, top_k=1
        ).result(timeout=60)
        assert got == greedy, (got, greedy)
        # a vanishingly small nucleus keeps only the argmax token
        got = engine.submit(
            prompt, max_tokens=6, temperature=5.0, top_p=1e-6
        ).result(timeout=60)
        assert got == greedy, (got, greedy)
        # per-request stop token ends the stream early
        stop = greedy[2]
        got = engine.submit(
            prompt, max_tokens=6, stop_token_ids=[stop]
        ).result(timeout=60)
        assert got == greedy[:3], (got, greedy)
        with pytest.raises(ValueError, match="top_p"):
            engine.submit(prompt, max_tokens=2, top_p=0.0)
    finally:
        engine.shutdown()


def test_plain_decode_path_selected_for_greedy_batches():
    """Perf guard: all-greedy batches must take the sort-free plain block;
    a top-k/top-p lane switches the dispatch to the filtered block."""
    config, params, engine = _tiny_engine()
    try:
        counts = {"plain": 0, "filtered": 0}
        orig_plain = engine._decode_block_plain
        orig_filtered = engine._decode_block_filtered

        def plain(*a):
            counts["plain"] += 1
            return orig_plain(*a)

        def filtered(*a):
            counts["filtered"] += 1
            return orig_filtered(*a)

        engine._decode_block_plain = plain
        engine._decode_block_filtered = filtered

        engine.generate([1, 2, 3], max_tokens=6)  # greedy
        assert counts["plain"] >= 1 and counts["filtered"] == 0

        engine.submit([1, 2, 3], max_tokens=6, top_k=2,
                      temperature=1.0).result(timeout=60)
        assert counts["filtered"] >= 1
    finally:
        engine.shutdown()


# ------------------------------------------------------------- tensor parallel


def _tp_mesh(n):
    from ray_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(tp=n), devices=jax.devices()[:n])


def test_tp_engine_matches_single_device_greedy():
    """The TP-sharded engine (params Megatron-split, KV pool sharded on
    kv heads over the 8-device mesh) must emit EXACTLY the single-device
    greedy tokens — sharding is an execution detail, not a semantics
    change."""
    from ray_tpu.models.transformer import TransformerConfig

    config = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=8, n_kv_heads=8,
        d_ff=128, max_seq=512, pos_emb="rope", norm="rmsnorm", act="swiglu",
        use_bias=False, dtype=jax.numpy.float32,
    )
    params = init_params(config, jax.random.PRNGKey(0))
    ecfg = PagedEngineConfig(
        max_slots=4, decode_block_steps=4,
        paged=PagedConfig(page_size=16, num_pages=64, max_pages_per_slot=8,
                          chunk_pages=2),
    )
    prompt = list(range(1, 20))
    ref = PagedLLMEngine(config, params, ecfg)
    try:
        want = ref.generate(prompt, max_tokens=10, temperature=0.0)
    finally:
        ref.shutdown()

    tp = PagedLLMEngine(config, params, ecfg, mesh=_tp_mesh(8))
    try:
        got = tp.generate(prompt, max_tokens=10, temperature=0.0)
        # continuous batching still works under the mesh
        streams = [tp.submit(list(range(2, 12)), max_tokens=6) for _ in range(6)]
        outs = [s.result(timeout=120) for s in streams]
    finally:
        tp.shutdown()
    assert got == want, (got, want)
    assert all(len(o) == 6 for o in outs)
    assert all(o == outs[0] for o in outs)


def test_tp_engine_rejects_indivisible_heads():
    from ray_tpu.models.transformer import TransformerConfig

    config = TransformerConfig(
        vocab_size=64, d_model=48, n_layers=1, n_heads=6, n_kv_heads=3,
        d_ff=96, max_seq=128, pos_emb="rope", norm="rmsnorm", act="swiglu",
        use_bias=False, dtype=jax.numpy.float32,
    )
    params = init_params(config, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="must divide"):
        PagedLLMEngine(
            config, params,
            PagedEngineConfig(max_slots=2, paged=PagedConfig(
                page_size=8, num_pages=32, max_pages_per_slot=4, chunk_pages=2
            )),
            mesh=_tp_mesh(4),
        )
