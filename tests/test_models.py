"""Model tests: GPT-2/Llama forward, decode-cache equivalence, sharded run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    TransformerConfig,
    count_params,
    decode_step,
    forward,
    get_config,
    init_cache,
    init_params,
    logical_axes,
    prefill,
)
from ray_tpu.parallel import MeshSpec, build_mesh, default_rules, shard_tree


@pytest.fixture(params=["gpt2-tiny", "llama-tiny"])
def model(request):
    config = get_config(request.param)
    params = init_params(config, jax.random.PRNGKey(0))
    return config, params


def test_forward_shapes(model):
    config, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, config.vocab_size)
    logits = forward(params, tokens, config)
    assert logits.shape == (2, 16, config.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_param_tree_matches_axes_tree(model):
    config, params = model
    axes = logical_axes(config)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_a = jax.tree_util.tree_flatten_with_path(axes, is_leaf=lambda x: isinstance(x, tuple))[0]
    paths_p = {tuple(str(k) for k in path) for path, _ in flat_p}
    paths_a = {tuple(str(k) for k in path) for path, _ in flat_a}
    assert paths_p == paths_a
    # every axes tuple has same rank as the parameter
    amap = {tuple(str(k) for k in path): a for path, a in flat_a}
    for path, leaf in flat_p:
        assert len(amap[tuple(str(k) for k in path)]) == leaf.ndim, path


def test_causality(model):
    """Changing a future token must not affect past logits."""
    config, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, config.vocab_size)
    logits1 = forward(params, tokens, config)
    tokens2 = tokens.at[0, 10].set((tokens[0, 10] + 1) % config.vocab_size)
    logits2 = forward(params, tokens2, config)
    np.testing.assert_allclose(
        np.asarray(logits1[0, :10]), np.asarray(logits2[0, :10]), atol=1e-5
    )
    assert not np.allclose(np.asarray(logits1[0, 10:]), np.asarray(logits2[0, 10:]))


def test_decode_matches_forward(model):
    """Step-by-step decode with cache == full forward, per position."""
    config, params = model
    b, s = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, config.vocab_size)
    full = forward(params, tokens, config)

    cache = init_cache(config, b, max_seq=config.max_seq)
    step = jax.jit(lambda p, c, t, pos: decode_step(p, c, t, pos, config))
    for t in range(s):
        positions = jnp.full((b,), t, dtype=jnp.int32)
        logits, cache = step(params, cache, tokens[:, t], positions)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, t]), atol=2e-4, rtol=2e-4
        )


def test_prefill_then_decode(model):
    """prefill(prompt) + decode_step == forward over the whole sequence."""
    config, params = model
    b, prompt_len = 2, 8
    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (b, prompt_len + 1), 0, config.vocab_size
    )
    full = forward(params, tokens, config)

    cache = init_cache(config, b)
    lengths = jnp.full((b,), prompt_len, dtype=jnp.int32)
    last_logits, cache = prefill(params, tokens[:, :prompt_len], lengths, cache, config)
    np.testing.assert_allclose(
        np.asarray(last_logits), np.asarray(full[:, prompt_len - 1]), atol=2e-4, rtol=2e-4
    )
    # one decode step after the prompt
    logits, cache = decode_step(
        params, cache, tokens[:, prompt_len], jnp.full((b,), prompt_len, jnp.int32), config
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, prompt_len]), atol=2e-4, rtol=2e-4
    )


def test_ragged_decode_positions():
    """Examples at different positions decode correctly in one batch."""
    config = get_config("llama-tiny")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 10), 0, config.vocab_size)
    full = forward(params, tokens, config)

    # example 0 is at position 5, example 1 at position 3
    cache = init_cache(config, 2)
    for t in range(6):
        pos = jnp.array([t, min(t, 3)], dtype=jnp.int32)
        cur = jnp.stack([tokens[0, t], tokens[1, min(t, 3)]])
        logits, cache = decode_step(params, cache, cur, pos, config)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(full[0, 5]), atol=2e-4, rtol=2e-4)


def test_sharded_forward_on_mesh():
    """FSDP+TP-sharded params produce the same logits as replicated."""
    config = get_config("llama-tiny")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (4, 16), 0, config.vocab_size)
    expected = forward(params, tokens, config)

    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    sharded = shard_tree(params, logical_axes(config), default_rules(), mesh)
    fwd = jax.jit(lambda p, t: forward(p, t, config))
    with jax.set_mesh(mesh):
        out = fwd(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-4, rtol=1e-4)


def test_param_counts_gpt2_small():
    config = get_config("gpt2-small")
    params = init_params(config, jax.random.PRNGKey(0))
    n = count_params(params)
    assert 120e6 < n < 130e6, n  # ~124M


def test_grad_flows(model):
    config, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0, config.vocab_size)

    def loss(p):
        logits = forward(p, tokens, config)
        from ray_tpu.ops import cross_entropy_loss

        l, _ = cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
        return l

    g = jax.grad(loss)(params)
    norms = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(g)]
    assert all(np.isfinite(norms))
    assert sum(norms) > 0


def test_fused_qkv_and_unroll_match_baseline():
    """The perf knobs are numerically inert."""
    config = get_config("llama-tiny")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, config.vocab_size)
    base = forward(params, tokens, config)
    fused = forward(params, tokens, config.replace(fused_qkv=True))
    unrolled = forward(params, tokens, config.replace(scan_unroll=4))
    np.testing.assert_allclose(np.asarray(base), np.asarray(fused), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(base), np.asarray(unrolled), atol=1e-6)
