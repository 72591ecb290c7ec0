"""Model tests: GPT-2/Llama forward, decode-cache equivalence, sharded run."""

import functools

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

    g = jax.jit(jax.grad(loss))(params)
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


# ------------------------------------------------- what a recomputing block keeps

REMAT_NAMES = ("mlp_up", "mlp_gate", "attn_residual")
# the attention kernel's output and its lse: named where the kernels run
# (`attn_impl="pallas"`, interpreted here), and kept together
ATTN_OUT = ("attn_out", "attn_lse")


def _remat_config(preset):
    """A preset of the dense family by name; `...-pallas`: through the flash
    kernels; `mixed-pallas`: the tiny mixed stack of tests/test_mixed_stack.py."""
    if preset == "mixed-pallas":
        from tests.test_mixed_stack import tiny

        return tiny(attn_impl="pallas")
    if preset.endswith("-pallas"):
        return get_config(preset[:-len("-pallas")]).replace(attn_impl="pallas")
    return get_config(preset)


def _remat_loss_and_grads(preset, remat, saved=()):
    from ray_tpu.models import model_family
    from ray_tpu.train.lm import lm_loss

    config = _remat_config(preset).replace(remat=remat)
    params = model_family(config).init_params(config, jax.random.PRNGKey(0))
    # the mixed stack's window is 16: a sequence that leaves it
    seq = 48 if preset == "mixed-pallas" else 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq + 1), 0, config.vocab_size)
    loss_fn = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(p, tokens, config, remat_saved=saved)[0]))
    return loss_fn(params)


def _kept_sets(names):
    return [tuple(n for i, n in enumerate(names) if mask >> i & 1)
            for mask in range(1, 2 ** len(names))]


def _kept_id(v):
    return v if isinstance(v, str) else "+".join(v) if v else "whole_block"


@pytest.fixture(scope="module")
def keeps_everything():
    """preset -> (loss, gradients) of the step with `remat` off, computed
    once a preset: what every kept set of that preset is compared with."""
    return functools.cache(lambda preset: _remat_loss_and_grads(preset, False))


# The `mixed-pallas` cases of this list and of `_KEPT_OUTPUT_CASES` take 20-45 s
# each: tests/test_models_long.py runs them, from these lists (the rule in
# tests/conftest.py).
_REMAT_CASES = [
    *(("llama-tiny", kept) for kept in [None, *_kept_sets(REMAT_NAMES)]),
    # a GELU block has no gate: what the rule can choose there
    *(("gpt2-tiny", kept) for kept in [None, *_kept_sets(("mlp_up", "attn_residual"))]),
    # through the kernels: the attention output with its lse, alone and beside the rest
    *(("llama-tiny-pallas", kept) for kept in [None, ATTN_OUT, ATTN_OUT + REMAT_NAMES]),
    *(("mixed-pallas", kept) for kept in [None, ATTN_OUT, ATTN_OUT + REMAT_NAMES]),
]


def _long(case):
    return case[0] == "mixed-pallas"


@pytest.mark.parametrize("preset,saved", [case for case in _REMAT_CASES if not _long(case)], ids=_kept_id)
def test_a_recomputing_block_gives_the_loss_and_gradients_of_one_that_keeps_everything(
        preset, saved, keeps_everything):
    """`remat` off, the whole block recomputed, and every set of names a
    policy can keep: the kept values are the ones the forward computed, so
    the loss and every gradient leaf are those of the step that keeps all."""
    want_loss, want_grads = keeps_everything(preset)
    loss, grads = _remat_loss_and_grads(preset, True, saved or ())
    assert float(loss) == float(want_loss)
    for (path, want), got in zip(jax.tree_util.tree_flatten_with_path(want_grads)[0],
                                 jax.tree.leaves(grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


def _dots_as_wide_as(jaxpr, width) -> int:
    """`dot_general`s that write a (B, S, width) activation, in a jaxpr and
    every jaxpr inside it (scan bodies, checkpoints, custom rules)."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shape = eqn.outvars[0].aval.shape
            count += len(shape) == 3 and shape[-1] == width
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _dots_as_wide_as(sub, width)
    return count


def test_gate_and_up_are_not_multiplied_again_in_the_backward_pass_when_kept():
    """Matmuls that write a d_ff-wide value, in the scanned block of a step
    and its gradient: the gate and up projections, and in the backward the
    gradient into the activation. A whole-block checkpoint runs gate and up
    once more; the policy that keeps both runs neither, nor one of a pair."""
    from ray_tpu.train.lm import lm_loss

    config = get_config("llama-tiny").replace(remat=True)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 17), jnp.int32)

    def wide_dots(config, saved=()):
        grad = jax.grad(lambda p: lm_loss(p, tokens, config, remat_saved=saved)[0])
        return _dots_as_wide_as(jax.make_jaxpr(grad)(params).jaxpr, config.d_ff)

    kept_all = wide_dots(config.replace(remat=False))
    assert kept_all == 3
    assert wide_dots(config) == kept_all + 2
    assert wide_dots(config, ("mlp_up",)) == kept_all + 1
    assert wide_dots(config, ("mlp_up", "mlp_gate")) == kept_all
    # the residual spares the output projection, not these
    assert wide_dots(config, ("attn_residual",)) == kept_all + 2


def _kernel_calls(jaxpr, name) -> int:
    """`pallas_call`s of that name, in a jaxpr and every jaxpr inside it."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count += eqn.params["name"] == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _kernel_calls(sub, name)
    return count


_KEPT_OUTPUT_CASES = {
    # one scanned block
    "dense": ("llama-tiny-pallas", {"flash_fwd": 1}),
    # `dS dS` scanned, `eS eF eS eS` unrolled: 1 + 3 windowed bodies, 1 full
    "mixed": ("mixed-pallas", {"flash_win_fwd": 4, "flash_fwd": 1}),
}


@pytest.mark.parametrize("preset,kernels", [pytest.param(*case, id=name) for name, case
                                            in _KEPT_OUTPUT_CASES.items() if not _long(case)])
def test_the_forward_kernel_is_not_run_again_in_the_backward_pass_when_output_and_lse_are_kept(
        preset, kernels):
    """The forward flash kernel in the jaxpr of a step's gradient, a block
    body: once where nothing is recomputed; twice under a whole-block
    checkpoint; once again when the policy keeps the output AND the lse;
    twice when it keeps the output alone (the backward kernels read the lse,
    and only the forward kernel writes it). The backward kernels run once
    whatever is kept."""
    from ray_tpu.models import model_family
    from ray_tpu.train.lm import lm_loss

    config = _remat_config(preset).replace(remat=True)
    params = model_family(config).init_params(config, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 49), jnp.int32)

    def calls(config, saved=()):
        grad = jax.grad(lambda p: lm_loss(p, tokens, config, remat_saved=saved)[0])
        jaxpr = jax.make_jaxpr(grad)(params).jaxpr
        return ({name: _kernel_calls(jaxpr, name) for name in kernels},
                {name: _kernel_calls(jaxpr, name.replace("fwd", "bwd_dkv_dq")) for name in kernels})

    once, twice = kernels, {name: 2 * n for name, n in kernels.items()}
    assert calls(config.replace(remat=False)) == (once, once)
    assert calls(config) == (twice, once)
    assert calls(config, ATTN_OUT) == (once, once)
    assert calls(config, ("attn_out",)) == (twice, once)
    assert calls(config, ("attn_lse",)) == (twice, once)
    # the matmul outputs spare no kernel
    assert calls(config, REMAT_NAMES) == (twice, once)
    assert calls(config, ATTN_OUT + REMAT_NAMES) == (once, once)
