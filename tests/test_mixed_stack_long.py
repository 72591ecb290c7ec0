"""The tests of tests/test_mixed_stack.py that take a whole tiny stack's loss
and every gradient (against its family's plain reference; against the step
that freezes no leaf): 20-40 s each, so they live in a file of few tests (the
rule in tests/conftest.py). Configurations and helpers are the origin's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import model_family
from ray_tpu.models.transformer import lm_head_weights
from ray_tpu.train.lm import lm_loss
from tests.test_mixed_stack import _logits_of, arch, arch_all_experts, seeded, tiny, tiny_all_experts


@pytest.fixture(scope="module")
def tiny_step():
    """The tiny stack of the origin, seeded, a batch, and the system's loss and
    gradients on it: one test compares them with the plain reference, the other
    with the step that freezes no leaf. -> (config, params, tokens, loss, grads)"""
    config = tiny()
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 49), 0, config.vocab_size)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: lm_loss(p, tokens, config)[0]))(params)
    return config, params, tokens, loss, grads


def test_forward_loss_and_every_gradient_match_the_plain_reference(tiny_step):
    """Logits, loss and every leaf's gradient of the system against
    benchmark/reference/afmoe_ref.py, float32, 1e-4: both attention kinds,
    both MLP kinds, the held share, a seeded non-zero `expert_bias`."""
    from benchmark.reference import afmoe_ref

    config, params, tokens, loss, grads = tiny_step
    family = model_family(config)

    def logits(p, t):
        hidden, _ = family.forward_hidden(p, t, config)
        return jnp.einsum("bse,ev->bsv", hidden, lm_head_weights(p, config))

    ours = jax.jit(logits)(params, tokens[:, :-1])
    theirs = jax.jit(functools.partial(afmoe_ref.forward_logits, **arch(config)))(params, tokens[:, :-1])
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=1e-4)
    (ref_loss, ref_grads) = jax.jit(jax.value_and_grad(
        lambda p: afmoe_ref.objective(p, tokens, **arch(config))))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(ref_grads)):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-3)
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * scale, jax.tree_util.keystr(path)
        if "expert_bias" in jax.tree_util.keystr(path) or "router" in jax.tree_util.keystr(path):
            # the bias enters the selection only, and the router is a frozen leaf here
            assert not np.asarray(g).any() and not np.asarray(r).any()


def test_a_frozen_leaf_loses_its_gradient_and_nothing_else_does(tiny_step):
    """`frozen_leaves` is read where a layer reads its parameters: the named
    leaf's gradient is zero, every other leaf's is what the unfrozen step
    computes (the gates still carry their gradient back into the layer's
    input), and a name no layer has is refused."""
    frozen, params, tokens, _, frozen_grads = tiny_step
    free = tiny(frozen_leaves=())
    grads = {"frozen": frozen_grads,
             "free": jax.jit(jax.grad(lambda p: lm_loss(p, tokens, free)[0]))(params)}
    flat = jax.tree_util.tree_flatten_with_path(grads["frozen"])[0]
    routers = 0
    for (path, g), f in zip(flat, jax.tree.leaves(grads["free"])):
        if "'router'" in jax.tree_util.keystr(path):
            routers += 1
            assert not np.asarray(g).any() and np.asarray(f).any()
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(f), rtol=1e-5, atol=1e-8,
                                       err_msg=jax.tree_util.keystr(path))
    assert routers == 4
    with pytest.raises(ValueError, match="no layer has such a leaf"):
        tiny(frozen_leaves=("rooter",))


def test_all_expert_stack_matches_its_plain_reference():
    """Logits, loss and every leaf's gradient of the system against
    benchmark/reference/smallthinker_ref.py, float32: both attention kinds,
    the router on the attention's input, ReGLU, the held share, a frozen
    router."""
    from benchmark.reference import smallthinker_ref

    config = tiny_all_experts()
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 49), 0, config.vocab_size)
    logits = _logits_of(config)
    ours = logits(params, tokens[:, :-1])
    theirs = jax.jit(functools.partial(smallthinker_ref.forward_logits, **arch_all_experts(config)))(
        params, tokens[:, :-1])
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=1e-4)
    # the reference's one departure, a block of positions at a time, changes no number
    blocked = jax.jit(functools.partial(
        smallthinker_ref.forward_logits, query_block=8, **arch_all_experts(config)))(params, tokens[:, :-1])
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(theirs), atol=1e-5)
    (loss, grads) = jax.jit(jax.value_and_grad(lambda p: lm_loss(p, tokens, config)[0]))(params)
    (ref_loss, ref_grads) = jax.jit(jax.value_and_grad(lambda p: smallthinker_ref.objective_part(
        p, tokens, total_tokens=tokens[:, 1:].size, query_block=8, **arch_all_experts(config))[0]))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(ref_grads)):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-3)
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * scale, jax.tree_util.keystr(path)
        if "router" in jax.tree_util.keystr(path):
            assert not np.asarray(g).any() and not np.asarray(r).any()
    # every weight through float8_e4m3 fails the same comparison
    low = jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), params)
    assert float(jnp.max(jnp.abs(logits(low, tokens[:, :-1]) - theirs))) > 1e-2
