"""The lfm2_moe stack's whole-model comparisons at a tiny size on the CPU: the
program's logits, loss, gradients and first two steps against the plain
reference (benchmark/reference/lfm2_moe_ref.py), through a tied head, a rotary
QK-normed GQA layer and a list of kinds whose attention layer is NOT at a
period's end. A file of few tests (the rule at the top of tests/conftest.py);
the light ones are in test_lfm2moe_model.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import lfm2_moe_ref as ref
from benchmark.reference import train_ref
from ray_tpu.models import model_family
from ray_tpu.train.lm import lm_loss

from test_lfm2moe_model import arch, tiny_lfm2
from test_mixed_stack import seeded  # noqa: E402


@pytest.fixture(scope="module")
def stack():
    config = tiny_lfm2(n_layers=4, mixer_kinds=("sconv", "full", "sconv", "sconv"))   # dC eF eC eC
    params = seeded(config, 3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, config.vocab_size)
    return config, params, tokens


def test_logits_loss_and_gradients_are_the_references(stack):
    config, params, tokens = stack
    family = model_family(config)
    hidden, scalars = jax.jit(lambda p, t: family.forward_hidden(p, t, config))(params, tokens[:, :-1])
    logits = hidden @ params["wte"].T
    want = jax.jit(lambda p, t: ref.forward_logits(p, t, **arch(config)))(params, tokens[:, :-1])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=1e-4)
    assert scalars["moe_load_max_over_mean"] >= 1 and "lm_head" not in params

    (loss, said), grads = jax.jit(jax.value_and_grad(
        functools.partial(lm_loss, config=config), has_aux=True))(params, tokens)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.objective(p, tokens, **arch(config))))(params)
    assert float(said["loss"]) == pytest.approx(float(want_loss), abs=2e-6)
    assert "moe_rows_held_share" in said
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), wanted in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(wanted))) + 1e-8
        np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(wanted) / scale, atol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))
    # the frozen router gets no gradient; the tied matrix takes both its uses'; every leaf of a conv layer one
    layer = grads["runs"][1][1]
    assert float(jnp.max(jnp.abs(layer["router"]))) == 0.0
    for name in ("sconv_in", "sconv_w", "sconv_out", "ln1_scale"):
        assert float(jnp.max(jnp.abs(layer[name]))) > 0.0, name
    never_seen = np.setdiff1d(np.arange(config.vocab_size), np.asarray(tokens[:, :-1]))
    assert float(jnp.max(jnp.abs(grads["wte"][never_seen]))) > 0.0     # a row no token looked up: the head's part


def test_the_objective_in_rows_adds_up_to_the_whole_batchs(stack):
    config, params, tokens = stack
    whole = jax.jit(lambda p: ref.objective(p, tokens, **arch(config)))(params)
    total = tokens.shape[0] * (tokens.shape[1] - 1)
    parts = [jax.jit(lambda p, rows: ref.objective_part(p, rows, total_tokens=total, head_rows=64, query_block=32,
                                                        **arch(config)))(params, tokens[i:i + 1])
             for i in range(tokens.shape[0])]
    assert float(sum(share for share, _ in parts)) == pytest.approx(float(whole), rel=1e-6)
    assert float(sum(ce for _, ce in parts)) / total == pytest.approx(float(whole), rel=1e-6)


def test_the_first_two_steps_follow_the_references(stack):
    """Clip, AdamW behind its warm-up: the program's optimizer on the program's
    gradients against train_ref.follow on the reference's, two steps."""
    config, params, tokens = stack
    trainer = {"learning_rate": 0.02, "total_steps": 100, "warmup_steps": 2, "end_lr_ratio": 0.1, "b1": 0.9,
               "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "grad_clip": 1.0}
    batches = [tokens, jax.random.randint(jax.random.PRNGKey(2), tokens.shape, 0, config.vocab_size)]
    total = tokens.shape[0] * (tokens.shape[1] - 1)
    reference = train_ref.follow(
        lambda: jax.tree.map(jnp.copy, params), batches, trainer,     # `follow` donates what it is handed
 stats=None, rows_at_a_time=1,
        part=functools.partial(ref.objective_part, total_tokens=total, head_rows=64, query_block=32,
                               **arch(config)))
    schedule = optax.warmup_cosine_decay_schedule(0.0, 0.02, 2, 100, 0.002)
    optimizer = optax.chain(optax.clip_by_global_norm(1.0),
                            optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=0.1))

    @jax.jit
    def step(p, state, batch):
        (_, said), grads = jax.value_and_grad(functools.partial(lm_loss, config=config), has_aux=True)(p, batch)
        updates, state = optimizer.update(grads, state, p)
        return optax.apply_updates(p, updates), state, said["loss"]

    p, state, losses = params, optimizer.init(params), []
    for batch in batches:
        p, state, loss = step(p, state, batch)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, reference["losses"], atol=5e-6)
    change = train_ref.leaf_norms(jax.tree.map(lambda a, b: a - b, p, params))
    np.testing.assert_allclose(change, reference["change_norms"], rtol=2e-3)
    # the first update's rate is 0 (the warm-up starts there): the change is the second's
    assert losses[1] != losses[0] and min(change) > 0


def test_keeping_the_mixers_values_changes_no_gradient(stack):
    config, params, tokens = stack
    loss = functools.partial(lm_loss, config=config)
    whole = jax.jit(jax.grad(lambda p: loss(p, tokens)[0]))(params)
    kept = jax.jit(jax.grad(lambda p: loss(p, tokens, remat_saved=(
        "sconv_in_proj", "sconv_conv_out", "sconv_residual", "attn_out", "attn_lse"))[0]))(params)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(kept)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
