"""The nemotron_h family in small against its plain reference
(benchmark/reference/nemotron_h_ref.py: the recurrence one position at a
time): a `MEMEM*EME` stack's loss and every leaf's gradient, and the 16
shares of one expert layer against the uncut layer; the same stack against
the mixer that convolved a slice of the projection (PR 52). Long tests (the rule at
the top of tests/conftest.py): at most six live here."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe_ref, nemotron_h_ref
from ray_tpu.models import moe
from ray_tpu.train.lm import lm_loss
from test_mixed_stack import seeded, tiny_pattern


def _arch(config):
    return dict(pattern=config.layer_pattern[:config.n_layers], ssm_groups=config.ssm_groups,
                ssm_state=config.ssm_state, norm_eps=config.norm_eps, top_k=config.top_k,
                route_scale=config.route_scale, held_experts=config.held_experts,
                frozen_leaves=config.frozen_leaves)


@pytest.mark.parametrize("remat", [True, False], ids=["blocks-recomputed", "nothing-recomputed"])
def test_tiny_pattern_stack_loss_and_gradients_equal_the_references(remat):
    """Seeded weights shaken off their initial values (norms off 1, a
    non-zero selection bias, matrices x 3: the scan's part of a mixer's output
    is then no rounding beside D x), 2 x 48 tokens: three chunks of 16."""
    config = tiny_pattern(remat=remat)
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 49), 0, config.vocab_size)
    (loss, scalars), grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(p, tokens, config), has_aux=True))(params)
    want, reference = jax.jit(jax.value_and_grad(
        lambda p: nemotron_h_ref.objective(p, tokens, time_block=16, **_arch(config))))(params)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    assert float(scalars["ssm_log_decay_chunk_min"]) < 0 and scalars["moe_load_max_over_mean"] >= 1
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, ours), theirs in zip(flat, jax.tree.leaves(reference)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(theirs)))
        if "router" in name or "expert_bias" in name:       # frozen, and a bias of the selection alone
            assert float(jnp.max(jnp.abs(ours))) == scale == 0.0, name
        else:
            assert scale > 0, name
            np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=2e-4 * scale, err_msg=name)


def _reference_layer(h, lp, config, held):
    """The plain reference's expert layer on normed activations: the shared
    expert and every one of the `held` experts on every token, gated."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(h @ lp["router"])
        gates, _ = afmoe_ref._gates(scores, lp["expert_bias"], config.top_k, config.route_scale)
        return nemotron_h_ref._relu2(h, lp["ws_up"], lp["ws_down"]) + sum(
            gates[..., e, None] * nemotron_h_ref._relu2(h, lp["we_up"][e], lp["we_down"][e])
            for e in range(held))


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """128 squared-ReLU experts over 16 chips, 8 each, top-6, gates x 2.5:
    the routed parts of the 16 shares, plus the shared expert ONCE, are the
    uncut layer's output, which is the plain reference's; every share routes
    over all 128 and every (token, choice) row lies on exactly one chip."""
    whole = tiny_pattern(n_experts=128, held_experts=None, n_layers=1, layer_pattern="E")
    lp = jax.tree.map(lambda w: w[0], seeded(whole, 2)["runs"][0][0])
    assert "we_gate" not in lp and "ws_gate" not in lp and lp["we_up"].shape == (128, 64, 32)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, whole.d_model))
    uncut, scalars = jax.jit(functools.partial(moe.moe_mlp, config=whole))(h, lp)
    total, rows = 0.0, 0.0
    for share in range(16):
        first = 8 * share
        config = dataclasses.replace(whole, held_experts=(first, first + 8),
                                     shared_expert_width=64 if share == 0 else 0)
        held = dict(lp, **{name: lp[name][first: first + 8] for name in ("we_up", "we_down")})
        part, part_scalars = jax.jit(functools.partial(moe.moe_mlp, config=config))(h, held)
        total, rows = total + part, rows + part_scalars["moe_rows_held"]
        np.testing.assert_array_equal(np.asarray(part_scalars["load"]), np.asarray(scalars["load"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)
    assert float(rows) == 2 * 24 * 6
    reference = jax.jit(functools.partial(_reference_layer, config=whole, held=128))(h, lp)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(reference), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_on_the_cpu_the_mixer_reads_xbc_out_of_the_whole_and_computes_what_a_slice_through_the_padded_sums_did(
        monkeypatch, dtype):
    """Off a TPU `causal_conv1d` is the XLA form it was (PR 48's three lines)
    on the columns it is handed an offset to, its output cut into x, B and C:
    the tiny tree's loss and every gradient equal, to the bit, those of the
    mixer that cut xBC out of the projection first, convolved the slice and
    split the result (the plan says `xla`)."""
    from ray_tpu.models import model_family
    from ray_tpu.ops import ssd

    config = tiny_pattern(dtype=dtype)
    assert model_family(config).plan(config, 2, 48)["ssm_conv_impl"] == "xla"
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 49), 0, config.vocab_size)

    def first_step():       # a new function each time: `jit` keys its cache on the function object
        return jax.jit(jax.value_and_grad(lambda p: lm_loss(p, tokens, config)[0]))(params)

    loss, grads = first_step()

    def of_the_slice(x, w, b, *, offset=0, splits, implementation=None):
        x = x[..., offset:offset + w.shape[0]]
        padded = jnp.pad(x, ((0, 0), (w.shape[1] - 1, 0), (0, 0)))
        taps = sum(w[:, j].astype(jnp.float32) * padded[:, j:j + x.shape[1]].astype(jnp.float32) for j in range(w.shape[1]))
        return jnp.split(jax.nn.silu(b.astype(jnp.float32) + taps).astype(x.dtype), np.cumsum(splits)[:-1], axis=-1)

    monkeypatch.setattr(ssd, "causal_conv1d", of_the_slice)
    want_loss, want = first_step()
    np.testing.assert_array_equal(np.asarray(loss), np.asarray(want_loss))
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), np.asarray(ref.astype(jnp.float32)))
    assert float(jnp.max(jnp.abs(grads["runs"][0][0]["ssm_conv_w"]))) > 0
