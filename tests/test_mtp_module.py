"""The multi-token prediction module in the objective and the whole
latent-attention model (PR 44) against the plain reference of the family
that runs them (benchmark/reference/glm4_moe_lite_ref.py): logits, both
losses, every leaf's gradient; what the step reports of the module; and what
the selective-recompute plan is told of the stack and the module's block."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import model_family
from ray_tpu.models.mixed_stack import block_costs, plan
from ray_tpu.models.transformer import attention_costs, lm_head_weights
from ray_tpu.train.lm import lm_loss

from test_latent_attention import arch, seeded, tiny_latent


@pytest.fixture(scope="module")
def by_the_reference():
    """The tiny model, a batch and what glm4_moe_lite_ref makes of them, each
    compiled as one program: (config, params, tokens, main logits, the
    module's logits, (objective, (main loss, module loss)), gradients, the
    objective as the sum of its row-at-a-time shares)."""
    from benchmark.reference import glm4_moe_lite_ref as ref

    config = tiny_latent()
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 49), 0, config.vocab_size)
    main = jax.jit(functools.partial(ref.forward_logits, **arch(config)))(params, tokens[:, :-1])
    module = jax.jit(functools.partial(ref.module_logits, **arch(config)))(params, tokens)
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.objective(p, tokens, mtp_loss_weight=0.3, **arch(config)), has_aux=True))(params)
    part = jax.jit(functools.partial(ref.objective_part, total_tokens=2 * 48, mtp_loss_weight=0.3,
                                     head_rows=16, query_block=16, **arch(config)))
    share = sum(part(params, tokens[i: i + 1])[0] for i in range(2))
    return config, params, tokens, main, module, value, grads, share


@pytest.mark.parametrize("chunk", [0, 16], ids=["dense-head", "chunked-head"])
def test_logits_both_losses_and_every_gradient_match_the_plain_reference(chunk, by_the_reference):
    """The whole model with its module against glm4_moe_lite_ref, float32:
    the main logits and the module's (1e-4 of logits of size ~1: the sums'
    order through four blocks), the main and the module's loss (1e-5) and
    every leaf's gradient of main + 0.3 x module (1e-4 of the leaf's largest
    entry); bfloat16 compute reads 1e-2 on the logits and fails each."""
    (config, params, tokens, ref_main_logits, ref_module_logits,
     (ref_objective, (ref_main, ref_module)), ref_grads, share) = by_the_reference
    family = model_family(config)

    def logits(p, t):
        hidden, routers = family.forward_hidden(p, t[:, :-1], config)
        module, _ = family.mtp_hidden(p, hidden, t[:, 1:], config, routers)
        head = lm_head_weights(p, config)
        return jnp.einsum("bse,ev->bsv", hidden, head), jnp.einsum("bse,ev->bsv", module, head)

    main, module = jax.jit(logits)(params, tokens)
    np.testing.assert_allclose(np.asarray(main), np.asarray(ref_main_logits), atol=1e-4)
    np.testing.assert_allclose(np.asarray(module), np.asarray(ref_module_logits), atol=1e-4)
    low = dataclasses.replace(config, dtype=jnp.bfloat16)
    rounded, _ = jax.jit(functools.partial(family.forward_hidden, config=low))(params, tokens[:, :-1])
    rounded = jnp.einsum("bse,ev->bsv", rounded.astype(jnp.float32), params["lm_head"])
    assert float(jnp.max(jnp.abs(rounded - main))) > 1e-3

    (objective, scalars), grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(p, tokens, config, chunk=chunk), has_aux=True))(params)
    assert abs(float(scalars["loss"]) - float(ref_main)) < 1e-5
    assert abs(float(scalars["mtp_loss"]) - float(ref_module)) < 1e-5
    assert abs(float(objective) - float(ref_objective)) < 1e-5
    assert float(objective) == pytest.approx(float(scalars["loss"]) + 0.3 * float(scalars["mtp_loss"]), abs=1e-6)
    assert float(scalars["num_tokens"]) == 2 * 48
    moved = 0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        scale = max(float(jnp.max(jnp.abs(r))), 1e-3)
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * scale, name
        if "expert_bias" in name or "router" in name:
            assert not np.asarray(g).any() and not np.asarray(r).any()     # selection only; frozen
        else:
            moved += bool(np.asarray(g).any())
    # every other leaf, the module's too, gets a gradient (router and bias: the scanned run's, the module's)
    assert moved == len(jax.tree.leaves(grads)) - 2 * 2
    # the row-at-a-time share the first training steps are followed with adds up to the same
    assert abs(float(share) - float(ref_objective)) < 1e-5


def test_the_step_reports_the_module_beside_the_stack():
    """`mtp_loss` is a scalar of its own, `loss` stays the next-token cross
    entropy (the objective without the module is the same number), and the
    module's expert layer counts as one more of the stack's in the means."""
    config = tiny_latent()
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, config.vocab_size)
    objective, scalars = jax.jit(lambda p: lm_loss(p, tokens, config))(params)
    plain, plain_scalars = jax.jit(lambda p: lm_loss(p, tokens, dataclasses.replace(config, mtp_modules=0)))(
        {k: v for k, v in params.items() if k != "mtp"})
    assert float(plain) == float(plain_scalars["loss"]) == float(scalars["loss"])
    assert "mtp_loss" not in plain_scalars and float(scalars["mtp_loss"]) > 0
    assert float(objective) > float(plain)
    family = model_family(config)

    @jax.jit
    def reports(p, t):
        hidden, routers = family.forward_hidden(p, t[:, :-1], config)
        return routers, family.mtp_hidden(p, hidden, t[:, 1:], config, routers)[1]

    routers, merged = reports(params, tokens)
    assert set(merged) == set(routers) == {"moe_load_max_over_mean", "moe_rows_held",
                                           "moe_rows_held_share", "moe_passes"}
    assert float(merged["moe_load_max_over_mean"]) >= float(routers["moe_load_max_over_mean"])
    own = 3 * float(merged["moe_rows_held"]) - 2 * float(routers["moe_rows_held"])     # 2 layers + 1
    assert 0 <= own <= 2 * 32 * 4
    assert float(merged["moe_rows_held_share"]) == pytest.approx(
        100 * float(merged["moe_rows_held"]) / (2 * 32 * 4), rel=1e-6)


def test_fused_head_step_with_the_module_is_the_dense_heads_step():
    """One step of `LMTrainer` with the module in the loss, the fused head
    (which a device of known size runs since PR 46, this cell with the whole
    sequence as its one chunk) against the dense one: both passes of the head,
    the module's with its mask, give the same losses, gradient norm and
    updated parameters (plain SGD, so that the update is the gradient)."""
    import optax

    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import LMTrainer

    config = tiny_latent()
    tokens = np.random.default_rng(5).integers(0, config.vocab_size, size=(8, 33)).astype(np.int32)

    def one_step(loss_chunk):
        trainer = LMTrainer(config, mesh_spec=MeshSpec(dp=8), seed=3, optimizer=optax.sgd(0.1),
                            loss_chunk=loss_chunk)
        out = trainer.train(iter([{"tokens": tokens}]), num_steps=1, report_every=1)
        assert trainer._step_fn_span.to_dict()["attrs"]["loss_chunk"] == loss_chunk
        return out, trainer.state.params

    dense, dense_params = one_step(0)
    fused, fused_params = one_step(32)
    for key in ("loss", "mtp_loss", "grad_norm", "num_tokens"):
        assert fused[key] == pytest.approx(dense[key], rel=1e-5), key
    for (path, got), want in zip(jax.tree_util.tree_flatten_with_path(fused_params)[0],
                                 jax.tree.leaves(dense_params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------- the recompute plan's prices


def test_the_plan_prices_the_five_matmuls_and_names_the_latents():
    config = tiny_latent()
    costs = attention_costs(config, 32, lambda weight: 1)
    q_width, latents = 4 * 16, 24 + 16 + 4
    down, up = 2 * 64 * latents, 2 * (24 * q_width + 16 * 4 * (12 + 16))
    scores, out_proj = 4 * q_width * 16, 2 * q_width * 64
    assert costs["flops"] == down + up + scores + out_proj
    names = [c.names for c in costs["candidates"]]
    assert names == [("attn_out", "attn_lse"), ("attn_residual",),
                     ("attn_latent_q", "attn_latent_kv", "attn_latent_k_rope")]
    kept = costs["candidates"][2]
    assert (kept.width, kept.flops, kept.worth, kept.tp_sum) == (latents, down, down, False)
    # under tp = 2 the up-projections, the scores and the output projection halve; the down-projections do not
    halved = attention_costs(config, 32, lambda weight: 2 if weight == "wq_b" else 1)
    assert halved["flops"] == down + (up + scores + out_proj) // 2
    assert halved["candidates"][1].tp_sum and halved["candidates"][2].width == latents
    # the module's block is one more run of the stack, unrolled, with its parameters' place
    whole = block_costs(config, 32)
    assert [(run["params"], run["layers"], run["scanned"]) for run in whole["runs"]] == [
        (("runs", 0), 1, False), (("runs", 1), 2, True), (("mtp", "block"), 1, False)]
    assert {c.names: c.layers for c in whole["candidates"]}[("attn_latent_q", "attn_latent_kv",
                                                            "attn_latent_k_rope")] == (1, 2, 1)
    said = plan(config, 2, 32)
    assert (said["layer_kinds"], said["attn_latent_q_rank"], said["attn_latent_kv_rank"],
            said["attn_rope_dims"], said["attn_head_dim"], said["mtp_modules"],
            said["mtp_loss_weight"]) == ("dL eL eL", 24, 16, 4, 16, 1, 0.3)
    assert "attn_window" not in said
    # a kept latent changes no number: the checkpoint's policy only moves work
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0, config.vocab_size)
    grads = [jax.jit(jax.grad(lambda p, saved=saved: lm_loss(p, tokens, config, remat_saved=saved)[0]))(params)
             for saved in ((), kept.names + ("attn_out", "attn_lse"))]
    for a, b in zip(*map(jax.tree.leaves, grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
