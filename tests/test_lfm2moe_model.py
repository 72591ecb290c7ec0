"""The lfm2_moe (LFM2-8B-A1B) stack on the mixed stack, at a tiny size on the
CPU: the mixers' kinds as a list, the sixth kind's leaves, costs and plan, a
full layer that rotates, a tied head, the gates' epsilon as data, and the
shares of an expert layer without a shared expert. The whole-model comparisons
with the plain reference (benchmark/reference/lfm2_moe_ref.py) are in
test_lfm2moe_model_long.py (the rule at the top of tests/conftest.py)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe_ref as ref
from ray_tpu.models import MixedStackConfig, model_family, moe
from ray_tpu.models.mixed_stack import _block, layer_kinds, stack_runs
from ray_tpu.ops import rope_frequencies

from test_mixed_stack import seeded  # noqa: E402 - every norm off 1, a non-zero selection bias, weights x 3

# published layers 1-5 of a list with its attention layers NOT at a period's end (2, 6, 10, ... 21 of 24)
LAYERS = ("sconv", "full", "sconv", "sconv", "sconv")


def tiny_lfm2(**kw) -> MixedStackConfig:
    """LFM2-8B-A1B's shape in small: published layers 1-5 (`dC eF eC eC eC`);
    3 taps over 64 channels; 4 query / 2 key-value heads of 16 with a QK-norm a
    head and rotary positions; 32 sigmoid-routed experts, top-4, 8 held, no
    shared one, gates over (sum + 1e-6); a tied head; float32."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=5, first_layer=1, n_heads=4, n_kv_heads=2, d_ff=32, d_ff_dense=96,
        max_seq=128, pos_emb="rope", norm="rmsnorm", act="swiglu", use_bias=False, tie_embeddings=True,
        rope_theta=1e6, norm_eps=1e-5, dtype=jnp.float32, remat=True, qk_norm_per_head=True,
        attn_full_rope=True, mixer_kinds=LAYERS, sconv_taps=3, n_dense_layers=2,
        n_experts=32, held_experts=(0, 8), top_k=4, norm_topk_prob=True, route_scale=1.0,
        router_score="sigmoid", router_select_bias=True, route_norm_eps=1e-6, shared_expert_width=0,
        router_aux_coeff=0.0, frozen_leaves=("router",))
    return MixedStackConfig(**{**base, **kw})


def arch(config, layer_types=None):
    types = layer_types or tuple({"sconv": "conv", "full": "full_attention"}[m] for m in config.mixer_kinds)
    return dict(layer_types=types, first_layer=config.first_layer, num_dense_layers=config.n_dense_layers,
                rope_theta=config.rope_theta, norm_eps=config.norm_eps, top_k=config.top_k,
                route_scale=config.route_scale, held_experts=config.held_experts,
                frozen_leaves=config.frozen_leaves)


# ------------------------------------------------------- the kinds as a list


@pytest.mark.parametrize("first,mixers,want,runs", [
    (1, LAYERS, "dC eF eC eC eC", [(1, 1), (4, 1)]),
    (0, ("sconv", "sconv", "full", "sconv", "sconv", "sconv", "full"), "dC dC eF eC eC eC eF", [(1, 2), (5, 1)]),
    (17, ("sconv", "full", "sconv", "sconv", "full", "sconv", "sconv"), "eC eF eC eC eF eC eC", [(3, 2), (1, 1)]),
    (2, ("full", "sliding", "sconv"), "eF eS eC", [(3, 1)]),
], ids=["the-cell", "from-layer-0", "the-lists-uneven-end", "a-window-in-the-list"])
def test_the_mixers_are_the_lists_and_the_mlps_the_index_rules(first, mixers, want, runs):
    config = tiny_lfm2(first_layer=first, n_layers=len(mixers), mixer_kinds=mixers)
    kinds = layer_kinds(config)
    assert " ".join(kind.code for kind in kinds) == want
    assert [(len(run.kinds), run.repeats) for run in stack_runs(kinds)] == runs


def test_a_stack_without_a_list_keeps_its_rules():
    """`mixer_kinds` (): window and full by the index, as before the sixth kind."""
    plain = tiny_lfm2(mixer_kinds=(), first_layer=0, n_layers=4, n_dense_layers=1, global_attn_every=4)
    assert " ".join(kind.code for kind in layer_kinds(plain)) == "dS eS eS eF"


@pytest.mark.parametrize("change, match", [
    (dict(mixer_kinds=LAYERS[:4]), "mixer_kinds"), (dict(mixer_kinds=("sconv",) * 4 + ("kda",)), "mixer_kinds"),
    (dict(layer_pattern="MEMEM", ssm_heads=2, ssm_head_dim=8, ssm_state=8, first_layer=0), "mixer_kinds: a stack told"),
    (dict(kv_lora_rank=16, qk_rope_dim=8, n_kv_heads=4), "mixer_kinds: a stack told"),
    (dict(mtp_modules=1), "mixer_kinds: a stack told"), (dict(sconv_taps=1), "sconv_taps"),
    (dict(frozen_leaves=("sconv_bias",)), "frozen_leaves"),
], ids=["a-short-list", "a-kind-no-list-names", "a-pattern-beside-it", "latent-attention-beside-it",
        "a-module-beside-it", "one-tap", "a-leaf-no-layer-has"])
def test_what_is_not_run_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        tiny_lfm2(**change)


# ------------------------------------------------ leaves, the tied head, plan


def test_the_tree_has_the_sixth_kinds_leaves_and_no_head_of_its_own():
    config = tiny_lfm2()
    family = model_family(config)
    params = family.init_params(config, jax.random.PRNGKey(0))
    axes = family.logical_axes(config)
    assert set(params) == set(axes) == {"wte", "runs", "lnf_scale"}
    conv, attention = params["runs"][0][0], params["runs"][1][0]
    assert {name: w.shape[1:] for name, w in conv.items() if name.startswith("sconv")} == {
        "sconv_in": (64, 192), "sconv_w": (64, 3), "sconv_out": (64, 64)}
    assert not [name for name in attention if name.startswith("sconv")] and "wq" not in conv
    assert attention["wk"].shape == (1, 64, 2, 16) and attention["q_norm_scale"].shape == (1, 16)
    assert axes["runs"][0][0]["sconv_in"] == ("layers", "embed", None)
    assert axes["runs"][0][0]["sconv_w"] == ("layers", None, None)
    assert axes["runs"][0][0]["sconv_out"] == ("layers", None, "embed")
    assert jax.tree.structure(jax.tree.map(lambda w: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    # the taps start as a depthwise convolution's do: U(+-1 / sqrt(taps))
    taps = np.asarray(conv["sconv_w"])
    assert np.abs(taps).max() <= 3 ** -0.5 and np.abs(taps).max() > 0.5
    # untied, the tree is the older one: the head a leaf of its own, every other leaf the same array
    untied = family.init_params(dataclasses.replace(config, tie_embeddings=False), jax.random.PRNGKey(0))
    assert untied["lm_head"].shape == (64, 256)
    np.testing.assert_array_equal(np.asarray(untied["wte"]), np.asarray(params["wte"]))
    from ray_tpu.models.transformer import lm_head_weights

    np.testing.assert_array_equal(np.asarray(lm_head_weights(params, config)), np.asarray(params["wte"]).T)


def test_the_step_reports_the_short_convolution_the_rotary_full_layer_and_the_tied_head():
    config = tiny_lfm2()
    said = model_family(config).plan(config, 2, 128)
    assert said["layer_kinds"] == "dC eF eC eC eC"
    assert {name: said[name] for name in ("sconv_channels", "sconv_taps", "sconv_impl", "sconv_rows",
                                          "attn_full_rope", "tie_embeddings")} == {
        "sconv_channels": 64, "sconv_taps": 3, "sconv_impl": "xla", "sconv_rows": 0, "attn_full_rope": True,
        "tie_embeddings": True}
    assert (said["moe_experts_routed"], said["moe_experts_held"], said["moe_shared_width"]) == (32, 8, 0)
    # a stack without the kind, positions on its full layers or a tied head says none of it
    older = tiny_lfm2(mixer_kinds=(), attn_full_rope=False, tie_embeddings=False, global_attn_every=2)
    assert not [name for name in model_family(older).plan(older, 2, 128)
                if name.startswith("sconv") or name in ("attn_full_rope", "tie_embeddings")]


def test_the_rule_may_keep_the_projection_the_ops_output_and_the_residual():
    config = tiny_lfm2()
    costs = model_family(config).block_costs(config, 128)
    by_name = {c.names: c for c in costs["candidates"]}
    # and, since PR 62, the QK-normed attention layer's operands with the norm's inputs, listed last
    operands = ("attn_q", "attn_k", "attn_v", "attn_q_proj", "attn_k_proj")
    # and, since PR 67, the held experts' buffer as its first pass wrote it
    buffer = moe.held_buffer_names(config)
    assert {("sconv_in_proj",), ("sconv_conv_out",), ("sconv_residual",), ("attn_out", "attn_lse"),
            ("attn_residual",), ("mlp_up",), ("mlp_gate",), (moe.ROUTING,), buffer, operands} == set(by_name)
    assert by_name[buffer].layers == by_name[moe.ROUTING,].layers == (0, 4)
    assert costs["candidates"][-1].names == operands and by_name[operands].layers == (0, 1)
    m = 64
    assert (by_name["sconv_in_proj",].width, by_name["sconv_in_proj",].flops) == (3 * m, 2 * m * 3 * m)
    assert (by_name["sconv_conv_out",].width, by_name["sconv_conv_out",].flops) == (m, 7 * m)
    assert (by_name["sconv_residual",].width, by_name["sconv_residual",].worth) == (m, 2 * m * m)
    # run 0 is the dense layer, run 1 the period: four short convolutions, one attention layer
    assert by_name["sconv_in_proj",].layers == (1, 3) and by_name["attn_residual",].layers == (0, 1)
    assert [run["layers"] for run in costs["runs"]] == [1, 4]
    # 2 x (3 m^2 + m^2) and 7 a channel a mixer beside the attention layer's, the MLP's and the experts'
    conv = 2 * (m * 3 * m + m * m) + 7 * m
    attention = 2 * m * (4 + 2 * 2) * 16 + 4 * 64 * 64 + 2 * 64 * m
    experts = 2 * m * 32 + 2 * m * 3 * 32 * 4 * 8 // 32
    assert costs["flops"] == 4 * conv + attention + 2 * 3 * m * 96 + 4 * experts


# ------------------------------------------------------------- single layers


def _layer(config, seed, at=0):
    lp = jax.tree.map(lambda w: w[0], seeded(config, seed)["runs"][0][at])
    return lp, jax.random.normal(jax.random.PRNGKey(seed + 10), (2, 48, config.d_model))


def test_a_short_convolution_block_is_the_references():
    config = tiny_lfm2(n_layers=1, first_layer=3, mixer_kinds=("sconv",))
    (kind,) = layer_kinds(config)
    assert kind.code == "eC"
    lp, x = _layer(config, 4)
    ours, scalars = jax.jit(lambda x, lp: _block(x, lp, config, kind, None, None))(x, lp)
    want, chosen = ref._layer(x, lp, attention=False, dense=False, theta=1e6, eps=1e-5, top_k=4, route_scale=1.0,
                              held_experts=(0, 8), frozen_leaves=(), query_block=16)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(want), atol=2e-5)
    # the rows sent to the held experts are the reference's choices among the first eight
    assert float(scalars["moe_rows_held"]) == float(np.sum(np.asarray(chosen) < 8))


@pytest.mark.parametrize("rope", [True, False], ids=["rotary", "no-positions"])
def test_a_full_layer_rotates_where_the_configuration_says_so(rope):
    """QK-norm a head, THEN rotary positions over the whole head of 16, grouped
    key-value heads: the reference's attention layer; with `attn_full_rope`
    False the layer encodes no positions, as Trinity's full layers."""
    config = tiny_lfm2(n_layers=1, first_layer=0, mixer_kinds=("full",), attn_full_rope=rope)
    (kind,) = layer_kinds(config)
    assert kind.code == "dF"
    lp, x = _layer(config, 5)
    tables = rope_frequencies(config.rotary_dims, 48, config.rope_theta)
    ours, _ = jax.jit(lambda x, lp: _block(x, lp, config, kind, tables, None))(x, lp)
    want, _ = ref._layer(x, lp, attention=True, dense=True, theta=1e6, eps=1e-5, top_k=4, route_scale=1.0,
                         held_experts=None, frozen_leaves=(), query_block=16)
    if rope:
        np.testing.assert_allclose(np.asarray(ours), np.asarray(want), atol=3e-5)
    else:
        assert float(jnp.max(jnp.abs(ours - want))) > 1e-3
        # and is the layer a stack of the index rules runs at its period's end
        older = tiny_lfm2(n_layers=1, first_layer=0, mixer_kinds=(), global_attn_every=1, attn_full_rope=False)
        again, _ = jax.jit(lambda x, lp: _block(x, lp, older, layer_kinds(older)[0], tables, None))(x, lp)
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(again))


# -------------------------------------------------------------- the router


def test_the_gates_epsilon_is_the_configurations():
    scores = jnp.array([[1e-6, 2e-6, 0.0, 0.0]])
    config = tiny_lfm2(n_experts=4, held_experts=None, top_k=2, router_select_bias=False)
    gates, experts = moe._route(scores, None, config)
    assert sorted(map(int, experts[0])) == [0, 1]
    np.testing.assert_allclose(np.sort(np.asarray(gates[0])), [1e-6 / 4e-6, 2e-6 / 4e-6], rtol=1e-6)
    older, _ = moe._route(scores, None, dataclasses.replace(config, route_norm_eps=1e-9))
    np.testing.assert_allclose(np.sort(np.asarray(older[0])), [1e-6 / 3.001e-6, 2e-6 / 3.001e-6], rtol=1e-5)
    assert moe.MoEConfig().route_norm_eps == 1e-9 and MixedStackConfig().route_norm_eps == 1e-9
    reference, _ = ref._gates(scores[None], jnp.zeros(4), 2, 1.0)
    np.testing.assert_allclose(np.asarray(reference[0, 0, :2]), [0.25, 0.5], rtol=1e-6)


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference():
    """The routed parts of the four chips' shares of one layer (8 experts each)
    are the uncut layer's output, which is the reference's: every share routes
    over all 32 experts, normalises the gates over all 4 chosen and computes
    the chosen experts it holds; there is no shared expert to count once."""
    whole = tiny_lfm2(n_layers=1, first_layer=2, mixer_kinds=("sconv",), held_experts=None)
    lp, h = _layer(whole, 2)
    uncut, scalars = jax.jit(functools.partial(moe.moe_mlp, config=whole))(h, lp)
    total, rows = 0.0, 0.0
    for share in range(4):
        first = 8 * share
        config = dataclasses.replace(whole, held_experts=(first, first + 8))
        held = dict(lp, **{name: lp[name][first: first + 8] for name in ("we_gate", "we_up", "we_down")})
        part, part_scalars = jax.jit(functools.partial(moe.moe_mlp, config=config))(h, held)
        total, rows = total + part, rows + part_scalars["moe_rows_held"]
        np.testing.assert_array_equal(np.asarray(part_scalars["load"]), np.asarray(scalars["load"]))
        # the share by the reference's own argument
        with jax.default_matmul_precision("highest"):
            plain, _ = jax.jit(functools.partial(ref.routed, top_k=4, route_scale=1.0,
                                                 held_experts=(first, first + 8)))(h, held)
        np.testing.assert_allclose(np.asarray(part), np.asarray(plain), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)
    assert float(rows) == 2 * 48 * 4            # every (token, choice) row lies on exactly one chip
    with jax.default_matmul_precision("highest"):
        reference, chosen = jax.jit(functools.partial(ref.routed, top_k=4, route_scale=1.0, held_experts=None))(h, lp)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(reference), atol=2e-5)
    assert chosen.shape == (2, 48, 4)
