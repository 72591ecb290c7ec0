"""The bailing_hybrid (Ling-3.0-flash) stack on the mixed stack, at a tiny size on
the CPU: the index rule that puts a delta-rule mixer beside latent attention,
the program's logits, loss and gradients against the plain reference
(benchmark/reference/bailing_hybrid_ref.py: the recurrence one position at a
time), group-limited routing and its shares, and the latent layer with no q
latent and values narrower than its keys."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import bailing_hybrid_ref as ref
from ray_tpu.models import MixedStackConfig, model_family, moe
from ray_tpu.models.mixed_stack import _block, layer_kinds, stack_runs
from ray_tpu.ops import rope_frequencies
from ray_tpu.train.lm import lm_loss

from test_mixed_stack import seeded  # noqa: E402 - every norm off 1, a non-zero selection bias, weights x 3


def tiny_ling(**kw) -> MixedStackConfig:
    """Ling-3.0-flash's shape in small: published layers 1-7 of a stack whose
    period is 6 (`dK eK eK eK eL eK eK`); 4 KDA heads of 16 x 16 in chunks of
    32; 4 latent heads of 16 + 8 with values of 16 from a latent of 16, no q
    latent, one gate logit a head; 32 sigmoid-routed experts in 4 groups of
    which 2 are kept, top-4, 8 held, beside a shared one; float32."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=7, first_layer=1, n_heads=4, d_head=24, d_ff=32, d_ff_dense=96,
        max_seq=128, pos_emb="rope", norm="rmsnorm", act="swiglu", use_bias=False, tie_embeddings=False,
        rope_theta=6e6, norm_eps=1e-6, dtype=jnp.float32, remat=True,
        q_lora_rank=0, kv_lora_rank=16, qk_rope_dim=8, v_head_dim=16, attn_gate=True, attn_gate_per_head=True,
        global_attn_every=6, n_dense_layers=2, kda_heads=4, kda_head_dim=16, kda_chunk=32,
        n_experts=32, held_experts=(0, 8), top_k=4, norm_topk_prob=True, route_groups=4, route_groups_kept=2,
        route_scale=2.5, router_score="sigmoid", router_select_bias=True, shared_expert_width=32,
        router_aux_coeff=0.0, frozen_leaves=("router",))
    return MixedStackConfig(**{**base, **kw})


def gated(config, seed):
    """`seeded` parameters whose delta-rule gates are spread over the bounded
    gate's whole range (three times the family's A_log and dt_bias saturate
    the sigmoid at no decay, where A_log has no gradient to compare)."""
    def spread(path, w):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 2), int(w.size))
        if "kda_a_log" in name:
            return jax.random.uniform(key, w.shape, minval=-0.5, maxval=0.5)
        return 2.0 * jax.random.normal(key, w.shape) if "kda_dt_bias" in name else w

    return jax.tree_util.tree_map_with_path(spread, seeded(config, seed))


def arch(config):
    return dict(first_layer=config.first_layer, layer_group_size=config.global_attn_every,
                first_k_dense_replace=config.n_dense_layers, qk_rope_dim=config.qk_rope_dim,
                rope_theta=config.rope_theta, norm_eps=config.norm_eps,
                kda_lower_bound=config.kda_gate_lower_bound, top_k=config.top_k,
                route_scale=config.route_scale, n_group=config.route_groups,
                topk_group=config.route_groups_kept, held_experts=config.held_experts,
                frozen_leaves=config.frozen_leaves)


# ------------------------------------------------------------ the index rule


@pytest.mark.parametrize("first,depth,want,runs", [
    (1, 7, "dK eK eK eK eL eK eK", [(1, 1), (6, 1)]),
    (0, 12, "dK dK eK eK eK eL eK eK eK eK eK eL", [(1, 2), (10, 1)]),
    (2, 6, "eK eK eK eL eK eK", [(6, 1)]),
])
def test_the_periods_last_layer_is_the_latent_one_counted_from_the_published_index(first, depth, want, runs):
    config = tiny_ling(first_layer=first, n_layers=depth)
    kinds = layer_kinds(config)
    assert " ".join(kind.code for kind in kinds) == want
    assert [(len(run.kinds), run.repeats) for run in stack_runs(kinds)] == runs
    # the reference's rule, written apart, says the same of every published layer
    for i, kind in enumerate(kinds, start=first):
        assert ref.layer_kind(i, layer_group_size=6, first_k_dense_replace=2) == (
            {"K": "kda", "L": "latent"}[kind.code[1]], {"d": "dense", "e": "experts"}[kind.code[0]])


def test_a_stack_without_the_delta_rule_keeps_its_kinds():
    """`kda_heads` 0: latent attention in every layer of a latent stack, window
    and full in the others, as before the fifth kind."""
    latent = tiny_ling(kda_heads=0, kda_head_dim=0, first_layer=0, n_layers=3, n_dense_layers=1)
    assert " ".join(kind.code for kind in layer_kinds(latent)) == "dL eL eL"
    plain = tiny_ling(kda_heads=0, kda_head_dim=0, first_layer=0, n_layers=4, n_dense_layers=1,
                      kv_lora_rank=0, qk_rope_dim=0, v_head_dim=None, global_attn_every=4)
    assert " ".join(kind.code for kind in layer_kinds(plain)) == "dS eS eS eF"


def test_what_is_not_run_is_refused_by_name():
    with pytest.raises(ValueError, match="kda_heads"):
        tiny_ling(kda_head_dim=0)
    with pytest.raises(ValueError, match="kda_heads"):
        tiny_ling(kda_gate_lower_bound=1.0)
    with pytest.raises(ValueError, match="first_layer"):
        tiny_ling(first_layer=-1)
    with pytest.raises(ValueError, match="layer_pattern"):
        tiny_ling(layer_pattern="MEMEMEM", ssm_heads=2, ssm_head_dim=8, ssm_state=8)
    with pytest.raises(ValueError, match="v_head_dim 32"):
        tiny_ling(v_head_dim=32)
    with pytest.raises(ValueError, match="attn_gate_per_head"):
        tiny_ling(attn_gate=False)
    with pytest.raises(ValueError, match="v_head_dim 8"):
        tiny_ling(kv_lora_rank=0, qk_rope_dim=0, v_head_dim=8, kda_heads=0)
    with pytest.raises(ValueError, match="group-limited routing"):
        moe._group_limited(jnp.zeros((2, 32)), tiny_ling(route_groups=4, route_groups_kept=5))
    with pytest.raises(ValueError, match="group-limited routing"):
        moe._group_limited(jnp.zeros((2, 32)), tiny_ling(route_groups=16, route_groups_kept=1))


# -------------------------------------------- the program against the reference


@pytest.fixture(scope="module")
def stack():
    config = tiny_ling(n_layers=3, global_attn_every=3)       # dK eL eK: every kind, half the compile
    params = gated(config, 3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, config.vocab_size)
    return config, params, tokens


def test_logits_loss_and_gradients_are_the_references(stack):
    config, params, tokens = stack
    family = model_family(config)
    hidden, scalars = jax.jit(lambda p, t: family.forward_hidden(p, t, config))(params, tokens[:, :-1])
    logits = hidden @ params["lm_head"]
    want = jax.jit(lambda p, t: ref.forward_logits(p, t, **arch(config)))(params, tokens[:, :-1])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=1e-4)
    # some channel decays by more than e^-100 inside a chunk of 32 and none by more than the bound allows
    assert -160.0 <= float(scalars["kda_log_decay_chunk_min"]) < -100.0 and scalars["moe_load_max_over_mean"] >= 1

    (loss, said), grads = jax.jit(jax.value_and_grad(
        functools.partial(lm_loss, config=config), has_aux=True))(params, tokens)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.objective(p, tokens, **arch(config))))(params)
    assert float(said["loss"]) == pytest.approx(float(want_loss), abs=2e-6)
    assert "kda_log_decay_chunk_min" in said and "moe_rows_held_share" in said
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), wanted in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(wanted))) + 1e-8
        np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(wanted) / scale, atol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))
    # the frozen router gets no gradient; every other leaf of a delta-rule layer does
    layer = grads["runs"][1][1]
    assert float(jnp.max(jnp.abs(layer["router"]))) == 0.0
    for name in ("kda_in", "kda_bg", "kda_conv_w", "kda_a_log", "kda_dt_bias", "kda_norm_scale", "kda_out"):
        assert float(jnp.max(jnp.abs(layer[name]))) > 0.0, name


def test_the_objective_in_rows_adds_up_to_the_whole_batchs(stack):
    config, params, tokens = stack
    whole = jax.jit(lambda p: ref.objective(p, tokens, **arch(config)))(params)
    total = tokens.shape[0] * (tokens.shape[1] - 1)
    parts = [jax.jit(lambda p, rows: ref.objective_part(p, rows, total_tokens=total, head_rows=64,
                                                        **arch(config)))(params, tokens[i:i + 1])
             for i in range(tokens.shape[0])]
    assert float(sum(share for share, _ in parts)) == pytest.approx(float(whole), rel=1e-6)
    assert float(sum(ce for _, ce in parts)) / total == pytest.approx(float(whole), rel=1e-6)


def test_the_step_reports_the_delta_rule_the_latent_layer_and_the_groups(stack):
    config = tiny_ling()
    said = model_family(config).plan(config, 2, 128)
    assert said["layer_kinds"] == "dK eK eK eK eL eK eK"
    assert {name: said[name] for name in (
        "kda_heads", "kda_head_dim", "kda_chunk", "kda_subchunk", "kda_impl", "kda_kernels",
        "kda_heads_per_step", "kda_state_bytes", "kda_prologue", "kda_epilogue", "kda_conv_impl",
        "kda_gate_lower_bound", "attn_latent_v_dim", "attn_latent_q_rank", "moe_route_groups",
        "moe_route_groups_kept")} == {
        "kda_heads": 4, "kda_head_dim": 16, "kda_chunk": 32, "kda_subchunk": 16, "kda_impl": "xla_chunked",
        "kda_kernels": 0, "kda_heads_per_step": 0, "kda_state_bytes": 0, "kda_prologue": "xla",
        "kda_epilogue": "xla", "kda_conv_impl": "xla",
        "kda_gate_lower_bound": -5.0, "attn_latent_v_dim": 16,
        "attn_latent_q_rank": 0, "moe_route_groups": 4, "moe_route_groups_kept": 2}
    costs = model_family(config).block_costs(config, 128)
    named = {name for candidate in costs["candidates"] for name in candidate.names}
    assert {"kda_chunk_out", "kda_chunk_states", "kda_chunk_o", "attn_out", "attn_lse", "attn_latent_kv",
            "attn_latent_k_rope", "mlp_up", "mlp_gate"} <= named and "attn_latent_q" not in named
    (rule,) = [c for c in costs["candidates"] if c.names == ("kda_chunk_out", "kda_chunk_states", "kda_chunk_o")]
    # y as the out-projection reads it, o as the norm's transpose does, and the chunks' float32 states
    assert rule.layers == (1, 5) and rule.width == 2 * 64 + 64 * 16 * 4 // (32 * 4)
    # an older latent stack says nothing new but the groups it does not have
    from test_latent_attention import tiny_latent

    older = model_family(tiny_latent()).plan(tiny_latent(), 2, 48)
    assert not [name for name in older if name.startswith("kda_") or name == "attn_latent_v_dim"]
    assert (older["moe_route_groups"], older["moe_route_groups_kept"]) == (1, 1)


def test_keeping_the_rules_output_and_states_changes_no_gradient(stack):
    config, params, tokens = stack
    loss = functools.partial(lm_loss, config=config)
    whole = jax.jit(jax.grad(lambda p: loss(p, tokens)[0]))(params)
    kept = jax.jit(jax.grad(lambda p: loss(p, tokens, remat_saved=(
        "kda_chunk_out", "kda_chunk_states", "kda_chunk_o", "attn_out", "attn_lse"))[0]))(params)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(kept)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ----------------------------------------------------- group-limited routing


def _expert_layer(config, seed=2):
    lp = jax.tree.map(lambda w: w[0], seeded(config, seed)["runs"][0][0])
    return lp, jax.random.normal(jax.random.PRNGKey(5), (2, 24, config.d_model))


def test_group_limited_selection_by_hand():
    """Two groups of four, one kept, top-2: the group whose two largest add up
    to more wins, though the single largest score lies in the other."""
    config = tiny_ling(n_experts=8, held_experts=None, route_groups=2, route_groups_kept=1, top_k=2)
    select = jnp.array([[0.9, 0.1, 0.1, 0.1, 0.6, 0.5, 0.0, 0.0],       # 1.0 < 1.1: group 1
                        [0.9, 0.3, 0.1, 0.1, 0.6, 0.5, 0.0, 0.0]])      # 1.2 > 1.1: group 0
    limited = moe._group_limited(select, config)
    np.testing.assert_array_equal(np.isfinite(np.asarray(limited)),
                                  [[False] * 4 + [True] * 4, [True] * 4 + [False] * 4])
    _, experts = moe._route(select, limited, config)
    assert sorted(map(int, experts[0])) == [4, 5] and sorted(map(int, experts[1])) == [0, 1]
    gates, chosen = ref._gates(select[None], jnp.zeros(8), top_k=2, route_scale=1.0, n_group=2, topk_group=1)
    assert sorted(map(int, chosen[0, 0])) == [4, 5] and sorted(map(int, chosen[0, 1])) == [0, 1]
    assert float(gates[0, 0, 4]) == pytest.approx(0.6 / 1.1) and float(gates[0, 1, 0]) == pytest.approx(0.75)


def test_the_four_shares_of_a_group_limited_layer_add_up_to_the_uncut_reference():
    """The routed parts of the four chips' shares of one layer (8 experts each:
    a routing group a chip), plus the shared expert once, are the uncut layer's
    output, which is the reference's: every share routes over all 32 experts in
    their 4 groups, keeps 2 groups, normalises the gates over all 4 chosen and
    computes the chosen experts it holds."""
    whole = tiny_ling(n_layers=1, first_layer=2, held_experts=None)
    lp, h = _expert_layer(whole)
    uncut, scalars = jax.jit(functools.partial(moe.moe_mlp, config=whole))(h, lp)
    total, rows = 0.0, 0.0
    for share in range(4):
        first = 8 * share
        config = dataclasses.replace(whole, held_experts=(first, first + 8),
                                     shared_expert_width=32 if share == 0 else 0)
        held = dict(lp, **{name: lp[name][first: first + 8] for name in ("we_gate", "we_up", "we_down")})
        part, part_scalars = jax.jit(functools.partial(moe.moe_mlp, config=config))(h, held)
        total, rows = total + part, rows + part_scalars["moe_rows_held"]
        np.testing.assert_array_equal(np.asarray(part_scalars["load"]), np.asarray(scalars["load"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)
    assert float(rows) == 2 * 24 * 4            # every (token, choice) row lies on exactly one chip
    # a token's four experts lie in at most two groups
    load = np.asarray(scalars["load"]).reshape(4, 8)
    assert load.sum() == 2 * 24 * 4 and (load.sum(axis=1) > 0).sum() >= 2

    @jax.jit
    def by_the_reference(h, lp):
        with jax.default_matmul_precision("highest"):
            x = jnp.zeros_like(h)
            out, chosen = ref._experts(x + h, dict(lp, ln2_scale=jnp.ones(whole.d_model)), eps=0.0,
                                       held_experts=None, top_k=4, route_scale=2.5, n_group=4, topk_group=2)
            return out - h, chosen

    reference, chosen = by_the_reference(h, lp)
    # `_experts` norms its input: hand it h over its own RMS, so that the norm returns h
    rms = jnp.sqrt(jnp.mean(h * h, axis=-1, keepdims=True))
    uncut_normed, _ = jax.jit(functools.partial(moe.moe_mlp, config=whole))(h / rms, lp)
    np.testing.assert_allclose(np.asarray(uncut_normed), np.asarray(reference), atol=2e-5)
    assert len(set(np.asarray(chosen // 8)[0, 0])) <= 2


# ------------------------------- the latent layer: no q latent, narrower values


def test_the_latent_layer_without_a_q_latent_and_with_narrow_values_is_the_references():
    """Keys of 16 + 8 and values of 16 reach the attention padded with zeros to
    ONE head size (128 lanes), the scale stays 24^-1/2, one gate logit a head
    gates the 16-wide output; against the reference's plain softmax."""
    config = tiny_ling(n_layers=1, first_layer=5)
    assert (config.value_dim, config.kernel_head_dim, config.head_dim) == (16, 128, 24)
    (kind,) = layer_kinds(config)
    assert kind.code == "eL"
    lp = jax.tree.map(lambda w: w[0], seeded(config, 4)["runs"][0][0])
    assert lp["wq"].shape == (64, 4, 24) and lp["wkv_b"].shape == (16, 4, 32)
    assert lp["wg"].shape == (64, 4) and lp["wo"].shape == (4, 16, 64) and "wq_a" not in lp
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 48, 64))
    tables = rope_frequencies(config.rotary_dims, 48, config.rope_theta)
    ours, _ = jax.jit(lambda x, lp: _block(x, lp, config, kind, tables, None))(x, lp)

    @jax.jit
    def plain(x, lp):
        with jax.default_matmul_precision("highest"):
            x = ref._latent(x, lp, rope=8, theta=config.rope_theta, eps=1e-6, query_block=16)
            return ref._experts(x, lp, eps=1e-6, held_experts=(0, 8), top_k=4, route_scale=2.5,
                                n_group=4, topk_group=2)[0]

    np.testing.assert_allclose(np.asarray(ours), np.asarray(plain(x, lp)), atol=3e-5)
    # values as wide as the keys need no padding: the kernels' head size is the head's own
    assert tiny_ling(v_head_dim=24).kernel_head_dim == 24
