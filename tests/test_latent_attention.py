"""Latent attention and the multi-token prediction module (PR 44): rotary
positions on a slice of a head, the flash kernels at D = 256, the mixed
stack's third attention kind and the module in the objective against the
plain reference of the family that runs them
(benchmark/reference/glm4_moe_lite_ref.py), a layer and a block at a time
(the whole model with its module: tests/test_mtp_module.py), the kind under
tensor parallelism, and the refusals."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from ray_tpu.models import MixedStackConfig, decode_step, init_cache, model_family, prefill
from ray_tpu.models.mixed_stack import LayerKind, _block, _layer_shapes, layer_kinds
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops import apply_rope, rope_frequencies
from ray_tpu.ops import attention as flash
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import default_rules, tree_specs

from test_mixed_stack import seeded  # noqa: E402 - every norm off 1, a non-zero selection bias, weights x 3


def tiny_latent(**kw) -> MixedStackConfig:
    """GLM-4.7-Flash's shape in small: 1 dense + 2 expert layers (dL eL eL)
    and one multi-token prediction module; 4 heads of 12 + 4 features over a
    model of 64, latents of 24 and 16; 32 sigmoid-routed experts top-4 of
    which 8 are held, beside a shared one; float32."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_head=16, d_ff=32, d_ff_dense=96,
        max_seq=64, pos_emb="rope", norm="rmsnorm", act="swiglu", use_bias=False,
        tie_embeddings=False, rope_theta=1e6, norm_eps=1e-5, dtype=jnp.float32, remat=True,
        q_lora_rank=24, kv_lora_rank=16, qk_rope_dim=4, v_head_dim=16,
        n_dense_layers=1, n_experts=32, held_experts=(0, 8), top_k=4, norm_topk_prob=True,
        route_scale=1.8, router_score="sigmoid", router_select_bias=True, shared_expert_width=32,
        router_aux_coeff=0.0, frozen_leaves=("router",), mtp_modules=1, mtp_loss_weight=0.3)
    return MixedStackConfig(**{**base, **kw})


def arch(config):
    return dict(num_dense_layers=config.n_dense_layers, qk_rope_dim=config.qk_rope_dim,
                rope_theta=config.rope_theta, norm_eps=config.norm_eps, top_k=config.top_k,
                route_scale=config.route_scale, held_experts=config.held_experts,
                frozen_leaves=config.frozen_leaves)


# ----------------------------------------------------------------- rotary slice


def test_rotary_positions_on_a_slice_are_the_whole_head_call_on_that_slice():
    """`rotary_dims` rotates the last features of every head exactly as the
    whole-head call rotates a head of that size, and passes the others
    through; one shared key head is a call with H = 1; the whole-head call
    is what it was, to the last bit, with or without the argument."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 10, 16))
    cos, sin = rope_frequencies(4, 32, 1e6)
    positions = jnp.asarray(np.random.default_rng(0).integers(0, 32, (2, 10)))
    for at in (None, positions):
        out = apply_rope(x, cos, sin, at, rotary_dims=4)
        np.testing.assert_array_equal(np.asarray(out[..., :12]), np.asarray(x[..., :12]))
        np.testing.assert_array_equal(np.asarray(out[..., 12:]),
                                      np.asarray(apply_rope(x[..., 12:], cos, sin, at)))
        one = apply_rope(x[:, :1, :, 12:], cos, sin, at)
        np.testing.assert_array_equal(np.asarray(one[:, 0]), np.asarray(out[:, 0, :, 12:]))
    whole_cos, whole_sin = rope_frequencies(16, 32, 1e4)
    whole = apply_rope(x, whole_cos, whole_sin)
    np.testing.assert_array_equal(np.asarray(whole),
                                  np.asarray(apply_rope(x, whole_cos, whole_sin, rotary_dims=16)))
    # by hand: feature j of the first half pairs with j + D/2 of the second
    angle = 3 * 1.0 / (1e4 ** (np.arange(0, 16, 2) / 16))
    np.testing.assert_allclose(
        np.asarray(whole[0, 0, 3, :8]),
        np.asarray(x[0, 0, 3, :8]) * np.cos(angle) - np.asarray(x[0, 0, 3, 8:]) * np.sin(angle), atol=1e-6)


# ------------------------------------------------------------ kernels at D = 256


@pytest.mark.parametrize("seq,block,tiles", [(1024, None, 1), (2048, None, 3), (1536, 512, 6)],
                         ids=["one-tile-a-head", "2x2-triangle", "3x3-triangle-of-512"])
def test_flash_kernels_at_head_size_256_match_the_reference(seq, block, tiles):
    """The same three kernels at D = 256 (interpret mode): one tile a head,
    and the lower triangle of tiles past it (the wide head's own 1,024 side,
    and explicit tiles of 512), where all three kernels walk the diagonal
    tile; output and the three gradients against `mha_reference`. float32
    inputs, 2e-5: what the order of the blockwise sums leaves; bfloat16 inputs
    read 1e-2."""
    wide, narrow = flash._head_choices(256), flash._head_choices(128)
    assert wide["vmem_limit_bytes"] == 64 * 2**20 and narrow["vmem_limit_bytes"] is None
    assert all(wide["diagonal_walk"].values()) and not narrow["diagonal_walk"]["flash_fwd"]
    side = block or wide["tile"]
    steps, live = flash.attention_grid_steps(seq, seq, True, seq, side, side)
    assert steps == live == tiles
    q, k, v, do = (jax.random.normal(key, (1, 2, seq, 256), jnp.float32)
                   for key in jax.random.split(jax.random.PRNGKey(seq), 4))

    def ours(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, implementation="pallas",
                                     block_q=block, block_kv=block)

    def theirs(q, k, v):
        return flash.mha_reference(q, k, v, causal=True)

    out, vjp = jax.vjp(ours, q, k, v)
    ref, ref_vjp = jax.vjp(theirs, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for name, g, r in zip("qkv", vjp(do), ref_vjp(do)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5 * float(jnp.max(jnp.abs(r))),
                                   err_msg=f"d{name}")


def test_the_plan_reports_what_a_wide_head_chose():
    wide = flash.attention_plan(8192, implementation="pallas", head_dim=256)
    assert (wide["attn_tile"], wide["attn_subtile"]) == (1024, "256x256")
    assert wide["attn_grid_steps"] == wide["attn_grid_steps_live"] == 8 * 9 // 2
    narrow = flash.attention_plan(8192, implementation="pallas", head_dim=128)
    # the same tile and sub-tile, and the backward walks the diagonal at both widths; a
    # head's resident dQ is twice a narrow head's
    assert narrow == {**wide, "attn_bwd_resident_bytes": 8192 * 128 * 4}
    assert wide["attn_bwd_resident_bytes"] == 8192 * 256 * 4 and wide["attn_bwd_kernels"] == 1
    # the forward walks the diagonal tile of a wide head and computes a narrow head's whole
    counts = {d: flash.attention_subtiles(8192, 8192, True, 8192, 1024, 1024, 256, 256,
                                          kernel="flash_fwd", head_dim=d) for d in (128, 256)}
    assert counts[256] == (28 * 16 + 8 * 10, 8 * 4, 1024) and counts[128] == (36 * 16, 8 * 16, 1024)
    # without the head size the plan is what it was, key for key
    assert set(flash.attention_plan(8192, implementation="pallas")) == set(narrow) - {
        "attn_tile", "attn_subtile", "attn_bwd_resident_bytes"}


# --------------------------------------------------- the layer, block and model


def test_a_latent_layer_has_its_seven_leaves_and_no_others():
    config = tiny_latent()
    assert " ".join(kind.code for kind in layer_kinds(config)) == "dL eL eL"
    shapes = _layer_shapes(config, LayerKind("latent", "dense"))
    attention = {name: shape for name, (shape, _, _) in shapes.items()
                 if name.startswith(("wq", "wk", "wv", "wo", "q_", "kv_"))}
    assert attention == {"wq_a": (64, 24), "q_a_norm_scale": (24,), "wq_b": (24, 4, 16),
                         "wkv_a": (64, 16 + 4), "kv_a_norm_scale": (16,),
                         "wkv_b": (16, 4, 12 + 16), "wo": (4, 16, 64)}
    # the up-projections over heads under tp, the down-projections and the latents' norms whole
    axes = {name: axes for name, (_, _, axes) in shapes.items()}
    assert axes["wq_b"] == axes["wkv_b"] == (None, "heads", "head_dim")
    assert axes["wq_a"] == axes["wkv_a"] == ("embed", None)
    assert axes["q_a_norm_scale"] == axes["kv_a_norm_scale"] == (None,)
    params = model_family(config).init_params(config, jax.random.PRNGKey(0))
    assert set(params["mtp"]) == {"enorm_scale", "hnorm_scale", "eh_proj", "norm_scale", "block"}
    assert params["mtp"]["eh_proj"].shape == (128, 64)
    assert set(params["mtp"]["block"]) == set(_layer_shapes(config, LayerKind("latent", "experts")))
    assert all(w.shape[0] == 1 for w in params["mtp"]["block"].values())
    # the parameters mirror their logical axes, the module's too
    logical = model_family(config).logical_axes(config)
    assert jax.tree.structure(params) == jax.tree.structure(
        logical, is_leaf=lambda x: isinstance(x, tuple))
    for w, leaf_axes in zip(jax.tree.leaves(params),
                            jax.tree.leaves(logical, is_leaf=lambda x: isinstance(x, tuple))):
        assert w.ndim == len(leaf_axes)
    # the table's size is the attention kind's: the rotary part, or the head
    assert config.rotary_dims == 4 and dataclasses.replace(config, kv_lora_rank=0).rotary_dims == 16


@pytest.mark.parametrize("mlp", ["dense", "experts"])
def test_a_block_matches_the_plain_reference(mlp):
    """One block of either MLP kind on a random stream against the
    reference's layer, float32, 2e-5 (a block's sums in another order; the
    same block in bfloat16 reads 3e-2)."""
    from benchmark.reference import glm4_moe_lite_ref as ref

    config = tiny_latent(n_layers=1, n_dense_layers=int(mlp == "dense"), mtp_modules=0)
    lp = jax.tree.map(lambda w: w[0], seeded(config)["runs"][0][0])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 48, 64))
    tables = rope_frequencies(config.rotary_dims, 48, config.rope_theta)
    ours, _ = jax.jit(lambda x, lp: _block(x, lp, config, LayerKind("latent", mlp), tables, None))(x, lp)
    theirs, chosen = jax.jit(ref._layer_fn(mlp == "dense", query_block=16, **arch(config)))(x, lp)
    assert (chosen is None) == (mlp == "dense")
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=2e-5)
    low = dataclasses.replace(config, dtype=jnp.bfloat16)
    rounded, _ = jax.jit(lambda x, lp: _block(x, lp, low, LayerKind("latent", mlp), tables, None))(
        x.astype(jnp.bfloat16), lp)
    assert float(jnp.max(jnp.abs(rounded.astype(jnp.float32) - theirs))) > 1e-3


# ------------------------------------------------------------- tensor parallelism


def test_a_latent_layer_under_tp_2_matches_one_device():
    """The block on a virtual dp=2 x tp=2 mesh, its parameters laid out by
    their logical axes (up-projections over heads, down-projections whole),
    against the same block on one device: float32, 2e-5."""
    config = tiny_latent(n_layers=1, n_dense_layers=1, mtp_modules=0)
    kind = LayerKind("latent", "dense")
    lp = jax.tree.map(lambda w: w[0], seeded(config)["runs"][0][0])
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 32, 64))
    tables = rope_frequencies(config.rotary_dims, 32, config.rope_theta)
    alone, _ = jax.jit(lambda x, lp: _block(x, lp, config, kind, tables, None))(x, lp)
    mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
    specs = tree_specs({name: axes for name, (_, _, axes) in _layer_shapes(config, kind).items()},
                       default_rules())
    assert specs["wq_b"] == specs["wkv_b"] == PartitionSpec(None, "tp", None)
    assert specs["wq_a"] == PartitionSpec("fsdp", None) and specs["wo"] == PartitionSpec("tp", None, "fsdp")
    sharded = {name: jax.device_put(w, NamedSharding(mesh, specs[name])) for name, w in lp.items()}
    xs = jax.device_put(x, NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), None, None)))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        together, _ = jax.jit(lambda x, lp: _block(x, lp, config, kind, tables, None))(xs, sharded)
    np.testing.assert_allclose(np.asarray(together), np.asarray(alone), atol=2e-5)


# ------------------------------------------------------------------- refusals


def test_what_is_not_run_is_refused_by_name():
    with pytest.raises(ValueError, match="v_head_dim 20"):      # wider than the keys; narrower ones are padded (PR 55)
        tiny_latent(v_head_dim=20)
    assert tiny_latent(v_head_dim=12).kernel_head_dim == 128 and tiny_latent().kernel_head_dim == 16
    with pytest.raises(ValueError, match="qk_rope_dim"):
        tiny_latent(qk_rope_dim=16)
    with pytest.raises(ValueError, match="one multi-token prediction module"):
        tiny_latent(mtp_modules=2)
    config = tiny_latent()
    params = model_family(config).init_params(config, jax.random.PRNGKey(0))
    dense = TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_head=16,
                              q_lora_rank=8, kv_lora_rank=8, qk_rope_dim=4)
    from ray_tpu.models import init_params
    from ray_tpu.serve.llm.paged import PagedConfig, init_paged_cache

    for refused in (lambda: init_params(dense, jax.random.PRNGKey(0)),
                    lambda: decode_step(params, {}, jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32), config),
                    lambda: prefill(params, jnp.zeros((1, 4), jnp.int32), jnp.ones((1,), jnp.int32), {}, config),
                    lambda: init_paged_cache(config, PagedConfig())):
        with pytest.raises(NotImplementedError, match="latent attention"):
            refused()
    assert init_cache(dataclasses.replace(config, kv_lora_rank=0, q_lora_rank=0, qk_rope_dim=0), 1)["k"].ndim == 5
