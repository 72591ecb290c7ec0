"""AOT lowering of FLAGSHIP-scale sharded train steps.

BASELINE.md's target configs include Llama-3-8B FSDP on a slice. 8B
params cannot materialize on the CI host, but the whole point of the
jit/pjit design is that sharding correctness is decided at TRACE time:
jax.eval_shape builds the abstract state and `step.lower(...)` runs the
full SPMD partitioner over the real 8B shapes on the 8-device mesh —
without allocating a byte of parameter memory. This is the same gate the
driver's dryrun applies to the tiny model, at flagship scale.
"""

import dataclasses

import jax
import pytest

from ray_tpu.models import get_config
from ray_tpu.models.transformer import logical_axes
from ray_tpu.parallel import MeshSpec, build_mesh, default_rules
from ray_tpu.parallel.sharding import tree_specs
from ray_tpu.train import default_optimizer, make_train_step
from ray_tpu.models.transformer import init_params
from ray_tpu.train.lm import TrainState, _sharding_tree, infer_state_specs


def _abstract_state_and_shardings(config, opt, mesh):
    rules = default_rules()
    param_specs = tree_specs(logical_axes(config), rules)

    def build(key):
        params = init_params(config, key)
        return TrainState(
            step=jax.numpy.zeros((), jax.numpy.int32),
            params=params,
            opt_state=opt.init(params),
            rng=jax.random.fold_in(key, 1),
        )

    abstract = jax.eval_shape(build, jax.random.PRNGKey(0))
    spec_tree = infer_state_specs(abstract, param_specs)
    spec_tree = dataclasses.replace(spec_tree, params=param_specs)
    shardings = _sharding_tree(spec_tree, mesh)
    abs_state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings,
    )
    return abs_state, shardings


@pytest.mark.parametrize("spec", [MeshSpec(fsdp=4, tp=2), MeshSpec(dp=2, fsdp=4)])
def test_llama3_8b_train_step_lowers_sharded(spec):
    config = get_config("llama3-8b")
    assert config.n_layers == 32 and config.d_model == 4096  # the real 8B
    mesh = build_mesh(spec)
    opt = default_optimizer(3e-4, total_steps=100)
    abs_state, shardings = _abstract_state_and_shardings(config, opt, mesh)
    step = make_train_step(config, opt, mesh, state_shardings=shardings)

    from jax.sharding import NamedSharding, PartitionSpec

    batch_sharding = NamedSharding(
        mesh, PartitionSpec(("dp", "fsdp"), None)
    )
    abs_batch = {
        "tokens": jax.ShapeDtypeStruct(
            (8, 2048 + 1), jax.numpy.int32, sharding=batch_sharding
        )
    }
    lowered = step.lower(abs_state, abs_batch)
    hlo = lowered.as_text()
    # the SPMD program targets all 8 partitions with a Shardy mesh naming
    # our axes, and the big params arrive SHARDED on the fsdp axis (not
    # replicated) with donated (aliased) outputs for in-place updates
    assert "mhlo.num_partitions = 8" in hlo
    assert "sdy.mesh" in hlo and '"fsdp"=' in hlo
    assert '{"fsdp"}' in hlo, "no parameter is fsdp-sharded in the HLO"
    assert "tf.aliasing_output" in hlo, "state donation missing"
    # params land sharded, not replicated: the fsdp axis must appear in
    # the sharding of at least one large parameter
    flat_sh = jax.tree.leaves(
        jax.tree.map(lambda s: s.spec, shardings.params)
    )
    assert any("fsdp" in str(s) for s in flat_sh)


def test_llama3_8b_state_bytes_scale_with_shards():
    """Per-device parameter bytes must shrink by the fsdp factor — the
    ZeRO-3 property, checked arithmetically from the abstract shapes."""
    config = get_config("llama3-8b")
    mesh = build_mesh(MeshSpec(fsdp=8))
    opt = default_optimizer(3e-4, total_steps=100)
    abs_state, shardings = _abstract_state_and_shardings(config, opt, mesh)
    total = 0
    sharded = 0
    for leaf, sh in zip(
        jax.tree.leaves(abs_state.params), jax.tree.leaves(shardings.params)
    ):
        nbytes = leaf.size * leaf.dtype.itemsize
        total += nbytes
        import numpy as np

        shard_shape = sh.shard_shape(leaf.shape)
        sharded += int(np.prod(shard_shape)) * leaf.dtype.itemsize
    assert total > 25e9  # ~8B fp32 params
    # per-device slice must be well under 1/4 of the total (fsdp=8)
    assert sharded < total / 4, (sharded, total)


def test_llama3_8b_tp_serving_lowers_sharded():
    """VERDICT r3 #2: the paged serving engine's decode block — the exact
    program PagedLLMEngine dispatches — must partition at Llama-3-8B
    shapes over a tp=8 mesh: params Megatron-split, the KV page pool
    sharded on the kv-head axis, token I/O replicated."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.serve.llm.paged import PagedConfig, init_paged_cache
    from ray_tpu.serve.llm.paged_engine import (
        _sample_plain,
        build_decode_block,
        serving_shardings,
    )

    config = get_config("llama3-8b")
    assert config.kv_heads == 8 and config.n_heads == 32
    mesh = build_mesh(MeshSpec(tp=8))
    pc = PagedConfig(page_size=64, num_pages=512, max_pages_per_slot=32,
                     chunk_pages=4)
    param_sh, cache_sh, rep = serving_shardings(config, mesh)

    abs_params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(lambda k: init_params(config, k), jax.random.PRNGKey(0)),
        param_sh,
    )
    abs_cache = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(lambda: init_paged_cache(config, pc)),
        cache_sh,
    )
    B, K = 8, 16
    decode = build_decode_block(config, pc.page_size, K, _sample_plain,
                                use_kernel=False)
    jitted = jax.jit(
        decode, donate_argnums=(1,),
        in_shardings=(param_sh, cache_sh, rep, rep, rep, rep, rep),
        out_shardings=(rep, rep, cache_sh),
    )
    i32 = jax.numpy.int32
    abs_in = (
        jax.ShapeDtypeStruct((B, pc.max_pages_per_slot), i32, sharding=rep),
        jax.ShapeDtypeStruct((B,), i32, sharding=rep),
        jax.ShapeDtypeStruct((B,), i32, sharding=rep),
        jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((B,), jax.numpy.float32, sharding=rep),
    )
    hlo = jitted.lower(abs_params, abs_cache, *abs_in).as_text()
    assert "mhlo.num_partitions = 8" in hlo
    assert '{"tp"}' in hlo, "nothing is tp-sharded in the serving HLO"
    # the vLLM property that matters on HBM: per-device KV pool bytes
    # shrink by the tp factor (pool sharded on kv heads, not replicated)
    k_leaf = jax.eval_shape(lambda: init_paged_cache(config, pc))["k"]
    shard_shape = cache_sh["k"].shard_shape(k_leaf.shape)
    assert np.prod(shard_shape) * 8 == np.prod(k_leaf.shape) * 1, (
        shard_shape, k_leaf.shape
    )
    # and at least one attention projection lands tp-sharded
    flat = jax.tree.leaves(jax.tree.map(lambda s: str(s.spec), param_sh))
    assert any("'tp'" in s for s in flat)


@pytest.mark.parametrize("context_mesh", [False, True], ids=["weights-mesh", "context-mesh"])
def test_mixtral_8x7b_moe_lowers_expert_parallel(context_mesh):
    """BASELINE config 3: the REAL Mixtral 8x7B shapes (8 experts, 32
    layers, d_ff 14336) lower through the partitioner on a dp2 x ep4
    mesh with expert-stacked weights sharded on the ep axis, through the
    trainer's objective. The mesh has an `ep` axis, so the GShard form is
    what lowers, whether the mesh is the context's or only the one the
    expert weights are sharded over (models/moe._mesh_of)."""
    import contextlib

    from ray_tpu.train.lm import lm_loss

    from ray_tpu.models import moe

    config = moe.mixtral_8x7b()
    assert config.n_experts == 8 and config.d_ff == 14336
    mesh = build_mesh(MeshSpec(dp=2, ep=4))
    rules = default_rules()
    param_specs = tree_specs(moe.logical_axes(config), rules)
    abstract = jax.eval_shape(
        lambda key: moe.init_params(config, key), jax.random.PRNGKey(0)
    )
    from jax.sharding import NamedSharding, PartitionSpec

    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs
    )
    abs_params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings,
    )
    batch_sharding = NamedSharding(mesh, PartitionSpec(("dp",), None))
    abs_tokens = jax.ShapeDtypeStruct(
        (8, 1024 + 1), jax.numpy.int32, sharding=batch_sharding
    )

    loss_fn = jax.jit(lambda p, t: lm_loss(p, t, config)[0])
    with jax.set_mesh(mesh) if context_mesh else contextlib.nullcontext():
        hlo = loss_fn.lower(abs_params, abs_tokens).as_text()
    # the GShard dispatch: a (B, S, E, C) one-hot at capacity 2.0 * 2 * 1024 / 8
    # contracted with the tokens, and no ragged group anywhere
    assert "tensor<8x1024x8x512x" in hlo and "ragged_dot" not in hlo
    assert "mhlo.num_partitions = 8" in hlo
    assert '{"ep"}' in hlo, "no expert-stacked weight is ep-sharded"
    # the expert-parallel property: per-device expert bytes shrink by ep
    import numpy as np

    expert_leaf = abstract["blocks"]["we_up"]
    sh = shardings["blocks"]["we_up"]
    shard = np.prod(sh.shard_shape(expert_leaf.shape))
    assert shard * 4 <= np.prod(expert_leaf.shape), "experts not sharded"
