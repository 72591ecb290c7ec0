"""Mesh / sharding-rule / collective tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from ray_tpu.parallel import (
    MeshSpec,
    P,
    build_mesh,
    default_rules,
    logical_to_spec,
    mesh_registry,
    override_rules,
    tree_specs,
    shard_tree,
)
from ray_tpu.parallel import collectives as col


@pytest.fixture
def mesh8():
    return build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))


def test_mesh_shape(mesh8):
    assert mesh8.shape["dp"] == 2
    assert mesh8.shape["fsdp"] == 2
    assert mesh8.shape["tp"] == 2
    assert mesh8.shape["sp"] == 1
    assert len(mesh8.devices.flatten()) == 8


def test_mesh_spec_validation():
    with pytest.raises(ValueError):
        build_mesh(MeshSpec(dp=3))  # 3 != 8 devices


def test_mesh_spec_with_devices():
    spec = MeshSpec(tp=2).with_devices(8, prefer="fsdp")
    assert spec.fsdp == 4 and spec.tp == 2


def test_registry(mesh8):
    reg = mesh_registry()
    reg.clear()
    reg.register("train", mesh8)
    assert reg.get("train") is mesh8
    with pytest.raises(ValueError):
        reg.register("train", mesh8)
    reg.clear()


def test_logical_to_spec_basic():
    rules = default_rules()
    spec = logical_to_spec(("batch", "embed"), rules)
    assert spec == P(("dp", "fsdp"), "fsdp") or spec == P(("dp", "fsdp"), None)
    # fsdp already used by batch -> embed falls back to replicated
    assert spec[1] is None


def test_logical_to_spec_no_reuse():
    rules = default_rules()
    spec = logical_to_spec(("embed", "mlp"), rules)
    assert spec == P("fsdp", "tp")
    # vocab and mlp both want tp; second use must drop
    spec2 = logical_to_spec(("mlp", "vocab"), rules)
    assert spec2 == P("tp", None)


def test_override_rules():
    rules = override_rules(default_rules(), embed="tp")
    assert dict(rules)["embed"] == "tp"
    assert dict(rules)["mlp"] == "tp"


def test_shard_tree(mesh8):
    params = {
        "wq": jnp.zeros((16, 8)),
        "wo": jnp.zeros((8, 16)),
    }
    logical = {
        "wq": ("embed", "heads"),
        "wo": ("heads", "embed"),
    }
    sharded = shard_tree(params, logical, default_rules(), mesh8)
    assert sharded["wq"].sharding.spec == P("fsdp", "tp")
    # Each shard of wq is (16/2, 8/2)
    shard = sharded["wq"].addressable_shards[0]
    assert shard.data.shape == (8, 4)


def test_collective_allreduce(mesh8):
    group = col.CollectiveGroup(mesh8, axis="dp", name="t")
    x = jnp.arange(8.0)
    out = group.allreduce(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 2)


def test_collective_mean_max(mesh8):
    group = col.CollectiveGroup(mesh8, axis="tp", name="t2")
    x = jnp.ones((4,))
    np.testing.assert_allclose(np.asarray(group.allreduce(x, "mean")), np.ones(4))
    np.testing.assert_allclose(np.asarray(group.allreduce(x, "max")), np.ones(4))


def test_collective_allgather(mesh8):
    group = col.CollectiveGroup(mesh8, axis="dp")
    x = jnp.arange(4.0)
    out = group.allgather(x)
    assert out.shape == (2, 4)


def test_collective_barrier(mesh8):
    group = col.CollectiveGroup(mesh8, axis="fsdp")
    group.barrier()  # completes without deadlock


def test_group_manager(mesh8):
    g = col.init_collective_group(mesh8, "dp", "mygroup")
    assert col.get_group("mygroup") is g
    out = col.allreduce(jnp.ones(2), "mygroup")
    np.testing.assert_allclose(np.asarray(out), [2.0, 2.0])
    col.destroy_collective_group("mygroup")


def test_in_graph_collectives_under_shard_map(mesh8):
    """The hot-path mode: psum inside shard_map inside jit."""
    from functools import partial

    @jax.jit
    @partial(jax.shard_map, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"))
    def normalize(x):
        total = col.psum(jnp.sum(x), "dp")
        return x / total

    x = jnp.arange(8.0) + 1
    out = normalize(x)
    np.testing.assert_allclose(float(jnp.sum(out)), 1.0, rtol=1e-6)


def test_sharded_matmul_end_to_end(mesh8):
    """pjit-style sharded matmul: batch over dp/fsdp, weights over tp."""
    from jax.sharding import NamedSharding

    x = jax.device_put(
        np.random.default_rng(0).standard_normal((8, 16)).astype(np.float32),
        NamedSharding(mesh8, P(("dp", "fsdp"), None)),
    )
    w = jax.device_put(
        np.random.default_rng(1).standard_normal((16, 32)).astype(np.float32),
        NamedSharding(mesh8, P(None, "tp")),
    )
    out = jax.jit(lambda a, b: a @ b)(x, w)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x) @ np.asarray(w), rtol=1e-4
    )
    assert out.sharding.spec in (P(("dp", "fsdp"), "tp"), P(("dp", "fsdp"), None))


# --------------------------- regression tests for eager-collective semantics


def test_allgather_of_group_sharded_input(mesh8):
    """allgather over an input sharded on the group axis must return the
    stacked shards, not per-member duplicated copies."""
    from jax.sharding import NamedSharding

    g = col.CollectiveGroup(mesh8, axis="dp", name="ag_sharded")
    x = jax.device_put(
        jnp.arange(8.0), NamedSharding(mesh8, PartitionSpec("dp"))
    )
    out = g.allgather(x)
    # row i == shard i of the input (the stacked-shards contract)
    assert out.shape == (2, 4)
    np.testing.assert_allclose(np.asarray(out[0]), np.arange(4.0))
    np.testing.assert_allclose(np.asarray(out[1]), np.arange(4.0) + 4)


def test_reducescatter_rejects_group_axis_in_spec(mesh8):
    from jax.sharding import NamedSharding

    g = col.CollectiveGroup(mesh8, axis="tp", name="rs_bad")
    y = jax.device_put(
        jnp.ones((4, 8)), NamedSharding(mesh8, PartitionSpec(None, "tp"))
    )
    with pytest.raises(ValueError, match="must not already be sharded"):
        g.reducescatter(y)


def test_reducescatter_basic(mesh8):
    g = col.CollectiveGroup(mesh8, axis="dp", name="rs_ok")
    x = jnp.ones((4, 8))
    out = g.reducescatter(x)
    assert out.shape == (4, 8)
    # every member contributed ones, summed over dp (size 2)
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones((4, 8)))


def test_eager_collectives_hit_jit_cache(mesh8):
    g = col.CollectiveGroup(mesh8, axis="dp", name="cachecheck")
    x = jnp.ones((8,))
    g.allreduce(x)
    assert len(g._jitted) == 1
    g.allreduce(x)
    g.allreduce(2 * x)
    assert len(g._jitted) == 1  # same (kind, op, spec) key -> one program
    g.allreduce(x, op="max")
    assert len(g._jitted) == 2


def test_broadcast_from_root(mesh8):
    from jax.sharding import NamedSharding

    g = col.CollectiveGroup(mesh8, axis="dp", name="bcast2")
    # replicated input: broadcast is identity-shaped
    x = jnp.arange(4.0)
    out = g.broadcast(x, root=0)
    assert out.shape == (4,)
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


# ----------------------------- the one training step on the virtual meshes


def _gpt2_tiny_losses(mesh, n_steps=8):
    """Loss of each of `n_steps` steps of make_train_step (dense head,
    adam) on one fixed batch, from the same seed on any mesh."""
    import optax
    from ray_tpu.models import get_config
    from ray_tpu.train import create_train_state, make_train_step

    config = get_config("gpt2-tiny")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 33), 0, config.vocab_size)
    opt = optax.adam(5e-3)
    state, sh = create_train_state(config, opt, jax.random.PRNGKey(0), mesh)
    step = make_train_step(config, opt, mesh, state_shardings=sh, loss_chunk=0)
    losses = []
    for _ in range(n_steps):
        state, m = step(state, {"tokens": tokens})
        losses.append(float(m["loss"]))
    return losses


@pytest.fixture(scope="module")
def one_device_losses():
    from ray_tpu.parallel.mesh import single_device_mesh

    return _gpt2_tiny_losses(single_device_mesh(jax.devices()[0]))


@pytest.mark.parametrize("axes", [dict(dp=8), dict(fsdp=8), dict(dp=2, fsdp=2, tp=2)],
                         ids=["dp8", "fsdp8", "dp2-fsdp2-tp2"])
def test_train_step_on_a_mesh_follows_the_one_device_trajectory(axes, one_device_losses):
    """make_train_step builds ONE program and XLA inserts the collectives:
    on data-parallel, fully-sharded and three-axis meshes the loss of
    every one of 8 steps is the one-device loss within 1e-4 relative
    (same math, another order of the sharded sums: 2e-6 on this model),
    and it falls."""
    losses = _gpt2_tiny_losses(build_mesh(MeshSpec(**axes)))
    assert losses[-1] < losses[0] - 0.5, losses
    np.testing.assert_allclose(losses, one_device_losses, rtol=1e-4)


def test_path_specs_search_semantics(mesh8):
    from ray_tpu.parallel.sharding import path_specs

    tree = {"decoder": {"wq": jnp.ones((4, 4)), "wq_norm": jnp.ones((4,))}}
    specs = path_specs(tree, [(r"wq_norm", PartitionSpec()), (r"wq", PartitionSpec("tp"))])
    assert specs["decoder"]["wq"] == PartitionSpec("tp")
    assert specs["decoder"]["wq_norm"] == PartitionSpec()


def test_chunked_head_on_an_fsdp_x_tp_mesh_matches_the_dense_head():
    """The chunked head (dx and dW computed in the chunk's forward pass,
    ops/losses.fused_linear_cross_entropy) is plain jnp code that GSPMD
    partitions: on fsdp=2 x tp=2, with the untied head sharded over both,
    one step gives the dense head's loss and updated parameters."""
    import optax
    from ray_tpu.models import get_config
    from ray_tpu.train import create_train_state, make_train_step

    config = get_config("llama-tiny")
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])
    tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 33), 0, config.vocab_size)

    def one_step(loss_chunk):
        opt = optax.sgd(0.1)   # the update is the gradient
        state, sh = create_train_state(config, opt, jax.random.PRNGKey(2), mesh)
        assert state.params["lm_head"].sharding.spec == P("fsdp", "tp")
        step = make_train_step(config, opt, mesh, state_shardings=sh, loss_chunk=loss_chunk)
        state, metrics = step(state, {"tokens": tokens})
        return metrics, state.params

    dense, dense_params = one_step(0)
    chunked, chunked_params = one_step(16)
    np.testing.assert_allclose(float(chunked["loss"]), float(dense["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(chunked["grad_norm"]), float(dense["grad_norm"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(dense_params), jax.tree.leaves(chunked_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4)
