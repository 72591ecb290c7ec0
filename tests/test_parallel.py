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


# ------------------------- quantized collectives & explicit dp sync drills


def test_quantize_int8_block_roundtrip_error_bound():
    x = jnp.asarray(np.random.default_rng(3).standard_normal((4, 512)), jnp.float32)
    q, s = col.quantize_int8_block(x, block=128)
    assert q.dtype == jnp.int8 and s.shape == (4, 4)
    deq = col.dequantize_int8_block(q, s)
    # per-block error bounded by half a quantization step
    err = np.abs(np.asarray(x) - np.asarray(deq))
    bound = np.repeat(np.asarray(s), 128, axis=1) * 0.5 + 1e-7
    assert (err <= bound).all()
    # zero blocks survive exactly
    z = jnp.zeros((1, 128))
    qz, sz = col.quantize_int8_block(z, block=128)
    np.testing.assert_array_equal(np.asarray(col.dequantize_int8_block(qz, sz)), 0.0)


def _dp8_mesh():
    return build_mesh(MeshSpec(dp=8))


def test_quantized_psum_rows_consistent_and_close():
    from functools import partial

    mesh = _dp8_mesh()
    n, k = 8, 1024
    x = np.random.default_rng(0).standard_normal((n, n, k)).astype(np.float32)
    exact = x.sum(axis=0)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
             out_specs=(P("dp"), P("dp")), check_vma=False)
    def qar(rows):
        red, err = col.quantized_psum_rows(rows[0], "dp", block=128)
        return red[None], err[None]

    red, err = qar(jnp.asarray(x))
    red, err = np.asarray(red), np.asarray(err)
    # every member reconstructs the SAME reduced tensor (consistency is
    # what keeps replicated optimizer states bit-identical across dp)
    for m in range(1, n):
        np.testing.assert_array_equal(red[0], red[m])
    rel = np.abs(red[0] - exact).max() / np.abs(exact).max()
    assert rel < 0.02, rel
    # error feedback closes the books: reduced + all members' residuals
    # equals the exact f32 sum (this identity is why EF converges)
    np.testing.assert_allclose(red[0] + err.sum(axis=0), exact, atol=1e-4)


def test_quantized_psum_scatter_rows_close_to_exact():
    from functools import partial

    mesh = _dp8_mesh()
    n, k = 8, 512
    x = np.random.default_rng(1).standard_normal((n, n, k)).astype(np.float32)
    exact = x.sum(axis=0)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
             out_specs=(P("dp"), P("dp")), check_vma=False)
    def qrs(rows):
        own, err = col.quantized_psum_scatter_rows(rows[0], "dp", block=128)
        return own[None], err[None]

    own, err = qrs(jnp.asarray(x))
    rel = np.abs(np.asarray(own) - exact).max() / np.abs(exact).max()
    assert rel < 0.02, rel
    np.testing.assert_allclose(
        np.asarray(own) + np.asarray(err).sum(axis=0), exact, atol=1e-4
    )


def test_dp_sync_bytes_accounting():
    p = 1_000_000
    full = col.dp_sync_bytes(p, 8, mode="f32")
    quant = col.dp_sync_bytes(p, 8, mode="int8", block=512)
    shard_quant = col.dp_sync_bytes(p, 8, mode="int8", shard_update=True, block=512)
    assert col.dp_sync_bytes(p, 1) == 0
    # int8 wire is ~3.9x cheaper than f32 on the grad stages
    assert full / quant > 3.5
    # sharded update pays int8 reduce-scatter + f32 param gather
    assert quant < shard_quant < full


def test_sharded_update_matches_replicated_exactly():
    """The dp_shard_update machinery (rows layout -> shard slice -> adam on
    the shard -> all-gather) must reproduce the replicated optimizer update
    BIT-FOR-BIT at f32 given the same synced gradients — adam is
    elementwise, so any divergence is a layout bug."""
    import optax
    from functools import partial
    from ray_tpu.train.lm import _from_rows, _to_rows

    mesh = _dp8_mesh()
    n, block = 8, 64
    params = {
        "w": jnp.asarray(np.random.default_rng(5).standard_normal((37, 11)), jnp.float32),
        "b": jnp.asarray(np.random.default_rng(6).standard_normal(13), jnp.float32),
    }
    grads = jax.tree.map(
        lambda p: jnp.asarray(
            np.random.default_rng(7).standard_normal(p.shape), jnp.float32
        ),
        params,
    )
    opt = optax.adam(3e-3)

    # replicated reference: three plain updates (jitted, same as the
    # sharded program — eager numerics fuse differently at the ulp level)
    @jax.jit
    def ref_step(p, g, st):
        upd, st = opt.update(g, st, p)
        return optax.apply_updates(p, upd), st

    state = opt.init(params)
    p_ref = params
    for _ in range(3):
        p_ref, state = ref_step(p_ref, grads, state)

    # sharded: opt state lives in rows layout, each member updates its row
    rows_template = jax.tree.map(lambda p: _to_rows(p, n, block), params)
    opt_rows = opt.init(rows_template)
    opt_specs = jax.tree.map(
        lambda x: P("dp") if getattr(x, "ndim", 0) >= 1 else P(), opt_rows
    )

    @jax.jit
    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), opt_specs),
        out_specs=(P(), opt_specs),
        check_vma=False,
    )
    def sharded_step(p, g, opt_local):
        my = jax.lax.axis_index("dp")
        g_shard = jax.tree.map(lambda x: _to_rows(x, n, block)[my], g)
        p_shard = jax.tree.map(lambda x: _to_rows(x, n, block)[my], p)
        opt_sq = jax.tree.map(
            lambda x: x[0] if getattr(x, "ndim", 0) >= 2 and x.shape[0] == 1 else x,
            opt_local,
        )
        upd, new_opt = opt.update(g_shard, opt_sq, p_shard)
        new_shard = optax.apply_updates(p_shard, upd)
        rows = jax.tree.map(
            lambda s_: jax.lax.all_gather(s_, "dp", axis=0, tiled=False),
            new_shard,
        )
        new_p = jax.tree.map(lambda r, x: _from_rows(r, x), rows, p)
        new_opt = jax.tree.map(
            lambda x: x[None] if getattr(x, "ndim", 0) >= 1 else x, new_opt
        )
        return new_p, new_opt

    p_sh = params
    for _ in range(3):
        p_sh, opt_rows = sharded_step(p_sh, grads, opt_rows)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_explicit_dp_step_variants_match_standard():
    """End-to-end make_train_step: the explicit shard_map dp paths (f32
    sharded update; int8 quantized all-reduce; both) track the standard
    XLA-partitioned step on a real model — f32 sharded is float-order-only
    off, int8 within quantization tolerance — and converge."""
    import optax
    from ray_tpu.models import get_config
    from ray_tpu.train import create_train_state, make_train_step

    config = get_config("gpt2-tiny")
    mesh = _dp8_mesh()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 33), 0, config.vocab_size)
    batch = {"tokens": tokens}

    def run(n_steps, **kw):
        opt = optax.adam(5e-3)
        state, sh = create_train_state(
            config, opt, jax.random.PRNGKey(0), mesh,
            dp_shard_update=kw.get("dp_shard_update", False),
            dp_error_feedback=kw.get("dp_allreduce_dtype") == "int8",
        )
        step = make_train_step(
            config, opt, mesh, state_shardings=sh, loss_chunk=0, **kw
        )
        losses = []
        for _ in range(n_steps):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        return state, losses

    s_std, l_std = run(8, dp_allreduce_dtype="f32", dp_shard_update=False)
    s_shard, l_shard = run(8, dp_shard_update=True)
    s_q, l_q = run(8, dp_allreduce_dtype="int8")

    # sharded f32: same math, different float association only
    for a, b in zip(jax.tree.leaves(s_std.params), jax.tree.leaves(s_shard.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(l_std, l_shard, rtol=1e-4)

    # int8 + error feedback: converges with the f32 run within tolerance
    assert l_std[-1] < l_std[0]  # the drill actually trains
    assert abs(l_q[-1] - l_std[-1]) < 0.05, (l_q, l_std)
    # error-feedback buffer is alive (non-zero residuals are being carried)
    ef_norm = sum(
        float(jnp.sum(jnp.abs(e))) for e in jax.tree.leaves(s_q.ef)
    )
    assert ef_norm > 0.0


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
