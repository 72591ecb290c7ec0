"""MoE: routing invariants, forward/backward, expert-parallel sharded run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.moe import (
    MoEConfig,
    forward_hidden,
    init_params,
    logical_axes,
    moe_tiny,
    topk_dispatch,
)
from ray_tpu.models.transformer import lm_head_weights
from ray_tpu.parallel import MeshSpec, build_mesh, default_rules, shard_tree
from ray_tpu.train.lm import lm_loss


def forward(params, tokens, config):
    """(logits, the routers' summed auxiliary loss)."""
    hidden, routers = forward_hidden(params, tokens, config)
    logits = jnp.einsum("bse,ev->bsv", hidden, lm_head_weights(params, config))
    return logits, routers["router_aux_loss"]


@pytest.fixture(scope="module")
def model():
    config = moe_tiny()
    params = init_params(config, jax.random.PRNGKey(0))
    return config, params


def test_topk_dispatch_invariants():
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4)), -1)
    dispatch, combine = topk_dispatch(probs, top_k=2, capacity=16)
    d = np.asarray(dispatch)
    c = np.asarray(combine)
    # ample capacity: every token dispatched exactly top_k times
    np.testing.assert_allclose(d.sum((2, 3)), 2.0)
    # each (expert, slot) holds at most one token
    assert (d.sum((0, 1)) <= 1.0 + 1e-6).all() or True  # per batch row:
    assert (d.sum(1) <= 1.0 + 1e-6).all()
    # combine weights per token sum to 1 (renormalized top-k)
    np.testing.assert_allclose(c.sum((2, 3)), 1.0, atol=1e-5)


def test_topk_dispatch_capacity_drops():
    # all tokens want expert 0 → only `capacity` survive
    probs = jnp.zeros((1, 8, 4)).at[:, :, 0].set(1.0)
    dispatch, _ = topk_dispatch(probs, top_k=1, capacity=3)
    assert float(dispatch.sum()) == 3.0


def test_forward_shapes_and_aux(model):
    config, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, config.vocab_size)
    logits, aux = jax.jit(lambda p, t: forward(p, t, config))(params, tokens)
    assert logits.shape == (2, 16, config.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # balanced-ish routing at init → aux near 1.0 (its minimum is 1)
    assert 0.9 < float(aux) / config.n_layers < 2.5


def test_param_axes_match(model):
    config, params = model
    axes = logical_axes(config)
    flat_p = {tuple(str(k) for k, _ in []) for _ in []}
    p_paths = {
        tuple(str(k) for k in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    a_paths = {
        tuple(str(k) for k in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(
            axes, is_leaf=lambda x: isinstance(x, tuple)
        )[0]
    }
    assert p_paths == a_paths


def test_grad_flows_including_router(model):
    config, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, config.vocab_size)
    grads = jax.jit(jax.grad(lambda p: lm_loss(p, tokens, config)[0]))(params)
    router_norm = float(jnp.linalg.norm(grads["blocks"]["router"]))
    expert_norm = float(jnp.linalg.norm(grads["blocks"]["we_up"]))
    assert np.isfinite(router_norm) and router_norm > 0
    assert np.isfinite(expert_norm) and expert_norm > 0


def test_expert_parallel_sharded_matches_replicated(model):
    config, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, config.vocab_size)
    expected, aux_e = forward(params, tokens, config)

    mesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2))
    sharded = shard_tree(params, logical_axes(config), default_rules(), mesh)
    assert sharded["blocks"]["we_up"].sharding.spec[1] == "ep"
    fwd = jax.jit(lambda p, t: forward(p, t, config))
    with jax.set_mesh(mesh):
        out, aux = fwd(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(float(aux), float(aux_e), rtol=1e-5)


@pytest.mark.parametrize("context_mesh", [False, True], ids=["weights-mesh", "context-mesh"])
def test_ep_sharded_params_take_the_gshard_form(model, context_mesh):
    """The form follows the mesh the step has: parameters sharded over an
    `ep` axis trace the GShard einsums (no ragged group, no per-shard
    shard_map) whether the mesh is the context's or only the one the
    expert weights carry; the same parameters on one device trace the
    dropless form."""
    import contextlib

    config, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, config.vocab_size)
    mesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2))
    sharded = shard_tree(params, logical_axes(config), default_rules(), mesh)
    with jax.set_mesh(mesh) if context_mesh else contextlib.nullcontext():
        traced = str(jax.make_jaxpr(lambda p: forward(p, tokens, config))(sharded))
    assert "ragged_dot" not in traced
    # no per-shard expert layer: the one kind of shard_map a context mesh with tp > 1 brings is the
    # attention projections' rings over `tp` (parallel/sequence_parallel.py), which hold no expert
    maps = traced.split("shard_map[")[1:]
    assert bool(maps) == context_mesh and all("ppermute" in m and "ragged" not in m for m in maps)
    assert "ragged_dot" in str(jax.make_jaxpr(lambda p: forward(p, tokens, config))(params))


def test_moe_training_reduces_loss(model):
    config, params = model
    import optax

    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 17), 0, config.vocab_size)

    @jax.jit
    def step(params, opt_state):
        (loss, _), grads = jax.value_and_grad(
            lambda p: lm_loss(p, tokens, config), has_aux=True
        )(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])


def _one_layer(config, key=0):
    from ray_tpu.models.moe import moe_mlp

    params = init_params(config, jax.random.PRNGKey(key))
    lp = jax.tree.map(lambda w: 3.0 * w[0], {k: params["blocks"][k] for k in (
        "router", *config.expert_weights)})
    h = jax.random.normal(jax.random.PRNGKey(key + 1), (2, 16, config.d_model))
    return moe_mlp, lp, h


def _dense_experts(h, lp, config, act, router_input=None):
    """Every expert on every token, gated by the renormalised top-k softmax
    of the router's logits: the expert layer as plain einsums."""
    with jax.default_matmul_precision("highest"):
        logits = (h if router_input is None else router_input) @ lp["router"]
        top, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), config.top_k)
        gates = jnp.einsum("bsk,bske->bse", top / top.sum(-1, keepdims=True),
                           jax.nn.one_hot(chosen, config.n_experts))
        hidden = act(jnp.einsum("bsm,emf->bsef", h, lp["we_gate"])) * jnp.einsum(
            "bsm,emf->bsef", h, lp["we_up"])
        return jnp.einsum("bse,bsef,efm->bsm", gates, hidden, lp["we_down"])


@pytest.mark.parametrize("expert_act,act", [("reglu", jax.nn.relu), ("swiglu", jax.nn.silu)])
def test_the_gated_unit_through_the_grouped_form_equals_the_dense_einsum(expert_act, act):
    """`MoEConfig.expert_act` is read between the grouped matmuls: ReGLU
    (relu(gate) * up) and SwiGLU alike equal every expert on every token,
    gated, output and gradient."""
    import dataclasses

    config = dataclasses.replace(moe_tiny(), expert_act=expert_act)
    moe_mlp, lp, h = _one_layer(config)
    out, _ = moe_mlp(h, lp, config)
    dense = jax.jit(lambda h, lp: _dense_experts(h, lp, config, act))(h, lp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5)
    ours = jax.jit(jax.grad(lambda lp: jnp.sum(moe_mlp(h, lp, config)[0] ** 2)))(lp)
    theirs = jax.jit(jax.grad(lambda lp: jnp.sum(_dense_experts(h, lp, config, act) ** 2)))(lp)
    for name in ("we_gate", "we_up", "we_down"):
        np.testing.assert_allclose(np.asarray(ours[name]), np.asarray(theirs[name]), atol=2e-4)
    with pytest.raises(ValueError, match="unknown expert activation"):
        moe_mlp(h, lp, dataclasses.replace(config, expert_act="geglu"))


def test_a_non_gated_expert_has_two_matrices_in_both_forms_and_equals_the_dense_einsum(model):
    """`expert_act="relu2"`: W_down relu(W_up m)^2, no `we_gate` leaf in the
    tree or its axes, two grouped matmuls; the grouped form equals every
    expert on every token, gated, output and gradient, and under an `ep` mesh
    the einsum form equals the grouped one."""
    import dataclasses

    config = dataclasses.replace(moe_tiny(), expert_act="relu2")
    assert config.expert_weights == ("we_up", "we_down")
    params = init_params(config, jax.random.PRNGKey(0))
    assert "we_gate" not in params["blocks"] and "we_gate" not in logical_axes(config)["blocks"]
    moe_mlp, lp, h = _one_layer(config)

    def dense(h, lp):
        logits = jnp.einsum("bsm,me->bse", h, lp["router"], precision=jax.lax.Precision.HIGHEST)
        top, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), config.top_k)
        gates = jnp.einsum("bsk,bske->bse", top / top.sum(-1, keepdims=True),
                           jax.nn.one_hot(chosen, config.n_experts))
        hidden = jnp.square(jax.nn.relu(jnp.einsum("bsm,emf->bsef", h, lp["we_up"])))
        return jnp.einsum("bse,bsef,efm->bsm", gates, hidden, lp["we_down"])

    out, _ = moe_mlp(h, lp, config)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jax.jit(dense)(h, lp)), atol=2e-5)
    ours = jax.jit(jax.grad(lambda lp: jnp.sum(moe_mlp(h, lp, config)[0] ** 2)))(lp)
    theirs = jax.jit(jax.grad(lambda lp: jnp.sum(dense(h, lp) ** 2)))(lp)
    for name in ("we_up", "we_down"):
        np.testing.assert_allclose(np.asarray(ours[name]), np.asarray(theirs[name]), atol=2e-4)
    config = dataclasses.replace(config, capacity_factor=8.0)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, config.vocab_size)
    expected, _ = forward(params, tokens, config)
    mesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2))
    sharded = shard_tree(params, logical_axes(config), default_rules(), mesh)
    with jax.set_mesh(mesh):
        out, _ = jax.jit(lambda p, t: forward(p, t, config))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-4, rtol=1e-4)


def test_relu_gated_experts_in_the_gshard_form_equal_the_grouped_form(model):
    """Under an `ep` mesh the einsum form reads the same `expert_act` (ample
    capacity: nothing is dropped)."""
    import dataclasses

    config, params = model
    config = dataclasses.replace(config, expert_act="reglu", capacity_factor=8.0)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, config.vocab_size)
    expected, _ = forward(params, tokens, config)
    swiglu, _ = forward(params, tokens, dataclasses.replace(config, expert_act="swiglu"))
    assert float(jnp.max(jnp.abs(expected - swiglu))) > 1e-4
    mesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2))
    sharded = shard_tree(params, logical_axes(config), default_rules(), mesh)
    with jax.set_mesh(mesh):
        out, _ = jax.jit(lambda p, t: forward(p, t, config))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-4, rtol=1e-4)


def test_the_router_may_read_another_tensor_than_the_experts():
    """`moe_mlp(h, lp, config, router_input=r)`: the choice and the gates
    come from `r`, the experts compute on `h`; without it the layer traces
    what it traced."""
    config = moe_tiny()
    moe_mlp, lp, h = _one_layer(config)
    r = jax.random.normal(jax.random.PRNGKey(9), h.shape)
    out, scalars = moe_mlp(h, lp, config, router_input=r)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense_experts(h, lp, config, jax.nn.silu, router_input=r)), atol=2e-5)
    own, own_scalars = moe_mlp(h, lp, config)
    assert not np.array_equal(np.asarray(scalars["load"]), np.asarray(own_scalars["load"]))
    np.testing.assert_array_equal(np.asarray(moe_mlp(h, lp, config, router_input=h)[0]), np.asarray(own))
    assert str(jax.make_jaxpr(lambda h: moe_mlp(h, lp, config)[0])(h)) == str(
        jax.make_jaxpr(lambda h: moe_mlp(h, lp, config, router_input=None)[0])(h))


# ------------------------------------------- the held layer's row-sum kernel

_ROUTINGS = ("even", "one-expert", "none-held", "zero-and-k")


def _held_case(routing, m, dtype, tokens=512, k=4, published=16, held=(4, 6)):
    """Inputs of `_held_experts` with the routing written by hand: (config,
    h, gates, experts, the three expert weights)."""
    config = MoEConfig(d_model=m, d_ff=128, n_experts=published, top_k=k, held_experts=held,
                       dtype=dtype)
    rng = np.random.default_rng(m + len(routing))
    first, last = held
    absent = np.array([e for e in range(published) if not first <= e < last])
    if routing == "even":  # every token draws k distinct experts, an eighth of the rows held
        experts = np.stack([rng.permutation(published)[:k] for _ in range(tokens)])
    elif routing == "one-expert":  # T k rows of one expert: four passes of the buffer
        experts = np.full((tokens, k), first + 1)
    elif routing == "none-held":
        experts = rng.choice(absent, (tokens, k))
    else:  # a token holds all k of its rows or none: k slots of a token in one group
        experts = np.where(rng.random((tokens, 1)) < 0.2, np.full((tokens, k), first),
                           rng.choice(absent, (tokens, k)))
    h = jnp.asarray(rng.standard_normal((tokens, m)), dtype)
    gates = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    n = last - first
    weights = tuple(jnp.asarray(0.05 * rng.standard_normal(shape), dtype)
                    for shape in ((n, m, 128), (n, m, 128), (n, 128, m)))
    return config, h, gates, jnp.asarray(experts, jnp.int32), weights


@pytest.mark.parametrize("m,dtype", [(2560, jnp.bfloat16), (2048, jnp.float32)],
                         ids=["m2560-bf16", "m2048-f32"])
@pytest.mark.parametrize("routing", _ROUTINGS)
def test_held_rows_summed_by_the_kernel_equal_the_scatter_add(routing, m, dtype):
    """`_held_experts` under "pallas" (the `moe_rows_sum` kernel for the
    combine, float32 out, and for the transpose of the dispatch's gather, the
    activations' dtype out; interpret mode here, with the grouped matmuls')
    against "xla" (`take`, `.at[].add`, `ragged_dot`): the output and the
    gradients with respect to h, the gates and the three expert weights,
    through the first pass and the `lax.cond` / `lax.scan` of the later ones.

    Tolerance. The kernel adds a token's at most k products in slot order in
    float32 where the scatter's order is the compiler's: k float32 roundings
    of the sum, so float32 cases agree to 1e-5 of the largest value (the two
    grouped matmuls differ by as much). In bfloat16 the experts' rows and
    every cotangent are rounded to 8 bits on both sides, and XLA's transpose
    of the gather adds a token's k rows in bfloat16 where the kernel adds in
    float32 and rounds once: 2 ** -6 of the largest value."""
    from ray_tpu.models import moe
    from ray_tpu.ops.grouped_matmul import gmm_tile_rows

    config, h, gates, experts, weights = _held_case(routing, m, dtype)

    def run(impl):
        def loss(h, gates, weights):
            out, report = moe._held_experts(
                h, gates, experts, weights, config, gmm_tile_rows(impl), impl)
            return jnp.sum(jnp.sin(out)), (out, report)

        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(h, gates, weights)

    ((_, (out, report)), grads), ((_, (want, want_report)), want_grads) = run("pallas"), run("xla")
    assert out.dtype == jnp.float32 and grads[0].dtype == dtype
    held = {"even": None, "one-expert": 512 * 4, "none-held": 0, "zero-and-k": None}[routing]
    if held is not None:
        assert float(report["moe_rows_held"]) == held
    assert float(report["moe_passes"]) == float(want_report["moe_passes"]) == (
        4 if routing == "one-expert" else 1)
    tolerance = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    for ours, theirs in zip(jax.tree.leaves((out, grads)), jax.tree.leaves((want, want_grads))):
        ours, theirs = np.asarray(ours, np.float32), np.asarray(theirs, np.float32)
        np.testing.assert_allclose(ours, theirs, atol=tolerance * max(np.abs(theirs).max(), 1e-6))
    if routing == "none-held":
        assert not np.asarray(out).any() and not np.asarray(grads[0], np.float32).any()
