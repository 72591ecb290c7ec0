"""The residual stream sharded over `tp` along the sequence
(parallel/sequence_parallel.py): where it engages and where it leaves the
program as it was, the two ring matmuls against the plain einsums, what a
four-device step's registry says of its `tp` collectives, the step against the
`tp` = 1 and the one-device step, and the plan's bytes. A CPU run gives values,
names and counts; no time is read here."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import transformer
from ray_tpu.models.configs import llama_tiny
from ray_tpu.parallel import MeshSpec, build_mesh, sequence_parallel as sp
from ray_tpu.train.lm import create_train_state, lm_loss, make_train_step
from ray_tpu.util import profiling

STEP = "jit_step_under_mesh"
TOKENS = np.random.default_rng(0).integers(0, 256, (8, 33)).astype(np.int32)


def _mesh(devices=4, **axes):
    return build_mesh(MeshSpec(**axes), devices=jax.devices()[:devices])


# ------------------------------------------------------- where it engages


@pytest.mark.parametrize("axes,devices,batch,seq,want", [
    (dict(fsdp=2, tp=2), 4, 8, 32, 2),
    (dict(tp=4), 4, 8, 32, 4),
    (dict(dp=2, tp=4), 8, 8, 32, 4),
    (dict(fsdp=4), 4, 8, 32, 1),            # tp = 1
    (dict(), 1, 8, 32, 1),                  # one device
    (dict(fsdp=2, tp=2), 4, 8, 33, 1),      # a sequence tp does not divide
    (dict(fsdp=2, tp=2), 4, 3, 32, 1),      # a batch the data axes do not divide
], ids=["2x2", "tp4", "dp2-tp4", "fsdp4", "one-device", "odd-sequence", "odd-batch"])
def test_the_stream_is_shared_by_tp_where_the_mesh_and_the_shape_allow(axes, devices, batch, seq, want):
    mesh = _mesh(devices, **axes)
    assert sp.stream_shards(mesh, batch, seq) == sp.stream_shards(mesh.abstract_mesh, batch, seq) == want


def test_no_mesh_and_a_manual_mesh_leave_the_stream_alone():
    assert sp.stream_shards(jax.sharding.get_abstract_mesh(), 8, 32) == 1
    mesh = _mesh(fsdp=2, tp=2)
    x = jnp.ones((8, 32, 16))
    assert sp.constrain_stream(x) is x                      # no context mesh
    seen = []

    def inside(x):      # somebody else's shard_map: the axes are manual there
        seen.append(sp.stream_shards(jax.sharding.get_abstract_mesh(), *x.shape[:2]))
        return sp.constrain_stream(x)

    jax.jit(jax.shard_map(inside, mesh=mesh, in_specs=P(("dp", "fsdp")), out_specs=P(("dp", "fsdp")),
                          check_vma=False))(x)
    assert seen == [1]


# ------------------------------------------------------- the two matmuls


@pytest.mark.parametrize("axes,devices", [(dict(fsdp=2, tp=2), 4), (dict(tp=4), 4), (dict(dp=2, tp=4), 8)],
                         ids=["2x2", "tp4", "dp2-tp4"])
@pytest.mark.parametrize("ordered", [True, False], ids=["in-sequence-order", "as-the-ring-delivers"])
def test_ring_matmuls_equal_the_plain_einsums_and_their_gradients(axes, devices, ordered):
    """gather-then-matmul of two weights off one gather, row-wise work, then
    matmul-then-scatter: values and gradients of all four operands against
    the plain einsums, with the results laid out as the docstrings say."""
    mesh = _mesh(devices, **axes)
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    h, w1 = jax.random.normal(k[0], (8, 32, 16)), jax.random.normal(k[1], (16, 24))
    w2, w3 = jax.random.normal(k[2], (16, 24)), jax.random.normal(k[3], (24, 16))

    def plain(h, w1, w2, w3):
        return jnp.einsum("bsf,fe->bse", jnp.tanh(jnp.einsum("bse,ef->bsf", h, w1))
                          * jnp.einsum("bse,ef->bsf", h, w2), w3)

    def ring(h, w1, w2, w3):
        h = sp.constrain_stream(h)
        up, gate = sp.column_parallel(h, ("bse,ef->bsf", w1), ("bse,ef->bsf", w2), ordered=ordered)
        if ordered:
            assert up.shape == (8, 32, 24)
            act = jnp.tanh(up) * gate
        else:       # a tuple of the ring's pieces each
            tp = mesh.shape["tp"]
            assert [piece.shape for piece in up] == [(8, 32 // tp, 24)] * tp
            act = [jnp.tanh(u) * g for u, g in zip(up, gate)]
        return sp.constrain_stream(sp.row_parallel("bsf,fe->bse", act, w3, ordered=ordered))

    def under_mesh(f):
        def call(*operands):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return f(*operands)
        shardings = tuple(NamedSharding(mesh, spec) for spec in (
            P(("dp", "fsdp"), None, None), P(None, "tp"), P(None, "tp"), P("tp", None)))
        return jax.jit(call, in_shardings=shardings)

    want, got = plain(h, w1, w2, w3), under_mesh(ring)(h, w1, w2, w3)
    data = mesh.shape["dp"] * mesh.shape["fsdp"]     # batch over the data axes, the sequence over tp
    assert got.sharding.shard_shape(got.shape) == (8 // data, 32 // mesh.shape["tp"], 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    loss = lambda f: lambda *operands: jnp.sum(jnp.sin(f(*operands)))      # noqa: E731
    want_grads = jax.grad(loss(plain), argnums=(0, 1, 2, 3))(h, w1, w2, w3)
    got_grads = under_mesh(jax.grad(loss(ring), argnums=(0, 1, 2, 3)))(h, w1, w2, w3)
    for got, want in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3, rtol=1e-3)


def test_heads_come_out_in_sequence_order_for_the_attention_kernel():
    mesh = _mesh(fsdp=2, tp=2)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    h, wq, wo = (jax.random.normal(k[0], (4, 16, 8)), jax.random.normal(k[1], (8, 4, 2)),
                 jax.random.normal(k[2], (4, 2, 8)))

    def ring(h, wq, wo):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            (q,) = sp.column_parallel(sp.constrain_stream(h), ("bse,ehd->bhsd", wq))
            return q, sp.row_parallel("bhsd,hde->bse", q, wo)

    shardings = tuple(NamedSharding(mesh, spec) for spec in (
        P(("dp", "fsdp"), None, None), P(None, "tp", None), P("tp", None, None)))
    q, out = jax.jit(ring, in_shardings=shardings)(h, wq, wo)
    assert q.sharding.shard_shape(q.shape) == (2, 2, 16, 2)      # batch over fsdp, heads over tp
    want = jnp.einsum("bse,ehd->bhsd", h, wq)
    np.testing.assert_allclose(np.asarray(q), np.asarray(want), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.einsum("bhsd,hde->bse", want, wo)),
                               atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------- the whole step


def _step(axes, devices, config=None):
    """(the jitted step of a tiny llama on the mesh given, its state, the mesh)."""
    config = config or llama_tiny().replace(remat=True)
    mesh = _mesh(devices, **axes)
    optimizer = optax.sgd(0.1)      # a parameter moves by its gradient: Adam's unit steps hide its size
    state, shardings = create_train_state(config, optimizer, jax.random.PRNGKey(0), mesh)
    return make_train_step(config, optimizer, mesh, state_shardings=shardings), state, mesh


def _two_steps(axes, devices, config=None):
    step, state, _ = _step(axes, devices, config)
    metrics = []
    for _ in range(2):
        state, scalars = step(state, {"tokens": jnp.asarray(TOKENS)})
        metrics.append((float(scalars["loss"]), float(scalars["grad_norm"])))
    return metrics, jax.tree.map(np.asarray, state.params)


@pytest.fixture(scope="module")
def one_device_steps():
    return _two_steps({}, 1)


@pytest.mark.parametrize("axes,devices", [(dict(fsdp=2, tp=2), 4), (dict(fsdp=4), 4), (dict(dp=2, tp=4), 8)],
                         ids=["fsdp2-tp2", "fsdp4-tp1", "dp2-tp4"])
def test_two_steps_equal_the_one_device_steps(one_device_steps, axes, devices):
    """Loss and gradient norm of both steps and every parameter after them
    (the second step's loss is computed from the first step's gradients)."""
    config = llama_tiny().replace(remat=True, n_kv_heads=4) if axes.get("tp") == 4 else None
    want_metrics, want_params = _two_steps({}, 1, config) if config else one_device_steps
    metrics, params = _two_steps(axes, devices, config)
    np.testing.assert_allclose(metrics, want_metrics, atol=1e-4, rtol=1e-4)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(want_params)):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("form", ["gpt2-bias-gelu-learned-positions", "fused-qkv"])
def test_the_dense_blocks_other_forms_on_fsdp2_tp2_equal_one_device(form):
    """Biases, gelu (one column-parallel matmul in the MLP) and learned
    positions; and the fused q/k/v projection, which takes the gathered
    stream by a constraint and its own einsum."""
    from ray_tpu.models.configs import gpt2_tiny

    config = gpt2_tiny() if form.startswith("gpt2") else llama_tiny().replace(remat=True, fused_qkv=True)
    want_metrics, want_params = _two_steps({}, 1, config)
    metrics, params = _two_steps(dict(fsdp=2, tp=2), 4, config)
    np.testing.assert_allclose(metrics, want_metrics, atol=1e-4, rtol=1e-4)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(want_params)):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def _registry(axes, devices):
    step, state, mesh = _step(axes, devices)
    compiled = step.lower(state, {"tokens": jnp.asarray(TOKENS)}).compile()
    text = profiling._module_text(compiled)
    table = profiling.program_ops_table(text)[1]
    found = profiling.program_collectives_table(
        text, tuple(mesh.shape.items()), profiling._module_shapes_text(compiled))
    return {(record.kind, record.axes, scope, table[name][0][1], record.bytes)
            for name, record in found.items() if not record.completes for scope in table[name][0][0]}


BLOCK_SCOPES = ("attn.out", "attn.proj", "mlp")


def test_a_step_on_fsdp2_tp2_holds_no_tp_all_reduce_in_its_blocks_and_permutes_in_their_place():
    where = _registry(dict(fsdp=2, tp=2), 4)
    config = llama_tiny()
    stream = TOKENS.shape[0] // 2 * (TOKENS.shape[1] - 1) * config.d_model * 4      # a device's rows, whole over tp
    # no sum of an activation over tp (the norms' scales sum their gradients over the rows: d_model numbers each)
    reduced = {(scope, p, size) for kind, axes, scope, p, size in where
               if kind == "all-reduce" and "tp" in axes and scope in BLOCK_SCOPES}
    assert all(size <= 4 * config.d_model * 4 for _, _, size in reduced), reduced
    moved = {(scope, p) for kind, axes, scope, p, size in where
             if kind in ("collective-permute", "reduce-scatter", "all-gather") and axes == ("tp",)
             and size == stream // 2}
    # the gathers in front of q, k, v and of up, gate; the scatters behind the two row-parallel
    # matmuls; the backward pass mirrors both, and the recomputed block gathers again
    assert {(scope, p) for scope in BLOCK_SCOPES for p in ("fwd", "bwd")} <= moved
    assert ("attn.proj", "recompute") in moved


def test_a_step_on_fsdp4_tp1_holds_no_tp_collective():
    assert not [row for row in _registry(dict(fsdp=4), 4) if "tp" in row[1]]


def _as_before(monkeypatch):
    """The three functions as the plain operations they stand for."""
    monkeypatch.setattr(transformer, "constrain_stream", lambda x, whole=False: x)
    monkeypatch.setattr(
        transformer, "column_parallel", lambda h, *matmuls, ordered=True: [
            y if ordered else (y,) for y in (jnp.einsum(eq, h, w.astype(h.dtype)) for eq, w in matmuls)])
    monkeypatch.setattr(
        transformer, "row_parallel", lambda eq, a, w, ordered=True: jnp.einsum(
            eq, a if ordered else a[0], w.astype((a if ordered else a[0]).dtype)))


def _step_text(axes, devices):
    step, state, _ = _step(axes, devices)
    return step.lower(state, {"tokens": jnp.asarray(TOKENS)}).as_text()


def test_on_a_mesh_with_tp_1_the_lowered_step_is_the_text_it_was(monkeypatch):
    mine = _step_text(dict(fsdp=4), 4)
    _as_before(monkeypatch)
    assert mine == _step_text(dict(fsdp=4), 4)


def test_on_a_mesh_with_tp_2_the_lowered_step_is_another(monkeypatch):
    mine = _step_text(dict(fsdp=2, tp=2), 4)
    _as_before(monkeypatch)
    assert mine != _step_text(dict(fsdp=2, tp=2), 4) and "collective_permute" in mine


def test_with_no_mesh_the_lowered_gradient_is_the_text_it_was(monkeypatch):
    config = llama_tiny().replace(remat=True)
    params = transformer.init_params(config, jax.random.PRNGKey(0))

    def text():     # a new function each time: jit keys its cache on the function object
        return jax.jit(jax.grad(lambda p, t: lm_loss(p, t, config)[0])).lower(params, TOKENS).as_text()

    mine = text()
    _as_before(monkeypatch)
    assert mine == text()


# ---------------------------------------------------------- the plan's bytes


@pytest.mark.parametrize("tp", [1, 2])
def test_the_plan_counts_the_stream_at_its_share_a_device(tp):
    """Mistral-7B's widths: under tp = 2 a kept `attn_residual`, the two
    sublayers' outputs and residuals and a block's input are half the stream a
    device; the gathered norms' outputs and the projections are not; under
    tp = 1 everything is as it was."""
    import os

    from benchmark import model_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = model_config.transformer_config(model_config.load_config(
        os.path.join(root, "benchmark/configs/mistral-7b-v0.3-train-4chip.json")))
    assert (config.d_model, config.n_heads, config.kv_heads, config.d_ff, config.n_layers) == (4096, 32, 8, 14336, 8)
    split = lambda weight: tp      # noqa: E731 - heads, kv_heads, mlp and the stream over tp
    costs = transformer.block_costs(config, 1024, split)
    e, q, kv, f = 4096, 4096 // tp, 1024 // tp, 14336 // tp
    (run,) = costs["runs"]
    assert run["stream_split"] == tp
    assert run["width"] == (e + 2 * e // tp + 2 * q + 2 * kv) + (e + 2 * e // tp + 3 * f)
    by_name = {c.names: c for c in costs["candidates"]}
    residual = by_name["attn_residual",]
    assert (residual.width, residual.tp_sum, residual.flops) == (e // tp, tp > 1, 2 * q * e)
    assert by_name["mlp_up",].width == by_name["mlp_gate",].width == f
    assert costs["flops"] == 8 * (2 * e * (q + 2 * kv) + 4 * q * 512 + 2 * q * e + 6 * e * f)
