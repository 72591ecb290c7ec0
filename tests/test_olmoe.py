"""OLMoE on the normal path (LMTrainer -> make_train_step -> the dropless
MoE layer) against the plain reference, benchmark/reference/olmoe_ref.py,
at a small size on the CPU, and the grouped-matmul kernels in interpret
mode against `ragged_dot`.

Tolerances, each with its reason:

LOGITS_REL_RMS = 1e-4, GRAD_REL_RMS = 1e-3. Both sides compute in
  float32 here (XLA:CPU's float32 dot is exact float32), so what is left
  is the order of summation: ~1e-6 a matmul. Logits measured 2e-6 to 4e-6,
  gradients up to 2e-5 (the router's, through the softmax); the limits
  are thirty times that, and a wrong gate rule, a dropped token or a
  missing QK-norm is off by 1e-2 or more.
BF16_LOGITS_REL_RMS = 0.02. bfloat16 compute (8 bits of mantissa) against
  the float32 reference through two layers measured 0.52% to 0.60% over
  three seeds, with the experts scaled to be as loud as the residual
  stream; weights rounded to float8 (3 bits of mantissa) or the gates
  renormalised (another gate rule) measured 5% to 7%.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmoe_ref, transformer_ref
from ray_tpu.models import configs, model_family, moe
from ray_tpu.models.moe import MoEConfig
from ray_tpu.models.transformer import lm_head_weights
from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.parallel import MeshSpec, build_mesh, default_rules, shard_tree
from ray_tpu.train.lm import lm_loss
from ray_tpu.train.trainer import LMTrainer

LOGITS_REL_RMS = 1e-4
GRAD_REL_RMS = 1e-3
BF16_LOGITS_REL_RMS = 0.02


def tiny_olmoe(top_k=2, **kw) -> MoEConfig:
    sizes = dict(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=32,
        max_seq=64, pos_emb="rope", norm="rmsnorm", act="swiglu", use_bias=False,
        tie_embeddings=False, qk_norm=True, norm_eps=1e-5, dtype=jnp.float32,
        n_experts=8, top_k=top_k, norm_topk_prob=False)
    return MoEConfig(**{**sizes, **kw})


def _arch(c: MoEConfig):
    return {"top_k": c.top_k, "norm_topk_prob": c.norm_topk_prob,
            "rope_theta": c.rope_theta, "norm_eps": c.norm_eps}


def _rel_rms(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def _forward(params, tokens, config):
    """(logits, the routers' summed auxiliary loss), as the step's dense head computes them."""
    hidden, routers = moe.forward_hidden(params, tokens, config)
    logits = jnp.einsum("bse,ev->bsv", hidden, lm_head_weights(params, config))
    return logits, routers["router_aux_loss"]


def _seeded(config, seed=0, batch=2, seq=32):
    params = moe.init_params(config, jax.random.PRNGKey(seed))
    # the router's N(0, 0.02) init makes near-uniform probabilities; a wider
    # one makes the choice of experts matter to the output
    params["blocks"]["router"] = params["blocks"]["router"] * 20.0
    params["blocks"]["q_norm_scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), params["blocks"]["q_norm_scale"].shape)
    tokens = jnp.asarray(
        np.random.default_rng(seed).integers(0, config.vocab_size, size=(batch, seq + 1)), jnp.int32)
    return params, tokens


@pytest.mark.parametrize("top_k,norm_topk", [(2, False), (3, False), (2, True)],
                         ids=["top2", "top3", "top2-renormalised"])
def test_forward_and_every_gradient_leaf_equal_the_reference(top_k, norm_topk):
    config = tiny_olmoe(top_k, norm_topk_prob=norm_topk)
    params, tokens = _seeded(config, seed=top_k)
    logits, aux = jax.jit(functools.partial(_forward, config=config))(params, tokens[:, :-1])
    ref_logits, ref_aux, _ = jax.jit(functools.partial(olmoe_ref.forward, **_arch(config)))(
        params, tokens[:, :-1])
    assert _rel_rms(logits, ref_logits) <= LOGITS_REL_RMS
    assert float(aux) == pytest.approx(float(ref_aux), rel=1e-5)

    grads = jax.jit(jax.grad(lambda p: lm_loss(p, tokens, config)[0]))(params)
    ref_grads = jax.jit(jax.grad(lambda p: olmoe_ref.objective(
        p, tokens, router_aux_loss_coef=config.router_aux_coeff, **_arch(config))[0]))(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    assert len(flat) == len(ref_flat)
    for path, grad in flat:
        assert float(jnp.linalg.norm(ref_flat[path])) > 0, path
        assert _rel_rms(grad, ref_flat[path]) <= GRAD_REL_RMS, jax.tree_util.keystr(path)


@pytest.mark.parametrize("case", ["two-experts-take-every-token", "an-expert-takes-none"])
def test_dropless_path_drops_nothing_whatever_the_routing(case):
    """Capacity would drop tokens here (GShard at factor 1.25 keeps 20 of
    the 64 rows an expert is sent); the dropless layer computes every
    (token, choice) row and equals the reference's sum, which applies
    every expert to every token."""
    config = tiny_olmoe(top_k=2)
    params, _ = _seeded(config, seed=5)
    lp = {k: v[0] for k, v in params["blocks"].items()}
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 32, config.d_model))
    logits = jnp.einsum("bsm,me->bse", h, lp["router"])
    if case == "two-experts-take-every-token":
        logits = logits.at[..., :2].add(30.0)     # experts 0 and 1 are every token's top two
    else:
        logits = logits.at[..., 3].add(-30.0)     # expert 3 is nobody's
    probs = jax.nn.softmax(logits, -1)
    weights = (lp["we_gate"], lp["we_up"], lp["we_down"])
    out, load, _ = moe._dropless_shard(h, probs, weights, config)
    load = np.asarray(load)
    assert load.sum() == 2 * 32 * config.top_k
    if case == "two-experts-take-every-token":
        assert load[0] == 64 and load[1] == 64 and load[2:].sum() == 0
    else:
        assert load[3] == 0

    gates, _ = olmoe_ref._gates(probs, config.top_k, config.norm_topk_prob)
    want = sum(
        gates[..., e, None]
        * ((jax.nn.silu(h @ lp["we_gate"][e]) * (h @ lp["we_up"][e])) @ lp["we_down"][e])
        for e in range(config.n_experts))
    assert _rel_rms(out, want) <= LOGITS_REL_RMS
    # the gradient reaches a loaded expert's weights and is exactly zero for an empty one's
    grad = jax.jit(jax.grad(lambda w: jnp.sum(
        moe._dropless_shard(h, probs, (weights[0], w, weights[2]), config)[0] ** 2)))(lp["we_up"])
    assert float(jnp.abs(grad[int(np.argmax(load))]).max()) > 0
    assert float(jnp.abs(grad[int(np.argmin(load))]).max()) == 0


def test_lmtrainer_first_step_is_the_reference_loss_and_reports_the_routers():
    """LMTrainer(MoEConfig) builds the MoE state and runs the dropless
    layer through make_train_step (here on a dp=8 mesh of virtual devices:
    no `ep` axis, so the dropless form, every device on its own rows): the first
    step's `loss` is the reference's cross entropy, `router_aux_loss` its
    load-balancing loss."""
    config = tiny_olmoe(top_k=2)
    trainer = LMTrainer(config, mesh_spec=MeshSpec(dp=8), total_steps=10, seed=3)
    assert "we_gate" in trainer.state.params["blocks"] and "w_up" not in trainer.state.params["blocks"]
    params0 = jax.tree.map(jnp.copy, trainer.state.params)
    tokens = np.random.default_rng(3).integers(0, 256, size=(8, 33)).astype(np.int32)
    out = trainer.train(iter([{"tokens": tokens}] * 3), num_steps=1, report_every=1)
    _, parts = jax.jit(functools.partial(
        olmoe_ref.objective, router_aux_loss_coef=config.router_aux_coeff, **_arch(config)))(
        params0, jnp.asarray(tokens))
    assert out["loss"] == pytest.approx(float(parts["cross_entropy"]), abs=1e-5)
    assert out["router_aux_loss"] == pytest.approx(float(parts["router_aux"]), rel=1e-5)
    assert 1.0 <= out["moe_load_max_over_mean"] <= config.n_experts
    attrs = trainer._step_fn_span.to_dict()["attrs"]
    assert attrs["moe_impl"] == "ragged_dot" and attrs["moe_experts"] == 8 and attrs["moe_top_k"] == 2
    assert attrs["moe_rows_per_step"] == 8 * 32 * 2 and attrs["moe_gmm_tile_rows"] == 1
    assert attrs["loss_chunk"] == 0
    # the loss falls, and the chunked head (the one the cell runs) gives the same first step
    later = trainer.train(iter([{"tokens": tokens}] * 8), num_steps=8, report_every=8)
    assert later["loss"] < out["loss"]
    chunked = LMTrainer(config, mesh_spec=MeshSpec(dp=8), total_steps=10, seed=3,
                        loss_chunk=16)
    first = chunked.train(iter([{"tokens": tokens}]), num_steps=1, report_every=1)
    assert first["loss"] == pytest.approx(out["loss"], abs=1e-5)
    assert chunked._step_fn_span.to_dict()["attrs"]["loss_chunk"] == 16


def test_chunked_head_step_of_the_tiny_olmoe_is_the_dense_heads_step():
    """The head the cell runs (`loss_chunk` > 0: the head's gradients
    computed in each chunk's forward pass) against the dense head through
    the whole step, expert layer and routers' loss included: the same loss,
    gradient norm and updated parameters (plain SGD, so that the update is
    the gradient)."""
    import optax

    config = tiny_olmoe(top_k=2)
    tokens = np.random.default_rng(4).integers(0, 256, size=(8, 33)).astype(np.int32)

    def one_step(loss_chunk):
        trainer = LMTrainer(config, mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2), seed=3,
                            optimizer=optax.sgd(0.1), loss_chunk=loss_chunk)
        out = trainer.train(iter([{"tokens": tokens}]), num_steps=1, report_every=1)
        return out, trainer.state.params

    dense, dense_params = one_step(0)
    chunked, chunked_params = one_step(16)
    for key in ("loss", "grad_norm", "router_aux_loss"):
        assert chunked[key] == pytest.approx(dense[key], rel=1e-5), key
    for (path, got), want in zip(jax.tree_util.tree_flatten_with_path(chunked_params)[0],
                                 jax.tree.leaves(dense_params)):
        assert _rel_rms(got, want) <= LOGITS_REL_RMS, jax.tree_util.keystr(path)


@pytest.mark.parametrize("axes,context_mesh", [
    (dict(dp=2, tp=2), True), (dict(fsdp=2, tp=2), False), (dict(dp=2, fsdp=2, sp=2), True)],
    ids=["dp2-tp2-context-mesh", "fsdp2-tp2-weights-mesh", "dp2-fsdp2-sp2-context-mesh"])
def test_dropless_layer_on_a_mesh_is_the_one_device_layer_per_shard(axes, context_mesh):
    """Under a mesh without `ep` every device sorts and computes its own
    tokens with its tp slice of every expert (one shard_map around the
    layer, nothing left to GSPMD that it cannot partition): the objective,
    the routers' scalars and EVERY gradient leaf equal the one-device
    values, whether the mesh is the context's or the weights'."""
    import contextlib

    config = tiny_olmoe(top_k=2)
    params, tokens = _seeded(config, seed=11, batch=4)
    fn = jax.value_and_grad(lambda p: lm_loss(p, tokens, config), has_aux=True)
    # a function of its own: the trace below has to see the sharded weights' mesh
    (want, want_scalars), want_grads = jax.jit(lambda p: fn(p))(params)

    mesh = build_mesh(MeshSpec(**axes), devices=jax.devices()[:int(np.prod(list(axes.values())))])
    sharded = shard_tree(params, moe.logical_axes(config), default_rules(), mesh)
    with jax.set_mesh(mesh) if context_mesh else contextlib.nullcontext():
        traced = str(jax.make_jaxpr(fn)(sharded))
        (got, scalars), grads = jax.jit(fn)(sharded)
    assert "shard_map" in traced and "ragged_dot" in traced
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for key in ("loss", "router_aux_loss", "moe_load_max_over_mean"):
        assert float(scalars[key]) == pytest.approx(float(want_scalars[key]), rel=1e-5), key
    for (path, grad), want_grad in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                                       jax.tree.leaves(want_grads)):
        assert _rel_rms(grad, want_grad) <= GRAD_REL_RMS, jax.tree_util.keystr(path)


def test_bfloat16_compute_stays_near_the_reference_and_a_lower_precision_does_not():
    config = tiny_olmoe(top_k=2, dtype=jnp.bfloat16)
    params, tokens = _seeded(config, seed=7)
    # experts as loud as the residual stream, so that they are judged
    params["blocks"]["we_down"] = params["blocks"]["we_down"] * 10.0
    ref_logits = jax.jit(functools.partial(olmoe_ref.forward_logits, **_arch(config)))(params, tokens[:, :-1])

    def off(params, config):
        logits, _ = _forward(params, tokens[:, :-1], config)
        return _rel_rms(logits.astype(jnp.float32), ref_logits)

    assert off(params, config) <= BF16_LOGITS_REL_RMS
    # weights rounded to float8 (3 bits of mantissa), or another gate rule, are outside
    float8 = jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), params)
    assert off(float8, config) > BF16_LOGITS_REL_RMS
    assert off(params, config.replace(norm_topk_prob=True)) > BF16_LOGITS_REL_RMS


def test_norm_eps_on_the_dense_mistral_block_equals_the_reference_at_that_value():
    """`norm_eps` reaches every RMSNorm of the dense block: small inputs
    (embedding scale 1e-3) make 1e-5 against 1e-6 a visible difference."""
    config = configs.llama_tiny().replace(n_layers=2, norm_eps=1e-5)
    params = model_family(config).init_params(config, jax.random.PRNGKey(0))
    params["wte"] = params["wte"] * 0.05
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, size=(2, 24)), jnp.int32)
    from ray_tpu.models import forward

    logits = forward(params, tokens, config)
    at_published, at_default = (
        jax.jit(functools.partial(transformer_ref.forward_logits, family="mistral", norm_eps=eps))(params, tokens)
        for eps in (1e-5, 1e-6))
    assert _rel_rms(logits, at_published) <= LOGITS_REL_RMS
    assert _rel_rms(at_default, at_published) > 10 * LOGITS_REL_RMS
    # and None keeps the old arithmetic
    assert _rel_rms(forward(params, tokens, config.replace(norm_eps=None)), at_default) <= LOGITS_REL_RMS


@pytest.mark.parametrize("sizes", [(16, 0, 8, 24), (1, 0, 0, 9), (13, 7, 1, 3)],
                         ids=["tile-multiples-and-empty", "one-and-zero", "no-multiple-of-the-tile"])
def test_grouped_matmul_kernels_equal_ragged_dot_through_the_layout(sizes):
    """The three Pallas kernels in interpret mode, fed as the MoE layer
    feeds them (dropless_layout pads every group to a positive multiple of
    the tile), against `ragged_dot` on the unpadded rows: the forward and
    both backward products."""
    tile, k, n = 8, 128, 256
    experts = np.repeat(np.arange(len(sizes)), sizes)
    np.random.default_rng(0).shuffle(experts)
    experts = jnp.asarray(experts[:, None], jnp.int32)           # (T, 1): top-1 rows
    rows = int(sum(sizes))
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), k, n)) * 0.1

    def through(impl, tile_rows):
        def fn(x, w):
            layout = moe.dropless_layout(experts, len(sizes), tile_rows)
            assert np.asarray(layout.sizes).tolist() == list(sizes)
            lhs = moe._take_rows(x, layout.slot_row, layout.row_slot[:, None])
            out = grouped_matmul(lhs, w, layout.padded_sizes, tile_rows=tile_rows,
                                 implementation=impl, interpret=True)
            y = moe._take_rows(out, layout.row_slot, layout.slot_row[:, None])
            return jnp.sum(jnp.sin(y)), y
        return jax.value_and_grad(fn, argnums=(0, 1), has_aux=True)(x, w)

    (_, y_ref), (dx_ref, dw_ref) = through("xla", 1)
    (_, y), (dx, dw) = through("pallas", tile)
    # direct: row r times its expert's matrix
    direct = jnp.einsum("rk,rkn->rn", x, w[experts[:, 0]])
    for got, want in ((y_ref, direct), (y, direct), (dx, dx_ref), (dw, dw_ref)):
        assert _rel_rms(got, want) <= LOGITS_REL_RMS
    for e, size in enumerate(sizes):
        if size == 0:
            assert float(jnp.abs(dw[e]).max()) == 0.0      # an empty group's block is written, as zeros


V5E_HBM = int(15.75 * 2 ** 30)


@pytest.mark.parametrize("cell,batch,seq,vocab,state_gb,step_gb,want", [
    # a device's state; its gradients and what its blocks hold when the head runs; readings:
    # PERF.md section 6, PR 46 (chip). Dense 143,573 tokens/s, the whole sequence 149,395
    ("train-gpt2s", 24, 1024, 50257, 1.49, 8.65, 1024),
    # a device's rows and its half of the vocabulary; one chunk read -0.03% against dense (PR 28)
    ("train-mistral7b-fsdp2tp2", 12, 1024, 16384, 6.04, 2.82, 1024),
    # 2,048 rows 85,961 tokens/s at 76.3% of the chip, the whole 4,096 87,462 at 77.9%
    ("train-olmoe-64e-4k", 4, 4096, 50304, 7.51, 2.50, 4096),
    # the whole sequence would run at the compiler's ceiling: 140,453 against 148,833
    ("gpt2s-at-batch-32", 32, 1024, 50257, 1.49, 11.37, 512),
    # the largest chunk whose own logits fit the room beside what is held
    ("a-200k-vocabulary-at-4k", 8, 4096, 200192, 7.5, 2.5, 1024),
    ("no-chunk-fits-so-the-smallest", 64, 1024, 200192, 10.5, 3.5, 128),
])
def test_head_takes_the_largest_chunk_that_fits_beside_what_is_held(
        cell, batch, seq, vocab, state_gb, step_gb, want):
    """On a device of known size the head is the fused one (no shipped cell
    keeps the dense head it ran before PR 46), its chunk the largest
    candidate, the whole sequence first, whose logits at 3 bytes each leave
    6.5% of the device free beside what the caller counts as held. Nothing
    live is read: the same numbers, the same form."""
    from ray_tpu.ops.losses import auto_loss_chunk

    assert auto_loss_chunk(batch, seq, vocab, V5E_HBM, resident_bytes=int(state_gb * 1e9),
                           step_bytes=int(step_gb * 1e9)) == want


def test_loss_chunk_for_counts_a_devices_share_of_the_state_from_the_shardings(monkeypatch):
    """`make_train_step(...).loss_chunk_for(shape, state)`: what a device
    holds beside the logits is its share of the state and of the gradients,
    and the logits are its rows' over its share of the vocabulary, read from
    the shardings and not from live memory. With room for the whole
    sequence's logits and HALF the state, one device (the whole state) takes
    the smaller chunk and a device of fsdp=2 x tp=2 (a quarter) the whole
    sequence."""
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import create_train_state, default_optimizer, make_train_step

    config = tiny_olmoe(top_k=2, max_seq=256)
    opt = default_optimizer(1e-3, total_steps=10)
    shape = (8, 257)

    def chunk_on(spec, room_for_state):
        mesh = build_mesh(spec, devices=jax.devices()[:spec.num_devices])
        state, shardings = create_train_state(config, opt, jax.random.PRNGKey(0), mesh)
        step = make_train_step(config, opt, mesh, state_shardings=shardings)
        whole = sum(x.nbytes for x in jax.tree.leaves((state, state.params)))
        logits = losses.loss_logits_bytes(
            shape[0] // (spec.dp * spec.fsdp), 256, config.vocab_size // spec.tp, 256)
        monkeypatch.setattr(losses, "device_hbm_bytes", lambda: int(
            (logits + room_for_state * whole) / (1 - losses.HBM_FREE_FRACTION)))
        return step.loss_chunk_for(shape, state)

    assert chunk_on(MeshSpec(), 0.5) == 128
    assert chunk_on(MeshSpec(), 1.5) == 256
    assert chunk_on(MeshSpec(fsdp=2, tp=2), 0.5) == 256
