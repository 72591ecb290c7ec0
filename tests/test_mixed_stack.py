"""The stack of more than one kind of layer (models/mixed_stack.py) and what
it asks of the parts it is built from: the runs, the block against the plain
reference of the family that runs it (benchmark/reference/afmoe_ref.py), the
expert layer told which experts it holds, the router's forms."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import MixedStackConfig, model_family, moe
from ray_tpu.models.mixed_stack import LayerKind, Run, layer_kinds, stack_runs
from ray_tpu.models.transformer import lm_head_weights
from ray_tpu.train.lm import lm_loss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny(**kw) -> MixedStackConfig:
    """2 dense + 4 expert layers (dS dS eS eF eS eS), 32 experts top-4 of
    which 8 are held, a window shorter than the sequence, 4 heads of 8 over a
    model of 64 (so head_dim is not d_model / n_heads), float32."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=6, n_heads=4, n_kv_heads=2, d_head=8, d_ff=32,
        d_ff_dense=96, max_seq=64, pos_emb="rope", norm="rmsnorm", act="swiglu", use_bias=False,
        tie_embeddings=False, norm_eps=1e-5, dtype=jnp.float32, remat=True,
        qk_norm_per_head=True, attn_gate=True, sandwich_norm=True, scale_embedding=True,
        sliding_window=16, global_attn_every=4, n_dense_layers=2, n_experts=32,
        held_experts=(0, 8), top_k=4, norm_topk_prob=True,
        route_scale=2.826, router_score="sigmoid", router_select_bias=True,
        shared_expert_width=32, router_aux_coeff=0.0, frozen_leaves=("router",))
    return MixedStackConfig(**{**base, **kw})


def arch(config):
    return dict(global_attn_every=config.global_attn_every, num_dense_layers=config.n_dense_layers,
                sliding_window=config.sliding_window, rope_theta=config.rope_theta,
                norm_eps=config.norm_eps, top_k=config.top_k, route_scale=config.route_scale,
                held_experts=config.held_experts, frozen_leaves=config.frozen_leaves)


def seeded(config, seed=0):
    """Parameters with every norm weight off 1, a NON-zero selection bias and
    weights large enough that the gates and the softmax are not flat."""
    params = model_family(config).init_params(config, jax.random.PRNGKey(seed))

    def shake(path, w):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), len(name) + int(w.size))
        if "expert_bias" in name:
            return 0.3 * jax.random.normal(key, w.shape)
        if "scale" in name:
            return w + 0.1 * jax.random.normal(key, w.shape)
        return 3.0 * w

    return jax.tree_util.tree_map_with_path(shake, params)


def _said(runs):
    """`2 x (dS) | eS eF eS eS`: a run as its repeats times its period."""
    period = lambda run: " ".join(k.code for k in run.kinds)  # noqa: E731
    return " | ".join(f"{run.repeats} x ({period(run)})" if run.repeats > 1 else period(run)
                      for run in runs)


@pytest.mark.parametrize("depth,dense,every,want", [
    (6, 2, 4, "2 x (dS) | eS eF eS eS"),
    (32, 2, 4, "2 x (dS) | 7 x (eS eF eS eS) | eS eF"),
    (8, 0, 1, "8 x (eF)"),
    (5, 5, 2, "2 x (dS dF) | dS")],
    ids=["the-cell", "the-published-depth", "all-alike", "dense-with-a-remainder"])
def test_layers_group_into_runs_of_a_period(depth, dense, every, want):
    config = tiny(n_layers=depth, n_dense_layers=dense, global_attn_every=every)
    kinds = layer_kinds(config)
    runs = stack_runs(kinds)
    assert _said(runs) == want
    # the runs are the stack, in order
    assert [k for run in runs for _ in range(run.repeats) for k in run.kinds] == kinds
    assert all(isinstance(run, Run) and isinstance(k, LayerKind) for run in runs for k in run.kinds)
    # compile time grows with the period: no run's body holds more than the pattern
    assert max(len(run.kinds) for run in runs) <= every


def test_parameters_are_stacked_a_run_and_mirror_their_axes():
    config = tiny()
    family = model_family(config)
    params = jax.eval_shape(lambda: family.init_params(config, jax.random.PRNGKey(0)))
    axes = family.logical_axes(config)
    is_axes = lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)  # noqa: E731
    flat_axes = jax.tree.leaves(axes, is_leaf=is_axes)
    flat = jax.tree.leaves(params)
    assert len(flat) == len(flat_axes) and all(len(a) == x.ndim for a, x in zip(flat_axes, flat))
    dense, experts = params["runs"]
    assert dense[0]["w_up"].shape == (2, 64, 96) and "router" not in dense[0]
    assert experts[1]["we_up"].shape == (1, 8, 64, 32)          # 8 of the 32 experts held
    assert experts[1]["router"].shape == (1, 64, 32) and experts[1]["expert_bias"].shape == (1, 32)
    assert dense[0]["wq"].shape == (2, 64, 4, 8) and dense[0]["q_norm_scale"].shape == (2, 8)


def _expert_layer(config, key=2):
    """One expert layer's parameters with ALL the published experts, and
    normed activations to feed it."""
    whole = dataclasses.replace(config, held_experts=None, n_layers=1, n_dense_layers=0,
                                global_attn_every=1)
    lp = jax.tree.map(lambda w: w[0], seeded(whole, key)["runs"][0][0])
    h = jax.random.normal(jax.random.PRNGKey(key + 1), (2, 24, config.d_model))
    return whole, lp, h


def _reference_mlp(h, lp, config, held):
    """The plain reference's expert layer on normed activations: the shared
    expert and every one of the `held` experts on every token, gated."""
    from benchmark.reference import afmoe_ref

    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(h @ lp["router"])
        gates, _ = afmoe_ref._gates(scores, lp["expert_bias"], config.top_k, config.route_scale)
        return afmoe_ref._swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"]) + sum(
            gates[..., e, None] * afmoe_ref._swiglu(h, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e])
            for e in range(held))


def _glm47flash_family():
    """Latent attention's family in small (tests/test_latent_attention.py) with
    its published 64 routed experts: eight shares of 8, top-4, gates x 1.8."""
    from test_latent_attention import tiny_latent

    return tiny_latent(n_experts=64, mtp_modules=0)


@pytest.mark.parametrize("family, shares", [(tiny, 4), (_glm47flash_family, 8)],
                         ids=["afmoe-4-shares-of-32", "glm4_moe_lite-8-shares-of-64"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(family, shares):
    """The routed parts of the chips' shares of one layer (8 experts each),
    plus the shared expert once, are the uncut layer's output: every share
    routes over all the published experts with its family's router,
    normalises the gates over all 4 chosen, and computes the chosen experts it
    holds."""
    whole, lp, h = _expert_layer(family())
    assert whole.n_experts == 8 * shares
    uncut, scalars = jax.jit(functools.partial(moe.moe_mlp, config=whole))(h, lp)
    total, rows = 0.0, 0.0
    for share in range(shares):
        first = 8 * share
        config = dataclasses.replace(whole, held_experts=(first, first + 8),
                                     shared_expert_width=32 if share == 0 else 0)
        held = dict(lp, **{name: lp[name][first: first + 8] for name in ("we_gate", "we_up", "we_down")})
        part, part_scalars = jax.jit(functools.partial(moe.moe_mlp, config=config))(h, held)
        total, rows = total + part, rows + part_scalars["moe_rows_held"]
        np.testing.assert_array_equal(np.asarray(part_scalars["load"]), np.asarray(scalars["load"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)
    assert float(rows) == 2 * 24 * 4            # every (token, choice) row lies on exactly one chip
    # and the uncut layer is the reference's
    reference = jax.jit(functools.partial(_reference_mlp, config=whole, held=8 * shares))(h, lp)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(reference), atol=2e-5)


@pytest.mark.parametrize("sent_here", ["every-choice", "none", "as-routed"])
def test_no_row_routed_to_a_held_expert_is_dropped(sent_here):
    """Whatever the routing: a selection bias that sends EVERY token's every
    choice to the held experts fills the buffer `held_passes_most` times over
    (2 passes of T k / 2 rows here) and is exact, forward and gradient, against
    every held expert applied to every token; one that sends none computes
    the shared expert alone."""
    config = tiny()
    whole, lp, h = _expert_layer(config)
    bias = {"every-choice": jnp.where(jnp.arange(32) < 8, 10.0, 0.0),
            "none": jnp.where(jnp.arange(32) < 8, -10.0, 0.0), "as-routed": lp["expert_bias"]}[sent_here]
    held = dict(lp, expert_bias=bias,
                **{name: lp[name][:8] for name in ("we_gate", "we_up", "we_down")})
    config = dataclasses.replace(whole, held_experts=(0, 8))
    tokens = h.shape[0] * h.shape[1]
    assert moe.held_buffer_rows(config, tokens, 1) == tokens * 4 // 2
    assert moe.held_passes_most(config, tokens, 1) == 2

    def ours(h, lp):
        out, scalars = moe.moe_mlp(h, lp, config)
        return jnp.sum(out * jnp.cos(out)), (out, scalars)

    def theirs(h, lp):
        out = _reference_mlp(h, lp, config, 8)
        return jnp.sum(out * jnp.cos(out)), out

    (_, (out, scalars)), grads = jax.jit(jax.value_and_grad(ours, (0, 1), has_aux=True))(h, held)
    (_, want), want_grads = jax.jit(jax.value_and_grad(theirs, (0, 1), has_aux=True))(h, held)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)
    rows = {"every-choice": tokens * 4, "none": 0}.get(sent_here)
    if rows is not None:
        assert float(scalars["moe_rows_held"]) == rows
        assert float(scalars["moe_passes"]) == (2 if rows else 1)
    else:
        assert 0 < float(scalars["moe_rows_held"]) < tokens * 4


def _held_layer(expert_act="swiglu", tokens_here=48):
    """A held layer of 8 of 64 experts, top-4, so that the buffer of twice the
    even share (T k / 4 rows) holds every routing in `held_passes_most` = 4
    passes; the first `tokens_here` tokens send all four choices to held
    experts, the others none. -> (inputs (h, gates, weights), the layer's
    value, (output, report) and gradients as a function of the inputs)."""
    config = moe.MoEConfig(d_model=32, d_ff=16, n_experts=64, top_k=4, held_experts=(0, 8),
                           expert_act=expert_act, dtype=jnp.float32)
    tokens, keys = 48, jax.random.split(jax.random.PRNGKey(7), 6)
    assert moe.held_buffer_rows(config, tokens, 1) == tokens and moe.held_passes_most(config, tokens, 1) == 4
    h = jax.random.normal(keys[0], (tokens, 32))
    gates = jax.nn.softmax(jax.random.normal(keys[1], (tokens, 4)))
    weights = tuple(0.3 * jax.random.normal(key, shape) for key, shape in
                    zip(keys[2:5], ((8, 32, 16), (8, 32, 16), (8, 16, 32))))
    held = jax.vmap(lambda key: jax.random.permutation(key, 8)[:4])(jax.random.split(keys[5], tokens))
    experts = jnp.where(jnp.arange(tokens)[:, None] < tokens_here, held, 8 + 4 * held)

    def layer(h, gates, weights):
        out, report = moe._held_experts(h, gates, experts, weights, config, 1, "xla")
        return jnp.sum(out * jnp.cos(out)), (out, report)

    return (h, gates, weights), jax.value_and_grad(layer, (0, 1, 2), has_aux=True)


@pytest.mark.parametrize("expert_act", ["swiglu", "reglu"])
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_every_pass_taken_adds_what_one_buffer_for_all_rows_computes(monkeypatch, passes, expert_act):
    """1, 2 and `held_passes_most` passes taken (40, 80 and all 192 rows sent
    here through a buffer of 48): the output, what the layer counts of itself
    and the gradients of `h`, the gates and the three weight stacks are those
    of ONE pass through a buffer that holds every row. With one pass taken
    they are, bit for bit, those of a layer that has no later pass at all:
    a pass not taken hands the sums back as they came."""
    inputs, layer = _held_layer(expert_act, tokens_here={1: 10, 2: 20, 4: 48}[passes])
    (_, (out, report)), grads = jax.jit(layer)(*inputs)
    assert float(report["moe_passes"]) == passes
    assert float(report["moe_rows_held"]) == {1: 40, 2: 80, 4: 192}[passes]
    with monkeypatch.context() as patch:
        patch.setattr(moe, "_HELD_BUFFER_SHARES", 64.0)     # one buffer for all T k rows
        # a function of its own: the trace has to read the patched constant
        (_, (want, want_report)), want_grads = jax.jit(lambda *args: layer(*args))(*inputs)
    assert float(want_report["moe_passes"]) == 1
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads), strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5)
    if expert_act == "reglu":
        assert float(report["moe_act_live_units"]) == float(want_report["moe_act_live_units"]) > 0
    if passes == 1:
        monkeypatch.setattr(moe, "held_passes_most", lambda config, tokens, tile: 1)
        (_, (first, _)), first_grads = jax.jit(lambda *args: layer(*args))(*inputs)
        for ours, theirs in zip(jax.tree.leaves((out, grads)), jax.tree.leaves((first, first_grads)), strict=True):
            np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


def test_a_pass_not_taken_fills_nothing_and_adds_nothing():
    """The layer's forward and gradient, as traced: every `cond` of the
    later passes (one test around all of them and one a pass, forward and
    backward) hands its operands back untouched in the branch not taken, and
    outside the branches taken nothing under `moe.passes` makes a value of
    the output's or of an input's size: no zero is filled, none is added."""
    inputs, layer = _held_layer()
    wide = {inputs[0].shape, *(w.shape for w in inputs[2])}     # (tokens, hidden) and the weight stacks
    conds = []

    def walk(jaxpr, path, taken):
        for eqn in jaxpr.eqns:
            here = f"{path}/{eqn.source_info.name_stack}"
            control = eqn.primitive.name in ("cond", "scan")
            if "moe.passes" in here and not taken and not control:
                assert not any(getattr(v.aval, "shape", None) in wide for v in eqn.outvars), (here, eqn.primitive)
            if eqn.primitive.name == "cond" and "moe.passes" in here:
                not_taken, later = eqn.params["branches"]
                conds.append(here)
                assert not not_taken.jaxpr.eqns and set(not_taken.jaxpr.outvars) <= set(not_taken.jaxpr.invars), here
                walk(later.jaxpr, here, True)
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, here, taken)

    walk(jax.make_jaxpr(layer)(*inputs).jaxpr, "", False)
    assert len(conds) == 4 and sum("transpose" in path for path in conds) == 2, conds


@pytest.mark.parametrize("sent_here", ["every-choice", "as-routed"])
def test_held_layer_through_the_grouped_matmul_kernels(monkeypatch, sent_here):
    """The held layer as a TPU runs it: the three `moe_gmm_*` kernels
    (interpret mode, 8-row tiles) over a buffer that is mostly empty, whose
    unused row tiles are neither computed nor copied, one pass
    or two, against `ragged_dot`: output and every gradient."""
    from ray_tpu.ops import grouped_matmul as gmm

    whole, lp, h = _expert_layer(tiny())
    config = dataclasses.replace(whole, held_experts=(0, 8))
    bias = lp["expert_bias"] if sent_here == "as-routed" else jnp.where(jnp.arange(32) < 8, 10.0, 0.0)
    held = dict(lp, expert_bias=bias,
                **{name: lp[name][:8] for name in ("we_gate", "we_up", "we_down")})

    def run(h, lp):
        out, scalars = moe.moe_mlp(h, lp, config)
        return jnp.sum(jnp.sin(out)), (out, scalars)

    want = jax.jit(jax.value_and_grad(run, (0, 1), has_aux=True))(h, held)
    monkeypatch.setattr(moe, "resolve_gmm_impl", lambda implementation=None: "pallas")
    monkeypatch.setattr(moe, "gmm_tile_rows", lambda implementation=None: 8)
    real = gmm.grouped_matmul
    seen = []

    def interpreted(lhs, rhs, sizes, **kw):
        seen.append(lhs.shape[0])
        return real(lhs, rhs, sizes, **dict(kw, interpret=True))

    monkeypatch.setattr(moe, "grouped_matmul", interpreted)
    # a function of its own: the trace has to take the patched kernels
    got = jax.jit(jax.value_and_grad(lambda h, lp: run(h, lp), (0, 1), has_aux=True))(h, held)
    # a pass's buffer: twice the even share and a tile a held expert, not all T k rows
    assert seen and set(seen) == {moe.held_buffer_rows(config, h.shape[0] * h.shape[1], 8) + 8 * 8}
    for ours, theirs in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=2e-5)
    assert float(got[0][1][1]["moe_passes"]) == (2 if sent_here == "every-choice" else 1)


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_router_forms_against_a_count_by_hand(score):
    """Scores softmax or sigmoid; the selection bias moves the choice and not
    the gate; renormalised; scaled. The renormalisation's epsilon is the one
    constant every router here has (1e-9): under a float32 sum of chosen
    scores it changes no bit against the source's 1e-20."""
    config = moe.MoEConfig(n_experts=4, top_k=2, router_score=score, router_select_bias=True,
                           norm_topk_prob=True, route_scale=2.0)
    total = jnp.float32(0.5)
    assert float(total + jnp.float32(1e-9)) == float(total + jnp.float32(1e-20)) == 0.5
    scores = jnp.asarray([[0.1, 0.4, 0.3, 0.2]])
    gates, experts = moe._route(scores, scores + jnp.asarray([0.5, 0.0, 0.0, 0.0]), config)
    assert experts.tolist() == [[0, 1]]                       # 0.6 and 0.4: the bias chose expert 0
    np.testing.assert_allclose(np.asarray(gates), [[2.0 * 0.1 / 0.5, 2.0 * 0.4 / 0.5]], rtol=1e-6)
    plain = dataclasses.replace(config, router_select_bias=False, norm_topk_prob=False, route_scale=1.0)
    gates, experts = moe._route(scores, None, plain)
    assert experts.tolist() == [[1, 2]] and np.allclose(np.asarray(gates), [[0.4, 0.3]])


def test_the_step_reports_the_stack_and_the_held_rows():
    """`plan` for the `train.init.step_fn` span, and the scalars every
    `train.report` carries."""
    config = tiny()
    family = model_family(config)
    plan = family.plan(config, 2, 48)
    assert plan["layer_kinds"] == "dS dS eS eF eS eS" and plan["attn_window"] == 16
    assert (plan["moe_router"], plan["moe_experts_held"], plan["moe_experts_routed"],
            plan["moe_shared_width"]) == ("sigmoid", 8, 32, 32)
    assert plan["moe_held_buffer_rows"] == 2 * 48 * 4 // 2 and plan["moe_held_passes_most"] == 2
    assert {"attn_window_subtiles_visited", "attn_window_subtiles_masked",
            "attn_window_subtiles_total", "attn_window_tiles_whole"} <= set(plan)
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 49), 0, config.vocab_size)
    _, scalars = jax.jit(functools.partial(lm_loss, config=config))(params, tokens)
    assert {"loss", "num_tokens", "moe_load_max_over_mean", "moe_rows_held", "moe_rows_held_share",
            "moe_passes"} == set(scalars)
    assert float(scalars["moe_rows_held_share"]) == pytest.approx(
        100 * float(scalars["moe_rows_held"]) / (2 * 48 * 4))
    assert 1 <= float(scalars["moe_passes"]) <= 2


def test_the_older_families_are_untouched_by_the_new_fields():
    """With no window, no held experts, softmax scores and no shared expert
    the dense and MoE families build the parameters they built and take the
    branches they took (the lowered programs of the three shipped cells are
    compared with the parent's on the chip: PERF.md section 6, PR 33)."""
    from ray_tpu.models import get_config

    dense, sparse = get_config("gpt2-tiny"), moe.moe_tiny()
    for config in (dense, sparse):
        assert config.d_head is None and config.head_dim == config.d_model // config.n_heads
        assert not (config.qk_norm_per_head or config.attn_gate or config.sandwich_norm
                    or config.scale_embedding)
    assert (sparse.router_score, sparse.router_select_bias, sparse.route_scale,
            sparse.shared_expert_width, sparse.held_experts) == ("softmax", False, 1.0, 0, None)
    params = moe.init_params(sparse, jax.random.PRNGKey(0))
    assert set(params["blocks"]) == {"ln1_scale", "wq", "wk", "wv", "wo", "ln2_scale", "router",
                                     "we_gate", "we_up", "we_down"}
    assert params["blocks"]["we_up"].shape[1] == sparse.n_experts == sparse.n_experts_held
    assert "blocks" in model_family(dense).init_params(dense, jax.random.PRNGKey(0))


# ------------------------------------------ an all-expert stack, routed before attention


def tiny_all_experts(**kw) -> MixedStackConfig:
    """8 expert layers (eF eS eS eS, twice), the full layer FIRST in its
    period, the router on the attention's input, ReGLU experts with no shared
    one, softmax gates over the chosen, plain pre-norm blocks: 32 experts
    top-3 of which 8 are held, float32."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, d_head=8, d_ff=32,
        max_seq=64, pos_emb="rope", norm="rmsnorm", act="swiglu", use_bias=False,
        tie_embeddings=False, norm_eps=1e-6, rope_theta=1.5e6, dtype=jnp.float32, remat=True,
        sliding_window=16, global_attn_every=4, global_attn_first=True, n_dense_layers=0,
        router_input="attention", n_experts=32, held_experts=(0, 8), top_k=3, norm_topk_prob=True,
        router_score="softmax", expert_act="reglu", router_aux_coeff=0.0, frozen_leaves=("router",))
    return MixedStackConfig(**{**base, **kw})


def arch_all_experts(config):
    return dict(global_attn_every=config.global_attn_every, sliding_window=config.sliding_window,
                rope_theta=config.rope_theta, norm_eps=config.norm_eps, top_k=config.top_k,
                held_experts=config.held_experts, frozen_leaves=config.frozen_leaves)


@pytest.mark.parametrize("depth,dense,first,want", [
    (8, 0, True, "2 x (eF eS eS eS)"),
    (52, 0, True, "13 x (eF eS eS eS)"),
    (6, 0, True, "eF eS eS eS eF eS"),
    (6, 2, False, "2 x (dS) | eS eF eS eS"),
    (6, 2, True, "dF dS | eS eS eF eS")],
    ids=["the-cell", "the-published-depth", "a-period-and-a-half", "global-last-as-before",
         "global-first-behind-dense-layers"])
def test_the_global_layer_first_in_its_period_is_one_scanned_run(depth, dense, first, want):
    config = tiny(n_layers=depth, n_dense_layers=dense, global_attn_first=first)
    kinds = layer_kinds(config)
    assert _said(stack_runs(kinds)) == want
    assert [k.attention == "full" for k in kinds] == [
        i % 4 == (0 if first else 3) for i in range(depth)]


def test_a_layer_has_the_leaves_its_flags_give_it_and_no_others():
    """The plain pre-norm all-expert layer has no output gate, no QK-norm, no
    norm on a sublayer's output, no selection bias and no shared expert, as
    leaves and as logical axes alike; the family that has them all keeps its
    tree, in its order, to the last bit of its initial values (the hash is
    the parent's, PR 36)."""
    import hashlib

    plain = tiny_all_experts()
    family = model_family(plain)
    params = family.init_params(plain, jax.random.PRNGKey(0))
    (period,) = params["runs"]
    assert [list(lp) for lp in period] == [[
        "ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "router", "we_gate", "we_up", "we_down"]] * 4
    assert jax.tree.structure(family.logical_axes(plain)["runs"], is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(jax.tree.map(lambda w: (), params["runs"]), is_leaf=lambda x: isinstance(x, tuple))
    assert period[0]["we_up"].shape == (2, 8, 64, 32) and period[0]["router"].shape == (2, 64, 32)
    # projections into the residual stream, with no norm behind them: 0.02 / sqrt(2 L)
    assert float(jnp.std(period[0]["wo"])) == pytest.approx(0.02 / 4, rel=0.05)
    assert float(jnp.std(period[0]["we_down"])) == pytest.approx(0.02 / 4, rel=0.05)
    assert float(jnp.std(period[0]["wq"])) == pytest.approx(0.02, rel=0.05)
    assert float(jnp.std(params["wte"])) == pytest.approx(0.02, rel=0.05)
    wide = family.init_params(dataclasses.replace(plain, embedding_std=1.0, router_std=0.06),
                              jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(wide["wte"]), np.asarray(params["wte"] / 0.02), rtol=1e-6)
    # a router's deviation is a scale on the same draw: every choice of experts stays
    np.testing.assert_allclose(np.asarray(wide["runs"][0][0]["router"]),
                               np.asarray(3 * period[0]["router"]), rtol=1e-6)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves({k: v for k, v in wide["runs"][0][0].items() if k != "router"}),
        jax.tree.leaves({k: v for k, v in period[0].items() if k != "router"})))
    # each flag brings its own leaves
    for flags, leaves in ((dict(attn_gate=True), {"wg"}),
                          (dict(qk_norm_per_head=True), {"q_norm_scale", "k_norm_scale"}),
                          (dict(sandwich_norm=True), {"ln1_post_scale", "ln2_post_scale"}),
                          (dict(router_select_bias=True), {"expert_bias"}),
                          (dict(shared_expert_width=32), {"ws_gate", "ws_up", "ws_down"})):
        more = jax.eval_shape(lambda: family.init_params(
            dataclasses.replace(plain, **flags), jax.random.PRNGKey(0)))["runs"][0][0]
        assert set(more) - set(period[0]) == leaves, flags
    with pytest.raises(ValueError, match="expert_bias"):
        tiny_all_experts(frozen_leaves=("expert_bias",))
    with pytest.raises(ValueError, match="router input"):
        tiny_all_experts(router_input="residual")
    # the family with every flag set: the parent's tree and the parent's numbers
    every = tiny()
    theirs = model_family(every).init_params(every, jax.random.PRNGKey(0))
    assert list(theirs["runs"][1][0]) == [
        "ln1_scale", "ln1_post_scale", "ln2_scale", "ln2_post_scale", "wq", "wk", "wv", "wg", "wo",
        "q_norm_scale", "k_norm_scale", "router", "expert_bias", "we_gate", "we_up", "we_down",
        "ws_gate", "ws_up", "ws_down"]
    digest = hashlib.sha256()
    for path, w in jax.tree_util.tree_flatten_with_path(theirs)[0]:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(np.asarray(w).tobytes())
    # under the harness's XLA flags (conftest.py: LLVM's optimiser off since PR 49); PR 48's tree gives
    # the same digest under them, and gave 3af119b6...0250b7f, PR 36's, under the flags it was pinned with
    assert digest.hexdigest() == "a2d32c08614b015e5593d3f68dcb7ce35151c6cd875e6f815cd818c4ed07e5fc"


def _logits_of(config):
    family = model_family(config)

    def logits(p, t):
        hidden, _ = family.forward_hidden(p, t, config)
        return jnp.einsum("bse,ev->bsv", hidden, lm_head_weights(p, config))

    return jax.jit(logits)


@pytest.mark.parametrize("router_input", ["attention", "mlp"])
def test_the_router_reads_the_tensor_the_configuration_names(router_input):
    """Perturbing layer 0's attention weights leaves THAT layer's chosen
    experts as they were where the router reads the attention's input, and
    changes them where it reads the MLP's; either way it changes the next
    layer's."""
    from benchmark.reference import smallthinker_ref

    config = tiny_all_experts(router_input=router_input, held_experts=None, n_experts=32, n_layers=4)
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 48), 0, config.vocab_size)

    def chosen_rows(p):
        """Rows a published expert, a layer: the block's two sublayers walked by hand (the
        block folds `load` to its max over mean)."""
        x = p["wte"][tokens]
        rope = moe.rope_frequencies(config.head_dim, tokens.shape[1], config.rope_theta)
        from ray_tpu.models.transformer import _norm, attention_sublayer

        rows = []
        for kind, lp in zip(layer_kinds(config), smallthinker_ref.layers_of(p)):
            sliding = kind.attention == "sliding"
            after = attention_sublayer(x, lp, config, rope if sliding else None, None,
                                       window=config.sliding_window if sliding else None)
            m = _norm(after, lp["ln2_scale"], None, config.norm, config.norm_eps)
            out, scalars = moe.moe_mlp(m, lp, config,
                                       router_input=x if router_input == "attention" else None)
            rows.append(scalars["load"])
            x = after + out
        return rows

    chosen_rows = jax.jit(chosen_rows)
    before = chosen_rows(params)
    shaken = jax.tree_util.tree_map_with_path(
        lambda path, w: w.at[0].multiply(-1.5) if jax.tree_util.keystr(path).endswith("[0]['wv']") else w,
        params)
    after = chosen_rows(shaken)
    same_layer = np.array_equal(before[0], after[0])
    assert same_layer == (router_input == "attention")
    assert not np.array_equal(before[1], after[1])


def test_the_eight_shares_of_an_all_expert_layer_add_up_to_the_uncut_reference():
    """Nothing is computed alike on every chip here (no shared expert), so
    the eight shares' outputs, each routed over all 32 experts by the layer's
    INPUT and gated by the softmax over all 3 chosen, add up to the uncut
    layer, which is the plain reference's; every ReGLU unit's count is
    between none and all."""
    from benchmark.reference import smallthinker_ref

    config = tiny_all_experts(n_experts=64, held_experts=None, n_layers=1, global_attn_every=1)
    lp = jax.tree.map(lambda w: w[0], seeded(config, 2)["runs"][0][0])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, config.d_model))
    routed_by = jax.random.normal(jax.random.PRNGKey(4), (2, 24, config.d_model))
    uncut, scalars = jax.jit(functools.partial(moe.moe_mlp, config=config))(h, lp, router_input=routed_by)
    total, rows, live = 0.0, 0.0, 0.0
    for share in range(8):
        first = 8 * share
        held = dict(lp, **{name: lp[name][first: first + 8] for name in ("we_gate", "we_up", "we_down")})
        part, part_scalars = jax.jit(functools.partial(
            moe.moe_mlp, config=dataclasses.replace(config, held_experts=(first, first + 8))))(
            h, held, router_input=routed_by)
        total, rows = total + part, rows + part_scalars["moe_rows_held"]
        live += part_scalars["moe_act_live_units"]
        np.testing.assert_array_equal(np.asarray(part_scalars["load"]), np.asarray(scalars["load"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)
    assert float(rows) == 2 * 24 * 3            # every (token, choice) row lies on exactly one chip

    @jax.jit
    def by_the_reference(h, lp, routed_by):
        with jax.default_matmul_precision("highest"):
            gates, chosen = smallthinker_ref._gates(routed_by @ lp["router"], config.top_k)
            reference = sum(gates[..., e, None] * smallthinker_ref._reglu(
                h, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e]) for e in range(64))
            # the units the ReLU leaves alive on the rows really sent: counted by hand
            gate = jnp.einsum("bsm,emf->bsef", h, lp["we_gate"])
            sent = jax.nn.one_hot(chosen, 64).sum(axis=2)                  # (B, S, E)
            return reference, jnp.sum((gate > 0) * sent[..., None])

    reference, by_hand = by_the_reference(h, lp, routed_by)
    by_hand = float(by_hand)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(reference), atol=2e-5)
    assert float(live) == by_hand and 0 < by_hand < 2 * 24 * 3 * config.d_ff


def test_the_all_expert_step_reports_its_router_its_unit_and_the_live_share():
    config = tiny_all_experts()
    plan = model_family(config).plan(config, 2, 48)
    assert plan["layer_kinds"] == "eF eS eS eS eF eS eS eS" and plan["attn_window"] == 16
    assert (plan["moe_router"], plan["moe_router_input"], plan["moe_expert_act"],
            plan["moe_experts_held"], plan["moe_experts_routed"], plan["moe_shared_width"]) == (
        "softmax", "attention", "reglu", 8, 32, 0)
    older = model_family(tiny()).plan(tiny(), 2, 48)
    assert (older["moe_router_input"], older["moe_expert_act"]) == ("mlp", "swiglu")
    params = seeded(config)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 49), 0, config.vocab_size)
    _, scalars = jax.jit(functools.partial(lm_loss, config=config))(params, tokens)
    assert {"loss", "num_tokens", "moe_load_max_over_mean", "moe_rows_held", "moe_rows_held_share",
            "moe_passes", "moe_act_live_share"} == set(scalars)
    assert 30.0 < float(scalars["moe_act_live_share"]) < 70.0       # a seeded gate is half alive
    # the rule that decides what a recomputing step keeps sees a period of four layers
    costs = model_family(config).block_costs(config, 48)
    assert [(run["scanned"], run["period"], run["layers"]) for run in costs["runs"]] == [(True, 4, 8)]
    assert [(run["scanned"], run["period"], run["layers"])
            for run in model_family(tiny()).block_costs(tiny(), 48)["runs"]] == [(True, 1, 2), (False, 4, 4)]


# ------------------------------------------- layers of ONE sublayer (PR 48)


def tiny_pattern(**kw) -> MixedStackConfig:
    """The nemotron_h family in small: `MEMEM*EME`, every layer one mixer
    behind one norm: Mamba-2 layers of 8 heads of 8 with a state of 16 in 2
    groups and a chunk of 16, attention of 4 / 2 heads of 16 without
    positions, 32 sigmoid-routed squared-ReLU experts (8 held, top-6, gates
    x 2.5) beside a shared one. The pattern is longer than the depth, as the
    published one is."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=9, layer_pattern="MEMEM*EMEMEM*E", n_heads=4,
        n_kv_heads=2, d_head=16, ssm_heads=8, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=16,
        d_ff=32, max_seq=64, pos_emb="rope", norm="rmsnorm", act="swiglu", use_bias=False,
        tie_embeddings=False, norm_eps=1e-5, dtype=jnp.float32, remat=True, n_experts=32,
        held_experts=(0, 8), top_k=6, norm_topk_prob=True, route_scale=2.5, router_score="sigmoid",
        router_select_bias=True, expert_act="relu2", shared_expert_width=64, router_aux_coeff=0.0,
        frozen_leaves=("router",), embedding_std=1.0, router_std=0.06)
    return MixedStackConfig(**{**base, **kw})


PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.mark.parametrize("pattern, want", [
    ("MEMEM*EME", "2 x (-M e-) | -M -F e- -M e-"),
    ("MEMEM*E", "2 x (-M e-) | -M -F e-"),
    (PUBLISHED_PATTERN, "5 x (-M e- -M e- -M -F e-) | 3 x (-M e-) | -M -F e- -M e- -M e- -M e- -M e-"),
    ("MMMM", "4 x (-M)"), ("EM*", "e- -M -F")],
    ids=["the-cell", "the-first-period", "the-published-52", "all-alike", "nothing-repeats"])
def test_a_pattern_of_one_sublayer_layers_groups_into_runs_of_whole_kinds(pattern, want):
    config = tiny_pattern(n_layers=len(pattern), layer_pattern=pattern)
    kinds = layer_kinds(config)
    runs = stack_runs(kinds)
    assert _said(runs) == want
    assert [k for run in runs for _ in range(run.repeats) for k in run.kinds] == kinds
    assert all(kind.sublayers == 1 for kind in kinds)
    codes = {"M": LayerKind("ssm", "none"), "*": LayerKind("full", "none"), "E": LayerKind("none", "experts")}
    assert kinds == [codes[character] for character in pattern]


def test_a_one_sublayer_layer_owns_one_norm_and_its_kinds_leaves_only():
    config = tiny_pattern()
    family = model_family(config)
    params = jax.eval_shape(lambda: family.init_params(config, jax.random.PRNGKey(0)))
    (mamba, experts), (_, attention, *_rest) = params["runs"]
    # (eval_shape hands a dict back in the order of its keys)
    assert set(mamba) == {"ln1_scale", "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
                          "ssm_d", "ssm_norm_scale", "ssm_out"}
    assert set(attention) == {"ln1_scale", "wq", "wk", "wv", "wo"}
    # a non-gated expert: two matrices an expert, two of the shared one
    assert set(experts) == {"ln2_scale", "router", "expert_bias", "we_up", "we_down", "ws_up", "ws_down"}
    assert mamba["ssm_in"].shape == (2, 64, 64 + (64 + 2 * 2 * 16) + 8)      # [z | x B C | dt]
    assert mamba["ssm_conv_w"].shape == (2, 128, 4) and mamba["ssm_out"].shape == (2, 8, 8, 64)
    assert experts["we_up"].shape == (2, 8, 64, 32) and experts["ws_up"].shape == (2, 64, 64)
    axes = family.logical_axes(config)["runs"][0][0]
    assert axes["ssm_out"] == ("layers", "ssm_heads", "head_dim", "embed")
    assert axes["ssm_a_log"] == ("layers", "ssm_heads")
    # the Mamba leaves start as the family starts them
    leaves = family.init_params(config, jax.random.PRNGKey(0))["runs"][0][0]
    a, step = np.exp(np.asarray(leaves["ssm_a_log"])), np.asarray(jax.nn.softplus(leaves["ssm_dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and 0.001 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    assert np.all(np.asarray(leaves["ssm_d"]) == 1) and np.all(np.asarray(leaves["ssm_norm_scale"]) == 1)
    assert np.all(np.asarray(leaves["ssm_conv_b"]) == 0) and np.abs(np.asarray(leaves["ssm_conv_w"])).max() <= 0.5
    # a projection into the residual stream: 0.02 over the root of the NINE sublayers the stack has
    assert float(jnp.std(leaves["ssm_out"])) == pytest.approx(0.02 / 3, rel=0.05)
    said = family.plan(config, 2, 48)
    assert said["layer_kinds"] == "-M e- -M e- -M -F e- -M e-"
    assert (said["ssm_heads"], said["ssm_head_dim"], said["ssm_state"], said["ssm_groups"], said["ssm_chunk"],
            said["ssm_conv_kernel"], said["ssm_scan_impl"], said["moe_expert_act"]) == (
        8, 8, 16, 2, 16, 4, "xla_chunked", "relu2")
    assert (said["ssm_gate_norm_impl"], said["ssm_gate_norm_rows"]) == ("xla", 0)
    assert (said["ssm_conv_impl"], said["ssm_conv_rows"]) == ("xla", 0)
    assert "attn_window" not in said


def test_the_in_projections_parts_with_the_whole_for_the_gate_and_the_convolution_differentiate_as_a_split():
    """`_gate_xbc_dt` hands the gated norm AND the convolution the WHOLE
    projection (the gate is read at its first features, xBC at those after
    them) beside dt: the values are `jnp.split`'s, and so is the gradient,
    whatever reads either whole (readers of their own columns alone, as the
    kernels are, and one of every feature)."""
    from ray_tpu.models.mixed_stack import _gate_xbc_dt

    projected = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 16 + 24 + 4))

    def through(split, read_gate, read_xbc):
        def loss(projected):
            z, xbc, step = split(projected)
            return jnp.sum(jnp.sin(read_gate(z))) + jnp.sum(read_xbc(xbc) ** 2) + jnp.sum(jnp.cos(step))
        return jax.value_and_grad(loss)(projected)

    parts = lambda t: jnp.split(t, [16, 40], axis=-1)                     # noqa: E731
    whole = lambda t: t                                                   # noqa: E731
    for got, want in (
            (through(lambda t: _gate_xbc_dt(t, 16, 24), lambda z: z[..., :16], lambda xbc: xbc[..., 16:40]),
             through(parts, whole, whole)),
            (through(lambda t: _gate_xbc_dt(t, 16, 24), whole, whole),
             through(lambda t: (t, t, parts(t)[2]), whole, whole))):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("change, match", [
    (dict(layer_pattern="MEME-EMEM"), "layer_pattern"), (dict(layer_pattern="MEM"), "at least n_layers"),
    (dict(ssm_heads=0), "ssm_heads"), (dict(ssm_groups=3), "ssm_heads"), (dict(mtp_modules=1), "patterned stack")],
    ids=["a-dense-mlp-layer", "shorter-than-the-depth", "no-heads", "ragged-groups", "with-a-module"])
def test_what_a_pattern_cannot_run_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        tiny_pattern(**change)


def test_block_costs_price_the_sublayers_a_layer_has():
    """An `M` layer costs its mixer and no MLP, an `E` layer its experts and
    no attention (two matrices an expert where it is not gated); a
    state-space layer names the scan's output WITH its states as one candidate
    and, since PR 57, the in-projection's output as another."""
    from ray_tpu.models.mixed_stack import _expert_costs, _ssm_costs, block_costs

    config = tiny_pattern()
    costs = block_costs(config, 48)
    assert [(run["scanned"], run["layers"], run["period"]) for run in costs["runs"]] == [
        (True, 4, 2), (False, 5, 5)]
    by_name = {c.names: c for c in costs["candidates"]}
    assert by_name["ssm_scan_out", "ssm_chunk_states"].layers == (2, 2)
    assert (by_name["ssm_in_proj",].layers, by_name["ssm_in_proj",].width) == ((2, 2), 64 + (64 + 2 * 2 * 16) + 8)
    assert by_name["attn_out", "attn_lse"].layers == (0, 1) and by_name["attn_residual",].layers == (0, 1)
    ssm, experts = _ssm_costs(config), _expert_costs(config, lambda weight: 1, 48)
    inner, conv = 64, 64 + 2 * 2 * 16
    assert ssm["flops"] == (2 * 64 * (inner + conv + 8) + 2 * 4 * conv
                            + 2 * 16 * 2 * 16 + 2 * 16 * inner + 4 * inner * 16 + 2 * inner * 64)
    assert experts["flops"] == int(2 * 64 * (32 + 2 * 64 + 2 * (6 * 8 / 32) * 32))
    gated = _expert_costs(dataclasses.replace(config, expert_act="swiglu"), lambda weight: 1, 48)
    assert gated["flops"] == int(2 * 64 * (32 + 3 * 64 + 3 * (6 * 8 / 32) * 32))
    # the held experts' buffer (PR 67): of 48 tokens top-6, twice the even share of 8 held of 32 is 144 rows and a
    # one-row tile an expert off a TPU: a slot's input and output, up (and gate) and two float32 words
    for costs_of, names, matmuls in ((experts, ("in", "up", "out", "slots"), 2), (gated, ("in", "gate", "up", "out", "slots"), 3)):
        buffer = costs_of["candidates"][-1]
        assert buffer.names == (*("moe_buffer_" + name for name in names), "moe_gmm_tiles")
        assert buffer.width == -(-(144 + 8) * (2 * 64 + (matmuls - 1) * 32 + 2) // 48)
        assert buffer.flops == int(2 * 64 * matmuls * (6 * 8 / 32) * 32)
    # every layer is one sublayer: the stack's FLOPs are the sum of the nine
    attention = next(c for c in costs["candidates"] if c.names == ("attn_residual",))
    assert costs["flops"] > 4 * ssm["flops"] + 4 * experts["flops"] + attention.flops


@pytest.mark.parametrize("backend, width, share", [
    ("cpu", 4096 + 1024, 0.043), ("tpu", 4096 + 4096, 0.174)], ids=["xla-chunked", "kernels"])
def test_the_scan_candidate_is_priced_by_the_form_that_runs(monkeypatch, backend, width, share):
    """At the published sizes (64 heads of 64, state 128, chunk 128) in
    bfloat16: the XLA form keeps a float32 state a block of 8 chunks (64 x
    128 x 128 x 4 B over 1,024 tokens: 1,024 features a token), the kernels
    the state that entered every chunk in bfloat16 (4,096 features a token),
    and the scan they spare counts at the form's measured share of the peak."""
    from ray_tpu.models.mixed_stack import _ssm_costs

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    config = tiny_pattern(ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8, ssm_chunk=128,
                          dtype=jnp.bfloat16)
    candidate = _ssm_costs(config)["candidates"][0]
    scan = 2 * 128 * 8 * 128 + 2 * 128 * 4096 + 4 * 4096 * 128
    assert (candidate.names, candidate.width, candidate.flops, candidate.worth) == (
        ("ssm_scan_out", "ssm_chunk_states"), width, scan, int(scan / share))


@pytest.mark.parametrize("backend, bound, share", [("cpu", -5.0, 0.02), ("tpu", -5.0, 0.085), ("tpu", -6.0, 0.02)],
                         ids=["xla-chunked", "kernels", "a-gate-the-kernels-do-not-tile"])
def test_the_delta_rule_candidate_is_priced_by_the_form_that_runs(monkeypatch, backend, bound, share):
    """At the published sizes (32 heads of 128, chunk 64) in bfloat16 either
    form keeps the gated, normed output, the rule's own o that the norm's
    transpose reads and the float32 state that entered
    every chunk (128 x 128 x 4 B a head over 64 tokens, 32 KB a token: 16,384
    features of two bytes beside the two outputs' 4,096 each), and
    the rule they spare, with the norm's arithmetic since PR 63, counts at the
    measured share of the peak of the form that runs
    (`ops/kda.resolve_kda_impl`)."""
    from test_ling3flash_model import tiny_ling

    from ray_tpu.models.mixed_stack import _kda_costs

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    config = tiny_ling(kda_heads=32, kda_head_dim=128, kda_chunk=64, kda_gate_lower_bound=bound, dtype=jnp.bfloat16)
    candidate = _kda_costs(config, True)["candidates"][0]
    rule = 32 * (2 * 64 * (4 * 128 + 64) + 10 * 128 * 128)
    assert (candidate.names, candidate.width, candidate.flops, candidate.worth) == (
        ("kda_chunk_out", "kda_chunk_states", "kda_chunk_o"), 2 * 4096 + 16384, rule + 8 * 4096, int(rule / share))
    # the stream after the out-projection is a candidate only where a sublayer follows the mixer (PR 60)
    assert [[c.names[0] for c in _kda_costs(config, follows)["candidates"]] for follows in (True, False)] == [
        ["kda_chunk_out", "kda_in_proj", "kda_residual"], ["kda_chunk_out", "kda_in_proj"]]


def test_the_three_shipped_mixed_stack_cells_keep_their_kinds_runs_and_leaves():
    """`train-trinity-mini-8k`, `train-smallthinker-16k` and `train-glm47flash-8k`
    resolve to the kinds, runs and leaf names they had before a layer could
    be one sublayer (their seeded trees and first losses were compared bit
    for bit with the parent's when PR 48 was built)."""
    from benchmark import model_config

    want = {
        "trinity-mini-train-1chip": ("dS dS eS eF eS eS", "2 x (dS) | eS eF eS eS"),
        "smallthinker-21b-a3b-train-1chip": ("eF eS eS eS eF eS eS eS", "2 x (eF eS eS eS)"),
        "glm-4.7-flash-train-1chip": ("dL eL eL eL eL", "dL | 4 x (eL)")}
    leaves = {
        "dS": ["ln1_scale", "ln1_post_scale", "ln2_scale", "ln2_post_scale", "wq", "wk", "wv", "wg", "wo",
               "q_norm_scale", "k_norm_scale", "w_gate", "w_up", "w_down"],
        "eF": ["ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "router", "we_gate", "we_up", "we_down"],
        "eL": ["ln1_scale", "ln2_scale", "wq_a", "q_a_norm_scale", "wq_b", "wkv_a", "kv_a_norm_scale", "wkv_b",
               "wo", "router", "expert_bias", "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down"]}
    for name, (kinds, runs) in want.items():
        config = model_config.transformer_config(model_config.load_config(
            os.path.join(ROOT, "benchmark", "configs", name + ".json")))
        assert " ".join(k.code for k in layer_kinds(config)) == kinds
        assert _said(stack_runs(layer_kinds(config))) == runs
        assert all(kind.sublayers == 2 for kind in layer_kinds(config))
        shapes = jax.eval_shape(lambda: model_family(config).init_params(config, jax.random.PRNGKey(0)))
        first = {run.kinds[0].code: sorted(period[0]) for run, period in zip(
            stack_runs(layer_kinds(config)), shapes["runs"])}
        for code, names in first.items():
            if code in leaves:
                assert names == sorted(leaves[code]), (name, code)


def test_serving_refuses_a_patterned_stack_by_name():
    """Training only: the dense cache's `decode_step` and `prefill` and the
    paged engine's pool refuse the `ssm` kind by name, as they do the latent
    kind."""
    from ray_tpu.models import decode_step, prefill
    from ray_tpu.serve.llm.paged import PagedConfig, init_paged_cache

    config = tiny_pattern()
    tokens, lengths = jnp.zeros((1, 4), jnp.int32), jnp.ones((1,), jnp.int32)
    for refused in (lambda: decode_step({}, {}, tokens[:, 0], lengths, config),
                    lambda: prefill({}, tokens, lengths, {}, config),
                    lambda: init_paged_cache(config, PagedConfig(page_size=8, num_pages=4))):
        with pytest.raises(NotImplementedError, match="state-space"):
            refused()
