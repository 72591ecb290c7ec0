"""What a recomputing step may keep of a state-space mixer and of a
delta-rule mixer beside its scan's (its rule's) output: the in-projection's
output (models/mixed_stack: `ssm_in_proj`, `kda_in_proj`). Kept, it changes no
loss and no gradient; at the two cells' published widths `block_costs` lists
it and the rule (train/lm.auto_remat_saved) takes it on a v5e beside what it
kept before. (The convolution's output lost on the chip and is not named:
PERF.md section 6, PR 57; the gated norm's is the rule's own output since
PR 63, `kda_chunk_out`, beside the o its transpose reads, `kda_chunk_o`.) Since PR 60 also what it may
keep of an expert layer and of the delta-rule mixer's tail, at the five
expert cells' published widths (tests/test_moe_remat.py holds the values
themselves on a tiny stack)."""

import functools
import os

import jax
import numpy as np
import pytest

from ray_tpu.models import model_family
from ray_tpu.ops import losses
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.train.lm import abstract_train_state, default_optimizer, lm_loss, make_train_step

from test_ling3flash_model import gated, tiny_ling  # noqa: E402
from test_mixed_stack import seeded, tiny_pattern  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the usable memory of the chip the cells run on (benchmark/configs/*-train-1chip.json)
V5E_BYTES = 16_909_336_064

NEW_NAMES = ("ssm_in_proj", "kda_in_proj")


def _ssm_stack():
    """`2 x (-M e-) | -M`: a state-space mixer in a scanned run and in an unrolled one."""
    config = tiny_pattern(layer_pattern="MEMEM", n_layers=5)
    return config, seeded(config, 4), ("ssm_in_proj", "ssm_scan_out", "ssm_chunk_states")


def _kda_stack():
    """`2 x (dK) | eK`: a delta-rule mixer in a scanned run and in an unrolled one."""
    config = tiny_ling(first_layer=0, n_layers=3)
    return config, gated(config, 3), ("kda_in_proj", "kda_chunk_out", "kda_chunk_states", "kda_chunk_o")


@pytest.mark.parametrize("stack", [_ssm_stack, _kda_stack], ids=["state-space", "delta-rule"])
def test_keeping_a_mixers_named_values_changes_neither_loss_nor_gradients(stack):
    """The in-projection's output kept beside the scan's (the rule's) output
    and states, in a scanned run and in an unrolled one: the loss and every
    gradient of the whole-block step, and the projection is gone from the
    backward pass's recomputation (one `dot_general` a layer body: the
    scan's, the unrolled layer's)."""
    config, params, names = stack()
    assert [run["scanned"] for run in model_family(config).block_costs(config, 64)["runs"]] == [True, False]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, config.vocab_size)
    loss = functools.partial(lm_loss, config=config)

    def value_and_gradients(saved):
        return jax.jit(jax.value_and_grad(lambda p: loss(p, tokens, remat_saved=saved)[0]))

    whole, kept = value_and_gradients(()), value_and_gradients(names)
    (whole_loss, whole_grads), (kept_loss, kept_grads) = whole(params), kept(params)
    assert float(whole_loss) == float(kept_loss)
    for a, b in zip(jax.tree.leaves(whole_grads), jax.tree.leaves(kept_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
    # beside what the rule kept before (the scan's or the rule's own einsums, off the CPU's backward pass)
    before = value_and_gradients(tuple(name for name in names if name not in NEW_NAMES))
    matmuls = [fn.lower(params).as_text().count("dot_general") for fn in (before, kept)]
    assert matmuls[0] - matmuls[1] == 2, matmuls


CELLS = {
    # (the configuration's file, a step's batch and sequence, the new candidate's features a row, what
    # the rule kept before it existed)
    "nemotron3nano": ("nemotron-3-nano-30b-a3b-train-1chip", 2, 8192, {"ssm_in_proj": 10304},
                      {"attn_out", "attn_lse", "attn_residual", "ssm_scan_out", "ssm_chunk_states"}),
    "ling3flash": ("ling-3.0-flash-train-1chip", 1, 4096, {"kda_in_proj": 16384},
                   {"attn_residual", "attn_out", "attn_lse", "mlp_up", "mlp_gate", "attn_latent_kv",
                    "attn_latent_k_rope", "kda_chunk_out", "kda_chunk_states", "kda_chunk_o"}),
}
# PR 60's candidates at the five expert cells' widths: (the configuration's file, a step's batch and sequence,
# {first name: (features a row, the layers that write it a run of the stack)}, what the rule keeps on a v5e).
# The routing is float32 logits an expert and 4 bytes a choice for the expert, its score and its row's place
# in the sorted order: (4 E + 12 k) / 2 features; every run that has expert layers counts, the multi-token
# prediction module's block (GLM's third run) among them
EXPERT_CELLS = {
    "ling3flash": ("ling-3.0-flash-train-1chip", 1, 4096,
                   {"moe_routing": ((4 * 512 + 12 * 8) // 2, (0, 6)), "moe_shared_gate": (768, (0, 6)),
                    "moe_shared_up": (768, (0, 6)), "kda_residual": (2560, (1, 5))},
                   CELLS["ling3flash"][4] | {"kda_in_proj", "moe_routing", "moe_shared_gate", "moe_shared_up",
                                             "kda_residual"}),
    "glm47flash": ("glm-4.7-flash-train-1chip", 2, 8192,
                   {"moe_routing": ((4 * 64 + 12 * 4) // 2, (0, 4, 1)), "moe_shared_gate": (1536, (0, 4, 1)),
                    "moe_shared_up": (1536, (0, 4, 1))},
                   {"attn_out", "attn_lse", "moe_routing", "moe_shared_gate"}),
    "trinity": ("trinity-mini-train-1chip", 2, 8192,
                {"moe_routing": ((4 * 128 + 12 * 8) // 2, (0, 4)), "moe_shared_gate": (1024, (0, 4)),
                 "moe_shared_up": (1024, (0, 4))},
                {"attn_out", "attn_lse", "moe_routing", "moe_shared_gate", "moe_shared_up"}),
    "smallthinker": ("smallthinker-21b-a3b-train-1chip", 1, 16384,
                     {"moe_routing": ((4 * 64 + 12 * 6) // 2, (8,))},
                     {"attn_out", "attn_lse", "moe_routing"}),
    "nemotron3nano": ("nemotron-3-nano-30b-a3b-train-1chip", 2, 8192,
                      {"moe_routing": ((4 * 128 + 12 * 6) // 2, (2, 2)), "moe_shared_up": (3712, (2, 2))},
                      # and, since PR 62, its one attention layer's q, k and v (tests/test_attn_remat.py): it
                      # stands in the unrolled run, whose kept values go as its gradients come
                      CELLS["nemotron3nano"][4] | {"ssm_in_proj", "moe_routing", "moe_shared_up",
                                                   "attn_q", "attn_k", "attn_v"}),
}


def _cell_plan(monkeypatch, cell, hbm_bytes):
    """(`block_costs` of a cell's configuration as a TPU runs it, the plan its
    step makes on a device of `hbm_bytes`, the estimates the rule was given,
    the configuration): shapes alone, nothing is allocated. `cell`: (the
    configuration's file, a step's batch and sequence, ...)."""
    from benchmark import model_config
    from ray_tpu.train import lm

    file, batch, seq = cell[:3]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # the kernels' forms, and their kept states
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: hbm_bytes)
    estimates = []
    step_peak_bytes = lm.step_peak_bytes
    monkeypatch.setattr(lm, "step_peak_bytes", lambda kept, **sizes: (
        estimates.append((frozenset(c.names[0] for c in kept), step_peak_bytes(kept, **sizes))), estimates[-1][1])[1])
    config = model_config.transformer_config(model_config.load_config(
        os.path.join(ROOT, "benchmark", "configs", file + ".json")))
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    opt = default_optimizer(3e-4, total_steps=1000)
    state, shardings = abstract_train_state(config, opt, mesh)
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    return (model_family(config).block_costs(config, seq), step.remat_plan_for((batch, seq + 1), state),
            dict(estimates), config)


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_cells_widths_list_the_new_candidates_and_a_v5e_keeps_the_in_projection(monkeypatch, cell):
    """At the published widths the in-projection's output is one candidate
    (z, xBC and dt, or q, k, v and f, come out of one matmul), written by
    every mixer layer of both runs and worth its matmul, as gate and up are:
    the chip fuses the identity that `jax.checkpoint` puts on a kept value
    into the matmul's output. On the cell's chip the rule keeps it, everything
    it kept before, and its estimate of the step stays under the ceiling; the
    bytes a run holds add up to the whole."""
    _, batch, seq, widths, before = CELLS[cell]
    costs, plan, estimates, _ = _cell_plan(monkeypatch, CELLS[cell], V5E_BYTES)
    by_name = {c.names[0]: c for c in costs["candidates"]}
    mixers = by_name["ssm_scan_out" if cell == "nemotron3nano" else "kda_chunk_out"].layers
    assert sum(mixers) in (4, 6)
    for name, width in widths.items():
        assert (by_name[name].names, by_name[name].width, by_name[name].layers) == ((name,), width, mixers)
        assert not by_name[name].tp_sum
    (projection,) = widths
    d_model = 2688 if cell == "nemotron3nano" else 2560
    assert by_name[projection].flops == by_name[projection].worth == 2 * d_model * widths[projection]
    kept = set(plan["remat_saved"])
    assert plan["remat"] == "selective" and projection in kept and before <= kept
    assert kept == EXPERT_CELLS[cell][4]      # with what PR 60 named
    assert estimates[frozenset(kept & set(by_name))] <= (1 - losses.HBM_FREE_FRACTION) * V5E_BYTES
    assert max(estimates.values()) > min(estimates.values())
    # a run's names and bytes: what a trace of the cell says of the plan that ran
    assert len(plan["remat_saved_by_run"]) == len(plan["remat_saved_bytes_by_run"]) == len(costs["runs"])
    assert sum(plan["remat_saved_bytes_by_run"]) == plan["remat_saved_bytes"]
    assert {name for names in plan["remat_saved_by_run"] for name in names} == kept
    assert all(projection in names for names in plan["remat_saved_by_run"])
    rows = batch * seq
    assert plan["remat_saved_bytes"] >= sum(mixers) * rows * widths[projection] * 2


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_device_of_unknown_size_keeps_nothing_of_a_mixer(monkeypatch, cell):
    costs, plan, estimates, _ = _cell_plan(monkeypatch, CELLS[cell], 0)
    assert (plan["remat"], plan["remat_saved"], plan["remat_saved_bytes"]) == ("whole_block", (), 0)
    assert (plan["remat_saved_by_run"], plan["remat_saved_bytes_by_run"]) == (((), ()), (0, 0))
    assert not estimates and plan["remat_recomputed_flops_share"] > 0.8


@pytest.mark.parametrize("cell", list(EXPERT_CELLS))
def test_the_expert_cells_widths_list_the_routing_the_shared_expert_and_the_mixers_tail(monkeypatch, cell):
    """At the published widths the routing is ONE candidate of every expert
    layer, the multi-token prediction module's among them (each run's count
    is the expert layers `stack_runs` gives it), worth six passes of the
    router's matmul and the selection's time; the shared expert's gate and up
    (up alone where it is not gated) are worth their matmul; a delta-rule
    mixer's gated norm's output and, where an MLP or an expert layer follows
    it, the stream after its out-projection are written by every mixer layer.
    On the cell's chip the rule keeps what is pinned here (on Ling everything
    named and everything it kept before; on the four cells with less room
    what fits, by bytes), its estimate of the step under the ceiling."""
    from ray_tpu.models import mixed_stack

    _, batch, seq, named, want = EXPERT_CELLS[cell]
    costs, plan, estimates, config = _cell_plan(monkeypatch, EXPERT_CELLS[cell], V5E_BYTES)
    by_name = {c.names[0]: c for c in costs["candidates"]}
    for name, (width, layers) in named.items():
        assert (by_name[name].names, by_name[name].width, by_name[name].layers, by_name[name].tp_sum) == (
            (name,), width, layers, False)
    new = {"moe_routing", "moe_shared_gate", "moe_shared_up", "kda_residual"}
    assert new & set(by_name) == set(named) and "kda_gate_norm_out" not in by_name
    # a run's expert layers, as the forward walks the stack, then the module's block
    runs = mixed_stack.stack_runs(mixed_stack.layer_kinds(config))
    experts = tuple(run.repeats * sum(kind.mlp == "experts" for kind in run.kinds) for run in runs)
    if config.mtp_modules:
        experts += (int(mixed_stack.mtp_kind(config).mlp == "experts"),)
    assert by_name["moe_routing"].layers == experts and len(experts) == len(costs["runs"])
    router = 2 * config.d_model * config.n_experts
    assert by_name["moe_routing"].flops == router
    assert by_name["moe_routing"].worth == 6 * router + 100_000 * config.n_experts
    for name in set(named) & {"moe_shared_gate", "moe_shared_up"}:
        assert by_name[name].flops == by_name[name].worth == 2 * config.d_model * config.shared_expert_width
    if "kda_residual" in named:
        mixers = tuple(run.repeats * sum(kind.attention == "kda" for kind in run.kinds) for run in runs)
        assert by_name["kda_residual"].layers == by_name["kda_chunk_out"].layers == mixers
        # the kernels' y as the out-projection reads it and their o as the norm's transpose does (PR 63: what
        # the rule's and the norm's candidates held, as one) with the chunks' float32 states, 128 x 128 x 4 B a
        # head over 64 tokens
        assert by_name["kda_chunk_out"].names == ("kda_chunk_out", "kda_chunk_states", "kda_chunk_o")
        assert by_name["kda_chunk_out"].width == 2 * 4096 + 32 * 128 * 128 * 4 // (64 * 2)
        assert by_name["kda_residual"].worth == 2 * 4096 * config.d_model
    kept = set(plan["remat_saved"])
    assert plan["remat"] == "selective" and kept == want
    assert estimates[frozenset(kept & set(by_name))] <= (1 - losses.HBM_FREE_FRACTION) * V5E_BYTES
    assert sum(plan["remat_saved_bytes_by_run"]) == plan["remat_saved_bytes"]
    assert plan["remat_saved_bytes"] == sum(
        sum(by_name[name].layers) * batch * seq * by_name[name].width * 2 for name in kept & set(by_name))



def test_a_plain_top_k_routing_keeps_its_indices_for_the_backward_pass():
    """A router that ranks its scores themselves (no selection bias: OLMoE,
    SmallThinker) takes values and indices from ONE top-k. `moe._top_k_named`
    is that call with JAX's own derivative reading the NAMED indices: same
    values, same gradient, the same operations lowered but for the index's
    reshape; and a checkpoint that keeps the routing selects once, where one
    that keeps nothing (or JAX's rule, whatever is kept) selects again."""
    import collections
    import re

    import jax.numpy as jnp

    from ray_tpu.models import moe

    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    scores = jax.nn.softmax(jax.random.normal(keys[0], (48, 16)), axis=-1)
    weights = jax.random.normal(keys[1], (48, 4))

    def operations(fn):
        return collections.Counter(re.findall(r"= \"?(?:stablehlo|chlo)\.([a-z_]+)", fn.lower(scores).as_text()))

    def graded(top_k, saved=None):
        def block(s):
            return jnp.sum(top_k(2.0 * s, 4)[0] * weights)

        if saved is not None:
            policy = jax.checkpoint_policies.save_only_these_names(*saved) if saved else None
            block = jax.checkpoint(block, policy=policy)
        return jax.jit(jax.value_and_grad(block))

    (ours, our_grad), (theirs, their_grad) = graded(moe._top_k_named)(scores), graded(jax.lax.top_k)(scores)
    assert float(ours) == float(theirs)
    np.testing.assert_array_equal(np.asarray(our_grad), np.asarray(their_grad))
    differ = operations(graded(moe._top_k_named))
    differ.subtract(operations(graded(jax.lax.top_k)))
    assert {name for name, n in differ.items() if n} <= {"reshape", "broadcast_in_dim"}
    selects = [operations(graded(top_k, saved))["top_k"] for top_k, saved in (
        (moe._top_k_named, ()), (moe._top_k_named, (moe.ROUTING,)), (jax.lax.top_k, (moe.ROUTING,)))]
    assert selects == [2, 1, 2]
