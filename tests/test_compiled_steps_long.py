"""Steps and layers that are lowered or compiled whole and never run, 20-50 s
each: the OLMoE expert layer on the described 2x2 (tests/test_tpu_compile.py)
and the tiny mixed step's operation table and its row-sum kernel
(from tests/test_step_scopes.py, with the fixture only they use). They live
in a file of few tests (the rule in tests/conftest.py); the other fixtures
and helpers are the origins'."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.train.lm import create_train_state, default_optimizer, make_train_step
from ray_tpu.util import profiling
from tests.test_step_scopes import ATTENTION, STEP, _scope_passes
from tests.test_tpu_compile import _kernel_calls, as_tpu, v5e  # noqa: F401 - fixtures


def test_moe_layer_under_a_mesh_runs_the_kernels_per_shard(v5e, as_tpu):
    """One OLMoE expert layer (64 experts of 1024, top-8) on fsdp=2 x tp=2:
    the layer shard_maps itself over the mesh the weights carry (no context
    mesh here), so each chip sorts its own two 4,096-token sequences and
    runs the nine `moe_gmm_*` calls of a forward and backward on its tp
    slice of every expert; nothing Mosaic is left to GSPMD."""
    from ray_tpu.models import moe

    config = moe.olmoe_1b_7b()
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=v5e.devices)

    def on_mesh(shape, spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    m, f, e = config.d_model, config.d_ff, config.n_experts
    lp = {
        "ln2_scale": on_mesh((m,), PartitionSpec()),
        "router": on_mesh((m, e), PartitionSpec("fsdp", None)),
        "we_gate": on_mesh((e, m, f), PartitionSpec(None, "fsdp", "tp")),
        "we_up": on_mesh((e, m, f), PartitionSpec(None, "fsdp", "tp")),
        "we_down": on_mesh((e, f, m), PartitionSpec(None, "tp", "fsdp")),
    }
    x = on_mesh((4, 4096, m), PartitionSpec(("dp", "fsdp"), None, None), jnp.bfloat16)

    def loss(lp, x):
        out, aux, _ = moe.moe_mlp_sublayer(x, lp, config)
        return out.astype(jnp.float32).sum() + aux

    compiled = jax.jit(jax.grad(loss)).lower(lp, x).compile()
    assert _kernel_calls(compiled) == 9
    # a chip holds its quarter of the three expert stacks (float32), not the whole
    assert compiled.memory_analysis().argument_size_in_bytes < 2 * (3 * e * m * f * 4) // 4


def _lowered_mixed_step():
    """The tiny mixed stack's step (dS dS scanned, eS eF unrolled), lowered
    for one device and never run."""
    from test_mixed_stack import tiny

    config = tiny(n_layers=4)
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    optimizer = default_optimizer(3e-4, total_steps=10)
    state, shardings = create_train_state(config, optimizer, jax.random.PRNGKey(0), mesh)
    step = make_train_step(config, optimizer, mesh, state_shardings=shardings)
    return step.lower(state, {"tokens": jnp.zeros((2, 33), jnp.int32)})


@pytest.fixture(scope="module")
def mixed():
    """The operation table of a tiny mixed stack's step (dS dS scanned, eS eF
    unrolled: window and full attention, a dense MLP, held experts with a
    shared one), compiled and never run."""
    program, table = profiling.program_ops_table(profiling._module_text(_lowered_mixed_step().compile()))
    assert program == STEP
    return table


def test_mixed_step_table_holds_both_attention_kinds_both_mlps_and_the_expert_layers_parts(mixed):
    pairs = _scope_passes(mixed)
    moe = {"moe", "moe.route", "moe.select", "moe.dispatch", "moe.experts", "moe.combine", "moe.passes",
           "moe.shared"}
    # every scope but the two a latent-attention stack with a prediction module adds (PR 44),
    # a state-space mixer's (PR 48), the dense family's EVA attention and next-byte heads (PR 51), a
    # delta-rule mixer's (PR 55) and a gated short convolution's (PR 61)
    assert {scope for scope, _ in pairs} == {
        s for s in profiling.STEP_SCOPES if s not in ("attn.latent", "mtp", "head.multibyte")
        and not s.startswith(("ssm", "kda", "sconv", "attn.eva"))}
    for sublayer in {"attn.window", "attn.full", "mlp"} | moe:
        assert {(sublayer, "fwd"), (sublayer, "recompute"), (sublayer, "bwd")} <= pairs
    for found in mixed.values():
        for scopes, _, _ in found:
            if set(scopes) & (moe - {"moe"}):
                assert "moe" in scopes
            if ATTENTION & set(scopes):
                assert ("attn.window" in scopes) != ("attn.full" in scopes)


def test_held_row_sum_kernel_is_one_body_a_signature_under_the_combine_and_the_dispatch(monkeypatch):
    """The tiny mixed step with the expert layer's kernels on (interpreted
    here): `moe_rows_sum` is lowered once a signature (float32 rows with
    gates, float32 rows without) and called from every layer, pass and
    recomputation, and each call keeps its call site's scopes: the combine's
    in the forward pass and its recomputation, the dispatch's (the transpose
    of its gather) in the backward pass, and nowhere else."""
    from ray_tpu.models import moe

    monkeypatch.setattr(moe, "resolve_gmm_impl", lambda implementation=None: "pallas")
    monkeypatch.setattr(moe, "gmm_tile_rows", lambda implementation=None: 16)
    lowered = _lowered_mixed_step()
    text = lowered.as_text()
    assert len(re.findall(r"func\.func private @moe_rows_sum\w*\(", text)) == 2
    # eS eF unrolled, the first pass and the later one, forward, recomputed and backward
    assert len(re.findall(r"call @moe_rows_sum", text)) == 2 * 2 * 3
    placed = set()
    for path in re.findall(r'op_name="([^"]*/moe_rows_sum/[^"]*)"', profiling._module_text(lowered.compile())):
        placed.add((tuple(sorted(set(profiling._SCOPE_ON_PATH.findall(path)) & {"moe.combine", "moe.dispatch"})),
                    profiling.op_pass(path)))
    assert placed == {(("moe.combine",), "fwd"), (("moe.combine",), "recompute"),
                      (("moe.dispatch",), "bwd")}
