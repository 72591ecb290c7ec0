"""The training cells' whole steps at real widths, compiled for a DESCRIBED v5e.

What tests/test_tpu_compile.py says of its kernels holds here: nothing runs,
and a compile that passes is not a chip run. Each test takes 25-130 s, so
they live apart from the kernel compiles (the rule at the top of
tests/conftest.py): this file grew by one test a model until its five took 380 s of the
450 a file of long tests may; since PR 48 a new model's goes to
tests/test_tpu_compile_cells_2.py.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from ray_tpu.parallel import MeshSpec, build_mesh
from tests.test_tpu_compile import _kernel_calls, as_tpu, v5e  # noqa: F401 - fixtures


def _cell_step_shapes(cell_config, mesh, tokens_shape):
    """What a training cell runs, as shapes on a described mesh: (its
    configuration, the optimizer, the state as `create_train_state` builds
    it, its shardings, a batch of tokens over the data axes)."""
    from benchmark import model_config
    from ray_tpu.train.lm import abstract_train_state, default_optimizer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = model_config.transformer_config(model_config.load_config(
        os.path.join(root, "benchmark/configs", cell_config + ".json")))
    opt = default_optimizer(3e-4, total_steps=1000)
    state, shardings = abstract_train_state(config, opt, mesh)
    state = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), state, shardings)
    tokens = jax.ShapeDtypeStruct(
        tokens_shape, jnp.int32, sharding=NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), None)))
    return config, opt, state, shardings, tokens


@pytest.fixture(scope="module")
def mistral_cell_step(v5e):
    """`train-mistral7b-fsdp2tp2` on the described 2x2: 24 x 1,025 tokens."""
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=v5e.devices)
    config, opt, state, shardings, tokens = _cell_step_shapes(
        "mistral-7b-v0.3-train-4chip", mesh, (24, 1025))
    return config, opt, mesh, shardings, state, tokens


GIB = 2 ** 30
# this step since PR 24's rehearsal; 6.84 GiB of temporaries until PR 45 made the backward ONE kernel,
# 6.51 until PR 54 laid the stream's sequences over `tp`
WHOLE_BLOCK_TEMP_GIB, WHOLE_BLOCK_TFLOP = 6.12, 14.98


@pytest.mark.parametrize("hbm_gib,want", [
    (0, ("whole_block", ())),
    (15.75, ("selective", ("attn_residual", "mlp_up", "mlp_gate", "attn_q", "attn_k", "attn_v"))),
], ids=["unknown-device-size", "v5e-15.75GiB"])
def test_mistral_cell_step_keeps_what_fits_and_compiles(
        as_tpu, monkeypatch, mistral_cell_step, hbm_gib, want):
    """The cell's whole step for the described v5e:2x2, with the head the
    rule gives it: the dense one on a device of unknown size, since PR 46
    the fused one (a device's 12 x 1,024 rows as one chunk) at the chip's
    15.75 GiB. A device
    of unknown size gets the whole-block program (5.63 GiB of arguments and
    6.12 of temporaries a chip, 14.98 TFLOP with the scanned block counted
    once, 16 all-reduces: 21 before PR 54, whose rings of permutes over `tp`
    stand where the blocks' five were). At the chip's 15.75 GiB the rule keeps gate, up
    and the residual after the output projection (its half of the sequences:
    3.0 GiB a device, 3.375 before PR 54) and, since PR 62, q, k and v as the
    kernel takes them (its heads of whole sequences: 0.5625 GiB more): two
    matmuls of 0.72 TFLOP, the output projection's 0.2 and the three
    projections' 0.31 less in the scanned block, no q, k or v matmul and no
    rotation in the recomputed pass (the ring still hands the normed stream's
    piece round once: the weight gradients read it),
    and no more than 5.1 GiB of temporaries
    over the whole-block program's (4.51 by this compiler's count; 4.20 for
    the three older names since PR 45,
    whose one backward kernel took 0.33 GiB off that program and 0.08 off this
    one; 3.95 of 6.84 before, which
    read 2.1 GiB over the chip's peak for the whole-block step and 25% over
    the kept values' own bytes: PERF.md section 6, PR 30). The attention
    kernel's output is a candidate since PR 34 and is not kept here: at
    S = 1,024 it is worth less than keeping it moves (with it this compiler
    counts 4.34 GiB over and a kernel call less, the chip 0.41 GB more and
    no gain: PERF.md section 6, PR 34)."""
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import make_train_step

    config, opt, mesh, shardings, state, tokens = mistral_cell_step
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: int(hbm_gib * GIB))
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    plan = step.remat_plan_for(tokens.shape, state)
    assert (plan["remat"], plan["remat_saved"]) == want
    assert step.loss_chunk_for(tokens.shape, state) == (1024 if hbm_gib else 0)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    memory, tflop = compiled.memory_analysis(), compiled.cost_analysis()["flops"] / 1e12
    all_reduces = compiled.as_text().count(" all-reduce(")
    assert _kernel_calls(compiled) == 3   # flash_fwd twice (the forward, its recomputation), flash_bwd_dkv_dq
    assert memory.argument_size_in_bytes / GIB == pytest.approx(5.63, abs=0.02)
    temp_gib = memory.temp_size_in_bytes / GIB
    if not want[1]:
        assert temp_gib == pytest.approx(WHOLE_BLOCK_TEMP_GIB, abs=0.15)
        assert tflop == pytest.approx(WHOLE_BLOCK_TFLOP, abs=0.05)
        assert all_reduces == 16
    else:
        assert WHOLE_BLOCK_TEMP_GIB + 3.5625 < temp_gib <= WHOLE_BLOCK_TEMP_GIB + 5.1
        assert plan["remat_saved_bytes"] / GIB == pytest.approx(3.5625, abs=0.001)
        assert tflop <= WHOLE_BLOCK_TFLOP - 1.9
        assert all_reduces == 17
        again = set(re.findall(r'rematted_computation/attn\.full/attn\.proj/([^"]*)"', compiled.as_text()))
        assert "shard_map/ppermute" in again and not [name for name in again if "dot_general" in name], again
        _mistral_step_says_its_collectives(compiled, mesh)


def _mistral_step_says_its_collectives(compiled, mesh):
    """What the TPU compiler emits for the cell's step, as the program's
    registry reads it (PR 53; `profiling.program_collectives_table`): every
    collective placed on the 2x2; since PR 54 no all-reduce of an activation
    along `tp` inside the blocks (the five left are the embedding's, the
    head's and its backward's) and in their place ten permute pairs of
    half an activation (four forward, two recomputed, four backward), the pieces of the ring matmuls (the MLP's forward has
    two: the gather in front of up and gate, the scatter behind down); the
    weights' gathers and the gradients' all-reduces along `fsdp`, 10 of the
    gathers (11 until PR 62: with no q, k, v matmul in the recomputed pass to carry it, the backward's
    gather of `wk` or `wv`, 4 MB, is a plain one) in the compiler's own asynchronous form (fusions
    `async-collective-start` / `-done` with the matmul fusion that carries the
    all-gather between them)."""
    from collections import Counter

    from ray_tpu.util import profiling

    text = profiling._module_text(compiled)
    table = profiling.program_ops_table(text)[1]
    found = profiling.program_collectives_table(
        text, tuple(mesh.shape.items()), profiling._module_shapes_text(compiled))
    assert set(found) <= set(table) and all(record.axes and record.bytes for record in found.values())
    whole = Counter((record.kind, record.half, record.axes) for record in found.values() if not record.completes)
    assert whole["all-gather", "start", ("fsdp",)] == 10 and whole["all-reduce", "", ("tp",)] == 5
    assert whole["all-reduce", "", ("fsdp",)] == 9 and sum(whole.values()) == 50
    carried = Counter(record.half for name, record in found.items() if name.startswith(("async-collective", "fusion.")))
    assert carried["start"] == carried["done"] == 10 and carried["under"] >= 10
    activation = 12 * 1024 * 4096 * 2
    in_blocks = {"attn.out", "attn.proj", "mlp"}
    assert not [name for name, record in found.items()
                if record.kind == "all-reduce" and "tp" in record.axes and record.bytes >= activation // 2
                and in_blocks & set(table[name][0][0])]
    pieces = [record for name, record in found.items()
              if record.kind == "collective-permute" and record.half == "start" and record.bytes == activation // 2]
    assert len(pieces) == 10 and {record.axes for record in pieces} == {("tp",)}
    mlp_fwd = [record for name, record in found.items() if record.half == "start"
               and record.kind == "collective-permute"
               and table[name][0][:2] == (("steplog.fwd_bwd_compute", "mlp"), "fwd")]
    assert [record.bytes for record in mlp_fwd] == [activation // 2] * 2


def _kernels_named(compiled, name) -> int:
    """Custom calls of the Pallas kernel `name` in the compiled module."""
    return len(re.findall(rf"^\s*%{name}[.\d]* = .*custom-call\(", compiled.as_text(), re.M))


def _held_row_sums(lowered, compiled, tokens_by_width) -> tuple:
    """Of a held-expert cell's step: (`moe_rows_sum` bodies in the lowered
    text: what a warm set-up traces and lowers; its calls in the compiled
    step; scatters that write `tokens_by_width`: the combine, float32, and
    the transpose of the dispatch's gather, bfloat16, as XLA has them: 8 of
    each in either cell's step before the kernel)."""
    bodies = lowered.as_text().count('kernel_name = "moe_rows_sum"')
    scatters = re.findall(rf"= (?:f32|bf16)\[{tokens_by_width}\]\S* scatter\(", compiled.as_text())
    return bodies, _kernels_named(compiled, "moe_rows_sum"), len(scatters)


def _held_passes_not_taken(lowered, compiled, tokens_by_width) -> tuple:
    """Of a held-expert cell's step: (Mosaic bodies in the lowered text, each
    traced and lowered in every run's set-up, cache or no cache: a call site
    more in a layer's body is what cost PR 40 7 s of `setup_s`; zeros of the
    expert layer's float32 output filled in the compiled step: what a `cond`
    of the later passes does whose untaken branch does not hand its operands
    back)."""
    fills = re.findall(rf"= f32\[{tokens_by_width}\]\S* broadcast\(", compiled.as_text())
    return _kernel_calls(lowered), len(fills)


def test_trinity_cell_step_keeps_the_attention_outputs_and_compiles(as_tpu, monkeypatch, v5e):
    """`train-trinity-mini-8k`'s whole step (2 x 8,193 tokens, the state as
    `create_train_state` builds it) for one described v5e chip of 15.75 GiB:
    the rule fuses the head and keeps the attention kernels' outputs, and
    the compiled step runs each forward flash kernel once a layer (the
    two scanned dense layers' in the forward loop's body alone, the four
    unrolled layers' once each) beside its one backward kernel; a
    whole-block step runs it twice. The kernel's own lse, (B, H, S, 1), is
    what a tiled layout pads 128 times: the value kept is (B, H, S)."""
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import make_train_step

    mesh = build_mesh(MeshSpec(), devices=[as_tpu])
    config, opt, state, shardings, tokens = _cell_step_shapes(
        "trinity-mini-train-1chip", mesh, (2, 8193))
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: int(15.75 * GIB))
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    plan = step.remat_plan_for(tokens.shape, state)
    # since PR 60 also the four expert layers' routing (40 MB) and their shared expert's gate and up (0.27
    # GB): the unrolled run's kept values go as its gradients come, so the estimate's largest moment, the
    # scanned dense run's, does not move (15.73 GB; the chip 88.56 -> 88.86% of its memory)
    assert (plan["remat"], plan["remat_saved"]) == ("selective", (
        "moe_routing", "attn_out", "attn_lse", "moe_shared_gate", "moe_shared_up"))
    assert plan["remat_saved_by_run"] == (("attn_out", "attn_lse"), plan["remat_saved"])
    # the whole sequence as the fused head's one chunk since PR 46 (the chip, one seed: 2,048 rows
    # 28,873 tokens/s at 89.18% of memory, the whole 8,192 28,902 at 88.56%)
    assert step.loss_chunk_for(tokens.shape, state) == 8192
    lowered = step.lower(state, {"tokens": tokens})
    compiled = lowered.compile()
    # one body a signature (float32 with gates, bfloat16 without) however many call it:
    # 4 layers x (the first pass, the later one) x (combine, its recomputation for the
    # norm after it, the dispatch's transpose); no scatter adds wide rows into tokens
    assert _held_row_sums(lowered, compiled, "16384,2048") == (2, 4 * 2 * 3, 0)
    bodies, fills = _held_passes_not_taken(lowered, compiled, "16384,2048")
    # PR 45's count of bodies (its parent's 125 less the five dQ kernels); a pass not taken fills nothing
    assert bodies <= 120 and fills == 0
    # dS dS scanned (one body forward, one backward), eS eF eS eS unrolled
    assert _kernels_named(compiled, "flash_win_fwd") == 1 + 3
    assert _kernels_named(compiled, "flash_fwd") == 1
    assert _kernels_named(compiled, "flash_win_bwd_dkv_dq") == 1 + 3
    assert _kernels_named(compiled, "flash_bwd_dkv_dq") == 1
    text = compiled.as_text()
    assert "flash_bwd_dq" not in text and "flash_win_bwd_dq" not in text
    assert "f32[2,32,8192,1]{3,2,1,0:T(8,128)}" in text      # 268 MB where written
    assert "f32[2,32,8192]{2,1,0:T(8,128)" in text           # 2.1 MB where kept
    # `lse_first`: in the schedule every unrolled layer's lse is reshaped before the
    # first backward kernel runs (left alone, where the backward reads it)
    lines = text[text.index("\nENTRY "):].splitlines()
    first_backward = next(i for i, line in enumerate(lines) if "%flash_win_bwd_dkv_dq" in line.split("=")[0])
    lse_outputs = [re.match(r"\s*(%[\w.\-]+) = f32\[2,32,8192,1\]", line).group(1) for line in lines
                   if re.match(r"\s*%[\w.\-]+ = f32\[2,32,8192,1\].* get-tuple-element\(%flash_(win_)?fwd", line)]
    assert len(lse_outputs) == 4
    for name in lse_outputs:
        reader = next(i for i, line in enumerate(lines) if f"({name})" in line or f"({name}," in line)
        assert reader < first_backward
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes / GIB == pytest.approx(8.61, abs=0.02)


def test_smallthinker_cell_step_keeps_what_a_four_layer_iteration_leaves_room_for(as_tpu, monkeypatch, v5e):
    """`train-smallthinker-16k`'s whole step (1 x 16,385 tokens) for one
    described v5e chip of 15.75 GiB: one scanned run whose iteration is four
    layers. Counted as a one-layer iteration the estimate kept everything and
    the compiler refused the step (17.04 GB with the head chunked); with the
    other three layers' slices counted the rule keeps the attention kernels'
    outputs, fuses the head, and the step compiles: each forward flash kernel
    once a layer of the period in the forward loop's body alone."""
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import make_train_step

    mesh = build_mesh(MeshSpec(), devices=[as_tpu])
    config, opt, state, shardings, tokens = _cell_step_shapes(
        "smallthinker-21b-a3b-train-1chip", mesh, (1, 16385))
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: int(15.75 * GIB))
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    plan = step.remat_plan_for(tokens.shape, state)
    # since PR 60 the eight expert layers' routing beside them (43 MB: the chip 92.50 -> 92.75% of its memory)
    assert (plan["remat"], plan["remat_saved"]) == ("selective", ("moe_routing", "attn_out", "attn_lse"))
    # the whole sequence as the fused head's one chunk since PR 46 (the chip, one seed: 2,048 rows
    # 25,871 tokens/s at 92.59% of memory, the whole 16,384 25,922 at 92.50%)
    assert step.loss_chunk_for(tokens.shape, state) == 16384
    lowered = step.lower(state, {"tokens": tokens})
    compiled = lowered.compile()
    # eF eS eS eS, scanned twice: one body forward, one backward
    assert _kernels_named(compiled, "flash_win_fwd") == 3 and _kernels_named(compiled, "flash_fwd") == 1
    assert _kernels_named(compiled, "flash_win_bwd_dkv_dq") == 3
    assert _kernels_named(compiled, "flash_bwd_dkv_dq") == 1
    assert "flash_bwd_dq" not in compiled.as_text() and "flash_win_bwd_dq" not in compiled.as_text()
    # 4 layers x 3 projections, forward and recomputed: the held buffer is recomputed whole (the routing is kept)
    assert _kernels_named(compiled, "moe_gmm_fwd") == 2 * 4 * 3 * 2
    # `moe_rows_sum`: one body a signature; 4 layers x 2 passes x (the combine, the
    # dispatch's transpose): nothing in the backward pass reads a recomputed combine
    assert _held_row_sums(lowered, compiled, "16384,2560") == (2, 4 * 2 * 2, 0)
    bodies, fills = _held_passes_not_taken(lowered, compiled, "16384,2560")
    # PR 45's count of bodies (its parent's 110 less the four dQ kernels); a pass not taken fills nothing
    assert bodies <= 106 and fills == 0
    assert compiled.memory_analysis().argument_size_in_bytes / GIB == pytest.approx(7.20, abs=0.02)


def test_glm47flash_cell_step_keeps_the_attention_outputs_and_the_latents_and_compiles(as_tpu, monkeypatch, v5e):
    """`train-glm47flash-8k`'s whole step (2 x 8,193 tokens) for one described
    v5e chip of 15.75 GiB: a dense latent-attention layer, four scanned expert
    layers and the multi-token prediction module's block. The rule keeps the
    attention kernels' outputs and the latents (after which the backward
    repeats the up-projections alone) beside the fused head; the compiled
    step runs the forward flash kernel at D = 256 once a body (the dense
    layer, the scan's body, the module), the module's block and the second
    pass of the head lie under `mtp`, and the latent projections under
    `attn.latent`."""
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import make_train_step
    from ray_tpu.util import profiling

    mesh = build_mesh(MeshSpec(), devices=[as_tpu])
    config, opt, state, shardings, tokens = _cell_step_shapes(
        "glm-4.7-flash-train-1chip", mesh, (2, 8193))
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: int(15.75 * GIB))
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    plan = step.remat_plan_for(tokens.shape, state)
    # since PR 60 the five expert layers' routing (the module's block is one) and their shared expert's gate
    # where the latents were: the routing's 25 MB come first by worth a byte and leave the latents' 0.264 GB
    # 12 MB over the ceiling, the gate's 0.252 GB, worth the same a byte, 7 MB under it (on the chip the step
    # is 585.2 -> 577.7 ms with it, at 89.3% of its memory either way: PERF.md section 6, PR 60)
    assert (plan["remat"], plan["remat_saved"]) == ("selective", (
        "moe_routing", "attn_out", "attn_lse", "moe_shared_gate"))
    # 6 layers x 16,384 rows x (5,120 + 40) bfloat16 features, 5 x 16,384 x (152 + 1,536)
    assert plan["remat_saved_bytes"] == (6 * (5120 + 40) + 5 * (152 + 1536)) * 16384 * 2
    # both passes of the head, the stack's and the module's, fused with the whole sequence as
    # the one chunk since PR 46 (the chip, one seed: dense 27,964 tokens/s at 90.28% of
    # memory, the whole 8,192 27,986 at 89.32%)
    assert step.loss_chunk_for(tokens.shape, state) == 8192
    lowered = step.lower(state, {"tokens": tokens})
    compiled = lowered.compile()
    for kernel in ("flash_fwd", "flash_bwd_dkv_dq"):
        assert _kernels_named(compiled, kernel) == 3
    assert "flash_win" not in compiled.as_text() and "flash_bwd_dq" not in compiled.as_text()
    # 5 expert layers in 2 bodies (the scan's, the module's) x 3 projections x (the first
    # pass through the held buffer, the later ones), forward and recomputed
    assert _kernels_named(compiled, "moe_gmm_fwd") == 2 * 3 * 2 * 2
    _, table = profiling.program_ops_table(profiling._module_text(compiled))
    scoped = [scopes for instances in table.values() for scopes, _, _ in instances]
    assert any("attn.latent" in scopes and "attn.proj" in scopes and "mtp" not in scopes for scopes in scoped)
    assert any({"mtp", "attn.latent"} <= set(scopes) for scopes in scoped)
    assert any({"mtp", "head"} <= set(scopes) for scopes in scoped)
    assert any({"mtp", "moe.experts"} <= set(scopes) for scopes in scoped)
    assert not any("attn.latent" in scopes and "attn.proj" not in scopes for scopes in scoped)
    # 706.5 M parameters x 12 bytes of state (the gradients are the step's own)
    assert compiled.memory_analysis().argument_size_in_bytes / GIB == pytest.approx(7.90, abs=0.02)
