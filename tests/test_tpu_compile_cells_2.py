"""More training cells' whole steps at real widths, compiled for a DESCRIBED
v5e: what tests/test_tpu_compile_cells.py says of its tests holds here (nothing
runs; a compile that passes is not a chip run). That file's five tests take
380 s of the 450 s a file of long tests may (the rule at the top of
tests/conftest.py), so a new model's compiled step lives here: at most six."""

import re

import jax  # noqa: F401
import pytest

from ray_tpu.parallel import MeshSpec, build_mesh
from tests.test_tpu_compile import as_tpu, v5e  # noqa: F401 - fixtures
from tests.test_tpu_compile_cells import GIB, _cell_step_shapes, _kernels_named


def test_nemotron3nano_cell_step_runs_the_scan_under_its_scopes_and_compiles(as_tpu, monkeypatch, v5e):
    """`train-nemotron3nano-8k`'s whole step (2 x 8,193 tokens) for one described
    v5e chip of 15.75 GiB: `ME` scanned twice, then `M*EME` unrolled. Each of
    the 7 layer bodies is one sublayer; the state-space mixer's five scopes
    lie inside `ssm` in the forward pass, its recomputation and the backward
    pass, its convolution, its scan and its gated norm are the two kernels
    each of ops/ssd, the convolution's reading xBC out of the in-projection's
    output as it is; the one attention layer runs the causal D = 128 flash kernels and
    the expert layers TWO grouped matmuls a pass (a non-gated expert); the
    fused head takes the whole sequence as its chunk."""
    from ray_tpu.models import model_family
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import make_train_step
    from ray_tpu.util import profiling

    mesh = build_mesh(MeshSpec(), devices=[as_tpu])
    config, opt, state, shardings, tokens = _cell_step_shapes(
        "nemotron-3-nano-30b-a3b-train-1chip", mesh, (2, 8193))
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: int(15.75 * GIB))
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    plan = step.remat_plan_for(tokens.shape, state)
    # what the rule kept before PR 57, the four state-space layers' in-projections beside it, since PR
    # 60 the four expert layers' routing and their shared expert's up (it is not gated) and, since PR 62,
    # the one attention layer's q, k and v (32 + 2 + 2 heads of 128: 0.151 GB in the unrolled run, whose
    # kept values go as its gradients come, so the estimate does not move; on the chip the peak did not
    # either, 90.77 -> 90.76%, and the step went from 356.5 to 354.0 ms: PERF.md section 6, PR 62)
    assert plan["remat"] == "selective" and set(plan["remat_saved"]) == {
        "ssm_scan_out", "ssm_chunk_states", "attn_out", "attn_lse", "attn_residual", "ssm_in_proj",
        "moe_routing", "moe_shared_up", "attn_q", "attn_k", "attn_v"}
    assert plan["remat_saved_by_run"] == (
        ("moe_routing", "ssm_in_proj", "moe_shared_up", "ssm_scan_out", "ssm_chunk_states"),
        ("moe_routing", "attn_out", "attn_lse", "attn_residual", "ssm_in_proj", "moe_shared_up",
         "attn_q", "attn_k", "attn_v", "ssm_scan_out", "ssm_chunk_states"))
    assert plan["remat_saved_bytes_by_run"] == (
        2 * 16384 * (10304 + 8192 + 292 + 3712) * 2,
        16384 * ((4096 + 64) + 2688 + (4096 + 2 * 256) + 2 * (10304 + 8192 + 292 + 3712)) * 2)
    assert step.loss_chunk_for(tokens.shape, state) == 8192
    said = model_family(config).plan(config, 2, 8192)
    assert (said["ssm_scan_impl"], said["ssm_scan_kernels"], said["ssm_scan_state_bytes"]) == (
        "pallas", 2, 8 * 64 * 128 * 4)
    assert (said["ssm_gate_norm_impl"], said["ssm_gate_norm_rows"]) == ("pallas", 256)
    assert (said["ssm_conv_impl"], said["ssm_conv_rows"]) == ("pallas", 256)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    # one attention layer, in the unrolled run: the forward kernel and its recomputation, one backward
    assert _kernels_named(compiled, "flash_bwd_dkv_dq") == 1 and "flash_win" not in compiled.as_text()
    # 4 expert layers in 3 bodies (the scan's, two unrolled) x 2 projections x (the first pass
    # through the held buffer, the later ones), forward and recomputed
    assert _kernels_named(compiled, "moe_gmm_fwd") == 3 * 2 * 2 * 2
    # 4 state-space layers in 3 bodies (the scan's, two unrolled): the rule keeps the scans' outputs
    # and the states that entered their chunks, so `ssd_fwd` runs in the forward pass alone (a plan
    # that kept neither would run it again under `recompute`) and `ssd_bwd` once a body
    assert {"ssm_scan_out", "ssm_chunk_states"} <= set(plan["remat_saved"])
    assert _kernels_named(compiled, "ssd_fwd") == 3 and _kernels_named(compiled, "ssd_bwd") == 3
    _, table = profiling.program_ops_table(profiling._module_text(compiled))
    for kernel, pass_ in (("ssd_fwd", "fwd"), ("ssd_bwd", "bwd")):
        assert len(table[kernel]) == 3
        for scopes, found, _ in table[kernel]:
            assert {"ssm", "ssm.scan"} <= set(scopes) and found == pass_, (kernel, scopes, found)
    # the gated norm's output is not kept: its forward kernel runs again in every body's recomputation
    # (the out-projection's weight gradient reads it), its backward kernel once a body
    assert _kernels_named(compiled, "ssm_gate_norm_fwd") == 6 and _kernels_named(compiled, "ssm_gate_norm_bwd") == 3
    assert sorted(found for _, found, _ in table["ssm_gate_norm_fwd"]) == ["fwd"] * 3 + ["recompute"] * 3
    for scopes, found, _ in table["ssm_gate_norm_fwd"] + table["ssm_gate_norm_bwd"]:
        assert {"ssm", "ssm.gate_norm"} <= set(scopes), scopes
    assert {found for _, found, _ in table["ssm_gate_norm_bwd"]} == {"bwd"}
    # the convolution keeps nothing but its arguments: forward and recomputed in every body, one backward
    assert _kernels_named(compiled, "ssm_conv_fwd") == 6 and _kernels_named(compiled, "ssm_conv_bwd") == 3
    assert sorted(found for _, found, _ in table["ssm_conv_fwd"]) == ["fwd"] * 3 + ["recompute"] * 3
    for scopes, found, _ in table["ssm_conv_fwd"] + table["ssm_conv_bwd"]:
        assert {"ssm", "ssm.conv"} <= set(scopes), scopes
    assert {found for _, found, _ in table["ssm_conv_bwd"]} == {"bwd"}
    # xBC reaches the kernels in the projection as the matmul wrote it, and their output the scan's
    # slices: no copy of a (2, 8,192, 6,144) array, nor of a (2, 8,192, 10,304) one (until PR 57 the
    # scanned run's forward projection was written S-minor and copied: PERF.md row 49 (h); kept, it is
    # written feature-minor into its stack, the identity `jax.checkpoint` puts on it fused into the
    # matmul's output: one matmul a body forward, none under `recompute`)
    text = compiled.as_text()
    copies = re.findall(r"= bf16\[(?:2,8192|16384),(?:6144|10304)\]\S* copy\([^\n]*", text)
    assert not copies, copies
    assert "/ssm.in_proj/bse,ef->bsf/dot_general" in text
    assert "rematted_computation/ssm/ssm.in_proj/bse,ef->bsf/dot_general" not in text
    pairs = {(scope, pass_) for instances in table.values() for scopes, pass_, _ in instances for scope in scopes}
    for scope in ("ssm", "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm"):
        assert {(scope, "fwd"), (scope, "recompute"), (scope, "bwd")} <= pairs, scope
    # the out-projection's output only feeds the next layer's input, which is kept: not run again
    assert {("ssm.out_proj", "fwd"), ("ssm.out_proj", "bwd")} <= pairs
    scoped = [set(scopes) for instances in table.values() for scopes, _, _ in instances]
    assert not any(scopes & {"ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj"}
                   and "ssm" not in scopes for scopes in scoped)
    assert not any({"ssm", "moe"} <= scopes or {"ssm", "attn.full"} <= scopes for scopes in scoped)
    assert not any("mlp" in scopes or "attn.window" in scopes for scopes in scoped)
    # 667.0 M parameters x 12 bytes of state (the gradients are the step's own). This compiler counts
    # 7.30 GiB of temporaries beside them for the parent's plan, 14.75 GiB in all, for the step that ran
    # on the chip at 13,691,970,560 B = 12.75 GiB, and 1.26 GiB more with the projections kept, for the
    # step that ran at 15,000,355,328 B = 13.97 GiB = 88.7% of the chip (my chip runs, PR 57): it reads
    # 2.0 GiB over the chip on both, so the band guards the program, and the chip's reading the fit.
    # Since PR 60 0.80 GiB more with the routing and the shared expert's up kept (0.49 GiB by their
    # shapes), for the step that ran at 15,348,006,912 B = 14.29 GiB = 90.8% (my chip run, PR 60): 2.5 over
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes / GIB == pytest.approx(7.45, abs=0.02)
    assert memory.temp_size_in_bytes / GIB == pytest.approx(7.30 + 1.26 + 0.80, abs=0.25)


def test_evabyte_cell_step_runs_the_kernels_once_a_shard_and_compiles(as_tpu, monkeypatch, v5e):
    """`train-evabyte-fsdp4-32k`'s whole step (4 x 32,769 bytes under fsdp=4,
    one sequence a chip) for the described v5e:2x2 at 15.75 GiB a chip: the
    scanned block runs the causal flash kernels over windows and the two far
    kernels inside one shard_map a call, forward, recomputed and backward,
    under `attn.eva`; the eight-head loss is the fused one, the whole
    sequence a chunk, under `head.multibyte`; and the step fits a chip."""
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import make_train_step
    from ray_tpu.util import profiling

    mesh = build_mesh(MeshSpec(fsdp=4), devices=v5e.devices)
    config, opt, state, shardings, tokens = _cell_step_shapes("evabyte-6.5b-train-4chip", mesh, (4, 32769))
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: int(15.75 * GIB))
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    plan = step.remat_plan_for(tokens.shape, state)
    assert plan["remat"] in ("whole_block", "selective")
    assert set(plan["remat_saved"]) <= {"attn_out", "attn_lse", "attn_residual", "mlp_up", "mlp_gate"}
    assert step.loss_chunk_for(tokens.shape, state) == 32768
    compiled = step.lower(state, {"tokens": tokens}).compile()
    kept = "attn_lse" in plan["remat_saved"]
    assert _kernels_named(compiled, "flash_bwd_dkv_dq") == 1 and _kernels_named(compiled, "eva_far_bwd") == 1
    assert _kernels_named(compiled, "flash_fwd") == _kernels_named(compiled, "eva_far_fwd") == (1 if kept else 2)
    assert "flash_win" not in compiled.as_text()
    _, table = profiling.program_ops_table(profiling._module_text(compiled))
    for kernel, scope in (("flash_fwd", "attn.eva.local"), ("eva_far_fwd", "attn.eva.far"),
                          ("flash_bwd_dkv_dq", "attn.eva.local"), ("eva_far_bwd", "attn.eva.far")):
        for scopes, found, _ in table[kernel]:
            assert {"attn.full", "attn.kernel", "attn.eva", scope} <= set(scopes), (kernel, scopes)
    assert {found for _, found, _ in table["eva_far_bwd"]} == {"bwd"}
    # what the TPU compiler emits between the four chips, as the program's registry reads it (PR 53):
    # all of it along `fsdp`; the MLP's and the projections' gathers cut into rings of
    # `collective-permute-start` / `-done` (a windowed einsum), 9 weights' gathers in its own
    # asynchronous form (`async-collective-start` / `-done` fusions)
    moved = profiling.program_collectives_table(
        profiling._module_text(compiled), tuple(mesh.shape.items()), profiling._module_shapes_text(compiled))
    assert set(moved) <= set(table) and {record.axes for record in moved.values()} == {("fsdp",)}
    whole = [record for record in moved.values() if not record.completes]
    assert len(whole) == 73 and all(record.bytes for record in whole)
    assert sum(record.kind == "collective-permute" and record.half == "start" for record in whole) == 58
    assert sum(record.kind == "all-gather" and record.half == "start" for record in whole) == 9
    pairs = {(scope, pass_) for instances in table.values() for scopes, pass_, _ in instances for scope in scopes}
    assert {("attn.eva.pool", "fwd"), ("attn.eva.pool", "bwd"), ("head.multibyte", "fwd"), ("mlp", "fwd"),
            ("attn.proj", "fwd"), ("attn.out", "fwd")} <= pairs
    # 12 bytes a parameter of state over four chips (the gradients are the step's own). This compiler
    # counts 15.4 GiB of temporaries beside them, 17.7 GiB a chip, for the step that ran on the chips
    # at a peak of 14,633,370,624 B = 13.63 GiB (my chip run, PR 51: size from the chip, not from the
    # rehearsal, as PR 30 found on Mistral's step): the band guards the program, not the fit
    memory = compiled.memory_analysis()
    parameters = sum(x.size for x in jax.tree.leaves(state.params))
    assert memory.argument_size_in_bytes / GIB == pytest.approx(12 * parameters / 4 / GIB, abs=0.05)
    assert memory.temp_size_in_bytes / GIB == pytest.approx(15.4, abs=0.8)


def test_ling3flash_cell_step_runs_the_delta_rule_under_its_scopes_and_compiles(as_tpu, monkeypatch, v5e):
    """`train-ling3flash-4k`'s whole step (1 x 4,097 tokens) for one described
    v5e chip of 15.75 GiB: `dK` then `eK eK eK eL eK eK`, all seven unrolled.
    The six delta-rule mixers' five scopes lie inside `kda`; their convolutions
    are ops/ssd's kernels (q with k in one call, v in another, read out of the
    in-projection as it is); the one latent layer runs the causal flash kernels
    at 256 (192-wide keys and 128-wide values padded with zeros); the expert
    layers the grouped matmuls of a held layer; the fused head takes the whole
    sequence as its chunk."""
    from ray_tpu.models import model_family
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import make_train_step
    from ray_tpu.util import profiling

    mesh = build_mesh(MeshSpec(), devices=[as_tpu])
    config, opt, state, shardings, tokens = _cell_step_shapes(
        "ling-3.0-flash-train-1chip", mesh, (1, 4097))
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: int(15.75 * GIB))
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    plan = step.remat_plan_for(tokens.shape, state)
    # everything the stack names: what the rule kept before PR 57, the six mixers' in-projections and, since
    # PR 60, the six expert layers' routing and shared gate and up and the stream after the mixers'
    # out-projection (the mixers' gated norm's output IS `kda_chunk_out` since PR 63, the kernels' y, and their
    # second kept output `kda_chunk_o` what the norm's transpose reads: the same bytes a token and mixer)
    assert plan["remat"] == "selective" and set(plan["remat_saved"]) == {
        "kda_chunk_out", "kda_chunk_states", "attn_out", "attn_lse", "attn_residual", "attn_latent_kv",
        "attn_latent_k_rope", "mlp_up", "mlp_gate", "kda_in_proj", "moe_routing", "moe_shared_gate",
        "moe_shared_up", "kda_chunk_o", "kda_residual"}
    assert [set(names) & {"kda_in_proj", "mlp_up", "attn_out", "moe_routing", "kda_residual"}
            for names in plan["remat_saved_by_run"]] == [
        {"kda_in_proj", "mlp_up", "kda_residual"}, {"kda_in_proj", "attn_out", "moe_routing", "kda_residual"}]
    assert plan["remat_saved_bytes_by_run"][1] == 4096 * (
        5 * (16384 + 20480 + 4096 + 2560) + (8192 + 64) + 2560 + 576 + 6 * (1072 + 2 * 768)) * 2
    assert step.loss_chunk_for(tokens.shape, state) == 4096
    said = model_family(config).plan(config, 1, 4096)
    assert said["layer_kinds"] == "dK eK eK eK eL eK eK"
    assert (said["kda_impl"], said["kda_chunk"], said["kda_subchunk"], said["kda_conv_impl"]) == (
        "pallas", 64, 16, "pallas")
    assert (said["kda_kernels"], said["kda_heads_per_step"], said["kda_state_bytes"]) == (2, 8, 8 * 128 * 128 * 4)
    assert said["kda_prologue"] == "kernel"     # the kernels make the recurrence's arguments of the mixer's (PR 58)
    assert said["kda_epilogue"] == "kernel"     # and norm o a head under the head's gate where it is (PR 63)
    assert (said["kda_heads"], said["kda_head_dim"], said["kda_gate_lower_bound"]) == (32, 128, -5.0)
    assert (said["attn_latent_q_rank"], said["attn_latent_v_dim"], said["attn_kernel_head_dim"]) == (0, 128, 256)
    assert (said["moe_route_groups"], said["moe_route_groups_kept"], said["moe_experts_held"]) == (8, 4, 8)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    # one latent layer: the forward kernel (and its recomputation unless its output is kept), one backward
    assert _kernels_named(compiled, "flash_bwd_dkv_dq") == 1 and "flash_win" not in compiled.as_text()
    # six mixers x two convolutions (q with k, v), forward and recomputed; one backward each
    assert _kernels_named(compiled, "ssm_conv_fwd") == 6 * 2 * 2 and _kernels_named(compiled, "ssm_conv_bwd") == 6 * 2
    # three forward grouped matmuls a layer, first pass and later ones, and none in a recomputed pass
    assert _kernels_named(compiled, "moe_gmm_fwd") > 0
    assert "rematted_computation/moe/moe.experts" not in compiled.as_text()
    _, table = profiling.program_ops_table(profiling._module_text(compiled))
    for scopes, _, _ in table["ssm_conv_fwd"] + table["ssm_conv_bwd"]:
        assert {"kda", "kda.conv"} <= set(scopes), scopes
    # the delta rule is its two kernels (PR 56): one forward a mixer, which keeps the states that entered
    # the chunks and o, none run again (the plan keeps `kda_chunk_out`, `kda_chunk_states` and `kda_chunk_o`),
    # one backward
    assert set(plan["remat_saved"]) >= {"kda_chunk_out", "kda_chunk_states", "kda_chunk_o"}
    assert sorted(found for _, found, _ in table["kda_fwd"]) == ["fwd"] * 6
    assert sorted(found for _, found, _ in table["kda_bwd"]) == ["bwd"] * 6
    for scopes, _, _ in table["kda_fwd"] + table["kda_bwd"]:
        assert {"kda", "kda.chunk"} <= set(scopes), scopes
    # and the scope `kda.chunk` is the kernels and little else (PR 58: they read the convolution's q and k, the
    # gate's input and beta's logits as the mixer has them): nothing of it runs again, XLA writes no float32
    # log-decay there (nor any float32 array of that size), and what it still does, 14 operations a mixer (the
    # three rows a channel and, since PR 63, the norm's scale tiled a head; the least log-decay's reduction and
    # the copies of the kept y and o forward; the two logits' cotangent's two small transposes and the three parameters'
    # gradients from the kernel's sums backward) is a fifth of the 395 operations it took around the kernels
    # before PR 58
    under_chunk = [(name, found) for name, instances in table.items() for scopes, found, _ in instances
                   if "kda.chunk" in scopes]
    assert not [name for name, found in under_chunk if found == "recompute"], under_chunk
    assert 12 < len(under_chunk) <= 90, len(under_chunk)
    text = compiled.as_text()
    assert not re.search(r"= f32\[(?:1,)?4096,(?:32,128|4096)\]\S* [^\n]*/kda\.chunk/", text)
    # nor is the in-projection (PR 57: `kda_in_proj` is kept); the small beta and gate projection is
    assert "rematted_computation/kda/kda.in_proj/bse,ef->bsf/dot_general" in text
    assert not re.search(
        r"bf16\[(?:1,)?4096,16384\][^\n]*rematted_computation/kda/kda.in_proj/bse,ef->bsf/dot_general", text)
    # nor, since PR 60, the out-projection (`kda_residual`), the router's matmul, the group limit's and the
    # choice's top-k's and the rows' sort (`moe_routing`), the shared expert's gate and up
    for again in (r"kda\.out_proj/bsf,fe->bse/dot_general", r"moe\.route/bsm,me->bse/dot_general",
                  r"moe\.shared/bsm,mf->bsf/dot_general", r"moe\.(?:route|select)/[^\n\"]*top_k",
                  r"moe\.dispatch/[^\n\"]*sort"):
        assert re.search(r"jvp\([^\n\"]*" + again, text), again          # the forward pass runs it
        assert not re.search(r"rematted_computation/[^\n\"]*" + again, text), again
    pairs = {(scope, pass_) for instances in table.values() for scopes, pass_, _ in instances for scope in scopes}
    for scope in ("kda", "kda.in_proj", "kda.conv", "kda.chunk", "kda.out_proj",
                  "attn.full", "attn.latent", "moe", "mlp", "head"):
        assert {(scope, "fwd"), (scope, "bwd")} <= pairs, scope
    # the norm a head under the head's gate is `kda_fwd`'s last lines and `kda_bwd`'s first (PR 63): no operation
    # of the program lies under `kda.gate_norm` in any pass, nor anywhere in its text, and no array of the
    # output's view a head, (4,096, 32, 128), is left in any pass of the mixer
    assert not [pair for pair in pairs if pair[0] == "kda.gate_norm"] and "kda.gate_norm" not in text
    assert not re.search(r"= \w+\[(?:1,)?4096,32,128\]\S* [^\n]*/kda/", text)
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("ling3flash step: arguments", memory.argument_size_in_bytes / GIB, "temporaries",
          memory.temp_size_in_bytes / GIB, "plan", plan)
    # the chip ran the parent's plan at 13,217,902,080 B and this one at 12.5 GiB (my chip runs, PR 57)
    assert total < (1 - losses.HBM_FREE_FRACTION) * 15.75 * GIB


def test_lfm2moe_cell_step_runs_the_short_convolution_under_its_scopes_and_compiles(as_tpu, monkeypatch, v5e):
    """`train-lfm2moe-8k`'s whole step (2 x 8,193 tokens) for one described v5e
    chip of 15.75 GiB: `dC` then `eF eC eC eC`, all five unrolled. The four
    short-convolution mixers' three scopes lie inside `sconv` in the forward
    pass and the backward; the one attention layer runs the causal flash
    kernels at D = 64 past one tile a head, rotary positions in front of them;
    the expert layers the grouped matmuls of a held layer; the fused head reads
    the tied matrix and takes the whole sequence as its chunk."""
    from ray_tpu.models import model_family
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import make_train_step
    from ray_tpu.util import profiling

    mesh = build_mesh(MeshSpec(), devices=[as_tpu])
    config, opt, state, shardings, tokens = _cell_step_shapes(
        "lfm2-8b-a1b-train-1chip", mesh, (2, 8193))
    assert "lm_head" not in state.params and "lm_head" not in shardings.params
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: int(15.75 * GIB))
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    plan = step.remat_plan_for(tokens.shape, state)
    print("lfm2moe plan", plan)
    # everything the stack names, since PR 62 the one attention layer's q, k and v as the kernel takes them
    # and, its QK-norm's inputs, q and k as the matmuls wrote them (16,384 rows x 5,632 features: 0.185 GB); since
    # PR 67 the four held expert layers' buffers: 34,816 slots of a gathered row, gate, up, an output and two words
    assert plan["remat"] == "selective" and set(plan["remat_saved"]) == {
        "sconv_in_proj", "sconv_conv_out", "sconv_residual", "mlp_up", "mlp_gate", "moe_routing", "attn_out",
        "attn_lse", "attn_residual", "attn_q", "attn_k", "attn_v", "attn_q_proj", "attn_k_proj",
        "moe_buffer_in", "moe_buffer_gate", "moe_buffer_up", "moe_buffer_out", "moe_buffer_slots", "moe_gmm_tiles"}
    buffer = -(-34816 * (2 * 2048 + 2 * 1792 + 4) // 16384)
    assert plan["remat_saved_bytes"] == (
        1_959_788_544 + 16384 * (2048 + 2 * 512 + 2048 + 512) * 2 + 4 * 16384 * buffer * 2)
    assert step.loss_chunk_for(tokens.shape, state) == 8192
    said = model_family(config).plan(config, 2, 8192)
    assert said["layer_kinds"] == "dC eF eC eC eC"
    assert (said["sconv_channels"], said["sconv_taps"], said["sconv_impl"], said["sconv_rows"]) == (2048, 3, "xla", 0)
    assert said["attn_full_rope"] is True and said["tie_embeddings"] is True
    assert (said["moe_experts_routed"], said["moe_experts_held"], said["moe_top_k"]) == (32, 8, 4)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    assert _kernels_named(compiled, "flash_bwd_dkv_dq") == 1 and "flash_win" not in compiled.as_text()
    # three forward grouped matmuls a layer, first pass and later ones, and none in a recomputed pass
    assert _kernels_named(compiled, "moe_gmm_fwd") > 0
    assert "rematted_computation/moe/moe.experts" not in compiled.as_text()
    # the attention layer's recomputed pass: the input norm and the QK-norm's statistics, no matmul
    text = compiled.as_text()
    assert "/attn.full/attn.proj/bse,ehd->bhsd/dot_general" in text
    assert "rematted_computation/attn.full/attn.proj/bse,ehd->bhsd/dot_general" not in text
    _, table = profiling.program_ops_table(profiling._module_text(compiled))
    pairs = {(scope, found) for instances in table.values() for scopes, found, _ in instances for scope in scopes}
    for scope in ("sconv", "sconv.in_proj", "sconv.conv", "sconv.out_proj", "attn.full", "moe", "mlp", "head"):
        assert {(scope, "fwd"), (scope, "bwd")} <= pairs, scope
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("lfm2moe step: arguments", memory.argument_size_in_bytes / GIB, "temporaries",
          memory.temp_size_in_bytes / GIB)
    parameters = sum(x.size for x in jax.tree.leaves(state.params))
    assert memory.argument_size_in_bytes / GIB == pytest.approx(12 * parameters / GIB, abs=0.05)
    assert total < (1 - losses.HBM_FREE_FRACTION) * 15.75 * GIB
