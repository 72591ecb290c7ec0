"""What PR 48 adds to the benchmark for `train-nemotron3nano-8k`: the
adapter's required work against a hand count, the shipped configuration
against the catalog row's published keys, the adapter's refusals by key,
`ssd_cost`'s numbers at the published sizes, the four new metric files, the
cell's place in BENCHMARK.json (subsets and names, no totals), and a CPU
rehearsal of a tiny cell of the family through the harness that ends
`correct` (and, with every weight through float8_e4m3, does not)."""

import json
import os
import re

import pytest

from bench_helpers import RESULT_KEYS, ROOT, float8_weights, load
from benchmark import model_config, roofline, ssd_cost
from benchmark.readers import scope_busy_share, ssm_scan_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_nemotron3nano")
CELL = "train-nemotron3nano-8k"
CONF = os.path.join(ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b-train-1chip.json")
# the catalog row `NVIDIA-Nemotron-3-Nano-30B-A3B-BF16` beside the model-configs guide: its `config`, every key
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}


def test_required_work_of_a_token_by_hand():
    """2.14 GFLOP at S = 8,192 (ISSUE 48): four Mamba mixers (45% of the
    forward), four expert layers with 6 x 8 / 128 routed experts a token
    beside the shared one (27%), the one attention layer (16%), the head over
    the slice (12%)."""
    conf = model_config.load_config(CONF)
    m, s = 2688, 8192
    mamba = 2 * (m * 10304 + 4096 * m) + 2 * 4 * 6144 + 5 * 64 * 64 * 128 + 2 * 4096
    attention = 2 * (m * (32 + 2 * 2) * 128 + 32 * 128 * m) + 4 * 128 * 32 * (s + 1) / 2
    experts = 2 * (m * 128 + 2 * m * 3712 + (6 * 8 / 128) * 2 * m * 1856)
    head = 2 * m * 16384
    forward = 4 * mamba + attention + 4 * experts + head
    per_token = model_config.train_flops_per_token(conf, s)
    assert per_token == pytest.approx(3 * forward, rel=1e-12)
    assert forward == pytest.approx(714e6, rel=2e-3) and per_token == pytest.approx(2.14e9, rel=2e-3)
    for part, want in ((4 * mamba, 320e6), (4 * experts, 192e6), (attention, 114e6), (head, 88e6)):
        assert part == pytest.approx(want, rel=0.005)
    assert 4 * mamba / forward == pytest.approx(0.45, abs=0.005)
    # linear in S but for the one attention layer
    assert model_config.train_flops_per_token(conf, 2 * s) - per_token == pytest.approx(
        3 * 4 * 128 * 32 * s / 2, rel=1e-9)
    shape = model_config.shape_numbers(conf)
    assert (shape["n_q_heads"], shape["n_kv_heads"], shape["head_dim"]) == (32, 2, 128)
    assert model_config.expert_layer(conf) == {
        "held": 8, "published": 128, "per_token": 6, "hidden": 2688, "width": 1856}
    assert model_config.attention_window(conf) is None
    assert model_config.adapter(conf).state_space_layer(conf) == {
        "layers": 4, "heads": 64, "head_dim": 64, "state": 128, "groups": 8, "conv_kernel": 4, "chunk": 128}


def test_configuration_carries_every_published_key():
    conf = load(CONF)
    differ = {k for k, v in CATALOG.items() if conf.get(k, "missing") != v}
    assert differ == {"num_hidden_layers", "n_routed_experts", "vocab_size"} == set(conf["reduced"])
    assert conf["published"] == {k: CATALOG[k] for k in conf["reduced"]}
    assert (conf["num_hidden_layers"], conf["n_routed_experts"], conf["vocab_size"]) == (9, 8, 16384)
    assert conf["share"]["chips_sharing_a_layer"] == 16 and 8 * 16384 == 131072 and 16 * 8 == 128
    assert conf["source"].endswith("nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    for group in ("deployment", "assumed", "departures", "sizing", "probe", "trainer"):
        assert conf[group], group
    assert conf["assumed"]["route_norm_eps"] == 1e-20
    assert {"no_rotary", "d_inner", "no_dt_clamp", "gate_then_norm", "route_norm_eps",
            "mamba_initialisation"} <= set(conf["assumed"]["why"])
    assert {"load_balancing", "router_gradient", "torch_dtype", "initialisation", "learning_rate"} <= set(
        conf["departures"])
    assert conf["program"]["remat"] is True and conf["program"]["frozen_leaves"] == ["router"]
    model_config.check_reduced(conf, "the shipped file")
    mc = model_config.transformer_config(conf)
    assert (mc.n_layers, mc.layer_pattern, mc.d_model, mc.n_heads, mc.kv_heads, mc.head_dim) == (
        9, "MEMEM*EME", 2688, 32, 2, 128)
    assert (mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state, mc.ssm_groups, mc.ssm_conv_kernel, mc.ssm_chunk,
            mc.ssm_conv_width) == (64, 64, 128, 8, 4, 128, 6144)
    assert (mc.ssm_dt_min, mc.ssm_dt_max, mc.ssm_dt_floor) == (0.001, 0.1, 0.0001)
    assert (mc.n_experts, mc.held_experts, mc.top_k, mc.d_ff, mc.shared_expert_width, mc.expert_act) == (
        128, (0, 8), 6, 1856, 3712, "relu2")
    assert (mc.router_score, mc.router_select_bias, mc.norm_topk_prob, mc.route_scale,
            mc.router_aux_coeff, mc.router_input) == ("sigmoid", True, True, 2.5, 0.0, "mlp")
    assert not (mc.qk_norm_per_head or mc.attn_gate or mc.sandwich_norm or mc.scale_embedding
                or mc.qk_norm or mc.use_bias or mc.tie_embeddings or mc.latent_attention or mc.mtp_modules)
    assert mc.remat and mc.norm_eps == 1e-5 and mc.frozen_leaves == ("router",)
    from ray_tpu.models.mixed_stack import layer_kinds, stack_runs

    assert " ".join(k.code for k in layer_kinds(mc)) == "-M e- -M e- -M -F e- -M e-"
    assert [(len(run.kinds), run.repeats) for run in stack_runs(layer_kinds(mc))] == [(2, 2), (5, 1)]
    # the sizing's parameter count
    mamba = 2688 * 10304 + 4096 * 2688
    attention = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
    expert_layer = 2688 * 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856
    total = 4 * mamba + attention + 4 * expert_layer + 2 * 16384 * 2688
    assert mamba == pytest.approx(38.7e6, rel=2e-3) and expert_layer == pytest.approx(100.1e6, rel=1e-3)
    assert total == pytest.approx(667.0e6, rel=1e-3) and 16 * total == pytest.approx(10.67e9, rel=1e-3)
    import jax

    from ray_tpu.models import model_family

    shapes = jax.eval_shape(lambda key: model_family(mc).init_params(mc, key), jax.random.PRNGKey(0))
    small = r"scale|expert_bias|ssm_conv|ssm_dt_bias|ssm_a_log|ssm_d'"     # norms, biases, a head's scalars
    assert sum(x.size for x in jax.tree.leaves(shapes)) == total + sum(
        x.size for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]
        if re.search(small, jax.tree_util.keystr(path)))


@pytest.mark.parametrize("change, match", [
    ({"hybrid_override_pattern": "ME-EM*EME"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "MEMEM*E"}, "hybrid_override_pattern has 7"),
    ({"n_group": 8, "topk_group": 4}, "n_group"), ({"topk_group": 2}, "topk_group"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"), ({"attention_bias": True}, "attention_bias"),
    ({"mlp_bias": True}, "mlp_bias"), ({"use_bias": True}, "use_bias"),
    ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"), ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"sliding_window": 4096}, "sliding_window"), ({"tie_word_embeddings": True}, "tie_word_embeddings")],
    ids=["a-dense-mlp-layer", "pattern-shorter-than-the-depth", "group-limited", "two-groups-chosen",
         "projection-bias", "attention-bias", "mlp-bias", "use-bias", "another-mamba-act", "a-gated-expert",
         "a-window", "tied-embeddings"])
def test_the_adapter_refuses_what_neither_program_nor_reference_runs(change, match):
    conf = dict(load(CONF), **change)
    with pytest.raises(ValueError, match=match):
        model_config.transformer_config(conf)
    with pytest.raises(ValueError, match=match):
        model_config.adapter(conf).reference_steps(conf, 16384)


def test_scan_cost_at_the_published_sizes_and_the_new_metric_files():
    cost = ssd_cost.scan_cost(batch=2, seq=8192, heads=64, head_dim=64, state=128, groups=8)
    assert cost["ops"] == 16384 * (5 * 64 * 64 * 128 + 2 * 4096)
    # x and y 4,096 each and B, C 1,024 each in bfloat16, dt 64 in float32: 20,736 bytes a token
    assert cost["bytes"] == 16384 * (2 * (2 * 4096 + 2 * 1024) + 4 * 64) == 16384 * 20736
    least = roofline.roofline_seconds(cost, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(0.415e-3, rel=0.01)
    for name, scope in (("ssm_mixer_busy_share", "ssm"), ("ssm_scan_busy_share", "ssm.scan"),
                        ("ssm_conv_busy_share", "ssm.conv")):
        meta = load(os.path.join(ROOT, "benchmark", "metrics", name + ".json"))
        assert (meta["reader"], meta["args"], meta["layer"], meta["unit"], meta["better"], meta["source"],
                meta["moves"]) == ("scope_busy_share", {"scopes": [scope]}, "train step", "%", "lower",
                                   "device_trace", "train_tokens_per_s")
        assert scope_busy_share.read({"trace": None}, **meta["args"]) is None
    meta = load(os.path.join(ROOT, "benchmark", "metrics", "ssm_scan_roofline.json"))
    assert (meta["reader"], meta["args"], meta["layer"], meta["unit"], meta["better"], meta["moves"]) == (
        "ssm_scan_roofline", {}, "train kernels", "%", "higher", "train_tokens_per_s")
    from ray_tpu.util import profiling

    assert {"ssm", "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj"} <= set(
        profiling.STEP_SCOPES)
    conf = model_config.load_config(CONF)
    ctx = {"conf": conf, "device": {"kind": "TPU v5 lite"}, "traffic": {"batch": 2, "seq": 8192}}
    # a run without a trace, a program without the table, a family without such a layer: nothing to read
    assert ssm_scan_roofline.read(dict(ctx, trace=None)) is None
    assert ssm_scan_roofline.read(dict(ctx, trace={"busy_s": 1.0, "program_seconds": {}})) is None
    glm = model_config.load_config(os.path.join(ROOT, "benchmark", "configs", "glm-4.7-flash-train-1chip.json"))
    assert ssm_scan_roofline.read(dict(ctx, conf=glm, trace={"program_seconds": {"jit_step": [1.0]}})) is None


def test_scan_roofline_counts_executions_times_layers_over_the_forward_scopes_time(monkeypatch):
    """3 executions of the traced step x 4 `M` layers x the least time of one
    scan, over what the table places under `ssm.scan` in the pass `fwd`; the
    recomputed and the backward scan's time is not in it."""
    from ray_tpu.util import profiling

    fwd = (("steplog.fwd_bwd_compute", "ssm", "ssm.scan"), "fwd", 0)
    table = {"fusion.1": (fwd,), "fusion.2": ((fwd[0], "recompute", 0),), "fusion.3": ((fwd[0], "bwd", 0),),
             "fusion.4": ((("steplog.fwd_bwd_compute", "ssm", "ssm.conv"), "fwd", 0),)}
    monkeypatch.setattr(profiling, "program_ops", lambda: {"jit_step_under_mesh": table})
    trace = {"program_seconds": {"jit_step_under_mesh": [0.4, 0.4, 0.4], "jit_other": [9.0]},
             "op_seconds": {"fusion.1": 0.048, "fusion.2": 0.05, "fusion.3": 0.1, "fusion.4": 0.01},
             "op_counts": {"fusion.1": 12, "fusion.2": 12, "fusion.3": 12, "fusion.4": 12}}
    ctx = {"conf": model_config.load_config(CONF), "device": {"kind": "TPU v5 lite"},
           "traffic": {"batch": 2, "seq": 8192}, "trace": trace}
    least = roofline.roofline_seconds(
        ssd_cost.scan_cost(batch=2, seq=8192, heads=64, head_dim=64, state=128, groups=8), "TPU v5 lite")["seconds"]
    assert ssm_scan_roofline.read(ctx) == pytest.approx(100 * 3 * 4 * least / 0.048, rel=1e-9)


def test_no_reader_and_no_harness_file_names_this_familys_keys():
    names = ("hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
             "moe_shared_expert_intermediate_size", "n_routed_experts", "conv_kernel")
    for folder in ("readers", "kinds"):
        for name in sorted(os.listdir(os.path.join(ROOT, "benchmark", folder))):
            if name.endswith(".py"):
                with open(os.path.join(ROOT, "benchmark", folder, name), encoding="utf-8") as f:
                    text = f.read()
                assert not [key for key in names if key in text], name
    # the reference imports nothing of the program (its helpers are the sibling reference's)
    with open(os.path.join(ROOT, "benchmark", "reference", "nemotron_h_ref.py"), encoding="utf-8") as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(ray_tpu|benchmark)", text, re.M)
    assert re.findall(r"^from (\S+) import", text, re.M) == ["__future__", "typing", ".afmoe_ref"]
    assert "lax.scan(position" in text        # the recurrence, a position at a time


def test_cell_joins_the_shared_metrics_by_name_and_adds_four(benchmark_json):
    reports = {m["name"] for group in ("end_to_end", "per_layer") for m in benchmark_json[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"train_tokens_per_s", "setup_s", "mfu", "data_wait_share", "flash_attn_busy_share",
            "flash_fwd_roofline", "peak_hbm_share_train", "compiles_in_window_train",
            "step_unscoped_busy_share", "recompute_busy_share", "head_busy_share", "optimizer_busy_share",
            "attn_proj_busy_share", "attn_layout_busy_share", "moe_gmm_busy_share",
            "moe_expert_load_max_over_mean", "moe_held_gmm_roofline", "moe_held_rows_off_even",
            "moe_layer_busy_share", "moe_dispatch_busy_share", "moe_combine_busy_share",
            "ssm_mixer_busy_share", "ssm_scan_busy_share", "ssm_conv_busy_share", "ssm_scan_roofline"} <= reports
    # no dense MLP, no window, no collective across chips, a router that holds all, ReGLU, latents, a module
    assert not {"mlp_busy_share", "flash_win_busy_share", "flash_win_fwd_roofline", "collective_exposed_share",
                "moe_gmm_roofline", "moe_act_live_share", "attn_latent_busy_share", "mtp_busy_share"} & reports
    cell = next(w for w in benchmark_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b-a3b-train-1chip", "lm-steps-host-batches-8k", 1)
    config = next(c for c in benchmark_json["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["source"] == load(CONF)["source"] and config["file"].endswith(cell["config"] + ".json")
    for name in ("ssm_mixer_busy_share", "ssm_scan_busy_share", "ssm_conv_busy_share", "ssm_scan_roofline"):
        (metric,) = [m for m in benchmark_json["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_tokens_per_s"
    assert sum(w["chips"] == 4 for w in benchmark_json["workloads"]) == 1


def _tiny_bench(benchmark_json):
    return dict(benchmark_json, workloads=[
        {"name": CELL, "config": "tiny-nemotron3nano-train", "traffic": "tiny-lm-steps", "chips": 1}])


def test_tiny_nemotron3nano_cell_rehearsal_ends_correct(benchmark_json):
    """The real cell's entries with a tiny tree behind them: LMTrainer on the
    nine layers `MEMEM*EME` (8 of 32 experts held, top-6, 3 chunks of 16 a
    sequence), its first two steps against nemotron_h_ref's recurrence, clip
    and AdamW; the traced line."""
    from benchmark import run

    result = json.loads(json.dumps(run.run_cell(
        _tiny_bench(benchmark_json), CELL, 2**31 + 48, 2.0, True, tree=TINY, require_tpu=False)))
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    checks = result["info"]["checks"]
    assert checks["loss_step1_gap"]["value"] < 1e-5          # float32 against float32
    assert checks["first_gradient_worst_leaf_difference"]["value"] < 1e-4
    assert checks["change_worst_leaf_gap"]["value"] < 1e-4
    assert checks["loss_last"] < checks["loss_first"]
    # counters and span readers answer on a CPU; the trace readers and `mfu` find no chip
    assert {"moe_expert_load_max_over_mean", "moe_held_rows_off_even", "data_wait_share",
            "compiles_in_window_train", "setup_train_init_s", "setup_compile_s",
            "setup_programs_built", "host_turnaround_ms", "step_dispatch_p50_ms"} <= set(result["metrics"])
    assert not {"ssm_mixer_busy_share", "ssm_scan_busy_share", "ssm_conv_busy_share", "ssm_scan_roofline",
                "mfu"} & set(result["metrics"])
    assert result["metrics"]["compiles_in_window_train"]["value"] == 0
    assert result["device"]["platform"] == "cpu"


def test_tiny_nemotron3nano_cell_with_every_weight_through_float8_is_not_correct(benchmark_json):
    from benchmark import run

    with float8_weights():
        result = run.run_cell(_tiny_bench(benchmark_json), CELL, 2**31 + 48, 1.0, False,
                              tree=TINY, require_tpu=False)
    assert result["correct"] is False
    checks = result["info"]["checks"]
    assert checks["first_loss_repeat_gap"]["value"] == 0.0      # the step that was timed is the one compared
    assert (checks["first_gradient_worst_leaf_difference"]["value"]
            > checks["first_gradient_worst_leaf_difference"]["limit"])
