"""What PR 55 adds to the benchmark for `train-ling3flash-4k`: the adapter's
required work against a hand count, the shipped configuration against the
catalog row's published keys, the adapter's refusals by key, `kda_cost`'s
numbers at the published sizes, the three new metric files and the new reader,
the cell's place in BENCHMARK.json (subsets and names, no totals). The CPU
rehearsal of a tiny cell of the family through the harness, traced and not, is
in test_ling3flash_cell_long.py (the rule at the top of tests/conftest.py)."""

import os
import re

import pytest

from bench_helpers import ROOT, load
from benchmark import kda_cost, model_config, roofline
from benchmark.readers import kda_chunk_fwd_roofline, scope_busy_share

CELL = "train-ling3flash-4k"
CONF = os.path.join(ROOT, "benchmark", "configs", "ling-3.0-flash-train-1chip.json")
# the catalog row `Ling-3.0-flash` beside the model-configs guide: its `config`, every key
CATALOG = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7, "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise", "group_norm_size": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6, "linear_silu": True,
    "max_position_embeddings": 262144, "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True, "moe_shared_expert_intermediate_size": 768,
    "mtp_loss_scaling_factor": 0, "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512, "num_experts_per_tok": 8,
    "num_hidden_layers": 42, "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1, "partial_rotary_factor": 0.5,
    "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5, "scale_router_input": False,
    "score_function": "sigmoid", "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2, "short_conv_kernel_size": 4,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False,
    "use_qk_norm": True, "use_qkv_bias": False, "v_head_dim": 128, "value_norm": False,
    "vocab_size": 157184, "model_type": "bailing_hybrid"}


def test_required_work_of_a_token_by_hand():
    """3.15 GFLOP at S = 4,096 (ISSUE 55: 3.1): six KDA mixers (their
    projections 6 x 105 MFLOP, 60% of the forward), the one latent layer, one
    dense MLP, six expert layers with 8 x 8 / 512 routed experts a token beside
    the shared one, the head over the slice."""
    conf = model_config.load_config(CONF)
    m, s, inner = 2560, 4096, 4096
    kda_proj = 2 * (m * (4 * inner + 64) + inner * m)
    kda = kda_proj + 2 * 4 * 3 * inner + 7 * 32 * 128 * 128
    latent = (2 * (m * 32 * 192 + m * 576 + 512 * 32 * 256 + m * 32 + 4096 * m)
              + 4 * 32 * 160 * (s + 1) / 2)
    experts = 2 * (m * 512 + 3 * m * 768 + (8 * 8 / 512) * 3 * m * 768)
    dense, head = 2 * 3 * m * 6144, 2 * m * 19648
    forward = 6 * kda + latent + dense + 6 * experts + head
    per_token = model_config.train_flops_per_token(conf, s)
    assert per_token == pytest.approx(3 * forward, rel=1e-12)
    assert kda_proj == pytest.approx(105.2e6, rel=2e-3) and forward == pytest.approx(1050e6, rel=2e-3)
    assert per_token == pytest.approx(3.15e9, rel=2e-3)
    assert 6 * kda_proj / forward == pytest.approx(0.60, abs=0.005)
    assert 6 * kda / forward == pytest.approx(0.624, abs=0.005)
    # linear in S but for the one latent layer, whose scores count 192 and 128 a key
    assert model_config.train_flops_per_token(conf, 2 * s) - per_token == pytest.approx(
        3 * 2 * (192 + 128) * 32 * s / 2, rel=1e-9)
    shape = model_config.shape_numbers(conf)
    assert (shape["n_q_heads"], shape["n_kv_heads"], shape["head_dim"]) == (32, 32, 160)
    assert model_config.expert_layer(conf) == {
        "held": 8, "published": 512, "per_token": 8, "hidden": 2560, "width": 768}
    assert model_config.attention_window(conf) is None
    family = model_config.adapter(conf)
    assert family.delta_rule_layer(conf) == {"layers": 6, "heads": 32, "head_dim": 128}
    assert family.layers_run(conf) == [("kda", "dense")] + [("kda", "experts")] * 3 + [
        ("latent", "experts")] + [("kda", "experts")] * 2


def test_configuration_carries_every_published_key():
    conf = load(CONF)
    differ = {k for k, v in CATALOG.items() if conf.get(k, "missing") != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"} == set(conf["reduced"])
    assert conf["published"] == {k: CATALOG[k] for k in conf["reduced"]}
    assert (conf["num_hidden_layers"], conf["num_experts"], conf["vocab_size"]) == (7, 8, 19648)
    assert conf["share"]["chips_sharing_a_layer"] == 64 and 8 * 19648 == 157184 and 64 * 8 == 512
    assert conf["source"].endswith("inclusionAI/Ling-3.0-flash/blob/main/config.json")
    for group in ("deployment", "assumed", "departures", "sizing", "probe", "trainer"):
        assert conf[group], group
    assert conf["assumed"]["route_norm_eps"] == 1e-20 and conf["assumed"]["kda_l2_eps"] == 1e-6
    assert {"layer_rule", "kda_gate", "kda_l2_eps", "kda_no_rotary", "output_gate", "latent",
            "rope_interleave", "router", "kda_initialisation"} <= set(conf["assumed"]["why"])
    assert {"mtp_module", "one_dense_layer", "load_balancing", "router_gradient", "initialisation"} <= set(
        conf["departures"])
    assert conf["program"]["remat"] is True and conf["program"]["frozen_leaves"] == ["router"]
    assert conf["program"]["first_layer"] == 1 and conf["first_k_dense_replace"] == 2
    model_config.check_reduced(conf, "the shipped file")
    mc = model_config.transformer_config(conf)
    assert (mc.n_layers, mc.first_layer, mc.d_model, mc.n_heads, mc.kv_heads, mc.head_dim, mc.value_dim,
            mc.kernel_head_dim) == (7, 1, 2560, 32, 32, 192, 128, 256)
    assert (mc.q_lora_rank, mc.kv_lora_rank, mc.qk_rope_dim, mc.rotary_dims, mc.rope_theta) == (
        0, 512, 64, 64, 6e6)
    assert (mc.kda_heads, mc.kda_head_dim, mc.kda_conv_kernel, mc.kda_chunk, mc.kda_gate_lower_bound) == (
        32, 128, 4, 64, -5.0)
    assert (mc.n_experts, mc.held_experts, mc.top_k, mc.d_ff, mc.d_ff_dense, mc.shared_expert_width,
            mc.expert_act, mc.route_groups, mc.route_groups_kept) == (512, (0, 8), 8, 768, 6144, 768, "swiglu", 8, 4)
    assert (mc.router_score, mc.router_select_bias, mc.norm_topk_prob, mc.route_scale,
            mc.router_aux_coeff, mc.router_input) == ("sigmoid", True, True, 2.5, 0.0, "mlp")
    assert mc.attn_gate and mc.attn_gate_per_head and mc.latent_attention
    assert not (mc.qk_norm_per_head or mc.sandwich_norm or mc.scale_embedding or mc.qk_norm or mc.use_bias
                or mc.tie_embeddings or mc.mtp_modules or mc.layer_pattern)
    assert mc.remat and mc.norm_eps == 1e-6 and mc.frozen_leaves == ("router",)
    from ray_tpu.models.mixed_stack import layer_kinds, stack_runs

    assert " ".join(k.code for k in layer_kinds(mc)) == "dK eK eK eK eL eK eK"
    assert [(len(run.kinds), run.repeats) for run in stack_runs(layer_kinds(mc))] == [(1, 1), (6, 1)]
    # the sizing's parameter count
    kda = 2560 * 4 * 4096 + 4096 * 2560 + 2560 * 64
    latent = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 4096 * 2560 + 2560 * 32
    expert_mlp = 2560 * 512 + 3 * 2560 * 768 + 8 * 3 * 2560 * 768
    total = 6 * kda + latent + 3 * 2560 * 6144 + 6 * expert_mlp + 2 * 19648 * 2560
    assert kda == pytest.approx(52.6e6, rel=2e-3) and latent == pytest.approx(31.97e6, rel=1e-3)
    assert expert_mlp == pytest.approx(54.40e6, rel=1e-3)
    assert total == pytest.approx(821.9e6, rel=1e-3) and 16 * total == pytest.approx(13.15e9, rel=1e-3)
    import jax

    from ray_tpu.models import model_family

    shapes = jax.eval_shape(lambda key: model_family(mc).init_params(mc, key), jax.random.PRNGKey(0))
    small = r"scale|expert_bias|kda_conv_w|kda_dt_bias|kda_a_log"      # norms, biases, taps, a head's scalars
    assert sum(x.size for x in jax.tree.leaves(shapes)) == total + sum(
        x.size for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]
        if re.search(small, jax.tree_util.keystr(path)))


@pytest.mark.parametrize("change, match", [
    ({"q_lora_rank": 768}, "q_lora_rank"), ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"kda_safe_gate": False}, "kda_safe_gate"), ({"use_kda_lora": True}, "use_kda_lora"),
    ({"gated_attention_proj_granularity_type": "elementwise"}, "gated_attention_proj_granularity_type"),
    ({"mtp_loss_scaling_factor": 0.3}, "mtp_loss_scaling_factor"), ({"use_qkv_bias": True}, "use_qkv_bias"),
    ({"num_kv_heads_for_linear_attn": 8}, "num_kv_heads_for_linear_attn"),
    ({"score_function": "softmax"}, "score_function"), ({"v_head_dim": 256}, "v_head_dim"),
    ({"kda_lower_bound": 0}, "kda_lower_bound"), ({"rotary_dim": 32}, "rotary_dim"),
    ({"expert_swiglu_limit_list": [0, 0, 4] + [0] * 39}, "expert_swiglu_limit_list"),
    ({"num_key_value_heads": 8}, "num_key_value_heads"), ({"tie_word_embeddings": True}, "tie_word_embeddings")],
    ids=["a-q-latent", "scaled-rotary", "an-unbounded-gate", "a-low-rank-gate", "a-gate-a-feature",
         "a-weighted-module", "qkv-bias", "fewer-linear-heads", "softmax-scores", "values-wider-than-keys",
         "no-lower-bound", "half-the-rope-part", "a-clamped-swiglu-in-a-layer-run", "grouped-kv-heads",
         "tied-embeddings"])
def test_the_adapter_refuses_what_neither_program_nor_reference_runs(change, match):
    conf = dict(load(CONF), **change)
    with pytest.raises(ValueError, match=match):
        model_config.transformer_config(conf)
    with pytest.raises(ValueError, match=match):
        model_config.adapter(conf).reference_steps(conf, 4096)


def test_a_stage_past_the_published_depth_is_refused():
    conf = load(CONF)
    conf["program"] = dict(conf["program"], first_layer=36)
    with pytest.raises(ValueError, match="layers 36..42 of 42"):
        model_config.transformer_config(conf)


def test_recurrence_cost_at_the_published_sizes_and_the_new_metric_files():
    cost = kda_cost.recurrence_cost(batch=1, seq=4096, heads=32, head_dim=128)
    assert cost["ops"] == 4096 * 7 * 32 * 128 * 128
    # q, k, v, o 4,096 each in bfloat16, the log-decay 4,096 and beta 32 in float32: 49,280 bytes a token
    assert cost["bytes"] == 4096 * (2 * 4 * 4096 + 4 * 4096 + 4 * 32) == 4096 * 49280
    least = roofline.roofline_seconds(cost, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(0.2465e-3, rel=0.01)
    for name, scope in (("kda_mixer_busy_share", "kda"), ("kda_chunk_busy_share", "kda.chunk")):
        meta = load(os.path.join(ROOT, "benchmark", "metrics", name + ".json"))
        assert (meta["reader"], meta["args"], meta["layer"], meta["unit"], meta["better"], meta["source"],
                meta["moves"]) == ("scope_busy_share", {"scopes": [scope]}, "train step", "%", "lower",
                                   "device_trace", "train_tokens_per_s")
        assert scope_busy_share.read({"trace": None}, **meta["args"]) is None
    meta = load(os.path.join(ROOT, "benchmark", "metrics", "kda_chunk_fwd_roofline.json"))
    assert (meta["reader"], meta["args"], meta["layer"], meta["unit"], meta["better"], meta["moves"]) == (
        "kda_chunk_fwd_roofline", {}, "train kernels", "%", "higher", "train_tokens_per_s")
    from ray_tpu.util import profiling

    assert {"kda", "kda.in_proj", "kda.conv", "kda.chunk", "kda.gate_norm", "kda.out_proj"} <= set(
        profiling.STEP_SCOPES)
    conf = model_config.load_config(CONF)
    ctx = {"conf": conf, "device": {"kind": "TPU v5 lite"}, "traffic": {"batch": 1, "seq": 4096}}
    # a run without a trace, a program without the table, a family without such a layer: nothing to read
    assert kda_chunk_fwd_roofline.read(dict(ctx, trace=None)) is None
    assert kda_chunk_fwd_roofline.read(dict(ctx, trace={"busy_s": 1.0, "program_seconds": {}})) is None
    glm = model_config.load_config(os.path.join(ROOT, "benchmark", "configs", "glm-4.7-flash-train-1chip.json"))
    assert kda_chunk_fwd_roofline.read(dict(ctx, conf=glm, trace={"program_seconds": {"jit_step": [1.0]}})) is None


def test_rule_roofline_counts_executions_times_layers_over_the_forward_scopes_time(monkeypatch):
    """3 executions of the traced step x 6 KDA layers x the least time of one
    recurrence, over what the table places under `kda.chunk` in the pass `fwd`;
    the recomputed and the backward rule's time is not in it."""
    from ray_tpu.util import profiling

    fwd = (("steplog.fwd_bwd_compute", "kda", "kda.chunk"), "fwd", 0)
    table = {"fusion.1": (fwd,), "fusion.2": ((fwd[0], "recompute", 0),), "fusion.3": ((fwd[0], "bwd", 0),),
             "fusion.4": ((("steplog.fwd_bwd_compute", "kda", "kda.conv"), "fwd", 0),)}
    monkeypatch.setattr(profiling, "program_ops", lambda: {"jit_step_under_mesh": table})
    trace = {"program_seconds": {"jit_step_under_mesh": [0.4, 0.4, 0.4], "jit_other": [9.0]},
             "op_seconds": {"fusion.1": 0.048, "fusion.2": 0.05, "fusion.3": 0.1, "fusion.4": 0.01},
             "op_counts": {"fusion.1": 18, "fusion.2": 18, "fusion.3": 18, "fusion.4": 18}}
    ctx = {"conf": model_config.load_config(CONF), "device": {"kind": "TPU v5 lite"},
           "traffic": {"batch": 1, "seq": 4096}, "trace": trace}
    least = roofline.roofline_seconds(
        kda_cost.recurrence_cost(batch=1, seq=4096, heads=32, head_dim=128), "TPU v5 lite")["seconds"]
    assert kda_chunk_fwd_roofline.read(ctx) == pytest.approx(100 * 3 * 6 * least / 0.048, rel=1e-9)


def test_no_reader_and_no_harness_file_names_this_familys_keys():
    names = ("layer_group_size", "kda_lower_bound", "kda_safe_gate", "short_conv_kernel_size",
             "num_kv_heads_for_linear_attn", "moe_shared_expert_intermediate_size", "topk_group", "qk_head_dim")
    for folder in ("readers", "kinds"):
        for name in sorted(os.listdir(os.path.join(ROOT, "benchmark", folder))):
            if name.endswith(".py"):
                with open(os.path.join(ROOT, "benchmark", folder, name), encoding="utf-8") as f:
                    text = f.read()
                assert not [key for key in names if key in text], name
    # the reference imports nothing of the program (its helpers are the sibling reference's)
    with open(os.path.join(ROOT, "benchmark", "reference", "bailing_hybrid_ref.py"), encoding="utf-8") as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(ray_tpu|benchmark)", text, re.M)
    assert re.findall(r"^from (\S+) import", text, re.M) == ["__future__", "typing", ".afmoe_ref"]
    assert "lax.scan(position" in text and "chunk" not in text.split('"""', 2)[2].replace("chunk_ce", "")


def test_cell_joins_the_shared_metrics_by_name_and_adds_three(benchmark_json):
    reports = {m["name"] for group in ("end_to_end", "per_layer") for m in benchmark_json[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"train_tokens_per_s", "setup_s", "mfu", "data_wait_share", "flash_attn_busy_share",
            "flash_fwd_roofline", "peak_hbm_share_train", "compiles_in_window_train",
            "step_unscoped_busy_share", "recompute_busy_share", "head_busy_share", "optimizer_busy_share",
            "attn_proj_busy_share", "attn_layout_busy_share", "mlp_busy_share", "moe_gmm_busy_share",
            "moe_expert_load_max_over_mean", "moe_held_rows_off_even",
            "moe_layer_busy_share", "moe_dispatch_busy_share", "moe_combine_busy_share",
            "attn_latent_busy_share", "kda_mixer_busy_share", "kda_chunk_busy_share",
            "kda_chunk_fwd_roofline"} <= reports
    # no window, no collective across chips, a router that holds all, ReGLU, a module, a state-space mixer;
    # ~64 rows a held expert are under one row tile a group, where the held kernels' roofline has nothing to read
    assert not {"moe_held_gmm_roofline", "flash_win_busy_share", "flash_win_fwd_roofline",
                "collective_exposed_share", "moe_gmm_roofline",
                "moe_act_live_share", "mtp_busy_share", "ssm_mixer_busy_share", "ssm_scan_busy_share",
                "ssm_conv_busy_share", "ssm_scan_roofline", "ssm_gate_norm_busy_share"} & reports
    cell = next(w for w in benchmark_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-3.0-flash-train-1chip", "lm-steps-host-batches-1x4k", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in benchmark_json["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == load(CONF)["source"] and config["file"].endswith(cell["config"] + ".json")
    assert len(config["why"]) <= 200
    for name in ("kda_mixer_busy_share", "kda_chunk_busy_share", "kda_chunk_fwd_roofline"):
        (metric,) = [m for m in benchmark_json["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_tokens_per_s"
    traffic = load(os.path.join(ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert {k: traffic[k] for k in ("kind", "batch", "seq", "zipf_a", "prefetch", "segment_steps")} == {
        "kind": "lm_steps", "batch": 1, "seq": 4096, "zipf_a": 1.1, "prefetch": 4, "segment_steps": 10}
