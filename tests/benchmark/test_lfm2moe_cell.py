"""What PR 61 adds to the benchmark for `train-lfm2moe-8k`: the adapter's
required work against a hand count, the shipped configuration against the
catalog row's published keys, the adapter's refusals by key, `sconv_cost`'s
numbers at the published sizes, the three new metric files and the new reader,
the cell's place in BENCHMARK.json (subsets and names, never a count). The CPU
rehearsal of a tiny cell of the family through the harness, traced and not, is
in test_lfm2moe_cell_long.py (the rule at the top of tests/conftest.py)."""

import os
import re

import pytest

from bench_helpers import ROOT, load
from benchmark import model_config, roofline, sconv_cost
from benchmark.readers import scope_busy_share, sconv_conv_fwd_roofline

CELL = "train-lfm2moe-8k"
CONF = os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b-train-1chip.json")
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
# the catalog row `LFM2-8B-A1B` beside the model-configs guide: its `config`, every key
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": LAYER_TYPES, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def test_required_work_of_a_token_by_hand():
    """1.30 GFLOP at S = 8,192 (ISSUE 61): four short-conv mixers 4 x 33.57
    MFLOP (31% of the forward), the attention layer 20.97 of projections +
    33.56 of scores, the dense MLP 88.1, four expert layers 4 x 22.15 (one
    held expert a token), the head over the slice 67.1."""
    conf = model_config.load_config(CONF)
    m, s = 2048, 8192
    conv = 2 * (m * 6144 + m * m) + 7 * m
    projections, scores = 2 * (m * (32 + 2 * 8) * 64 + 2048 * m), 4 * 64 * 32 * (s + 1) / 2
    dense, experts, head = 2 * 3 * m * 7168, 2 * (m * 32 + (4 * 8 / 32) * 3 * m * 1792), 2 * m * 16384
    forward = 4 * conv + projections + scores + dense + 4 * experts + head
    per_token = model_config.train_flops_per_token(conf, s)
    assert per_token == pytest.approx(3 * forward, rel=1e-12)
    assert conv == pytest.approx(33.57e6, rel=1e-3) and projections == pytest.approx(20.97e6, rel=1e-3)
    assert scores == pytest.approx(33.56e6, rel=1e-3) and experts == pytest.approx(22.15e6, rel=1e-3)
    assert forward == pytest.approx(432.6e6, rel=1e-3) and per_token == pytest.approx(1.298e9, rel=1e-3)
    assert 4 * conv / forward == pytest.approx(0.31, abs=0.005)
    # linear in S but for the one attention layer
    assert model_config.train_flops_per_token(conf, 2 * s) - per_token == pytest.approx(3 * 4 * 64 * 32 * s / 2, rel=1e-9)
    shape = model_config.shape_numbers(conf)
    assert (shape["n_q_heads"], shape["n_kv_heads"], shape["head_dim"]) == (32, 8, 64)
    assert model_config.expert_layer(conf) == {
        "held": 8, "published": 32, "per_token": 4, "hidden": 2048, "width": 1792}
    assert model_config.attention_window(conf) is None
    family = model_config.adapter(conf)
    assert family.short_conv_layer(conf) == {"layers": 4, "channels": 2048, "taps": 3}
    assert family.layers_run(conf) == ["conv", "full_attention", "conv", "conv", "conv"]


def test_configuration_carries_every_published_key():
    conf = load(CONF)
    differ = {k for k, v in CATALOG.items() if conf.get(k, "missing") != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"} == set(conf["reduced"])
    assert conf["published"] == {k: CATALOG[k] for k in conf["reduced"]}
    assert (conf["num_hidden_layers"], conf["num_experts"], conf["vocab_size"]) == (5, 8, 16384)
    assert conf["share"]["chips_sharing_a_layer"] == 4 and 4 * 16384 == 65536 and 4 * 8 == 32
    assert [i for i, kind in enumerate(conf["layer_types"]) if kind == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert conf["source"].endswith("LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    for group in ("deployment", "assumed", "departures", "sizing", "probe", "trainer"):
        assert conf[group], group
    assert (conf["assumed"]["tie_embedding"], conf["assumed"]["head_dim"], conf["assumed"]["route_norm_eps"]) == (
        True, 64, 1e-6)
    assert {"tie_embedding", "head_dim", "route_norm_eps", "qk_norm", "rotary", "conv_thirds",
            "conv_no_activation", "final_norm", "router"} <= set(conf["assumed"]["why"])
    assert {"depth", "one_dense_layer", "load_balancing", "router_gradient", "torch_dtype",
            "initialisation"} <= set(conf["departures"])
    assert conf["program"]["remat"] is True and conf["program"]["frozen_leaves"] == ["router"]
    assert conf["program"]["first_layer"] == 1 and conf["num_dense_layers"] == 2
    assert {"on_the_chip", "parameters", "required_work"} <= set(conf["sizing"])
    model_config.check_reduced(conf, "the shipped file")
    mc = model_config.transformer_config(conf)
    assert (mc.n_layers, mc.first_layer, mc.d_model, mc.n_heads, mc.kv_heads, mc.head_dim, mc.rotary_dims,
            mc.rope_theta) == (5, 1, 2048, 32, 8, 64, 64, 1e6)
    assert (mc.mixer_kinds, mc.sconv_taps, mc.attn_full_rope, mc.tie_embeddings) == (
        ("sconv", "full", "sconv", "sconv", "sconv"), 3, True, True)
    assert (mc.n_experts, mc.held_experts, mc.top_k, mc.d_ff, mc.d_ff_dense, mc.shared_expert_width,
            mc.expert_act, mc.route_groups) == (32, (0, 8), 4, 1792, 7168, 0, "swiglu", 1)
    assert (mc.router_score, mc.router_select_bias, mc.norm_topk_prob, mc.route_scale, mc.route_norm_eps,
            mc.router_aux_coeff, mc.router_input) == ("sigmoid", True, True, 1.0, 1e-6, 0.0, "mlp")
    assert mc.qk_norm_per_head and not (mc.attn_gate or mc.sandwich_norm or mc.scale_embedding or mc.qk_norm
                                        or mc.use_bias or mc.latent_attention or mc.mtp_modules
                                        or mc.layer_pattern or mc.kda_heads)
    assert mc.remat and mc.norm_eps == 1e-5 and mc.frozen_leaves == ("router",)
    from ray_tpu.models.mixed_stack import layer_kinds, stack_runs

    assert " ".join(k.code for k in layer_kinds(mc)) == "dC eF eC eC eC"
    assert [(len(run.kinds), run.repeats) for run in stack_runs(layer_kinds(mc))] == [(1, 1), (4, 1)]
    # the sizing's parameter count
    conv = 2048 * 6144 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    expert_mlp = 2048 * 32 + 8 * 3 * 2048 * 1792
    total = 4 * conv + attention + 3 * 2048 * 7168 + 4 * expert_mlp + 16384 * 2048
    assert conv == pytest.approx(16.78e6, rel=1e-3) and attention == pytest.approx(10.49e6, rel=1e-3)
    assert expert_mlp == pytest.approx(88.15e6, rel=1e-3)
    assert total == pytest.approx(507.8e6, rel=1e-3) and 16 * total == pytest.approx(8.13e9, rel=1e-3)
    import jax

    from ray_tpu.models import model_family

    shapes = jax.eval_shape(lambda key: model_family(mc).init_params(mc, key), jax.random.PRNGKey(0))
    small = r"scale|expert_bias|sconv_w"      # norms, biases, taps
    assert sum(x.size for x in jax.tree.leaves(shapes)) == total + sum(
        x.size for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]
        if re.search(small, jax.tree_util.keystr(path)))
    assert "lm_head" not in shapes


@pytest.mark.parametrize("change, match", [
    ({"conv_bias": True}, "conv_bias"), ({"conv_L_cache": 1}, "conv_L_cache"),
    ({"layer_types": LAYER_TYPES[:5]}, "layer_types has 5 entries"),
    ({"layer_types": ["sliding_attention"] + LAYER_TYPES[1:]}, "layer_types names"),
    ({"num_hidden_layers": 24}, r"layers 1..24 of the 24"),
    ({"norm_topk_prob": False}, "norm_topk_prob"), ({"use_expert_bias": False}, "use_expert_bias"),
    ({"assumed": {"tie_embedding": False}}, "tie_embedding"),
    ({"assumed": {"route_norm_eps": 1e-20}}, "route_norm_eps"),
    ({"num_attention_heads": 24}, "hidden_size is no whole number")],
    ids=["a-bias-on-the-taps", "one-tap", "a-cut-list", "a-kind-the-list-may-not-name",
         "a-depth-past-the-list", "gates-as-they-are", "no-selection-bias", "an-untied-head",
         "another-epsilon", "heads-that-do-not-divide"])
def test_the_adapter_refuses_what_neither_program_nor_reference_runs(change, match):
    conf = dict(load(CONF), **change)
    with pytest.raises(ValueError, match=match):
        model_config.transformer_config(conf)
    with pytest.raises(ValueError, match=match):
        model_config.adapter(conf).reference_steps(conf, 16384)


def test_a_stage_past_the_published_depth_is_refused_and_another_stage_reads_its_own_entries():
    conf = load(CONF)
    conf["program"] = dict(conf["program"], first_layer=20)
    with pytest.raises(ValueError, match="layers 20..24 of the 24"):
        model_config.transformer_config(conf)
    conf["program"] = dict(conf["program"], first_layer=17)      # 17-21: conv, attention, conv, conv, attention
    mc = model_config.transformer_config(conf)
    assert mc.mixer_kinds == ("sconv", "full", "sconv", "sconv", "full")
    assert model_config.adapter(conf).short_conv_layer(conf)["layers"] == 3


def test_gated_conv_cost_at_the_published_sizes_and_the_new_metric_files():
    cost = sconv_cost.gated_conv_cost(batch=2, seq=8192, channels=2048, taps=3)
    assert cost["ops"] == 16384 * 7 * 2048
    # B, C, X read and y written in bfloat16: 16,384 bytes a token
    assert cost["bytes"] == 16384 * 2 * 4 * 2048 == 16384 * 16384
    least = roofline.roofline_seconds(cost, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(0.328e-3, rel=0.01)
    for name, scope in (("sconv_mixer_busy_share", "sconv"), ("sconv_conv_busy_share", "sconv.conv")):
        meta = load(os.path.join(ROOT, "benchmark", "metrics", name + ".json"))
        assert (meta["reader"], meta["args"], meta["layer"], meta["unit"], meta["better"], meta["source"],
                meta["moves"]) == ("scope_busy_share", {"scopes": [scope]}, "train step", "%", "lower",
                                   "device_trace", "train_tokens_per_s")
        assert scope_busy_share.read({"trace": None}, **meta["args"]) is None
    meta = load(os.path.join(ROOT, "benchmark", "metrics", "sconv_conv_fwd_roofline.json"))
    assert (meta["reader"], meta["args"], meta["layer"], meta["unit"], meta["better"], meta["moves"]) == (
        "sconv_conv_fwd_roofline", {}, "train kernels", "%", "higher", "train_tokens_per_s")
    from ray_tpu.util import profiling

    assert {"sconv", "sconv.in_proj", "sconv.conv", "sconv.out_proj"} <= set(profiling.STEP_SCOPES)
    conf = model_config.load_config(CONF)
    ctx = {"conf": conf, "device": {"kind": "TPU v5 lite"}, "traffic": {"batch": 2, "seq": 8192}}
    # a run without a trace, a program without the table, a family without such a layer: nothing to read
    assert sconv_conv_fwd_roofline.read(dict(ctx, trace=None)) is None
    assert sconv_conv_fwd_roofline.read(dict(ctx, trace={"busy_s": 1.0, "program_seconds": {}})) is None
    glm = model_config.load_config(os.path.join(ROOT, "benchmark", "configs", "glm-4.7-flash-train-1chip.json"))
    assert sconv_conv_fwd_roofline.read(dict(ctx, conf=glm, trace={"program_seconds": {"jit_step": [1.0]}})) is None


def test_conv_roofline_counts_executions_times_layers_over_the_forward_scopes_time(monkeypatch):
    """3 executions of the traced step x 4 conv layers x the least time of one
    gated convolution, over what the table places under `sconv.conv` in the
    pass `fwd`; the recomputed and the backward op's time is not in it, nor the
    projections'. A table without the scope (the parent's) leaves nothing to read."""
    from ray_tpu.util import profiling

    fwd = (("steplog.fwd_bwd_compute", "sconv", "sconv.conv"), "fwd", 0)
    table = {"fusion.1": (fwd,), "fusion.2": ((fwd[0], "recompute", 0),), "fusion.3": ((fwd[0], "bwd", 0),),
             "fusion.4": ((("steplog.fwd_bwd_compute", "sconv", "sconv.in_proj"), "fwd", 0),)}
    monkeypatch.setattr(profiling, "program_ops", lambda: {"jit_step_under_mesh": table})
    trace = {"program_seconds": {"jit_step_under_mesh": [0.25, 0.25, 0.25], "jit_other": [9.0]},
             "op_seconds": {"fusion.1": 0.012, "fusion.2": 0.05, "fusion.3": 0.1, "fusion.4": 0.01},
             "op_counts": {"fusion.1": 12, "fusion.2": 12, "fusion.3": 12, "fusion.4": 12}}
    ctx = {"conf": model_config.load_config(CONF), "device": {"kind": "TPU v5 lite"},
           "traffic": {"batch": 2, "seq": 8192}, "trace": trace}
    least = roofline.roofline_seconds(
        sconv_cost.gated_conv_cost(batch=2, seq=8192, channels=2048, taps=3), "TPU v5 lite")["seconds"]
    assert sconv_conv_fwd_roofline.read(ctx) == pytest.approx(100 * 3 * 4 * least / 0.012, rel=1e-9)
    without = {name: instances for name, instances in table.items() if name == "fusion.4"}
    monkeypatch.setattr(profiling, "program_ops", lambda: {"jit_step_under_mesh": without})
    assert sconv_conv_fwd_roofline.read(ctx) is None


def test_no_reader_and_no_harness_file_names_this_familys_keys():
    names = ("conv_L_cache", "conv_bias", "num_dense_layers", "use_expert_bias", "layer_types")
    for folder in ("readers", "kinds"):
        for name in sorted(os.listdir(os.path.join(ROOT, "benchmark", folder))):
            if name.endswith(".py"):
                with open(os.path.join(ROOT, "benchmark", folder, name), encoding="utf-8") as f:
                    text = f.read()
                assert not [key for key in names if key in text], name
    # the reference imports nothing of the program (its helpers are the sibling reference's)
    with open(os.path.join(ROOT, "benchmark", "reference", "lfm2_moe_ref.py"), encoding="utf-8") as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(ray_tpu|benchmark)", text, re.M)
    assert re.findall(r"^from (\S+) import", text, re.M) == ["__future__", "typing", ".afmoe_ref"]
    # the convolution as shifted products of a padded array, no convolution primitive and no activation on it
    body = text.split('"""', 2)[2]
    assert "z[:, j:j + s]" in body and "conv_general" not in body and "lm_head" not in body


def test_cell_joins_the_shared_metrics_by_name_and_adds_three(benchmark_json):
    reports = {m["name"] for group in ("end_to_end", "per_layer") for m in benchmark_json[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"train_tokens_per_s", "setup_s", "mfu", "data_wait_share", "flash_attn_busy_share",
            "flash_fwd_roofline", "peak_hbm_share_train", "compiles_in_window_train", "setup_train_init_s",
            "setup_compile_s", "setup_programs_built", "setup_cost_analysis_s", "setup_untraced_share",
            "host_turnaround_ms", "step_dispatch_p50_ms",
            "step_unscoped_busy_share", "recompute_busy_share", "head_busy_share", "optimizer_busy_share",
            "attn_proj_busy_share", "attn_layout_busy_share", "mlp_busy_share", "moe_gmm_busy_share",
            "moe_expert_load_max_over_mean", "moe_held_gmm_roofline", "moe_held_rows_off_even",
            "moe_layer_busy_share", "moe_dispatch_busy_share", "moe_combine_busy_share",
            "sconv_mixer_busy_share", "sconv_conv_busy_share", "sconv_conv_fwd_roofline"} <= reports
    # no window, no collective across chips, a router that holds all, ReGLU, a module, latent attention,
    # a state-space or a delta-rule mixer
    assert not {"flash_win_busy_share", "flash_win_fwd_roofline", "collective_exposed_share", "moe_gmm_roofline",
                "moe_act_live_share", "mtp_busy_share", "attn_latent_busy_share", "ssm_mixer_busy_share",
                "ssm_conv_busy_share", "ssm_scan_roofline", "kda_mixer_busy_share",
                "kda_chunk_fwd_roofline"} & reports
    cell = next(w for w in benchmark_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b-train-1chip", "lm-steps-host-batches-8k", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in benchmark_json["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == load(CONF)["source"] and config["file"].endswith(cell["config"] + ".json")
    assert len(config["why"]) <= 200
    for name in ("sconv_mixer_busy_share", "sconv_conv_busy_share", "sconv_conv_fwd_roofline"):
        (metric,) = [m for m in benchmark_json["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_tokens_per_s"
    # the cell and its configuration are appended: what was there stays in its place
    assert benchmark_json["workloads"][-1]["name"] == CELL and benchmark_json["configs"][-1]["name"] == cell["config"]
    traffic = load(os.path.join(ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert {k: traffic[k] for k in ("kind", "batch", "seq", "zipf_a", "prefetch", "segment_steps")} == {
        "kind": "lm_steps", "batch": 2, "seq": 8192, "zipf_a": 1.1, "prefetch": 4, "segment_steps": 10}
