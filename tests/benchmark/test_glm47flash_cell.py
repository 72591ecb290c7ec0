"""What PR 44 adds to the benchmark for `train-glm47flash-8k`: the adapter's
required work against a hand count, the shipped configuration against the
catalog row's published keys, the adapter's refusals, the two new metric
files over the accepted `scope_busy_share` reader, `flash_fwd_roofline`
through this adapter's sizes (20 / 20 heads of 256), and a CPU rehearsal of
a tiny cell of the family through the harness that ends `correct` (and, with
every weight through float8_e4m3, does not)."""

import json
import os
import re

import pytest

from bench_helpers import RESULT_KEYS, ROOT, float8_weights, load
from benchmark import model_config, roofline
from benchmark.readers import flash_fwd_roofline, scope_busy_share

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_glm47flash")
CELL = "train-glm47flash-8k"
CONF = os.path.join(ROOT, "benchmark", "configs", "glm-4.7-flash-train-1chip.json")
# the catalog row `GLM-4.7-Flash` beside the model-configs guide: its `config`, every key
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
    "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def test_required_work_of_a_token_by_hand():
    """3.63 GFLOP at S = 8,192 (ISSUE 44): six latent-attention layers (five
    run and the module's), 4 x 8 / 64 routed experts a token beside the shared
    one, `eh_proj`, two head passes over the slice."""
    conf = model_config.load_config(CONF)
    m, s, heads, d = 2048, 8192, 20, 256
    latent = m * 768 + 768 * heads * d + m * (512 + 64) + 512 * heads * (192 + 256) + heads * 256 * m
    scores = 4 * heads * d * (s + 1) / 2
    dense = 3 * m * 10240
    experts = m * 64 + 3 * m * 1536 * (1 + 4 * 8 / 64)
    head = m * 19360
    forward = 2 * (6 * latent + dense + 5 * experts + 2 * m * m + 2 * head) + 6 * scores
    per_token = model_config.train_flops_per_token(conf, s)
    assert per_token == pytest.approx(3 * forward, rel=1e-12)
    assert forward == pytest.approx(1208.5e6, rel=1e-3) and per_token == pytest.approx(3.63e9, rel=0.002)
    assert latent == pytest.approx(21.76e6, rel=1e-3)
    # the issue's parts, forward MFLOP: scores 503, latent projections 261, dense 126,
    # expert layers 143, eh_proj 17, head passes 159
    for part, want in ((6 * scores, 503e6), (2 * 6 * latent, 261e6), (2 * dense, 126e6),
                       (2 * 5 * experts, 143e6), (2 * 2 * m * m, 16.8e6), (2 * 2 * head, 159e6)):
        assert part == pytest.approx(want, rel=0.005)
    assert (6 * scores + 2 * 6 * latent) / forward == pytest.approx(0.63, abs=0.005)
    # what `flash_fwd_roofline` reads of the adapter: the kernels see 20 / 20 heads of 256
    shape = model_config.shape_numbers(conf)
    assert (shape["n_q_heads"], shape["n_kv_heads"], shape["head_dim"]) == (20, 20, 256)
    assert shape["d_ff"] == int(1.5 * 1536)
    assert model_config.expert_layer(conf) == {
        "held": 8, "published": 64, "per_token": 4, "hidden": 2048, "width": 1536}
    assert model_config.attention_window(conf) is None


def test_configuration_carries_every_published_key():
    conf = load(CONF)
    differ = {k for k, v in CATALOG.items() if conf.get(k, "missing") != v}
    assert differ == {"num_hidden_layers", "n_routed_experts", "vocab_size"} == set(conf["reduced"])
    assert conf["published"] == {k: CATALOG[k] for k in conf["reduced"]}
    assert (conf["num_hidden_layers"], conf["n_routed_experts"], conf["vocab_size"]) == (5, 8, 19360)
    assert conf["share"]["chips_sharing_a_layer"] == 8 and 8 * 19360 == 154880
    assert conf["source"].endswith("zai-org/GLM-4.7-Flash/blob/main/config.json")
    for group in ("deployment", "assumed", "departures", "sizing", "probe", "trainer"):
        assert conf[group], group
    assert conf["assumed"]["mtp_loss_weight"] == 0.3 and conf["assumed"]["route_norm_eps"] == 1e-20
    assert {"mtp_loss_weight", "mtp_module", "rotary", "router", "route_norm_eps"} <= set(conf["assumed"]["why"])
    assert {"load_balancing", "router_gradient", "torch_dtype", "initialisation"} <= set(conf["departures"])
    assert conf["program"]["remat"] is True and conf["program"]["frozen_leaves"] == ["router"]
    model_config.check_reduced(conf, "the shipped file")
    mc = model_config.transformer_config(conf)
    assert (mc.n_layers, mc.n_dense_layers, mc.n_heads, mc.kv_heads, mc.head_dim, mc.d_model) == (
        5, 1, 20, 20, 256, 2048)
    assert (mc.q_lora_rank, mc.kv_lora_rank, mc.qk_rope_dim, mc.v_head_dim, mc.rotary_dims,
            mc.latent_attention) == (768, 512, 64, 256, 64, True)
    assert (mc.n_experts, mc.held_experts, mc.top_k, mc.d_ff, mc.d_ff_dense, mc.shared_expert_width) == (
        64, (0, 8), 4, 1536, 10240, 1536)
    assert (mc.router_score, mc.router_select_bias, mc.norm_topk_prob, mc.route_scale,
            mc.router_aux_coeff, mc.router_input) == ("sigmoid", True, True, 1.8, 0.0, "mlp")
    assert (mc.mtp_modules, mc.mtp_loss_weight, mc.frozen_leaves) == (1, 0.3, ("router",))
    assert not (mc.qk_norm_per_head or mc.attn_gate or mc.sandwich_norm or mc.scale_embedding
                or mc.qk_norm or mc.use_bias or mc.tie_embeddings)
    assert mc.remat and mc.norm_eps == 1e-5 and mc.rope_theta == 1e6
    from ray_tpu.models.mixed_stack import layer_kinds, stack_runs

    assert " ".join(k.code for k in layer_kinds(mc)) == "dL eL eL eL eL"
    assert [(len(run.kinds), run.repeats) for run in stack_runs(layer_kinds(mc))] == [(1, 1), (1, 4)]
    # the sizing's parameter count
    latent = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 20 * 448 + 5120 * 2048
    expert_layer = latent + 2048 * 64 + 9 * 3 * 2048 * 1536
    total = latent + 3 * 2048 * 10240 + 5 * expert_layer + 2 * 2048 * 2048 + 2 * 19360 * 2048
    assert latent == pytest.approx(21.76e6, rel=1e-3) and expert_layer == pytest.approx(106.8e6, rel=1e-3)
    assert total == pytest.approx(706.5e6, rel=1e-3) and 16 * total == pytest.approx(11.30e9, rel=1e-3)
    import jax

    from ray_tpu.models import model_family

    shapes = jax.eval_shape(lambda key: model_family(mc).init_params(mc, key), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == total + sum(     # the norms and the biases
        x.size for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]
        if re.search(r"scale|expert_bias", jax.tree_util.keystr(path)))


@pytest.mark.parametrize("change, match", [
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"v_head_dim": 128}, "v_head_dim 128"),
    ({"num_key_value_heads": 4}, "num_key_value_heads"),
    ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
    ({"topk_method": "greedy"}, "topk_method")],
    ids=["group-limited", "two-groups-chosen", "scaled-rotary", "narrow-values", "grouped-kv-heads",
         "two-modules", "another-selection"])
def test_the_adapter_refuses_what_neither_program_nor_reference_runs(change, match):
    conf = dict(load(CONF), **change)
    with pytest.raises(ValueError, match=match):
        model_config.transformer_config(conf)
    with pytest.raises(ValueError, match=match):
        model_config.adapter(conf).reference_steps(conf, 16384)


def test_the_new_metrics_are_data_over_the_accepted_reader_and_the_roofline_reads_d_256():
    for name, scope in (("attn_latent_busy_share", "attn.latent"), ("mtp_busy_share", "mtp")):
        meta = load(os.path.join(ROOT, "benchmark", "metrics", name + ".json"))
        assert (meta["reader"], meta["args"], meta["layer"], meta["unit"], meta["better"], meta["source"],
                meta["moves"]) == ("scope_busy_share", {"scopes": [scope]}, "train step", "%", "lower",
                                   "device_trace", "train_tokens_per_s")
        # a run without a trace, and a program without the table (the parent's): nothing to read
        assert scope_busy_share.read({"trace": None}, **meta["args"]) is None
        assert scope_busy_share.read({"trace": {"busy_s": 1.0, "program_seconds": {}}}, **meta["args"]) is None
    from ray_tpu.util import profiling

    assert {"attn.latent", "mtp"} <= set(profiling.STEP_SCOPES)
    # one forward call of the cell: 2 x 20 heads of 256 over the causal pairs of 8,192
    trace = {"op_seconds": {"flash_fwd": 6 * 10e-3}, "op_counts": {"flash_fwd": 6}}
    ctx = {"trace": trace, "conf": model_config.load_config(CONF), "device": {"kind": "TPU v5 lite"},
           "traffic": {"batch": 2, "seq": 8192}}
    cost = roofline.flash_fwd_cost(batch=2, seq=8192, n_q_heads=20, n_kv_heads=20, head_dim=256)
    assert cost["ops"] == 4 * 2 * 20 * 256 * (8192 * 8193 // 2)
    least = roofline.roofline_seconds(cost, "TPU v5 lite")["seconds"]
    assert flash_fwd_roofline.read(ctx) == pytest.approx(100 * least / 10e-3, rel=1e-9)


def test_no_reader_and_no_harness_file_names_this_familys_keys():
    names = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "n_routed_experts", "num_nextn_predict_layers", "first_k_dense_replace")
    for folder in ("readers", "kinds"):
        for name in sorted(os.listdir(os.path.join(ROOT, "benchmark", folder))):
            if name.endswith(".py"):
                with open(os.path.join(ROOT, "benchmark", folder, name), encoding="utf-8") as f:
                    text = f.read()
                assert not [key for key in names if key in text], name
    # the reference imports nothing of the program (its helpers are the sibling reference's)
    with open(os.path.join(ROOT, "benchmark", "reference", "glm4_moe_lite_ref.py"), encoding="utf-8") as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(ray_tpu|benchmark)", text, re.M)
    assert re.findall(r"^from (\S+) import", text, re.M) == ["__future__", "typing", ".afmoe_ref"]


def test_cell_joins_the_shared_metrics_and_adds_two(benchmark_json):
    reports = {m["name"] for group in ("end_to_end", "per_layer") for m in benchmark_json[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"train_tokens_per_s", "setup_s", "mfu", "flash_attn_busy_share", "flash_fwd_roofline",
            "peak_hbm_share_train", "compiles_in_window_train", "step_unscoped_busy_share",
            "attn_proj_busy_share", "mlp_busy_share", "moe_gmm_busy_share",
            "moe_expert_load_max_over_mean", "moe_held_gmm_roofline", "moe_held_rows_off_even",
            "moe_layer_busy_share", "moe_dispatch_busy_share", "moe_combine_busy_share",
            "attn_latent_busy_share", "mtp_busy_share"} <= reports
    # no window, every collective across chips, a router that holds all, ReGLU: not this cell's
    assert not {"flash_win_busy_share", "flash_win_fwd_roofline", "collective_exposed_share",
                "moe_gmm_roofline", "moe_act_live_share"} & reports
    cell = next(w for w in benchmark_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-4.7-flash-train-1chip", "lm-steps-host-batches-8k", 1)
    config = next(c for c in benchmark_json["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["source"] == load(CONF)["source"] and config["file"].endswith(cell["config"] + ".json")
    for name in ("attn_latent_busy_share", "mtp_busy_share"):
        (metric,) = [m for m in benchmark_json["per_layer"] if m["name"] == name]
        assert CELL in metric["workloads"] and metric["moves"] == "train_tokens_per_s"
    assert sum(w["chips"] == 4 for w in benchmark_json["workloads"]) == 1


def _tiny_bench(benchmark_json):
    return dict(benchmark_json, workloads=[
        {"name": CELL, "config": "tiny-glm47flash-train", "traffic": "tiny-lm-steps", "chips": 1}])


@pytest.mark.parametrize("trace", [False, True], ids=["end-to-end-line", "traced-line"])
def test_tiny_glm47flash_cell_rehearsal_ends_correct(benchmark_json, trace):
    """The real cell's entries with a tiny tree behind them: LMTrainer on 1
    dense + 2 expert latent-attention layers and the module (8 of 32 experts
    held, top-4), its first two steps against glm4_moe_lite_ref's objective
    (main + 0.3 x module), clip and AdamW."""
    from benchmark import run

    result = json.loads(json.dumps(run.run_cell(
        _tiny_bench(benchmark_json), CELL, 2**31 + 44, 2.0, trace, tree=TINY, require_tpu=False)))
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    checks = result["info"]["checks"]
    assert checks["loss_step1_gap"]["value"] < 1e-5          # float32 against float32
    assert checks["first_gradient_worst_leaf_difference"]["value"] < 1e-4
    assert checks["change_worst_leaf_gap"]["value"] < 1e-4
    assert checks["loss_last"] < checks["loss_first"]
    if not trace:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    else:
        # counters and span readers answer on a CPU; the trace readers and `mfu` find no chip
        assert {"moe_expert_load_max_over_mean", "moe_held_rows_off_even", "data_wait_share",
                "compiles_in_window_train", "setup_train_init_s", "setup_compile_s",
                "setup_programs_built", "host_turnaround_ms", "step_dispatch_p50_ms"} <= set(result["metrics"])
        assert not {"attn_latent_busy_share", "mtp_busy_share", "mfu"} & set(result["metrics"])
        assert result["metrics"]["compiles_in_window_train"]["value"] == 0
        assert 0.0 <= result["metrics"]["moe_held_rows_off_even"]["value"] <= 75.0   # 8 of 32 held
    assert result["device"]["platform"] == "cpu"


def test_tiny_glm47flash_cell_with_every_weight_through_float8_is_not_correct(benchmark_json):
    from benchmark import run

    with float8_weights():
        result = run.run_cell(_tiny_bench(benchmark_json), CELL, 2**31 + 44, 1.0, False,
                              tree=TINY, require_tpu=False)
    assert result["correct"] is False
    checks = result["info"]["checks"]
    assert checks["first_loss_repeat_gap"]["value"] == 0.0      # the step that was timed is the one compared
    assert (checks["first_gradient_worst_leaf_difference"]["value"]
            > checks["first_gradient_worst_leaf_difference"]["limit"])
