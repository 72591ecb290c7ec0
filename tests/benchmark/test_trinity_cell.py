"""What PR 33 adds to the benchmark for `train-trinity-mini-8k`: the
adapter's required work and the windowed call's cost against hand counts, the
two new readers on a synthetic trace and span record, the shipped
configuration against the catalog row's published keys, and a CPU rehearsal
of a tiny cell of the family through the harness that ends `correct`."""

import json
import os

import pytest

from bench_helpers import RESULT_KEYS, ROOT, load
from benchmark import model_config, moe_cost, roofline, window_cost
from benchmark.adapters import afmoe
from benchmark.readers import (flash_win_fwd_roofline, moe_held_gmm_roofline,
                               moe_held_rows_off_even)

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_trinity")
CELL = "train-trinity-mini-8k"
CONF = os.path.join(ROOT, "benchmark", "configs", "trinity-mini-train-1chip.json")
# the catalog row `Trinity-Mini` beside the model-configs guide: its `config`, every key
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}


def test_required_work_of_a_token_by_hand():
    """2.69 GFLOP at S = 8,192 (ISSUE 33): window pairs for the sliding
    layers, 8 x 16 / 128 routed experts a token, the shared expert whole, the
    head over the slice."""
    conf = model_config.load_config(CONF)
    m, d, s, w = 2048, 128, 8192, 2048
    projections = m * d * (32 + 4 + 4 + 32) + 32 * d * m            # q, k, v, gate; output
    visible_sliding = (w * (w + 1) / 2 + (s - w) * w) / s            # 1,792.1 keys a query
    scores = 4 * 32 * d * (5 * visible_sliding + (s + 1) / 2)
    dense = 2 * 3 * m * 6144
    experts = 4 * (m * 128 + 3 * m * 1024 * (1 + 8 * 16 / 128))
    head = m * 25024
    by_hand = 3 * (2 * (6 * projections + dense + experts + head) + scores)
    per_token = model_config.train_flops_per_token(conf, s)
    assert per_token == pytest.approx(by_hand, rel=1e-12)
    assert per_token == pytest.approx(2.69e9, rel=0.002)
    forward = by_hand / 3
    assert 2 * 6 * projections / forward == pytest.approx(327e6 / 897e6, abs=0.005)
    assert scores / forward == pytest.approx(214e6 / 897e6, abs=0.005)
    assert visible_sliding == pytest.approx(1792, abs=0.2)
    # a window as long as the sequence is the causal count
    full = dict(conf, sliding_window=s)
    assert afmoe.train_flops_per_token(full, s) == pytest.approx(
        by_hand + 3 * 4 * 32 * d * 5 * ((s + 1) / 2 - visible_sliding), rel=1e-12)
    # what `flash_fwd_roofline` reads of the adapter
    shape = model_config.shape_numbers(conf)
    assert (shape["n_q_heads"], shape["n_kv_heads"], shape["head_dim"]) == (32, 4, 128)


def test_configuration_carries_every_published_key():
    conf = load(CONF)
    differ = {k for k, v in CATALOG.items() if conf.get(k, "missing") != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"} == set(conf["reduced"])
    assert conf["published"] == {k: CATALOG[k] for k in conf["reduced"]}
    assert (conf["num_hidden_layers"], conf["num_experts"], conf["vocab_size"]) == (6, 16, 25024)
    assert conf["share"]["chips_sharing_a_layer"] == 8 and 8 * 25024 == 200192
    # the published list of layer kinds is what the rule generates, for all 32 layers
    assert afmoe.layer_types(conf, 32) == CATALOG["layer_types"]
    with pytest.raises(ValueError, match="layer_types"):
        model_config.transformer_config(dict(conf, layer_types=["full_attention"] * 32))
    mc = model_config.transformer_config(conf)
    assert (mc.n_layers, mc.n_dense_layers, mc.global_attn_every, mc.sliding_window) == (6, 2, 4, 2048)
    assert (mc.n_heads, mc.kv_heads, mc.head_dim, mc.d_model) == (32, 4, 128, 2048)
    assert mc.head_dim != mc.d_model // mc.n_heads
    assert (mc.n_experts, mc.held_experts, mc.top_k, mc.d_ff, mc.d_ff_dense,
            mc.shared_expert_width) == (128, (0, 16), 8, 1024, 6144, 1024)
    assert (mc.router_score, mc.router_select_bias, mc.norm_topk_prob,
            mc.route_scale, mc.router_aux_coeff) == ("sigmoid", True, True, 2.826, 0.0)
    assert mc.qk_norm_per_head and mc.attn_gate and mc.sandwich_norm and mc.scale_embedding
    assert mc.remat and mc.norm_eps == 1e-5 and not mc.tie_embeddings
    from ray_tpu.models.mixed_stack import layer_kinds

    assert " ".join(k.code for k in layer_kinds(mc)) == "dS dS eS eF eS eS"
    # the sizing's parameter count
    attention = 2048 * 128 * (32 + 4 + 4 + 32) + 32 * 128 * 2048
    expert_layer = attention + 2048 * 128 + 3 * 2048 * 1024 * (1 + 16)
    total = 2 * (attention + 3 * 2048 * 6144) + 4 * expert_layer + 2 * 25024 * 2048
    assert attention == pytest.approx(27.26e6, rel=1e-3) and total == pytest.approx(770.5e6, rel=1e-3)
    traffic = load(os.path.join(ROOT, "benchmark", "traffic", "lm-steps-host-batches-8k.json"))
    assert (traffic["kind"], traffic["batch"], traffic["seq"], traffic["zipf_a"], traffic["prefetch"],
            traffic["segment_steps"]) == ("lm_steps", 2, 8192, 1.1, 4, 10)


def test_windowed_call_cost_by_hand():
    # query i scores min(i + 1, W) keys
    assert window_cost.window_pairs(8, 3) == 1 + 2 + 3 * 6 == sum(min(i + 1, 3) for i in range(8))
    assert window_cost.window_pairs(8192, 2048) == sum(min(i + 1, 2048) for i in range(8192))
    assert window_cost.window_pairs(1024, 2048) == roofline.causal_pairs(1024, 1024)
    cost = window_cost.flash_win_fwd_cost(batch=2, seq=8192, window=2048, n_q_heads=32,
                                          n_kv_heads=4, head_dim=128)
    pairs = 2048 * 2049 // 2 + 6144 * 2048
    assert cost["ops"] == 4 * 2 * 32 * 128 * pairs
    # q and o rows of 32 heads, k and v rows of 4, bfloat16; the float32 lse row
    assert cost["bytes"] == 2 * 2 * 128 * 8192 * (2 * 32 + 2 * 4) + 2 * 32 * 8192 * 4
    least = roofline.roofline_seconds(cost, "TPU v5 lite")
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(2.4415e-3, rel=1e-3)
    # the full causal call of the same shape needs 2.29 times the operations
    whole = roofline.flash_fwd_cost(batch=2, seq=8192, n_q_heads=32, n_kv_heads=4, head_dim=128)
    assert whole["ops"] / cost["ops"] == pytest.approx(2.286, abs=0.002)
    assert whole["bytes"] == cost["bytes"]


def _ctx(trace, **more):
    return dict({"trace": trace, "conf": load(CONF), "device": {"kind": "TPU v5 lite"},
                 "traffic": {"batch": 2, "seq": 8192}, "t0": 130.0, "t1": 140.0}, **more)


def _report(end, **attrs):
    return {"name": "train.report", "start_mono": end - 0.01, "end_mono": end, "attrs": attrs}


def test_windowed_roofline_reader_on_a_synthetic_trace():
    least = 4 * 2 * 32 * 128 * (2048 * 2049 // 2 + 6144 * 2048) / 197e12
    # one step: five sliding layers, forward and its recomputation, 6 ms a call; the one
    # full layer's kernels are another metric's
    trace = {"busy_s": 0.5,
             "op_seconds": {"flash_win_fwd": 10 * 6e-3, "flash_win_bwd_dkv": 5 * 9e-3,
                            "flash_win_bwd_dq": 5 * 8e-3, "flash_fwd": 2 * 14e-3},
             "op_counts": {"flash_win_fwd": 10, "flash_win_bwd_dkv": 5, "flash_win_bwd_dq": 5,
                           "flash_fwd": 2}}
    assert flash_win_fwd_roofline.read(_ctx(trace)) == pytest.approx(100 * least / 6e-3, rel=1e-9)
    # the parent's program, or a cell with no windowed layer: nothing to read
    older = {"busy_s": 0.5, "op_seconds": {"flash_fwd": 0.03}, "op_counts": {"flash_fwd": 2}}
    assert flash_win_fwd_roofline.read(_ctx(older)) is None
    assert flash_win_fwd_roofline.read(_ctx(None)) is None
    assert flash_win_fwd_roofline.read(dict(_ctx(trace), conf={"model_type": "gpt2"})) is None
    # the busy share is the accepted reader with the windowed kernels' prefix, and the accepted
    # `flash_fwd` / `flash_bwd_*` prefixes do not see them
    from benchmark.readers import op_busy_share

    meta = load(os.path.join(ROOT, "benchmark", "metrics", "flash_win_busy_share.json"))
    assert meta["reader"] == "op_busy_share"
    assert op_busy_share.read({"trace": trace}, **meta["args"]) == pytest.approx(
        100 * (0.060 + 0.045 + 0.040) / 0.5)
    accepted = load(os.path.join(ROOT, "benchmark", "metrics", "flash_attn_busy_share.json"))
    assert op_busy_share.read({"trace": trace}, **accepted["args"]) == pytest.approx(100 * 0.028 / 0.5)


def test_held_readers_price_the_rows_the_program_counted(monkeypatch):
    spans = [_report(129.0, moe_rows_held=9e4, moe_passes=1.0, moe_rows_held_share=68.7),  # set-up
             _report(134.0, moe_rows_held=24000.0, moe_passes=1.0, moe_rows_held_share=18.31),
             _report(139.0, moe_rows_held=16000.0, moe_passes=1.0, moe_rows_held_share=12.21),
             {"name": "train.init.step_fn", "start_mono": 1.0, "end_mono": 2.0,
              "attrs": {"moe_gmm_tile_rows": 256}}]
    for reader in (moe_held_gmm_roofline, moe_held_rows_off_even):
        monkeypatch.setattr(reader, "program_spans", lambda: spans)
    # one step: 4 expert layers x 3 projections x (forward, its recomputation, dlhs, drhs)
    trace = {"busy_s": 0.5,
             "op_seconds": {"moe_gmm_fwd": 24 * 0.9e-3, "moe_gmm_dlhs": 12 * 0.9e-3,
                            "moe_gmm_drhs": 12 * 0.9e-3},
             "op_counts": {"moe_gmm_fwd": 24, "moe_gmm_dlhs": 12, "moe_gmm_drhs": 12}}
    # the mean of the WINDOW's reports (the trace covers a segment of it, not the last step)
    cost = moe_cost.gmm_cost(rows=20000.0, k=2048, n=1024, groups=16)
    assert cost["ops"] == 2 * 20000 * 2048 * 1024
    least = roofline.roofline_seconds(cost, "TPU v5 lite")["seconds"]
    assert moe_held_gmm_roofline.read(_ctx(trace)) == pytest.approx(100 * least / 0.9e-3, rel=1e-9)
    # two passes through the buffer in one report: its calls are priced at half its rows
    spans[1]["attrs"]["moe_passes"] = 2.0
    fewer = roofline.roofline_seconds(
        moe_cost.gmm_cost(rows=14000.0, k=2048, n=1024, groups=16), "TPU v5 lite")["seconds"]
    assert moe_held_gmm_roofline.read(_ctx(trace)) == pytest.approx(100 * fewer / 0.9e-3, rel=1e-9)
    # the rows' distance from an even router's share, 100 x 16 / 128, by the window's mean
    meta = load(os.path.join(ROOT, "benchmark", "metrics", "moe_held_rows_off_even.json"))
    assert (meta["reader"], meta["better"]) == ("moe_held_rows_off_even", "lower")
    assert moe_held_rows_off_even.read(_ctx(trace)) == pytest.approx((18.31 + 12.21) / 2 - 12.5)
    for report in spans[1:3]:
        report["attrs"]["moe_rows_held_share"] = 0.0     # a router that sends nothing here
    assert moe_held_rows_off_even.read(_ctx(trace)) == pytest.approx(12.5)
    # under one row tile a group the kernels' time is the weights' alone: no number
    for report in spans[1:3]:
        report["attrs"].update(moe_rows_held=16 * 256 - 1.0, moe_passes=1.0)
    assert moe_held_gmm_roofline.read(_ctx(trace)) is None
    # a program without the counter (the parent's), without the kernels, without a trace
    for reader in (moe_held_gmm_roofline, moe_held_rows_off_even):
        monkeypatch.setattr(reader, "program_spans", lambda: [_report(139.0, loss=1.0)])
    assert moe_held_gmm_roofline.read(_ctx(trace)) is None
    assert moe_held_rows_off_even.read(_ctx(trace)) is None
    for reader in (moe_held_gmm_roofline, moe_held_rows_off_even):
        monkeypatch.setattr(reader, "program_spans", lambda: None)
    assert moe_held_gmm_roofline.read(_ctx(trace)) is None
    assert moe_held_rows_off_even.read(_ctx(trace)) is None
    assert moe_held_gmm_roofline.read(_ctx(None)) is None
    # a configuration that holds every expert has no share to be off
    olmoe = load(os.path.join(ROOT, "benchmark", "configs", "olmoe-1b-7b-train-1chip.json"))
    assert moe_held_rows_off_even.read(dict(_ctx(trace), conf=olmoe)) is None


def test_cell_joins_the_shared_metrics_and_not_the_two_that_would_misread(benchmark_json):
    reports = {m["name"] for group in ("end_to_end", "per_layer") for m in benchmark_json[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"train_tokens_per_s", "setup_s", "mfu", "flash_attn_busy_share", "flash_fwd_roofline",
            "moe_gmm_busy_share", "moe_expert_load_max_over_mean", "flash_win_busy_share",
            "flash_win_fwd_roofline", "moe_held_gmm_roofline", "moe_held_rows_off_even"} <= reports
    # `moe_gmm_roofline` prices batch x seq x 8 rows over `num_experts` groups, eight times the
    # rows this chip is routed; collectives exist only across chips
    assert not {"moe_gmm_roofline", "collective_exposed_share"} & reports
    assert len(reports) == 2 + 19
    assert len(benchmark_json["per_layer"]) == 21 and len(benchmark_json["workloads"]) == 4
    assert sum(w["chips"] == 4 for w in benchmark_json["workloads"]) == 1


@pytest.mark.parametrize("trace", [False, True], ids=["end-to-end-line", "traced-line"])
def test_tiny_trinity_cell_rehearsal_ends_correct(benchmark_json, trace):
    """The real cell's entries with a tiny tree behind them: LMTrainer on 2
    dense + 4 expert layers (8 of 32 experts held, top-4, window 16 of 48),
    its first two steps against afmoe_ref's objective, clip and AdamW."""
    from benchmark import run

    bench = dict(benchmark_json, workloads=[
        {"name": CELL, "config": "tiny-trinity-train", "traffic": "tiny-lm-steps", "chips": 1}])
    result = json.loads(json.dumps(
        run.run_cell(bench, CELL, 2**31 + 33, 2.0, trace, tree=TINY, require_tpu=False)))
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
        assert set(result["metrics"]) == {
            "moe_expert_load_max_over_mean", "moe_held_rows_off_even", "data_wait_share",
            "compiles_in_window_train", "setup_train_init_s", "setup_compile_s",
            "setup_programs_built", "setup_cost_analysis_s", "setup_untraced_share",
            "host_turnaround_ms", "step_dispatch_p50_ms"}
        assert result["metrics"]["compiles_in_window_train"]["value"] == 0
        assert 0.0 <= result["metrics"]["moe_held_rows_off_even"]["value"] <= 75.0   # 8 of 32 held
    assert result["device"]["platform"] == "cpu"
