"""What PR 36 adds to the benchmark for `train-smallthinker-16k`: the
adapter's required work against hand counts, the shipped configuration
against the catalog row's published keys, the four family readers on the
recorded trace through this adapter's sizes, the adapter's refusals, the
new counter's metric file, and a CPU rehearsal of a tiny cell of the family
through the harness that ends `correct` (and, with every weight through
float8_e4m3, does not)."""

import json
import os
import re

import pytest

from bench_helpers import RESULT_KEYS, ROOT, float8_weights, load
from benchmark import model_config, moe_cost, roofline, window_cost
from benchmark import trace_reduce as tr
from benchmark.adapters import smallthinker
from benchmark.readers import (flash_win_fwd_roofline, moe_held_gmm_roofline,
                               moe_held_rows_off_even, report_span_attribute)

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_smallthinker")
CELL = "train-smallthinker-16k"
CONF = os.path.join(ROOT, "benchmark", "configs", "smallthinker-21b-a3b-train-1chip.json")
KIND = "TPU v5 lite"
# the catalog row `SmallThinker-21BA3B-Instruct` beside the model-configs guide: its `config`, every key
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1] * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
# a span record as the cell writes one: two reports inside the window
SPANS = [
    {"name": "train.init.step_fn", "start_mono": 1.0, "end_mono": 2.0,
     "attrs": {"moe_gmm_tile_rows": 256}},
    {"name": "train.report", "start_mono": 128.9, "end_mono": 129.0,            # set-up
     "attrs": {"moe_rows_held": 9e4, "moe_passes": 1.0, "moe_rows_held_share": 91.6,
               "moe_act_live_share": 12.0}},
    {"name": "train.report", "start_mono": 133.9, "end_mono": 134.0,
     "attrs": {"moe_rows_held": 12100.5, "moe_passes": 1.0, "moe_rows_held_share": 12.3093,
               "moe_act_live_share": 50.25}},
    {"name": "train.report", "start_mono": 138.9, "end_mono": 139.0,
     "attrs": {"moe_rows_held": 12544.0, "moe_passes": 1.0, "moe_rows_held_share": 12.7604,
               "moe_act_live_share": 49.5}},
]


def test_required_work_of_a_token_by_hand():
    """3.15 GFLOP at S = 16,384 (ISSUE 36): window pairs for the six sliding
    layers, 6 x 8 / 64 routed experts a token, no shared expert, the head
    over the slice."""
    conf = model_config.load_config(CONF)
    m, d, s, w = 2560, 128, 16384, 4096
    projections = m * d * (28 + 4 + 4) + 28 * d * m                  # q, k, v; output
    visible_sliding = (w * (w + 1) / 2 + (s - w) * w) / s            # 3,584.1 keys a query
    scores = 4 * 28 * d * (6 * visible_sliding + 2 * (s + 1) / 2)
    layer = projections + m * 64 + 6 * 8 / 64 * 3 * m * 768
    head = m * 18992
    by_hand = 3 * (2 * (8 * layer + head) + scores)
    per_token = model_config.train_flops_per_token(conf, s)
    assert per_token == pytest.approx(by_hand, rel=1e-12)
    assert per_token == pytest.approx(3.15e9, rel=0.002)
    assert projections == pytest.approx(20.97e6, rel=1e-3) and layer == pytest.approx(25.56e6, rel=1e-3)
    assert visible_sliding == pytest.approx(3584.1, abs=0.1)
    forward = by_hand / 3
    assert forward == pytest.approx(1049e6, rel=1e-3)
    # the issue's shares: scores 52%, projections 32%, head 9%, held experts 7%
    assert scores / forward == pytest.approx(0.52, abs=0.005)
    assert 2 * 8 * projections / forward == pytest.approx(0.32, abs=0.005)
    assert 2 * head / forward == pytest.approx(0.09, abs=0.005)
    assert 2 * 8 * 6 * 8 / 64 * 3 * m * 768 / forward == pytest.approx(0.07, abs=0.005)
    # a window as long as the sequence is the causal count
    full = dict(conf, sliding_window_size=s)
    assert smallthinker.train_flops_per_token(full, s) == pytest.approx(
        by_hand + 3 * 4 * 28 * d * 6 * ((s + 1) / 2 - visible_sliding), rel=1e-12)
    # at half the context a sliding layer prunes 25% of its causal pairs, here 56%
    assert 1 - visible_sliding / ((s + 1) / 2) == pytest.approx(0.5625, abs=0.001)
    # what `flash_fwd_roofline` reads of the adapter
    shape = model_config.shape_numbers(conf)
    assert (shape["n_q_heads"], shape["n_kv_heads"], shape["head_dim"]) == (28, 4, 128)
    assert shape["d_ff"] == 6 * 768 // 8


def test_configuration_carries_every_published_key():
    conf = load(CONF)
    differ = {k for k, v in CATALOG.items() if conf.get(k, "missing") != v}
    assert differ == {"num_hidden_layers", "moe_num_primary_experts", "vocab_size"} == set(conf["reduced"])
    assert conf["published"] == {k: CATALOG[k] for k in conf["reduced"]}
    assert (conf["num_hidden_layers"], conf["moe_num_primary_experts"], conf["vocab_size"]) == (8, 8, 18992)
    assert conf["share"]["chips_sharing_a_layer"] == 8 and 8 * 18992 == 151936
    assert conf["source"].endswith("PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    for group in ("deployment", "assumed", "departures", "sizing", "probe", "trainer"):
        assert conf[group], group
    assert conf["program"] == {"remat": True, "frozen_leaves": ["router"], "embedding_std": 1.0,
                               "router_std": 0.06}
    assert {"router_input", "model_type", "secondary_experts", "rotary"} <= set(conf["assumed"]["why"])
    assert {"router_gradient", "initialisation", "load_balancing"} <= set(conf["departures"])
    model_config.check_reduced(conf, "the shipped file")
    mc = model_config.transformer_config(conf)
    assert (mc.n_layers, mc.n_dense_layers, mc.global_attn_every, mc.global_attn_first,
            mc.sliding_window) == (8, 0, 4, True, 4096)
    assert (mc.n_heads, mc.kv_heads, mc.head_dim, mc.d_model, mc.max_seq) == (28, 4, 128, 2560, 16384)
    assert (mc.n_experts, mc.held_experts, mc.top_k, mc.d_ff, mc.shared_expert_width) == (
        64, (0, 8), 6, 768, 0)
    assert (mc.router_score, mc.router_select_bias, mc.norm_topk_prob, mc.route_scale,
            mc.router_aux_coeff, mc.router_input, mc.expert_act) == (
        "softmax", False, True, 1.0, 0.0, "attention", "reglu")
    assert not (mc.qk_norm_per_head or mc.attn_gate or mc.sandwich_norm or mc.scale_embedding
                or mc.qk_norm or mc.use_bias or mc.tie_embeddings)
    assert mc.remat and mc.norm_eps == 1e-6 and mc.rope_theta == 1.5e6 and mc.embedding_std == 1.0
    assert mc.router_std == 0.06
    assert mc.frozen_leaves == ("router",)
    from ray_tpu.models.mixed_stack import layer_kinds, stack_runs

    assert " ".join(k.code for k in layer_kinds(mc)) == "eF eS eS eS eF eS eS eS"
    assert [(len(run.kinds), run.repeats) for run in stack_runs(layer_kinds(mc))] == [(4, 2)]
    # the sizing's parameter count
    attention = 2560 * 128 * (28 + 4 + 4) + 28 * 128 * 2560
    layer = attention + 2560 * 64 + 8 * 3 * 2560 * 768
    total = 8 * layer + 2 * 18992 * 2560
    assert attention == pytest.approx(20.97e6, rel=1e-3) and layer == pytest.approx(68.32e6, rel=1e-3)
    assert total == pytest.approx(643.8e6, rel=1e-3) and 16 * total == pytest.approx(10.30e9, rel=1e-3)
    traffic = load(os.path.join(ROOT, "benchmark", "traffic", "lm-steps-host-batches-16k.json"))
    assert (traffic["kind"], traffic["batch"], traffic["seq"], traffic["zipf_a"], traffic["prefetch"],
            traffic["segment_steps"]) == ("lm_steps", 1, 16384, 1.1, 4, 10)


@pytest.mark.parametrize("change, match", [
    ({"sliding_window_layout": [1, 1, 1, 0] * 13}, "sliding_window_layout"),
    ({"rope_layout": [0, 1, 1, 1] * 12 + [1, 1, 1, 1]}, "rope_layout"),
    ({"moe_primary_router_apply_softmax": False}, "softmax"),
    ({"norm_topk_prob": False}, "renormalised"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "unscaled rotary")],
    ids=["global-last", "a-layer-off-the-rule", "sigmoid-router", "gates-as-they-are", "scaled-rotary"])
def test_the_adapter_refuses_what_neither_program_nor_reference_runs(change, match):
    conf = dict(load(CONF), **change)
    with pytest.raises(ValueError, match=match):
        model_config.transformer_config(conf)
    with pytest.raises(ValueError, match=match):
        model_config.adapter(conf).reference_steps(conf, 16384)
    assert smallthinker.layout(52) == CATALOG["rope_layout"] == CATALOG["sliding_window_layout"]


@pytest.fixture(scope="module")
def trace():
    """The recorded trace (16 ms of a chip, tests/benchmark/data), reduced,
    with its three measured `flash_fwd` calls also booked under the names
    the expert layer's and the window's kernels carry (the recording is of a
    program that has neither, and the readers find them by name)."""
    reduced = tr.reduce_trace(load(os.path.join(HERE, "data", "recorded_trace.json")))
    (name,) = [k for k in reduced["op_seconds"] if k.startswith("flash_fwd")]
    more = ("moe_gmm_fwd custom-call", "moe_gmm_dlhs custom-call", "flash_win_fwd custom-call")
    return dict(reduced,
                op_seconds=dict(reduced["op_seconds"], **{k: reduced["op_seconds"][name] for k in more}),
                op_counts=dict(reduced["op_counts"], **{k: reduced["op_counts"][name] for k in more}))


def _ctx(trace):
    return {"trace": trace, "conf": model_config.load_config(CONF), "device": {"kind": KIND},
            "traffic": {"batch": 1, "seq": 16384}, "t0": 130.0, "t1": 140.0}


def _share_of_least(trace, prefixes, cost):
    least = roofline.roofline_seconds(cost, KIND)["seconds"]
    return 100.0 * tr.count_of(trace, prefixes) * least / tr.seconds_of(trace, prefixes)


@pytest.mark.parametrize("metric", ["flash_win_fwd_roofline", "moe_held_gmm_roofline",
                                    "moe_held_rows_off_even", "moe_act_live_share"])
def test_the_family_readers_read_this_adapters_sizes(monkeypatch, trace, metric):
    """Each reader's number is the arithmetic written out with THIS family's
    sizes (28 / 4 heads of 128, window 4,096; 8 of 64 experts of 768 on a
    hidden size of 2,560), which it reaches through `model_config` alone."""
    for reader in (moe_held_gmm_roofline, moe_held_rows_off_even, report_span_attribute):
        monkeypatch.setattr(reader, "program_spans", lambda: SPANS)
    ctx = _ctx(trace)
    assert model_config.expert_layer(ctx["conf"]) == {
        "held": 8, "published": 64, "per_token": 6, "hidden": 2560, "width": 768}
    assert model_config.attention_window(ctx["conf"]) == 4096
    if metric == "flash_win_fwd_roofline":
        cost = window_cost.flash_win_fwd_cost(batch=1, seq=16384, window=4096, n_q_heads=28,
                                              n_kv_heads=4, head_dim=128)
        assert cost["ops"] == 4 * 28 * 128 * (4096 * 4097 // 2 + 12288 * 4096)
        assert flash_win_fwd_roofline.read(ctx) == _share_of_least(trace, ("flash_win_fwd",), cost)
    elif metric == "moe_held_gmm_roofline":
        cost = moe_cost.gmm_cost(rows=(12100.5 + 12544.0) / 2, k=2560, n=768, groups=8)
        assert moe_held_gmm_roofline.read(ctx) == _share_of_least(trace, ("moe_gmm_",), cost)
    elif metric == "moe_held_rows_off_even":
        assert moe_held_rows_off_even.read(ctx) == pytest.approx(
            abs((12.3093 + 12.7604) / 2 - 100.0 * 8 / 64), rel=1e-12)
    else:
        meta = load(os.path.join(ROOT, "benchmark", "metrics", "moe_act_live_share.json"))
        assert (meta["reader"], meta["layer"], meta["unit"], meta["better"], meta["source"],
                meta["moves"]) == ("report_span_attribute", "moe kernels", "%", "lower",
                                   "program_counter", "train_tokens_per_s")
        # the last report inside the window; a program without the counter has nothing to read
        assert report_span_attribute.read(ctx, **meta["args"]) == 49.5
        monkeypatch.setattr(report_span_attribute, "program_spans",
                            lambda: [dict(s, attrs={"moe_rows_held": 1.0}) for s in SPANS])
        assert report_span_attribute.read(ctx, **meta["args"]) is None


def test_no_reader_and_no_harness_file_names_this_familys_keys():
    names = ("moe_num_primary_experts", "moe_ffn_hidden_size", "moe_num_active_primary_experts",
             "sliding_window_size", "sliding_window_layout", "rope_layout")
    for folder in ("readers", "kinds"):
        for name in sorted(os.listdir(os.path.join(ROOT, "benchmark", folder))):
            if name.endswith(".py"):
                with open(os.path.join(ROOT, "benchmark", folder, name), encoding="utf-8") as f:
                    text = f.read()
                assert not [key for key in names if key in text], name
    # the reference imports nothing of the program
    with open(os.path.join(ROOT, "benchmark", "reference", "smallthinker_ref.py"), encoding="utf-8") as f:
        assert not re.search(r"^\s*(from|import)\s+(ray_tpu|\.\.?\s*import|benchmark)", f.read(), re.M)


def test_cell_joins_the_shared_metrics_and_adds_one(benchmark_json):
    reports = {m["name"] for group in ("end_to_end", "per_layer") for m in benchmark_json[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"train_tokens_per_s", "setup_s", "mfu", "flash_attn_busy_share", "flash_fwd_roofline",
            "moe_gmm_busy_share", "moe_expert_load_max_over_mean", "flash_win_busy_share",
            "flash_win_fwd_roofline", "moe_held_gmm_roofline", "moe_held_rows_off_even",
            "moe_act_live_share"} <= reports
    assert not {"moe_gmm_roofline", "collective_exposed_share"} & reports
    assert len(reports) == 2 + 20
    cell = next(w for w in benchmark_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b-train-1chip", "lm-steps-host-batches-16k", 1)
    assert benchmark_json["workloads"][-1] == cell and benchmark_json["configs"][-1]["name"] == cell["config"]
    assert benchmark_json["per_layer"][-1]["name"] == "moe_act_live_share"
    assert benchmark_json["per_layer"][-1]["workloads"] == [CELL]
    assert sum(w["chips"] == 4 for w in benchmark_json["workloads"]) == 1


def _tiny_bench(benchmark_json):
    return dict(benchmark_json, workloads=[
        {"name": CELL, "config": "tiny-smallthinker-train", "traffic": "tiny-lm-steps", "chips": 1}])


@pytest.mark.parametrize("trace", [False, True], ids=["end-to-end-line", "traced-line"])
def test_tiny_smallthinker_cell_rehearsal_ends_correct(benchmark_json, trace):
    """The real cell's entries with a tiny tree behind them: LMTrainer on 8
    all-expert layers (8 of 32 experts held, top-3, window 16 of 48, the
    router on the attention's input), its first two steps against
    smallthinker_ref's objective, clip and AdamW."""
    from benchmark import run

    result = json.loads(json.dumps(run.run_cell(
        _tiny_bench(benchmark_json), CELL, 2**31 + 36, 2.0, trace, tree=TINY, require_tpu=False)))
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
            "moe_expert_load_max_over_mean", "moe_held_rows_off_even", "moe_act_live_share",
            "data_wait_share", "compiles_in_window_train", "setup_train_init_s", "setup_compile_s",
            "setup_programs_built", "setup_cost_analysis_s", "setup_untraced_share",
            "host_turnaround_ms", "step_dispatch_p50_ms"}
        assert result["metrics"]["compiles_in_window_train"]["value"] == 0
        assert 0.0 < result["metrics"]["moe_act_live_share"]["value"] < 100.0
    assert result["device"]["platform"] == "cpu"


def test_tiny_smallthinker_cell_with_every_weight_through_float8_is_not_correct(benchmark_json):
    from benchmark import run

    with float8_weights():
        result = run.run_cell(_tiny_bench(benchmark_json), CELL, 2**31 + 36, 1.0, False,
                              tree=TINY, require_tpu=False)
    assert result["correct"] is False
    checks = result["info"]["checks"]
    assert checks["first_loss_repeat_gap"]["value"] == 0.0      # the step that was timed is the one compared
    assert (checks["first_gradient_worst_leaf_difference"]["value"]
            > checks["first_gradient_worst_leaf_difference"]["limit"])
