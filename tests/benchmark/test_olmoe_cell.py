"""What PR 27 adds to the benchmark for `train-olmoe-64e-4k`: the
adapter's sizes and the grouped matmul's cost against hand counts, the two
new readers on a synthetic trace and span record, the shipped
configuration against the published sizes, and a CPU rehearsal of a tiny
OLMoE cell through the harness that ends `correct`."""

import json
import os

import pytest

from bench_helpers import RESULT_KEYS, ROOT, load
from benchmark import model_config, moe_cost, roofline
from benchmark.readers import moe_gmm_roofline, report_span_attribute

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_olmoe")
CELL = "train-olmoe-64e-4k"
CONF = os.path.join(ROOT, "benchmark", "configs", "olmoe-1b-7b-train-1chip.json")


def test_shapes_are_the_active_parameters_by_hand():
    conf = model_config.load_config(CONF)
    shape = model_config.shape_numbers(conf)
    assert set(shape) == {"n_layers", "d_model", "n_q_heads", "n_kv_heads", "head_dim",
                          "d_ff", "vocab", "gated_mlp"}
    assert shape["d_ff"] == 8 * 1024 and shape["head_dim"] == 128 and shape["n_layers"] == 1
    # a token's matmul weights in one layer: q, k, v, o and its 8 experts' three matrices
    attn = 4 * 2048 * 2048
    experts = 8 * 3 * 2048 * 1024
    head = 2048 * 50304
    scores = 4 * 16 * 128 * (4096 + 1) / 2
    by_hand = 3 * (2 * (attn + experts + head) + scores)
    per_token = roofline.train_flops_per_token(seq=4096, **shape)
    assert per_token == pytest.approx(by_hand, rel=1e-12)
    assert per_token == pytest.approx(1.07e9, rel=0.005)         # ISSUE 27's 1.07 GFLOP a token
    assert 2 * 3 * head / by_hand == pytest.approx(0.58, abs=0.01)   # the head's share of it
    assert 2 * 3 * experts / by_hand == pytest.approx(0.28, abs=0.01)


def test_configuration_carries_every_published_size():
    conf = load(CONF)
    catalog = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
               "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
               "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
               "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
               "tie_word_embeddings": False, "vocab_size": 50304}
    differ = {k for k, v in catalog.items() if conf.get(k, "missing") != v}
    assert differ == {"num_hidden_layers"} == set(conf["reduced"])
    assert conf["num_hidden_layers"] == 1 and conf["published"] == {"num_hidden_layers": 16}
    mc = model_config.transformer_config(conf)
    assert (mc.n_experts, mc.top_k, mc.d_ff, mc.norm_topk_prob) == (64, 8, 1024, False)
    assert mc.qk_norm and mc.norm_eps == 1e-5 and mc.head_dim == 128 and not mc.remat
    # the sizing's parameter count
    per_layer = 64 * 3 * 2048 * 1024 + 4 * 2048 ** 2 + 2048 * 64 + 2 * 2048 + 2 * 2048
    assert per_layer == pytest.approx(419.6e6, rel=1e-3)
    assert 16 * (per_layer + 2 * 50304 * 2048 + 2048) == pytest.approx(10.0e9, rel=0.005)
    traffic = load(os.path.join(ROOT, "benchmark", "traffic", "lm-steps-host-batches-4k.json"))
    assert (traffic["batch"], traffic["seq"], traffic["zipf_a"], traffic["prefetch"],
            traffic["segment_steps"]) == (4, 4096, 1.1, 4, 10)


def test_grouped_matmul_cost_by_hand():
    cost = moe_cost.gmm_cost(rows=131072, k=2048, n=1024, groups=64)
    assert cost["ops"] == 2 * 131072 * 2048 * 1024 == 549755813888
    # rows in (2048 wide) and out (1024 wide) once, 64 matrices once, bfloat16
    assert cost["bytes"] == 2 * (131072 * 2048 + 131072 * 1024 + 64 * 2048 * 1024) == 1073741824
    least = roofline.roofline_seconds(cost, "TPU v5 lite")
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(2.7906e-3, rel=1e-4)
    # the down projection swaps k and n: the same operations and bytes
    assert moe_cost.gmm_cost(rows=131072, k=1024, n=2048, groups=64) == cost


def _ctx(trace):
    return {"trace": trace, "conf": load(CONF), "device": {"kind": "TPU v5 lite"},
            "traffic": {"batch": 4, "seq": 4096}}


def test_roofline_reader_on_a_synthetic_trace():
    least = 549755813888 / 197e12
    # one step of one layer: three projections x three products, 4.5 ms a call
    trace = {"busy_s": 0.2,
             "op_seconds": {"moe_gmm_fwd": 3 * 4.5e-3, "moe_gmm_dlhs": 3 * 4.5e-3,
                            "moe_gmm_drhs": 3 * 4.5e-3, "flash_fwd": 0.01},
             "op_counts": {"moe_gmm_fwd": 3, "moe_gmm_dlhs": 3, "moe_gmm_drhs": 3, "flash_fwd": 1}}
    assert moe_gmm_roofline.read(_ctx(trace)) == pytest.approx(100 * least / 4.5e-3, rel=1e-9)
    # a program whose experts run ragged_dot (or the parent, with no MoE): nothing to read
    assert moe_gmm_roofline.read(_ctx({"busy_s": 0.2, "op_seconds": {"fusion.1": 0.1},
                                       "op_counts": {"fusion.1": 4}})) is None
    assert moe_gmm_roofline.read(_ctx(None)) is None
    # the busy share is the accepted reader with the kernels' prefix
    from benchmark.readers import op_busy_share

    meta = load(os.path.join(ROOT, "benchmark", "metrics", "moe_gmm_busy_share.json"))
    assert meta["reader"] == "op_busy_share"
    assert op_busy_share.read({"trace": trace}, **meta["args"]) == pytest.approx(
        100 * 9 * 4.5e-3 / 0.2)


def test_report_attribute_reader_takes_the_last_report_in_the_window(monkeypatch):
    def report(end, **attrs):
        return {"name": "train.report", "start_mono": end - 0.01, "end_mono": end, "attrs": attrs}

    spans = [report(129.0, moe_load_max_over_mean=9.0),      # set-up
             report(132.0, moe_load_max_over_mean=1.5),
             report(139.0, moe_load_max_over_mean=1.25),     # the last inside [130, 140)
             report(141.0, moe_load_max_over_mean=7.0),
             {"name": "train.step", "start_mono": 139.1, "end_mono": 139.5,
              "attrs": {"moe_load_max_over_mean": 3.0}}]
    monkeypatch.setattr(report_span_attribute, "program_spans", lambda: spans)
    ctx = {"t0": 130.0, "t1": 140.0}
    assert report_span_attribute.read(ctx, attribute="moe_load_max_over_mean") == 1.25
    # a dense model's reports carry no such attribute; a program without the record has no spans
    assert report_span_attribute.read(ctx, attribute="router_aux_loss") is None
    monkeypatch.setattr(report_span_attribute, "program_spans", lambda: None)
    assert report_span_attribute.read(ctx, attribute="moe_load_max_over_mean") is None


@pytest.mark.parametrize("trace", [False, True], ids=["end-to-end-line", "traced-line"])
def test_tiny_olmoe_cell_rehearsal_ends_correct(benchmark_json, trace):
    """The real cell's entries with a tiny tree behind them: LMTrainer on a
    2-layer, 8-expert top-2 OLMoE, its first two steps against olmoe_ref's objective, clip and AdamW."""
    from benchmark import run

    bench = dict(benchmark_json, workloads=[
        {"name": CELL, "config": "tiny-olmoe-train", "traffic": "tiny-lm-steps", "chips": 1}])
    result = json.loads(json.dumps(
        run.run_cell(bench, CELL, 2**31 + 27, 2.0, trace, tree=TINY, require_tpu=False)))
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
        # counters and span readers answer on a CPU (the cell's own and the shared ones it
        # joined in PR 31); the trace readers and `mfu` find no chip and are left out
        assert set(result["metrics"]) == {
            "moe_expert_load_max_over_mean", "data_wait_share", "compiles_in_window_train",
            "setup_train_init_s", "setup_compile_s", "setup_programs_built", "setup_cost_analysis_s",
            "setup_untraced_share", "host_turnaround_ms", "step_dispatch_p50_ms"}
        assert result["metrics"]["compiles_in_window_train"]["value"] == 0
        assert 1.0 <= result["metrics"]["moe_expert_load_max_over_mean"]["value"] <= 8.0
    assert result["device"]["platform"] == "cpu"
