"""A CPU rehearsal of a tiny cell of the bailing_hybrid family through the
harness as `train-ling3flash-4k` runs it (tests/benchmark/test_ling3flash_cell.py
has the light tests): traced and plain, each ending `correct`, and with every
weight through float8_e4m3, not. ~50 s a run: a file of few tests (the rule at
the top of tests/conftest.py)."""

import json
import os

import pytest

from bench_helpers import RESULT_KEYS, float8_weights

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny_ling3flash")
CELL = "train-ling3flash-4k"


def _the_spans_carry_the_rule():
    """`train.init.step_fn` carries the delta rule's, the latent layer's and
    the groups' counters, and every `train.report` the worst layer's most
    negative cumulative log-decay inside a chunk."""
    from ray_tpu.util.tracing import tracer

    spans = tracer().spans()
    init = [s for s in spans if s["name"] == "train.init.step_fn"][-1]["attrs"]
    assert {"kda_heads", "kda_head_dim", "kda_chunk", "kda_subchunk", "kda_impl", "kda_conv_impl",
            "kda_gate_lower_bound", "attn_latent_v_dim", "moe_route_groups", "moe_route_groups_kept",
            "layer_kinds"} <= set(init)
    assert init["layer_kinds"] == "dK eL eK" and init["kda_impl"] == "xla_chunked"
    reports = [s["attrs"] for s in spans if s["name"] == "train.report"]
    assert reports and all(-160.0 <= r["kda_log_decay_chunk_min"] < 0 for r in reports[-3:])


def _tiny_bench(benchmark_json):
    return dict(benchmark_json, workloads=[
        {"name": CELL, "config": "tiny-ling3flash-train", "traffic": "tiny-lm-steps", "chips": 1}])


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "plain"])
def test_tiny_ling3flash_cell_rehearsal_ends_correct(benchmark_json, trace):
    """The real cell's entries with a tiny tree behind them: LMTrainer on the
    three layers `dK eL eK` (published layers 1-3 of a period of 3; 8 of 32
    experts in 4 groups held, top-4, 4 chunks of 32 a sequence), its first two steps against
    bailing_hybrid_ref's recurrence, clip and AdamW; the traced line and the
    plain one."""
    from benchmark import run

    result = json.loads(json.dumps(run.run_cell(
        _tiny_bench(benchmark_json), CELL, 2**31 + 55, 2.0, trace, tree=TINY, require_tpu=False)))
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    checks = result["info"]["checks"]
    assert checks["loss_step1_gap"]["value"] < 1e-5          # float32 against float32
    assert checks["first_gradient_worst_leaf_difference"]["value"] < 2e-4
    assert checks["change_worst_leaf_gap"]["value"] < 2e-4
    assert checks["loss_last"] < checks["loss_first"]
    if not trace:
        assert {"train_tokens_per_s", "setup_s"} <= set(result["metrics"])
        _the_spans_carry_the_rule()
        return
    # counters and span readers answer on a CPU; the trace readers and `mfu` find no chip
    assert {"moe_expert_load_max_over_mean", "moe_held_rows_off_even", "data_wait_share",
            "compiles_in_window_train", "setup_train_init_s", "setup_compile_s",
            "setup_programs_built", "host_turnaround_ms", "step_dispatch_p50_ms"} <= set(result["metrics"])
    assert not {"kda_mixer_busy_share", "kda_chunk_busy_share", "kda_chunk_fwd_roofline",
                "attn_latent_busy_share", "mfu"} & set(result["metrics"])
    assert result["metrics"]["compiles_in_window_train"]["value"] == 0
    assert result["device"]["platform"] == "cpu"


def test_tiny_ling3flash_cell_with_every_weight_through_float8_is_not_correct(benchmark_json):
    from benchmark import run

    with float8_weights():
        result = run.run_cell(_tiny_bench(benchmark_json), CELL, 2**31 + 55, 1.0, False,
                              tree=TINY, require_tpu=False)
    assert result["correct"] is False
    checks = result["info"]["checks"]
    assert checks["first_loss_repeat_gap"]["value"] == 0.0      # the step that was timed is the one compared
    assert (checks["first_gradient_worst_leaf_difference"]["value"]
            > checks["first_gradient_worst_leaf_difference"]["limit"])
