"""A CPU rehearsal of a tiny cell of the lfm2_moe family through the harness as
`train-lfm2moe-8k` runs it (tests/benchmark/test_lfm2moe_cell.py has the light
tests): traced and plain, each ending `correct`, and with every weight through
float8_e4m3, not. ~40 s a run: a file of few tests (the rule at the top of
tests/conftest.py)."""

import json
import os

import pytest

from bench_helpers import RESULT_KEYS, float8_weights

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny_lfm2moe")
CELL = "train-lfm2moe-8k"


def _the_spans_carry_the_mixer():
    """`train.init.step_fn` carries the short convolution's counters, the
    rotary full layer's and the tied head's, and the layers' kinds with the
    code `C`; `remat_saved` is what the rule kept (nothing on a CPU)."""
    from ray_tpu.util.tracing import tracer

    spans = tracer().spans()
    init = [s for s in spans if s["name"] == "train.init.step_fn"][-1]["attrs"]
    assert {"sconv_channels", "sconv_taps", "sconv_impl", "sconv_rows", "attn_full_rope", "tie_embeddings",
            "layer_kinds", "remat_saved", "moe_experts_held"} <= set(init)
    assert init["layer_kinds"] == "dC eF eC eC" and init["sconv_impl"] == "xla" and init["sconv_rows"] == 0
    assert (init["sconv_channels"], init["sconv_taps"], init["attn_full_rope"], init["tie_embeddings"]) == (
        64, 3, True, True)
    assert tuple(init["remat_saved"]) == () and init["remat"] == "whole_block"
    reports = [s["attrs"] for s in spans if s["name"] == "train.report"]
    assert reports and all(r["moe_load_max_over_mean"] >= 1 for r in reports[-3:])


def _tiny_bench(benchmark_json):
    return dict(benchmark_json, workloads=[
        {"name": CELL, "config": "tiny-lfm2moe-train", "traffic": "tiny-lm-steps", "chips": 1}])


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "plain"])
def test_tiny_lfm2moe_cell_rehearsal_ends_correct(benchmark_json, trace):
    """The real cell's entries with a tiny tree behind them: LMTrainer on the
    four layers `dC eF eC eC` (published layers 1-4 of a list whose attention
    layers are at 2 and 5 of 8; 8 of 32 experts held, top-4; a tied head over
    256 ids), its first two steps against lfm2_moe_ref's, clip and AdamW; the
    traced line and the plain one."""
    from benchmark import run

    result = json.loads(json.dumps(run.run_cell(
        _tiny_bench(benchmark_json), CELL, 2**31 + 61, 2.0, trace, tree=TINY, require_tpu=False)))
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    checks = result["info"]["checks"]
    assert checks["loss_step1_gap"]["value"] < 1e-5          # float32 against float32
    assert checks["first_gradient_worst_leaf_difference"]["value"] < 2e-4
    assert checks["change_worst_leaf_gap"]["value"] < 2e-4
    assert checks["loss_last"] < checks["loss_first"]
    if not trace:
        assert {"train_tokens_per_s", "setup_s"} <= set(result["metrics"])
        _the_spans_carry_the_mixer()
        return
    # counters and span readers answer on a CPU; the trace readers and `mfu` find no chip
    assert {"moe_expert_load_max_over_mean", "moe_held_rows_off_even", "data_wait_share",
            "compiles_in_window_train", "setup_train_init_s", "setup_compile_s",
            "setup_programs_built", "host_turnaround_ms", "step_dispatch_p50_ms"} <= set(result["metrics"])
    assert not {"sconv_mixer_busy_share", "sconv_conv_busy_share", "sconv_conv_fwd_roofline",
                "flash_fwd_roofline", "mfu"} & set(result["metrics"])
    assert result["metrics"]["compiles_in_window_train"]["value"] == 0
    assert result["device"]["platform"] == "cpu"


def test_tiny_lfm2moe_cell_with_every_weight_through_float8_is_not_correct(benchmark_json):
    from benchmark import run

    with float8_weights():
        result = run.run_cell(_tiny_bench(benchmark_json), CELL, 2**31 + 61, 1.0, False,
                              tree=TINY, require_tpu=False)
    assert result["correct"] is False
    checks = result["info"]["checks"]
    assert checks["first_loss_repeat_gap"]["value"] == 0.0      # the step that was timed is the one compared
    assert (checks["first_gradient_worst_leaf_difference"]["value"]
            > checks["first_gradient_worst_leaf_difference"]["limit"])
