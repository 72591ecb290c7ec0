"""CPU rehearsal of the training cells at a tiny preset, 2-second window:
the command's last line has the contract's keys and the cell's metrics."""

import pytest

from bench_helpers import RESULT_KEYS, expected_metrics, rehearse


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-train-4dev"])
def test_end_to_end_line(benchmark_json, cell):
    result = rehearse(benchmark_json, cell, trace=False)
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == expected_metrics(benchmark_json, cell, "end_to_end")
    for metric in result["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    assert result["device"]["platform"] == "cpu"   # a rehearsal, never a device number


def test_traced_line_reports_what_a_cpu_can_count(benchmark_json):
    result = rehearse(benchmark_json, "tiny-train", trace=True)
    assert RESULT_KEYS <= set(result) and result["correct"] is True
    names = set(result["metrics"])
    # counters and host-clock readers answer; trace readers find no chip
    # in the trace and return nothing, so the harness leaves them out
    assert {"data_wait_share", "compiles_in_window_train"} <= names
    assert not names & {"flash_attn_busy_share", "flash_fwd_roofline"}
    assert names <= expected_metrics(benchmark_json, "tiny-train", "per_layer")
    assert result["metrics"]["compiles_in_window_train"]["value"] == 0
    assert "busy_s" not in result["device"]


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-train-4dev"])
def test_first_steps_equal_the_reference(benchmark_json, cell):
    """Float32 against float32: the step's first two losses, its first
    gradient a leaf and the parameters' change a leaf against the plain
    reference's gradient, clip and AdamW (also on the dp=2 x fsdp=2 x tp=2
    mesh, where the reference's arrays lie as the program's do)."""
    from benchmark import run

    result = rehearse(benchmark_json, cell, trace=False, seed=2**31 + 11)
    checks = result["info"]["checks"]
    assert result["correct"] is True
    assert checks["first_loss_repeat_gap"]["value"] == 0
    assert checks["loss_step1_gap"]["value"] < 1e-5 and checks["loss_step2_gap"]["value"] < 1e-5
    assert checks["first_gradient_worst_leaf_difference"]["value"] < 1e-4
    assert checks["first_gradient_worst_leaf_norm_gap"] < 1e-4
    assert checks["change_worst_leaf_gap"]["value"] < 1e-4
    assert all(0 < checks[name]["limit"] <= 1e-3 for name in (
        "loss_step1_gap", "first_gradient_worst_leaf_difference", "change_worst_leaf_gap"))
    assert checks["loss_last"] < checks["loss_first"]
    assert run.CompileCounter.EVENT.endswith("backend_compile_duration")
