"""CPU rehearsal of the serving cells at a tiny preset, 2-second window."""

import pytest

from bench_helpers import RESULT_KEYS, expected_metrics, rehearse


@pytest.fixture(autouse=True)
def _leave_no_traffic_behind():
    """The rehearsal drives real traffic: leave the process-wide metrics
    registry and the per-tenant windows as found."""
    from ray_tpu.serve import tenancy
    from ray_tpu.util.metrics import registry

    yield
    registry().clear()
    tenancy.reset()


@pytest.mark.parametrize("cell", ["tiny-chat", "tiny-docs", "tiny-queue"])
def test_end_to_end_line(benchmark_json, cell):
    result = rehearse(benchmark_json, cell, trace=False)
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == expected_metrics(benchmark_json, cell, "end_to_end")
    for metric in result["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    checks = result["info"]["checks"]
    assert checks["logits_rel_rms_vs_reference"] < 1e-4    # float32 on the CPU
    assert checks["probe_tokens_equal"] >= checks["probe_tokens_judged"]


def test_traced_line_reports_counters_and_spans(benchmark_json):
    result = rehearse(benchmark_json, "tiny-chat", trace=True, seed=2**31 + 5)
    assert RESULT_KEYS <= set(result) and result["correct"] is True
    names = set(result["metrics"])
    assert {"route_wait_p50_ms", "queue_wait_p90_ms", "ttft_p50_ms", "generator_late_ms",
            "decode_lane_occupancy", "kv_pool_peak_occupancy", "page_stalls_per_request",
            "compiles_in_window"} <= names
    assert not names & {"ragged_attn_roofline", "decode_step_device_ms"}   # no chip in the trace
    assert names <= expected_metrics(benchmark_json, "tiny-chat", "per_layer")
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < result["metrics"]["decode_lane_occupancy"]["value"] <= 100


def test_a_renamed_engine_helper_fails_in_set_up():
    """`_warm_bucket_helpers` reaches two private names of the engine: if
    the program renames them, set-up fails instead of a window compiling."""
    from benchmark.kinds.closed_loop import ServeSystem

    system = object.__new__(ServeSystem)
    system.engine = lambda: object()
    with pytest.raises(AttributeError, match="_scatter_tokens"):
        system._warm_bucket_helpers()
