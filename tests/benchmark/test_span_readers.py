"""The readers of the program's span record, on hand-made spans with
known answers, and a CPU rehearsal of the tiny training cell that lists
the seven metrics they serve."""

import pytest

from bench_helpers import load, rehearse, tiny_benchmark
from benchmark.readers import (program_span_turnaround, program_span_untraced_share,
                               program_spans)

SPAN_METRICS = {
    "setup_train_init_s", "setup_compile_s", "setup_programs_built",
    "setup_cost_analysis_s", "setup_untraced_share", "host_turnaround_ms",
    "step_dispatch_p50_ms",
}
COMPILE = ["compile.trace", "compile.lower", "compile.backend", "compile.cache_load"]


def span(name, start, end):
    return {"name": name, "start_mono": start, "end_mono": end}


# process start at 100, window [130, 140): 30 s of set-up
CTX = {"t0": 130.0, "t1": 140.0, "setup_s": 30.0}
SPANS = [
    span("train.init", 105.0, 115.0),
    span("compile.trace", 106.0, 108.0),
    span("compile.trace", 106.5, 107.5),        # nested in the one above
    span("compile.backend", 108.0, 110.0),
    span("compile.cache_load", 116.0, 116.5),
    span("train.loop", 116.0, 128.0),
    span("train.loop", 130.0, 132.0015),
    span("train.loop", 132.0025, 134.0005),     # the caller took 1 ms between its calls
    span("train.loop", 134.0008, 139.95),
    span("train.report.cost", 120.0, 124.0),
    span("compile.backend", 121.0, 123.5),
    # the window: three segments' boundaries
    span("train.step.dispatch", 130.100, 130.102),
    span("train.report.read", 131.0, 132.000),
    span("train.report.cost", 132.0, 132.001),
    span("train.step.dispatch", 132.002, 132.004),
    span("train.step.dispatch", 132.010, 132.016),
    span("train.report.read", 133.0, 134.000),
    span("train.step.dispatch", 134.001, 134.006),
    span("train.report.read", 139.0, 139.900),
    span("compile.backend", 139.5, 139.6),      # inside the window: not set-up
    span("train.step.dispatch", 140.001, 140.003),      # after the window
]


@pytest.fixture
def spans(monkeypatch):
    def give(found):
        monkeypatch.setattr(program_spans, "program_spans", lambda: found)
        monkeypatch.setattr(program_span_turnaround, "program_spans", lambda: found)
        monkeypatch.setattr(program_span_untraced_share, "program_spans", lambda: found)
    give(SPANS)
    return give


@pytest.mark.parametrize("args, expected", [
    (dict(names=["train.init"], phase="setup", stat="sum"), 10.0),
    (dict(names=COMPILE, phase="setup", stat="sum"), 8.0),
    # the nested trace counts once: 2 + 2 + 0.5 + 2.5
    (dict(names=COMPILE, phase="setup", stat="covered"), 7.0),
    (dict(names=["compile.backend"], phase="setup", stat="count"), 2),
    (dict(names=["compile.backend"], phase="window", stat="count"), 1),
    (dict(names=["train.report.cost"], phase="setup", stat="sum"), 4.0),
    (dict(names=["train.step.dispatch"], phase="window", stat="p50", unit="ms"), 5.0),
    (dict(names=["no.such.span"], phase="setup", stat="count"), 0),
    (dict(names=["no.such.span"], phase="window", stat="p50"), None),
])
def test_named_spans_in_set_up_and_in_the_window(spans, args, expected):
    value = program_spans.read(dict(CTX), **args)
    assert value == (expected if expected is None else pytest.approx(expected))


def test_turnaround_is_report_read_to_the_next_dispatch(spans):
    # 132.000 -> 132.004 and 134.000 -> 134.006; the read at 139.9 has its
    # next dispatch after the window and is not a pair
    value = program_span_turnaround.read(
        dict(CTX), start="train.report.read", end="train.step.dispatch")
    assert value in (pytest.approx(4.0), pytest.approx(6.0))
    # only what the program's own `train.loop` spans cover: 4 ms less the
    # 1 ms between the two calls (where a traced run starts the profiler)
    assert program_span_turnaround.read(
        dict(CTX), start="train.report.read", end="train.step.dispatch",
        within="train.loop") in (pytest.approx(3.0), pytest.approx(5.7))
    only = [s for s in SPANS if s["end_mono"] < 133.0]
    spans(only)
    assert program_span_turnaround.read(
        dict(CTX), start="train.report.read", end="train.step.dispatch") == pytest.approx(4.0)
    spans([s for s in SPANS if s["name"] != "train.step.dispatch"])
    assert program_span_turnaround.read(
        dict(CTX), start="train.report.read", end="train.step.dispatch") is None


def test_untraced_share_is_set_up_less_the_union(spans):
    # covered before the window: [105, 115] and [116, 128] = 22 of 30 s
    assert program_span_untraced_share.read(dict(CTX)) == pytest.approx(100 * 8 / 30)
    # a span that began before the process's clock start is cut to it
    spans(SPANS + [span("early", 90.0, 103.0)])
    assert program_span_untraced_share.read(dict(CTX)) == pytest.approx(100 * 5 / 30)


@pytest.mark.parametrize("found", [None, []])
def test_a_program_without_the_record_leaves_the_metric_out(spans, monkeypatch, found):
    spans(found or None)
    assert program_spans.read(dict(CTX), names=["train.init"], phase="setup", stat="sum") is None
    assert program_spans.read(dict(CTX), names=COMPILE, phase="setup", stat="count") is None
    assert program_span_turnaround.read(dict(CTX), start="a", end="b") is None
    assert program_span_untraced_share.read(dict(CTX)) is None


def test_spans_of_the_parents_record_have_no_mono_stamp(monkeypatch):
    """The parent's tracer hands out wall stamps alone: nothing to read."""
    from ray_tpu.util import tracing

    class Old:
        def spans(self, limit=0):
            return [{"name": "train.init", "start_ts": 1.0, "end_ts": 2.0}]

    monkeypatch.setattr(tracing, "tracer", lambda: Old())
    assert program_spans.program_spans() is None
    assert program_spans.read(dict(CTX), names=["train.init"], phase="setup", stat="sum") is None


def test_metric_files_name_spans_the_program_records():
    """Each metric's arguments use the span names the trainer and the
    compile listener write, letter for letter."""
    import inspect

    from ray_tpu.train import trainer
    from ray_tpu.util import tracing

    written = inspect.getsource(trainer) + inspect.getsource(tracing)
    for name in SPAN_METRICS:
        args = load(f"{bench_root()}/metrics/{name}.json").get("args", {})
        for span_name in args.get("names", []) + [args.get("start"), args.get("end")]:
            if span_name is None:
                continue
            kind = span_name.split(".", 1)[1] if span_name.startswith("compile.") else None
            assert (f'"{span_name}"' in written
                    or (kind and f'"{kind}"' in written)), (name, span_name)


def bench_root():
    import os

    from bench_helpers import ROOT

    return os.path.join(ROOT, "benchmark")


def test_rehearsal_reports_the_seven_span_metrics(benchmark_json):
    bench = tiny_benchmark(benchmark_json, "tiny-train")
    listed = {m["name"] for m in bench["per_layer"] if "tiny-train" in m.get("workloads", [])}
    assert SPAN_METRICS <= listed
    result = rehearse(benchmark_json, "tiny-train", trace=True)
    assert result["correct"] is True
    metrics = result["metrics"]
    assert SPAN_METRICS <= set(metrics)
    assert metrics["setup_train_init_s"]["value"] > 0
    assert metrics["setup_compile_s"]["value"] > 0
    assert metrics["setup_programs_built"]["value"] >= 1      # no persistent cache in the tests
    assert metrics["setup_cost_analysis_s"]["value"] >= 0
    assert 0 < metrics["setup_untraced_share"]["value"] < 100
    assert metrics["host_turnaround_ms"]["value"] > 0
    assert metrics["step_dispatch_p50_ms"]["value"] > 0
    assert metrics["host_turnaround_ms"]["unit"] == "ms"
    # the rule of the window holds for the program's own count too
    assert metrics["compiles_in_window_train"]["value"] == 0
