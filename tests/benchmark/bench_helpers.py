"""Shared pieces of the benchmark's CPU tests (imported by name; the
directory is no package, so that it cannot shadow the `benchmark`
package): the tiny tree and a BENCHMARK.json in which each tiny cell
reports what its real twin does."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")
# tiny cell -> the shipped (or spare) cell whose metrics it reports
TWINS = {
    "tiny-train": "train-gpt2s",
    "tiny-chat": "serve-chat-sessions",
    "tiny-docs": "serve-docs-batch",
    "tiny-queue": "serve-chat-sessions",
    "tiny-train-4dev": "train-gpt2s",
}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def with_spare(real):
    """BENCHMARK.json and, appended, the cells that benchmark/spare.json
    keeps (measured, not shipped): the harness is tested on both."""
    spare = load(os.path.join(ROOT, "benchmark", "spare.json"))
    return dict(real, **{group: real[group] + spare[group]
                         for group in ("configs", "workloads", "end_to_end", "per_layer")})


def tiny_benchmark(real, cell):
    """BENCHMARK.json with the tiny cells in place of the real ones; `cell`
    reports every metric its twin reports."""
    twin = TWINS[cell]
    real = with_spare(real)
    assert twin in {w["name"] for w in real["workloads"]}
    bench = dict(real, workloads=load(os.path.join(TINY, "BENCHMARK.tiny.json"))["workloads"])
    for group in ("end_to_end", "per_layer"):
        bench[group] = [
            dict(m, workloads=[cell]) if twin in m.get("workloads", [twin]) else m
            for m in real[group]
        ]
    return bench


def rehearse(real, cell, trace, seed=7):
    from benchmark import run

    result = run.run_cell(tiny_benchmark(real, cell), cell, seed, 2.0, trace,
                          tree=TINY, require_tpu=False)
    # the last line of the command is this object, serialised
    return json.loads(json.dumps(result))


def expected_metrics(real, cell, group):
    twin = TWINS[cell]
    real = with_spare(real)
    return {m["name"] for m in real[group] if twin in m.get("workloads", [twin])}
