"""Fixtures of the benchmark's CPU tests (helpers are in bench_helpers.py)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import bench_helpers  # noqa: E402


@pytest.fixture(scope="session")
def benchmark_json():
    return bench_helpers.load(os.path.join(bench_helpers.ROOT, "BENCHMARK.json"))
