"""The command itself: it refuses to measure without a chip, or without
the program."""

import os
import subprocess
import sys

from bench_helpers import ROOT


def test_no_accelerator_no_result(benchmark_json, monkeypatch, capsys):
    import pytest

    from benchmark import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    cell = benchmark_json["workloads"][0]["name"]
    with pytest.raises(SystemExit) as exit_info:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert exit_info.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""      # no result line


def test_only_the_benchmark_files_no_result(tmp_path, benchmark_json):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`, the command exits non-zero and prints no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    for path in benchmark_json["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__", "_out"))
    cell = benchmark_json["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, *benchmark_json["command"][1:], "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_unknown_device_has_no_peaks():
    import pytest

    from benchmark import roofline

    assert roofline.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")
