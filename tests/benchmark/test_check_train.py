"""`check.train_correct` shown to fail: its control (the program's step with
its weights through float8_e4m3, the nearest precision below the one the
cells compute in) and three faults of the timed path, each through a whole
tiny run of the harness (`run.run_cell` without its look for a chip) that has
to end with `correct` false at the tiny trees' committed limits. The same
control and the same faults run on the chip at the cells' own sizes from the
builder's scratch script (PERF.md section 3, PR 31)."""

import json
import os

import pytest

import bench_helpers as bh
from benchmark import run

OLMOE_TREE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny_olmoe")
# the tiny trees compute in float32, where program and reference agree to 1e-5: their
# configurations' `probe` groups carry that precision's limits, as the cells' carry bfloat16's
GRADIENT = "first_gradient_worst_leaf_difference"


def _run(benchmark_json, cell, seed=2**31 + 31):
    if cell == "tiny-olmoe":
        bench = dict(benchmark_json, workloads=[{
            "name": "train-olmoe-64e-4k", "config": "tiny-olmoe-train",
            "traffic": "tiny-lm-steps", "chips": 1}])
        result = run.run_cell(bench, "train-olmoe-64e-4k", seed, 1.0, False,
                              tree=OLMOE_TREE, require_tpu=False)
    else:
        result = run.run_cell(bh.tiny_benchmark(benchmark_json, cell), cell, seed, 1.0, False,
                              tree=bh.TINY, require_tpu=False)
    return json.loads(json.dumps(result))


@pytest.mark.parametrize("cell, names", [
    ("tiny-train", None), ("tiny-train", bh.MLP_WEIGHTS),
    ("tiny-olmoe", None), ("tiny-olmoe", bh.MLP_WEIGHTS),
], ids=["gpt2-every-weight", "gpt2-mlp", "olmoe-every-weight", "olmoe-experts"])
def test_a_step_with_float8_weights_is_not_correct_by_its_first_gradient(
        benchmark_json, capfd, cell, names):
    sound = _run(benchmark_json, cell)
    assert sound["correct"] is True
    limit = sound["info"]["checks"][GRADIENT]["limit"]
    assert sound["info"]["checks"][GRADIENT]["value"] < limit / 10
    assert sound["info"]["checks"]["first_loss_repeat_gap"]["value"] == 0
    with bh.float8_weights(names):
        low = _run(benchmark_json, cell)
    checks = low["info"]["checks"]
    assert low["correct"] is False
    assert checks[GRADIENT]["value"] > 3 * limit
    # the control is the same step twice over, so the measured trainer's first loss is repeated
    assert checks["first_loss_repeat_gap"]["value"] == 0
    assert checks["loss_last"] < checks["loss_first"]
    err = capfd.readouterr().err
    assert f"program against reference: {GRADIENT}" in err
    # each number compared is printed beside its limit, last on standard error
    assert "compared (each number beside its limit)" in err.strip().splitlines()[-1]


@pytest.mark.parametrize("fault, caught_by", [
    ("state_unchanged", "change_worst_leaf_gap"),
    ("half_the_batch_left_out", GRADIENT),
    ("loss_altered", "loss_step1_gap"),
])
def test_a_broken_timed_path_is_not_correct(benchmark_json, capfd, fault, caught_by):
    with bh.broken_step(fault):
        result = _run(benchmark_json, "tiny-train")
    assert result["correct"] is False
    checks = result["info"]["checks"]
    assert checks[caught_by]["value"] > checks[caught_by]["limit"]
    assert f"program against reference: {caught_by}" in capfd.readouterr().err
