"""chip_smoke.py's phases at tiny sizes on the CPU, and the script itself.

The phases are the same functions the chip runs; only the configs are cut
down here (the steering is in this file, the script has no option for
it). On the CPU the static rules resolve to the reference paths ("xla"
attention, the gather reference), which is what each call names as the
implementation it expects — a phase fails when another one ran.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from ray_tpu.models import get_config  # noqa: E402
from ray_tpu.models.transformer import TransformerConfig  # noqa: E402
from ray_tpu.ops.ragged_paged_attention import RAGGED_REFERENCE  # noqa: E402
from ray_tpu.parallel import MeshSpec  # noqa: E402


@pytest.fixture(scope="module")
def cache_counter():
    return chip_smoke.CompileCacheCounter()


@pytest.fixture(autouse=True)
def _leave_no_traffic_behind():
    """The phases drive real traffic: leave the process-wide metrics
    registry and the per-tenant TTFT windows as found (tests after this
    module read them)."""
    from ray_tpu.serve import tenancy
    from ray_tpu.util.metrics import registry

    yield
    registry().clear()
    tenancy.reset()


def _tiny_llama(**kw) -> TransformerConfig:
    # rope positions for the default 16-page slots; one layer compiles fast
    return get_config("llama-tiny").replace(n_layers=1, max_seq=1024, **kw)


def test_runtime_phase_without_a_chip():
    info = chip_smoke.phase_runtime(0)
    assert info["task_platform"] == "cpu"
    assert "TPU" not in info["cluster_resources"]


def test_runtime_phase_fails_when_the_chip_is_not_reported():
    with pytest.raises(chip_smoke.SmokeFailure, match="JAX holds 1 chip"):
        chip_smoke.phase_runtime(1)


def test_train_phase_tiny(cache_counter):
    info = chip_smoke.phase_train(
        get_config("gpt2-tiny").replace(n_layers=1), batch=8, seq=32, steps=3,
        expect_impl="xla", cache=cache_counter,
    )
    assert info["attention_impl"] == "xla"
    # printed next to it: how far the kernels' sub-tile walk engages (the
    # reference has no sub-tiles)
    assert (info["attn_subtiles_visited"], info["attn_subtiles_masked"],
            info["attn_subtiles_total"]) == (0, 0, 0)
    assert info["kernels_in_step_program"] == {}
    assert info["loss_last"] < info["loss_first"]
    assert info["steps"] == 4


def test_train_phase_fails_on_another_implementation(cache_counter):
    with pytest.raises(chip_smoke.SmokeFailure, match="resolved to 'xla'"):
        chip_smoke.phase_train(
            get_config("gpt2-tiny"), batch=8, seq=32, steps=1,
            expect_impl="pallas", cache=cache_counter,
        )


def test_serve_phase_tiny():
    info = chip_smoke.phase_serve(
        _tiny_llama(), max_slots=4, traffic=(8, 600, 40), probe=300,
        max_tokens=6, expect_impl=RAGGED_REFERENCE,
    )
    assert info["attention_impl"] == RAGGED_REFERENCE
    assert info["kernels_in_mixed_tick_program"] == {}
    assert info["tokens_equal_to_gather_reference"] is True
    assert len(info["probe_tokens"]) == 6
    assert info["engine"]["generated_tokens"] == 4 * 6
    assert info["engine"]["mixed_ticks_with_decode"] >= 1


def test_serve_depth_is_the_deepest_that_fits():
    from ray_tpu.serve.llm.paged import PagedConfig

    llama = get_config("llama3-8b").replace(param_dtype=jnp.bfloat16)
    depth, why = chip_smoke.serve_depth(llama, PagedConfig(), 16 << 30)
    assert depth % 4 == 0 and 4 <= depth < llama.n_layers
    assert why["need_at_depth_gib"] <= why["budget_gib"]
    assert why["need_at_full_depth_gib"] > why["hbm_gib"] * 0.8
    # four more layers would not fit the budget
    per_layer = why["weights_per_layer_gib"] + why["pool_per_layer_gib"]
    assert why["need_at_depth_gib"] + 4 * per_layer > why["budget_gib"]
    with pytest.raises(chip_smoke.SmokeFailure, match="not even 4 layers"):
        chip_smoke.serve_depth(llama, PagedConfig(), 2 << 30)


def test_store_phase_builds_and_uses_the_native_arena():
    info = chip_smoke.phase_store()
    assert info["default_tier"] == "python"
    assert info["native_arena"].startswith(("built from", "unavailable: g++"))


def test_sharded_train_phase_on_four_virtual_devices():
    info = chip_smoke.phase_train_sharded(
        _tiny_llama(dtype=jnp.float32),
        [MeshSpec(dp=2, fsdp=2, tp=2)],  # the harness has 8 devices
        batch=8, seq=32, expect_impl="xla",
    )
    (mesh,) = info["meshes"].values()
    assert abs(mesh["losses"][0] - info["one_device_reference_loss"]) < 1e-3
    assert mesh["param_split_ways"] == 4
    assert len(mesh["param_bytes_per_device"]) == 8
    assert max(mesh["param_bytes_per_device"].values()) < info["total_param_bytes"] / 3


def test_tensor_parallel_serve_phase_on_virtual_devices():
    config = _tiny_llama(n_heads=4, n_kv_heads=4, dtype=jnp.float32)
    info = chip_smoke.phase_serve_tp(
        config, tp=4, max_slots=2, probe=70, max_tokens=4,
        expect_impl=RAGGED_REFERENCE,
    )
    assert info["common_prefix_with_tensor_parallel_1"] == info["of_tokens"] == 4
    assert info["activation_dtype"] == "float32"
    assert info["init_params_devices"] == [0]


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_script_refuses_to_pass_without_a_chip(args):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert '"ok": true' not in proc.stdout
