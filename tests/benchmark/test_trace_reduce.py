"""trace_reduce.py against a small recorded trace: 16 ms of one chip around
the boundary of two gpt2-small train steps (TPU v5e, PR 24's explore run):
205 operations, three flash_fwd calls, one 2.67 ms gap in which the host
was reading the step's metrics back."""

import os

import pytest

from benchmark import trace_reduce as tr

from bench_helpers import load

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "recorded_trace.json")


@pytest.fixture(scope="module")
def planes():
    return load(TRACE)


@pytest.fixture(scope="module")
def reduced(planes):
    return tr.reduce_trace(planes)


def brute_force_busy_ns(planes):
    """Independent of union(): sweep over sorted boundaries."""
    (ops,) = [l["events"] for p in planes if p["name"] == "/device:TPU:0"
              for l in p["lines"] if l["name"] == "XLA Ops"]
    edges = sorted([(e[1], 1) for e in ops] + [(e[1] + e[2], -1) for e in ops])
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_busy_union_and_idle_share(planes, reduced):
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(brute_force_busy_ns(planes) / 1e9, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.013453956, rel=1e-6)
    assert reduced["span_s"] == pytest.approx(0.016124141, rel=1e-6)
    idle_share = 1 - reduced["busy_s"] / reduced["span_s"]
    assert idle_share == pytest.approx(0.16560, abs=1e-4)


def test_time_by_kernel_name(reduced):
    assert tr.count_of(reduced, ["flash_fwd"]) == 3
    assert tr.seconds_of(reduced, ["flash_fwd"]) == pytest.approx(0.003341983, rel=1e-6)
    assert tr.seconds_of(reduced, ["flash_bwd_dkv", "flash_bwd_dq"]) == 0
    top_name, top_seconds = reduced["breakdown"]["device_ops"][0]
    assert top_name == "flash_fwd custom-call bf16[24,12,1024,64]"
    assert top_seconds == pytest.approx(0.003341983, rel=1e-6)
    assert len(reduced["breakdown"]["device_ops"]) <= 10


def test_the_long_gap_is_named_by_what_the_host_did(reduced):
    name, seconds = reduced["breakdown"]["idle_gaps"][0]
    assert name == "np.asarray(jax.Array)"
    assert seconds == pytest.approx(0.002666215, rel=1e-6)
    assert len(reduced["breakdown"]["idle_gaps"]) <= 10


def test_programs_and_collectives(reduced):
    assert list(reduced["program_seconds"]) == ["jit_step_under_mesh"]
    assert len(reduced["program_seconds"]["jit_step_under_mesh"]) == 2
    assert reduced["collective_s"] == 0 and reduced["collective_exposed_s"] == 0


def test_parse_op_and_collectives():
    text = ("%flash_bwd_dkv.34 = (bf16[24,12,1024,64]{3,2,1,0:T(8,128)(2,1)}, "
            "bf16[24,12,1024,64]{3,2,1,0:T(8,128)(2,1)}) custom-call(bf16[24,12,1024,64]{3,2,1,0} %x)")
    assert tr.parse_op(text) == {"name": "flash_bwd_dkv", "opcode": "custom-call",
                                 "shape": "bf16[24,12,1024,64]"}
    assert tr.op_key("%fusion.575 = (f32[768]{0:T(1024)S(1)}, f32[24,1024]{1,0}) fusion(f32[1]{0} %a)") \
        == "fusion.575 fusion f32[768]"
    assert tr.is_collective("%all-reduce.3 = f32[4096]{0} all-reduce(f32[4096]{0} %g), replica_groups={}")
    assert tr.is_collective("%all-gather-start.1 = (bf16[8]{0}, bf16[16]{0}) all-gather-start(bf16[8]{0} %p)")
    assert not tr.is_collective("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)")


def test_exposed_collective_time_is_what_compute_does_not_cover():
    ops = [
        ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0.0, 100.0],
        ["%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p)", 100.0, 50.0],     # exposed
        ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 150.0, 100.0],
    ]
    hidden = [["%all-gather-start.1 = (f32[8]{0}, f32[16]{0}) all-gather-start(f32[8]{0} %p)", 160.0, 80.0]]
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}, {"name": "Async XLA Ops", "events": hidden}]}]
    reduced = tr.reduce_trace(planes)
    assert reduced["collective_s"] == pytest.approx(130e-9)
    assert reduced["collective_exposed_s"] == pytest.approx(50e-9)
    assert reduced["busy_s"] == pytest.approx(250e-9)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]


def test_a_trace_without_a_chip_reduces_to_nothing():
    assert tr.reduce_trace([{"name": "/host:CPU", "lines": []}])["devices"] == 0
