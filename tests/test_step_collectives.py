"""What the compiled step says of its collectives (util/profiling): the
registry read off recorded module text in every spelling of the device
groups, the mesh axes a group runs along, an asynchronous collective's
operations counted as one, the join of a profile's operation seconds to the
registry and the operation table, and the attributes the span
`train.report.ops` carries. Nothing here times anything: a CPU run gives
names, counts and bytes."""

import jax
import numpy as np
import pytest

from ray_tpu.models.configs import llama_tiny
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import LMTrainer
from ray_tpu.util import profiling, tracing

STEP = "jit_step_under_mesh"
MESH_2X2 = (("dp", 1), ("fsdp", 2), ("tp", 2))
MESH_4X1 = (("fsdp", 4), ("tp", 1))

# the spellings of the TPU compiler's four-chip steps (train-mistral7b-fsdp2tp2 and
# train-evabyte-fsdp4-32k compiled for a described v5e:2x2), cut to a line each and
# printed with result shapes: a listed and two iota forms of `replica_groups`, a tuple
# result, a permute's pairs as a start / done, the compiler's own asynchronous form
# (fusions named async-collective-start / -done around a matmul fusion that carries the
# all-gather), an `async-start` wrapper, a fusion that holds a synchronous collective, a
# reducer's and a fusion's insides, and a group that is no cut of the mesh
HLO = """HloModule jit_step_under_mesh, is_scheduled=true

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%fused_computation.338 (p: bf16[1,2048,4,128]) -> bf16[1,4096,4,128] {
  %p = bf16[1,2048,4,128] parameter(0)
  %all-gather.113 = bf16[1,4096,4,128] all-gather(%p), channel_id=109, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={1}, use_global_device_ids=true, metadata={op_name="jit(step_under_mesh)/steplog.fwd_bwd_compute/jvp()/while/body/closed_call/attn.full/attn.proj/bse,ehd->bhsd/dot_general"}
  ROOT %custom-call.13 = bf16[1,4096,4,128] custom-call(%all-gather.113), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.381 (p: bf16[1,2048,4,128], q: bf16[12,1024,4096]) -> (f32[12,4,1024,128], bf16[1,4096,4,128]) {
  %p = bf16[1,2048,4,128] parameter(0)
  %q = bf16[12,1024,4096] parameter(1)
  %convolution.130 = f32[12,4,1024,128] convolution(%q, %p), metadata={op_name="jit(step_under_mesh)/steplog.fwd_bwd_compute/jvp()/while/body/closed_call/attn.full/attn.proj/bse,ehd->bhsd/dot_general"}
  %all-gather.115 = bf16[1,4096,4,128] all-gather(%p), channel_id=109, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={1}, use_global_device_ids=true
  ROOT %tuple.177 = (f32[12,4,1024,128], bf16[1,4096,4,128]) tuple(%convolution.130, %all-gather.115)
}

%fused_computation.340 (p: bf16[1,2048,4,128]) -> bf16[1,4096,4,128] {
  %p = bf16[1,2048,4,128] parameter(0)
  %all-gather.117 = bf16[1,4096,4,128] all-gather(%p), channel_id=109, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={1}, use_global_device_ids=true
  ROOT %custom-call.15 = bf16[1,4096,4,128] custom-call(%p, %all-gather.117), custom_call_target="AsyncCollectiveDone"
}

%fused_computation.7 (p: f32[128,256]) -> f32[64,256] {
  %p = f32[128,256] parameter(0)
  ROOT %reduce-scatter.3 = f32[64,256] reduce-scatter(%p), channel_id=7, replica_groups={{0,2},{1,3}}, dimensions={0}, to_apply=%add.1, metadata={op_name="jit(step_under_mesh)/steplog.fwd_bwd_compute/transpose(jvp())/mlp/bsf,fe->bse/dot_general"}
}

%async_computation.2 (p: bf16[8,64]) -> bf16[8,256] {
  %p = bf16[8,64] parameter(0)
  ROOT %all-gather.9 = bf16[8,256] all-gather(%p), channel_id=31, replica_groups={}, dimensions={1}
}

%body.1 (t: (s32[], bf16[12,1024,4096])) -> (s32[], bf16[12,1024,4096]) {
  %t = (s32[], bf16[12,1024,4096]) parameter(0)
  %x = bf16[12,1024,4096] get-tuple-element(%t), index=1
  %all-reduce.94 = bf16[12,1024,4096] all-reduce(%x), channel_id=14, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add.1, metadata={op_name="jit(step_under_mesh)/steplog.fwd_bwd_compute/jvp()/while/body/closed_call/mlp/bsf,fe->bse/dot_general"}
  %async-collective-start = (bf16[1,2048,4,128], bf16[1,4096,4,128], u32[]) fusion(%x), kind=kCustom, calls=%fused_computation.338
  %fusion.381 = (f32[12,4,1024,128], bf16[1,4096,4,128]) fusion(%async-collective-start, %x), kind=kOutput, calls=%async_collective_fusion.381
  %async-collective-done = bf16[1,4096,4,128] fusion(%fusion.381), kind=kCustom, calls=%fused_computation.340, metadata={op_name="jit(step_under_mesh)/steplog.fwd_bwd_compute/jvp()/while/body/closed_call/attn.full/attn.proj/bse,ehd->bhsd/dot_general"}
  %all-reduce.103 = (bf16[12,1024,4096], bf16[12,1024]) all-reduce(%x, %x), channel_id=116, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add.1, metadata={op_name="jit(step_under_mesh)/steplog.fwd_bwd_compute/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn.full/attn.proj/bse,ehd->bhsd/dot_general"}
  ROOT %tuple.1 = (s32[], bf16[12,1024,4096]) tuple(%t, %all-reduce.94)
}

ENTRY %main.1 (x: bf16[12,1024,4096]) -> bf16[12,1024,4096] {
  %x = bf16[12,1024,4096] parameter(0)
  %while.1 = (s32[], bf16[12,1024,4096]) while(%x), condition=%cond.1, body=%body.1
  %collective-permute-start.2 = (s32[12,1024,1], s32[12,1024,1], u32[], u32[]) collective-permute-start(%x), channel_id=59, source_target_pairs={{0,0},{1,2},{2,1},{3,3}}, metadata={op_name="jit(step_under_mesh)/steplog.fwd_bwd_compute/jvp(embed)/gather"}
  %collective-permute-done.2 = s32[12,1024,1] collective-permute-done(%collective-permute-start.2), metadata={op_name="jit(step_under_mesh)/steplog.fwd_bwd_compute/jvp(embed)/gather"}
  %collective-permute-start.1 = (bf16[32,7168], bf16[32,7168], u32[], u32[]) collective-permute-start(%x), channel_id=121, source_target_pairs={{0,2},{1,3}}
  %collective-permute-done.1 = bf16[32,7168] collective-permute-done(%collective-permute-start.1)
  %all-reduce.78 = (f32[], f32[], f32[]) all-reduce(%x, %x, %x), channel_id=72, replica_groups=[1,4]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add.1, metadata={op_name="jit(step_under_mesh)/steplog.optimizer_update/reduce_sum"}
  %fusion.7 = f32[64,256] fusion(%x), kind=kCustom, calls=%fused_computation.7
  %all-gather-start.4 = ((bf16[8,64]), bf16[8,256], u32[]) async-start(%x), calls=%async_computation.2
  %all-gather-done.4 = bf16[8,256] async-done(%all-gather-start.4), calls=%async_computation.2
  %all-to-all.5 = bf16[4,8] all-to-all(%x), channel_id=66, replica_groups={{0,1,2},{3}}, dimensions={0}
  ROOT %copy.8 = bf16[12,1024,4096] copy(%x)
}
"""
ACT = 12 * 1024 * 4096 * 2      # a bfloat16 activation of Mistral's step on one chip: 100,663,296 B


# ------------------------------------------------------------ groups and axes


@pytest.mark.parametrize("spelled,groups", [
    ("replica_groups={{0,1},{2,3}}, use_global_device_ids=true", ((0, 1), (2, 3))),
    ("replica_groups=[2,2]<=[4], to_apply=%add", ((0, 1), (2, 3))),
    ("channel_id=3, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={1}", ((0, 2), (1, 3))),
    ("replica_groups=[1,4]<=[2,2]T(1,0)", ((0, 2, 1, 3),)),
    ("replica_groups=[4,2]<=[2,2,2]T(2,0,1)", ((0, 2), (4, 6), (1, 3), (5, 7))),
    ("replica_groups={}, dimensions={0}", ((0, 1, 2, 3),)),
    ("source_target_pairs={{0,1},{1,2},{2,3},{3,0}}", ((0, 1), (1, 2), (2, 3), (3, 0))),
    ("dimensions={0}", ()),
], ids=["listed", "iota", "iota-transposed", "iota-one-group", "iota-three-axes", "empty-is-all", "pairs", "none"])
def test_device_groups_in_every_spelling(spelled, groups):
    assert profiling.collective_groups(spelled, devices=4) == groups
    if "<=" in spelled:     # the iota form is numpy's reshape, transpose, reshape
        dims, reshape, perm = (profiling._numbers(part) for part in profiling._GROUP_IOTA.search(spelled).groups())
        ids = np.arange(int(np.prod(reshape))).reshape(reshape).transpose(perm or range(len(reshape)))
        assert groups == tuple(map(tuple, ids.reshape(dims).tolist()))


@pytest.mark.parametrize("groups,mesh,pairs,axes", [
    (((0, 1), (2, 3)), MESH_2X2, False, ("tp",)),
    (((0, 2), (1, 3)), MESH_2X2, False, ("fsdp",)),
    (((0, 2, 1, 3),), MESH_2X2, False, ("fsdp", "tp")),
    (((0, 1, 2, 3),), MESH_4X1, False, ("fsdp",)),
    (((0, 1), (2, 3)), MESH_4X1, False, ()),            # half a ring: no cut of a 4 x 1 mesh
    (((0, 3), (1, 2)), MESH_2X2, False, ()),            # the diagonals of the 2 x 2
    (((0, 1, 2), (3,)), MESH_2X2, False, ()),
    (((0, 1), (2, 3), (4, 5), (6, 7)), MESH_2X2, False, ()),   # devices the mesh has not
    (((0,), (1,), (2,), (3,)), MESH_2X2, False, ()),    # every device alone: along no axis
    ((), MESH_2X2, False, ()),
    (((0, 1), (2, 3)), (), False, ()),                  # no mesh given
    (((0, 2), (1, 3)), MESH_2X2, True, ("fsdp",)),
    (((0, 1), (1, 2), (2, 3), (3, 0)), MESH_4X1, True, ("fsdp",)),
    (((0, 0), (1, 2), (2, 1), (3, 3)), MESH_2X2, True, ("fsdp", "tp")),
    (((0, 0), (1, 1)), MESH_2X2, True, ()),
], ids=["2x2-tp", "2x2-fsdp", "2x2-all-four", "4x1-all-four", "4x1-half", "2x2-diagonals", "uneven",
        "foreign-devices", "singletons", "no-groups", "no-mesh", "pairs-fsdp", "pairs-ring", "pairs-both",
        "pairs-to-self"])
def test_the_axes_device_groups_run_along(groups, mesh, pairs, axes):
    assert profiling.group_axes(groups, mesh, pairs) == axes


def test_array_bytes_of_printed_shapes():
    assert profiling._array_bytes("bf16[12,1024,4096]") == [ACT]
    assert profiling._array_bytes("(f32[4096], f32[], pred[8], s4[16], token[])") == [16384, 4, 8, 8]
    assert profiling._array_bytes("(bf16[32,7168], bf16[32,7168], u32[], u32[])") == [458752, 458752, 4, 4]
    assert profiling._array_bytes("f8e4m3fn[2,3]{1,0:T(8,128)}") == [6]


# --------------------------------------------------------------- the registry


@pytest.fixture(scope="module")
def recorded():
    return profiling.program_ops_table(HLO)[1], profiling.program_collectives_table(HLO, MESH_2X2)


def test_registry_of_a_recorded_module(recorded):
    """Keyed as the table keys its operations: the wrapper's or the fusion's
    name where one holds the collective, never a fusion's or a reducer's
    inside; every spelling read, the unplaced group kept."""
    table, found = recorded
    assert set(found) == {
        "all-reduce.94", "async-collective-start", "fusion.381", "async-collective-done", "all-reduce.103",
        "collective-permute-start.2", "collective-permute-done.2", "collective-permute-start.1",
        "collective-permute-done.1", "all-reduce.78", "fusion.7", "all-gather-start.4", "all-gather-done.4",
        "all-to-all.5"}
    assert set(found) <= set(table)
    c = profiling.Collective
    assert found["all-reduce.94"] == c("all-reduce", "", "", ((0, 1), (2, 3)), ("tp",), ACT)
    # a tuple's arrays add up; the listed spelling
    assert found["all-reduce.103"] == c("all-reduce", "", "", ((0, 1), (2, 3)), ("tp",), ACT + 12 * 1024 * 2)
    assert found["all-reduce.78"] == c("all-reduce", "", "", ((0, 2, 1, 3),), ("fsdp", "tp"), 12)
    # a reduce-scatter's full array is its operand: the result times the group
    assert found["fusion.7"] == c("reduce-scatter", "", "", ((0, 2), (1, 3)), ("fsdp",), 2 * 64 * 256 * 4)
    assert found["all-to-all.5"] == c("all-to-all", "", "", ((0, 1, 2), (3,)), (), 64)


def test_an_asynchronous_collective_is_one_collective_in_several_operations(recorded):
    _, found = recorded
    gathered = 4096 * 4 * 128 * 2
    start = found["async-collective-start"]
    assert start == profiling.Collective("all-gather", "start", "", ((0, 2), (1, 3)), ("fsdp",), gathered)
    # the done by the channel its pieces share, and the matmul fusion the all-gather is carried through
    assert found["async-collective-done"] == start._replace(half="done", completes="async-collective-start")
    assert found["fusion.381"] == start._replace(half="under", completes="async-collective-start")
    # a native pair: the done by its operand; the start's tuple is (operand, result, contexts)
    ring = found["collective-permute-start.1"]
    assert ring == profiling.Collective("collective-permute", "start", "", ((0, 2), (1, 3)), ("fsdp",), 458752)
    assert found["collective-permute-done.1"] == ring._replace(half="done", completes="collective-permute-start.1")
    assert found["collective-permute-start.2"].axes == ("fsdp", "tp")
    assert found["collective-permute-start.2"].bytes == 12 * 1024 * 4
    # an `async-start` / `async-done` wrapper takes kind, groups and bytes from the computation it calls
    wrapped = found["all-gather-start.4"]
    assert wrapped == profiling.Collective("all-gather", "start", "", ((0, 1, 2, 3),), ("fsdp", "tp"), 8 * 256 * 2)
    assert found["all-gather-done.4"] == wrapped._replace(half="done", completes="all-gather-start.4")
    whole = [name for name, record in found.items() if not record.completes]
    assert len(whole) == 9 and sum(not found[name].axes for name in whole) == 1


def test_shapes_may_come_from_a_second_print_and_a_text_without_groups_is_not_searched(recorded):
    _, found = recorded
    import re

    bare = re.sub(r" = (\(.*?\)|\S+) ([a-z\-]+\()", r" = \2", HLO)      # as `_module_text` prints: no shapes
    assert "bf16[12,1024,4096] all-reduce" not in bare and " = all-reduce(" in bare
    assert profiling.program_ops_table(bare) == profiling.program_ops_table(HLO)
    assert {name: record.bytes for name, record in profiling.program_collectives_table(bare, MESH_2X2).items()} == \
        dict.fromkeys(found, 0)
    assert profiling.program_collectives_table(bare, MESH_2X2, shapes_text=HLO) == found
    # a 4 x 1 mesh: the same groups are other axes, or none
    other = profiling.program_collectives_table(HLO, MESH_4X1)
    assert other["all-reduce.78"].axes == ("fsdp",) and other["all-reduce.94"].axes == ()
    one_chip = "\n".join(line for line in HLO.splitlines()
                         if "replica_groups" not in line and "source_target_pairs" not in line)
    assert profiling._walk_module(one_chip)[2] == []


# ------------------------------------------------------------------- the join


def test_collective_seconds_on_a_recorded_reduced_trace(recorded):
    """Ten steps of an eight-layer loop: the synchronous all-reduces whole, a
    pair summed with its calls counted once, the carrier's seconds beside the
    collective's and not in them, an operation the table does not hold under
    pass `other`, a collective that never ran left out."""
    table, found = recorded
    op_seconds = {"all-reduce.94": 0.196, "all-reduce.103": 0.204, "async-collective-start": 0.002,
                  "async-collective-done": 0.010, "fusion.381": 0.300, "collective-permute-start.1": 0.001,
                  "collective-permute-done.1": 0.003, "all-reduce.78": 0.0005, "all-to-all.5": 0.004,
                  "fusion.12": 1.0}
    op_counts = {"all-reduce.94": 80.0, "all-reduce.103": 80.0, "async-collective-start": 80.0,
                 "async-collective-done": 80.0, "fusion.381": 80.0, "collective-permute-start.1": 10.0,
                 "collective-permute-done.1": 10.0, "all-reduce.78": 10.0, "all-to-all.5": 10.0, "fusion.12": 10.0}
    unheld = {name: instances for name, instances in table.items() if name != "all-to-all.5"}
    rows = profiling.collective_seconds(op_seconds, op_counts, unheld, found)
    assert [row["seconds"] for row in rows] == sorted((row["seconds"] for row in rows), reverse=True)
    by = {(row["kind"], row["axes"], row["scopes"], row["pass"]): row for row in rows}
    assert set(by) == {
        ("all-reduce", ("tp",), ("mlp",), "fwd"),
        ("all-reduce", ("tp",), ("attn.full", "attn.proj"), "recompute"),
        ("all-gather", ("fsdp",), ("attn.full", "attn.proj"), "fwd"),
        ("collective-permute", ("fsdp",), (), "other"),
        ("all-reduce", ("fsdp", "tp"), ("steplog.optimizer_update",), "optimizer"),
        ("all-to-all", (), (), "other")}
    mlp = by["all-reduce", ("tp",), ("mlp",), "fwd"]
    assert (mlp["calls"], mlp["bytes_per_call"], mlp["seconds"]) == (80.0, ACT, 0.196)
    assert mlp["gbytes_per_s"] == pytest.approx(80 * ACT / 0.196 / 1e9) and mlp["under_seconds"] == 0.0
    gather = by["all-gather", ("fsdp",), ("attn.full", "attn.proj"), "fwd"]
    assert gather["calls"] == 80.0 and gather["seconds"] == pytest.approx(0.012)
    assert gather["under_seconds"] == 0.300 and gather["bytes"] == 80 * 4096 * 4 * 128 * 2
    ring = by["collective-permute", ("fsdp",), (), "other"]
    assert (ring["calls"], ring["seconds"], ring["bytes_per_call"]) == (10.0, pytest.approx(0.004), 458752)
    assert sum(row["seconds"] for row in rows) == pytest.approx(
        sum(s for name, s in op_seconds.items() if name in found and found[name].half != "under"))
    assert profiling.collective_seconds(op_seconds, op_counts, table, {}) == []
    assert profiling.collective_seconds({}, {}, table, found) == []


def test_a_captures_record_carries_the_join_beside_the_scopes(recorded, monkeypatch, tmp_path):
    """`capture_local_profile`'s meta: `collective_seconds` for every
    registered program that ran collectives in the capture, axes and scopes
    as strings (the record is JSON), beside `scope_seconds` as it was."""
    import json
    from types import SimpleNamespace

    table, found = recorded

    def event(name, start, duration):
        return SimpleNamespace(name=name, start_ns=start, duration_ns=duration)

    chip = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[event(STEP + "(77)", 0, 10_000)]),
        SimpleNamespace(name="XLA Ops", events=[
            event("%all-reduce.94 = bf16[12,1024,4096]{2,1,0} all-reduce(%x), channel_id=14", 100, 2_000),
            event("%async-collective-start = (bf16[1,2048,4,128]{3,2,1,0}) fusion(%x), kind=kCustom", 2_200, 10),
            event("%fusion.381 = (f32[12,4,1024,128]{3,2,1,0}) fusion(%y), kind=kOutput", 2_300, 3_000),
            event("%async-collective-done = bf16[1,4096,4,128]{3,2,1,0} fusion(%z), kind=kCustom", 5_400, 90),
            event("%copy.8 = bf16[12,1024,4096]{2,1,0} copy(%x)", 6_000, 500)])])
    (tmp_path / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", lambda path: SimpleNamespace(planes=[chip]))
    monkeypatch.setattr(profiling, "_program_ops", {STEP: table, "jit_idle": table})
    monkeypatch.setattr(profiling, "_program_collectives", {STEP: found, "jit_idle": found})
    scopes, moved = profiling._captured_splits(str(tmp_path))
    assert set(scopes) == set(moved) == {STEP} and scopes[STEP]["total_s"] == pytest.approx(5.6e-6)
    rows = json.loads(json.dumps(moved))[STEP]
    assert [(row["kind"], row["axes"], row["scopes"], row["pass"], row["calls"]) for row in rows] == [
        ("all-reduce", "tp", "mlp", "fwd", 1.0), ("all-gather", "fsdp", "attn.full/attn.proj", "fwd", 1.0)]
    assert rows[1]["seconds"] == pytest.approx(100e-9) and rows[1]["under_seconds"] == pytest.approx(3e-6)
    assert rows[0]["gbytes_per_s"] == pytest.approx(ACT / 2e-6 / 1e9)
    # what `ray_tpu profile` prints under the passes' shares: one line a (kind, axes)
    from ray_tpu.cli import _collective_lines

    assert _collective_lines(rows + [dict(rows[0], scopes="head", seconds=1e-6, calls=2.0, bytes=1e6)]) == [
        "all-reduce over tp: 3 calls, 33.89 MB a call, 0.0 ms, 33887.8 GB/s",
        "all-gather over fsdp: 1 calls, 4.19 MB a call, 0.0 ms, 41943.0 GB/s"]
    monkeypatch.setattr(profiling, "_program_collectives", {})      # a one-chip step: the scopes alone
    assert profiling._captured_splits(str(tmp_path)) == (scopes, {})
    assert profiling._captured_splits(str(tmp_path / "nothing")) == ({}, {})


# ------------------------------------------------------- train.report.ops


def _ops_span(monkeypatch, spec, devices):
    monkeypatch.setitem(profiling.DEVICE_PEAKS, "cpu", (1e12, 100e9))
    monkeypatch.setattr(profiling, "_program_ops", {})
    monkeypatch.setattr(profiling, "_program_collectives", {})
    monkeypatch.setattr(jax, "devices", lambda *a, held=jax.devices()[:devices]: held)
    tracing.tracer().clear()
    trainer = LMTrainer(llama_tiny().replace(remat=True), mesh_spec=spec, total_steps=10)
    rng = np.random.default_rng(0)
    trainer.train(iter([{"tokens": rng.integers(0, 256, (4, 33)).astype(np.int32)}]), num_steps=1, report_every=1)
    (ops,) = [s for s in tracing.tracer().spans(limit=10**6) if s["name"] == "train.report.ops"]
    tracing.tracer().clear()
    return ops["attrs"], profiling.program_ops()[STEP], profiling.program_collectives()[STEP]


def test_a_step_on_four_devices_says_its_collectives_and_both_axes(monkeypatch):
    attrs, table, found = _ops_span(monkeypatch, MeshSpec(fsdp=2, tp=2), 4)
    assert attrs["collectives"] == sum(not record.completes for record in found.values()) > 0
    assert (attrs["collective_axes"], attrs["collectives_unplaced"]) == ("fsdp,tp", 0)
    assert set(found) <= set(table)
    axes = {record.axes for record in found.values()}
    assert {("fsdp",), ("tp",)} <= axes and all(record.bytes > 0 for record in found.values())
    # the model says which: the MLP's partial sums reach their rows by permutes over `tp` (an all-reduce
    # over `tp` until PR 54 laid the stream's sequences over it), the projections' gradients are summed over `fsdp`
    where = {(record.kind, record.axes, scope, table[name][0][1]) for name, record in found.items()
             for scope in table[name][0][0]}
    assert {("collective-permute", ("tp",), "mlp", "fwd"), ("all-reduce", ("fsdp",), "attn.proj", "bwd")} <= where
    assert ("all-reduce", ("tp",), "mlp", "fwd") not in where
    rows = profiling.collective_seconds(dict.fromkeys(found, 1e-3), dict.fromkeys(found, 1.0), table, found)
    assert sum(row["calls"] for row in rows) == attrs["collectives"]


def test_a_step_on_one_device_says_none(monkeypatch):
    attrs, _, found = _ops_span(monkeypatch, MeshSpec(), 1)
    assert (attrs["collectives"], attrs["collective_axes"], attrs["collectives_unplaced"]) == (0, "", 0)
    assert found == {} and attrs["ops"] > 0
