"""Device time by sublayer and by pass (util/profiling): the closed set of
scope names a train step uses, the operation table read off a compiled
step, the join of a profile's operation seconds to it, the benchmark's
reader over both, and the span `train.report.ops` under which `LMTrainer`
builds the table. Nothing here times anything: a CPU run gives names and
counts."""

import os
import re
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from ray_tpu.core.config import cfg
from ray_tpu.models.configs import llama_tiny
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import LMTrainer
from ray_tpu.util import profiling, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
STEP = "jit_step_under_mesh"
ATTENTION = {"attn.proj", "attn.kernel", "attn.out"}
PHASES = {"steplog.fwd_bwd_compute", "steplog.optimizer_update"}


def _batches(n, batch=4, seq=32, vocab=256):
    rng = np.random.default_rng(0)
    return iter([{"tokens": rng.integers(0, vocab, (batch, seq + 1)).astype(np.int32)}
                 for _ in range(n)])


def _report_spans():
    return [s for s in tracing.tracer().spans(limit=10**6) if s["name"].startswith("train.report")]


@pytest.fixture(scope="module")
def dense():
    """A tiny Llama-style trainer that recomputes its blocks, driven two
    steps with cost accounting on (a nominal row of peaks for the CPU): ->
    (its operation table, the text it was read from, the report spans)."""
    peaks = pytest.MonkeyPatch()
    peaks.setitem(profiling.DEVICE_PEAKS, "cpu", (1e12, 100e9))
    peaks.setattr(profiling, "_program_ops", {})
    tracing.tracer().clear()
    trainer = LMTrainer(llama_tiny().replace(remat=True), mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2),
                        total_steps=10)
    texts = []
    module_text = profiling._module_text
    peaks.setattr(profiling, "_module_text", lambda c: texts.append(module_text(c)) or texts[-1])
    trainer.train(_batches(2), num_steps=2, report_every=1)
    spans, tables = _report_spans(), profiling.program_ops()
    peaks.undo()
    tracing.tracer().clear()
    return tables[STEP], texts[0], spans


def _scope_passes(table):
    return {(scope, pass_) for found in table.values() for scopes, pass_, _ in found
            for scope in scopes}


def _named(text, table, path_pattern):
    """The first operation of the table whose op_name in the text matches."""
    for line in text.splitlines():
        found = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"", line)
        if found and found.group(1) in table and re.search(path_pattern, found.group(2)):
            return found.group(1)
    raise AssertionError(f"no operation under {path_pattern}")


# ------------------------------------------------------------------ the table


def test_dense_step_table_holds_the_familys_scopes_in_every_pass(dense):
    table, _, _ = dense
    pairs = _scope_passes(table)
    assert {scope for scope, _ in pairs} == PHASES | ATTENTION | {"embed", "attn.full", "mlp", "head"}
    for sublayer in ATTENTION | {"attn.full", "mlp"}:     # a recomputing block: three passes each
        assert {(sublayer, "fwd"), (sublayer, "recompute"), (sublayer, "bwd")} <= pairs
    assert {("embed", "fwd"), ("head", "fwd"), ("head", "bwd")} <= pairs
    assert ("steplog.optimizer_update", "optimizer") in pairs
    assert not {pass_ for _, pass_ in pairs} - set(profiling.STEP_PASSES)
    # a sublayer's parts lie inside the layer's kind
    for found in table.values():
        for scopes, _, _ in found:
            if ATTENTION & set(scopes):
                assert "attn.full" in scopes


@pytest.mark.parametrize("path,scope,pass_", [
    (r"^(?!.*(transpose|rematted)).*jvp\(.*\bmlp/.*dot_general$", "mlp", "fwd"),
    (r"rematted_computation/.*attn\.proj/.*dot_general$", "attn.proj", "recompute"),
    (r"^(?!.*rematted).*transpose\(jvp\(.*\bmlp/.*dot_general$", "mlp", "bwd"),
    (r"^jit\(step_under_mesh\)/steplog\.optimizer_update/", "steplog.optimizer_update", "optimizer"),
], ids=["forward", "recomputed", "backward", "optimizer"])
def test_dense_step_table_classifies_a_known_instruction(dense, path, scope, pass_):
    table, text, _ = dense
    ((scopes, found_pass, _),) = table[_named(text, table, path)]
    assert scope in scopes and found_pass == pass_


def test_op_pass_precedence():
    assert profiling.op_pass("jit(s)/steplog.fwd_bwd_compute/transpose(jvp(x))/jvp()/checkpoint/"
                             "rematted_computation/moe/mul") == "recompute"
    assert profiling.op_pass("jit(s)/steplog.fwd_bwd_compute/transpose(jvp())/while/body/mlp/dot") == "bwd"
    assert profiling.op_pass("jit(s)/steplog.fwd_bwd_compute/jvp(embed)/gather") == "fwd"
    assert profiling.op_pass("jit(s)/steplog.optimizer_update/mul") == "optimizer"
    assert profiling.op_pass("jit(s)/jit(_threefry_fold_in)/add") == "other"
    assert profiling.op_pass("") == "other"


HLO = """HloModule jit_toy, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p), metadata={op_name="jit(toy)/steplog.fwd_bwd_compute/jvp(mlp)/neg"}
}

%reducer (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%body.1 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %flash_fwd.2 = f32[8]{0} custom-call(%t), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/steplog.fwd_bwd_compute/transpose(jvp())/while/body/checkpoint/rematted_computation/attn.full/attn.kernel/flash_fwd/pallas_call"}
  %copy.7 = f32[8]{0} copy(%flash_fwd.2)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%t, %copy.7)
}

ENTRY %main.1 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %flash_fwd.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/steplog.fwd_bwd_compute/jvp(attn.full)/attn.kernel/flash_fwd/pallas_call"}
  %reduce.1 = f32[] reduce(%flash_fwd.1, %x), dimensions={0}, to_apply=%reducer, metadata={op_name="jit(toy)/steplog.fwd_bwd_compute/jvp(head)/reduce_sum"}
  %while.1 = (s32[], f32[8]{0}) while(%x), condition=%cond.1, body=%body.1
  %mul.3 = f32[8]{0} multiply(%x, %x), metadata={op_name="jit(toy)/steplog.optimizer_update/jit(mlp)/mul"}
  ROOT %copy.8 = f32[8]{0} copy(%mul.3)
}
"""


def test_table_of_a_hand_written_module():
    """A kernel keyed without its instance suffix, with both instances; a
    fusion without metadata placed by what it fuses; neither the fusion's
    inside, the reducer nor parameters and tuples; `jit(mlp)` is a function's
    name and no scope."""
    program, table = profiling.program_ops_table(HLO)
    assert program == "jit_toy"
    assert set(table) == {"flash_fwd", "copy.7", "fusion.1", "reduce.1", "while.1", "mul.3", "copy.8"}
    body, entry = 2, 3
    assert table["flash_fwd"] == (
        (("steplog.fwd_bwd_compute", "attn.full", "attn.kernel"), "recompute", body),
        (("steplog.fwd_bwd_compute", "attn.full", "attn.kernel"), "fwd", entry))
    assert table["fusion.1"] == ((("steplog.fwd_bwd_compute", "mlp"), "fwd", entry),)
    assert table["mul.3"] == ((("steplog.optimizer_update",), "optimizer", entry),)
    assert table["copy.8"] == (((), "other", entry),)
    with pytest.raises(profiling.ProfilingError):
        profiling.program_ops_table("not a module")


# ------------------------------------------------------------------- the join


def test_scope_seconds_splits_a_kernel_by_how_often_each_instance_ran():
    _, table = profiling.program_ops_table(HLO)
    # ten runs of the program; the loop's body ran three times a run
    op_seconds = {"flash_fwd": 4.0, "copy.7": 0.3, "fusion.1": 1.0, "reduce.1": 0.5,
                  "mul.3": 2.0, "copy.8": 0.2, "fusion.77": 0.25}
    op_counts = {"flash_fwd": 40, "copy.7": 30, "fusion.1": 10, "reduce.1": 10, "mul.3": 10,
                 "copy.8": 10, "fusion.77": 10}
    split = profiling.scope_seconds(op_seconds, op_counts, table)
    assert split["total"] == pytest.approx(8.25)
    assert split["unmatched_ops"] == {"fusion.77": 0.25}
    assert split["unscoped_ops"] == pytest.approx({"copy.7": 0.3, "copy.8": 0.2})
    # 30 of the kernel's 40 calls were the loop's, the recomputed instance
    assert split["by_scope_pass"]["attn.kernel", "recompute"] == pytest.approx(3.0)
    assert split["by_scope_pass"]["attn.kernel", "fwd"] == pytest.approx(1.0)
    assert split["by_scope_pass"]["attn.full", "fwd"] == pytest.approx(1.0)
    assert split["by_scope_pass"]["mlp", "fwd"] == pytest.approx(1.0)
    assert split["by_scope_pass"]["head", "fwd"] == pytest.approx(0.5)
    assert split["by_pass"] == pytest.approx(
        {"fwd": 2.5, "recompute": 3.0, "optimizer": 2.0, "other": 0.5})
    # every second is in one pass or unmatched
    assert sum(split["by_pass"].values()) + 0.25 == pytest.approx(split["total"])


def test_scope_seconds_splits_evenly_where_nothing_says_how_often():
    _, table = profiling.program_ops_table(HLO)
    split = profiling.scope_seconds({"flash_fwd": 4.0}, {"flash_fwd": 40}, table)
    assert split["by_pass"] == pytest.approx({"fwd": 2.0, "recompute": 2.0})
    # a branch never taken ran none of its instances
    split = profiling.scope_seconds({"flash_fwd": 4.0, "fusion.1": 1.0},
                                    {"flash_fwd": 10, "fusion.1": 10}, table)
    assert split["by_scope_pass"]["attn.kernel", "fwd"] == pytest.approx(4.0)
    assert ("attn.kernel", "recompute") not in split["by_scope_pass"] or \
        split["by_scope_pass"]["attn.kernel", "recompute"] == 0.0


def test_profiled_op_seconds_reads_the_steps_runs_alone():
    def event(name, start, duration):
        return SimpleNamespace(name=name, start_ns=start, duration_ns=duration)

    def line(name, events):
        return SimpleNamespace(name=name, events=events)

    chip = SimpleNamespace(name="/device:TPU:0", lines=[
        line("XLA Modules", [event("jit_toy(123)", 100, 1000), event("jit_convert(9)", 2000, 50),
                             event("jit_toy(123)", 3000, 1000)]),
        line("XLA Ops", [
            event("%fusion.1 = f32[8]{0} fusion(%x), kind=kLoop", 110, 200),
            event("%flash_fwd.1 = f32[8]{0} custom-call(%fusion.1)", 320, 300),
            event("%while.1 = (s32[], f32[8]{0}) while(%x)", 630, 400),
            event("%flash_fwd.2 = f32[8]{0} custom-call(%t)", 640, 350),
            event("%fusion.1 = f32[8]{0} fusion(%y)", 2010, 30),          # another program's
            event("%fusion.1 = f32[8]{0} fusion(%x), kind=kLoop", 3010, 200),
        ])])
    host = SimpleNamespace(name="/host:CPU", lines=[line("XLA Ops", [event("%fusion.1 = f32[8] fusion()", 120, 5)])])
    seconds, counts = profiling.profiled_op_seconds([host, chip], "jit_toy")
    assert seconds == pytest.approx({"fusion.1": 400e-9, "flash_fwd": 650e-9})
    assert counts == {"fusion.1": 2, "flash_fwd": 2}
    assert profiling.profiled_op_seconds([host, chip], "jit_other") == ({}, {})


# ------------------------------------------------------------ the closed set


def test_every_named_scope_of_the_step_is_in_the_closed_set_and_every_name_is_used():
    files = [os.path.join(ROOT, "ray_tpu/train/lm.py")]
    for folder in ("ray_tpu/models", "ray_tpu/ops"):
        files += [os.path.join(ROOT, folder, name) for name in sorted(os.listdir(os.path.join(ROOT, folder)))
                  if name.endswith(".py")]
    used = set()
    for path in files:
        with open(path, encoding="utf-8") as f:
            source = f.read()
        for call in re.findall(r"named_scope\(([^)]*)\)", source):
            literals = re.findall(r"[\"']([^\"']+)[\"']", call)
            assert literals, f"{path}: a named_scope whose name is no literal: {call!r}"
            used.update(literals)
    assert used == set(profiling.STEP_SCOPES)
    assert len(set(profiling.STEP_SCOPES)) == len(profiling.STEP_SCOPES)


# ----------------------------------------------------------------- the reader


def _fake_trace(table):
    """A reduced trace in which every operation of the table ran once a
    step for a millisecond, and one the table does not hold."""
    op_seconds = {name: 1e-3 * len(found) for name, found in table.items()}
    op_seconds["fusion.99999"] = 5e-3
    return {"busy_s": sum(op_seconds.values()), "op_seconds": op_seconds,
            "op_counts": {name: float(len(found)) for name, found in table.items()},
            "program_seconds": {STEP: [sum(op_seconds.values())]}}


def test_reader_answers_none_without_a_trace_or_a_table(dense, monkeypatch):
    from benchmark.readers import scope_busy_share

    table, _, _ = dense
    monkeypatch.setattr(profiling, "_program_ops", {})
    assert scope_busy_share.read({}, scopes=["head"]) is None
    assert scope_busy_share.read({"trace": None}, scopes=["head"]) is None
    assert scope_busy_share.read({"trace": _fake_trace(table)}, scopes=["head"]) is None
    monkeypatch.setattr(profiling, "_program_ops", {"jit_another_program": table})
    assert scope_busy_share.read({"trace": _fake_trace(table)}, scopes=["head"]) is None
    monkeypatch.delattr(profiling, "program_ops")            # the parent of this PR
    assert scope_busy_share.read({"trace": _fake_trace(table)}, scopes=["head"]) is None


def test_reader_gives_the_tables_own_shares_on_a_fake_trace(dense, monkeypatch):
    from benchmark.harness import BENCH_DIR, load_json
    from benchmark.readers import scope_busy_share

    table, _, _ = dense
    monkeypatch.setattr(profiling, "_program_ops", {STEP: table})
    ctx = {"trace": _fake_trace(table)}
    instances = [i for found in table.values() for i in found]
    total = len(instances) + 5

    def share(keep):
        return pytest.approx(100.0 * sum(1 for i in instances if keep(i)) / total)

    def metric(name):
        meta = load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))
        assert meta["reader"] == "scope_busy_share"
        return scope_busy_share.read(ctx, **meta["args"])

    assert metric("head_busy_share") == share(lambda i: "head" in i[0])
    assert metric("mlp_busy_share") == share(lambda i: "mlp" in i[0])
    assert metric("recompute_busy_share") == share(lambda i: i[1] == "recompute")
    assert metric("optimizer_busy_share") == share(lambda i: i[1] == "optimizer")
    assert metric("attn_proj_busy_share") == share(lambda i: {"attn.proj", "attn.out"} & set(i[0]))
    assert metric("moe_layer_busy_share") == 0.0
    unscoped = sum(1 for i in instances if not profiling.sublayer_scoped(i[0]))
    assert metric("step_unscoped_busy_share") == pytest.approx(100.0 * (unscoped + 5) / total)
    # the five passes are all of the matched time
    passes = sum(scope_busy_share.read(ctx, passes=[p]) for p in profiling.STEP_PASSES)
    assert passes == pytest.approx(100.0 * len(instances) / total)
    # operations left out by name leave the share's base, the device's busy time, alone
    kernel_ops = sum(1 for name, found in table.items() for i in found if "attn.kernel" in i[0])
    assert scope_busy_share.read(ctx, scopes=["attn.kernel"]) == pytest.approx(100.0 * kernel_ops / total)
    prefix = next(name for name, found in table.items() if "attn.kernel" in found[0][0]).split(".")[0]
    left = sum(1 for name, found in table.items() for i in found
               if "attn.kernel" in i[0] and not name.startswith(prefix))
    assert scope_busy_share.read(ctx, scopes=["attn.kernel"], exclude_prefixes=[prefix]) == \
        pytest.approx(100.0 * left / total)


# ------------------------------------------------------- train.report.ops


def test_first_report_builds_the_table_under_a_span_of_its_own(dense):
    table, text, spans = dense
    reports = [s for s in spans if s["name"] == "train.report"]
    assert len(reports) == 2
    (ops,) = [s for s in spans if s["name"] == "train.report.ops"]
    first = sorted(reports, key=lambda s: s["start_mono"])[0]
    assert ops["parent_id"] == first["span_id"]
    leaves = sorted((s for s in spans if s["parent_id"] == first["span_id"]),
                    key=lambda s: s["start_mono"])
    assert [s["name"] for s in leaves] == [
        "train.report.read", "train.report.cost", "train.report.ops", "train.report.publish"]
    for before, after in zip(leaves, leaves[1:]):     # one clock reading a boundary
        assert after["start_mono"] == before["end_mono"]
    instances = [i for found in table.values() for i in found]
    # what the module says of its collectives rides the same span (tests/test_step_collectives.py)
    said = {key: ops["attrs"].pop(key) for key in ("collectives", "collective_axes", "collectives_unplaced")}
    assert said["collectives"] > 0 and said["collective_axes"] == "dp,fsdp,tp"
    assert ops["attrs"] == {
        "program": STEP, "ops": len(instances),
        "ops_scoped": sum(profiling.sublayer_scoped(i[0]) for i in instances),
        "text_bytes": len(text)}
    assert 0 < ops["attrs"]["ops_scoped"] < ops["attrs"]["ops"]


def test_no_span_and_no_table_with_cost_accounting_off(monkeypatch):
    monkeypatch.setitem(profiling.DEVICE_PEAKS, "cpu", (1e12, 100e9))
    monkeypatch.setattr(profiling, "_program_ops", {})
    tracing.tracer().clear()
    cfg.set(profile_cost_accounting=False)
    try:
        trainer = LMTrainer(llama_tiny(), mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2), total_steps=10)
        metrics = trainer.train(_batches(1), num_steps=1, report_every=1)
    finally:
        cfg.reset("profile_cost_accounting")
    names = {s["name"] for s in _report_spans()}
    tracing.tracer().clear()
    assert "train.report.cost" in names and "train.report.ops" not in names
    assert profiling.program_ops() == {} and "mfu" not in metrics
    assert trainer._step_compiled is None
