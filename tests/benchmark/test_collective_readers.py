"""The two readers over the program's collective registry
(`benchmark/readers/collective_busy_share.py`, `collective_bandwidth.py`)
and the five metrics that use them, on a reduced trace recorded on the chips:
`data/collectives_trace.json` is what a traced run of
`train-mistral7b-fsdp2tp2` left (its `op_seconds` and `op_counts` cut to the
collectives' names and the ten longest other operations, the rows of the
step's operation table and the registry for those names). Nothing here times
anything."""

import json
import os

import pytest

from bench_helpers import ROOT, load
from benchmark.readers import collective_bandwidth, collective_busy_share
from ray_tpu.util import profiling

STEP = "jit_step_under_mesh"
METRICS = ("collective_busy_share", "collective_tp_busy_share", "collective_fsdp_busy_share",
           "collective_tp_gbytes_per_s", "collective_fsdp_gbytes_per_s")


@pytest.fixture(scope="module")
def recorded():
    kept = load(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "collectives_trace.json"))
    table = {name: tuple((tuple(scopes), pass_, computation) for scopes, pass_, computation in instances)
             for name, instances in kept["table"].items()}
    registry = {name: profiling.Collective(**dict(record, groups=tuple(map(tuple, record["groups"])),
                                                   axes=tuple(record["axes"])))
                for name, record in kept["collectives"].items()}
    return kept["trace"], table, registry


@pytest.fixture
def registered(recorded, monkeypatch):
    trace, table, registry = recorded
    monkeypatch.setattr(profiling, "_program_ops", {STEP: table})
    monkeypatch.setattr(profiling, "_program_collectives", {STEP: registry})
    return {"trace": trace}


def metric(name, ctx):
    meta = load(os.path.join(ROOT, "benchmark", "metrics", name + ".json"))
    reader = {"collective_busy_share": collective_busy_share, "collective_bandwidth": collective_bandwidth}
    return reader[meta["reader"]].read(ctx, **meta["args"])


def test_the_five_metrics_on_the_recorded_trace(registered, recorded, benchmark_json):
    """The shares are the registry's operations' own seconds over busy_s, the
    single-axis ones and the both-axes remainder add up to the whole, and
    the rates are bytes x calls over the same seconds."""
    trace, _, registry = recorded
    busy = trace["busy_s"]

    def seconds(keep):
        return sum(trace["op_seconds"].get(name, 0.0) for name, record in registry.items()
                   if record.half != "under" and keep(record))

    def moved(keep):
        return sum(record.bytes * max(trace["op_counts"].get(name, 0.0) for name, other in registry.items()
                                      if name == first or other.completes == first)
                   for first, record in registry.items() if not record.completes and keep(record))

    whole = metric("collective_busy_share", registered)
    tp, fsdp = metric("collective_tp_busy_share", registered), metric("collective_fsdp_busy_share", registered)
    assert whole == pytest.approx(100.0 * seconds(lambda r: True) / busy)
    assert tp == pytest.approx(100.0 * seconds(lambda r: r.axes == ("tp",)) / busy)
    assert fsdp == pytest.approx(100.0 * seconds(lambda r: r.axes == ("fsdp",)) / busy)
    both = collective_busy_share.read(registered, axes=["fsdp", "tp"])
    assert both == collective_busy_share.read(registered, axes=["tp", "fsdp"]) > 0.0
    unplaced = 100.0 * seconds(lambda r: not r.axes) / busy
    assert tp + fsdp + both + unplaced == pytest.approx(whole, abs=1e-9)
    assert 0.0 < fsdp < whole < 100.0 and 0.0 < tp < whole
    assert metric("collective_tp_gbytes_per_s", registered) == pytest.approx(
        moved(lambda r: r.axes == ("tp",)) / seconds(lambda r: r.axes == ("tp",)) / 1e9)
    assert metric("collective_fsdp_gbytes_per_s", registered) == pytest.approx(
        moved(lambda r: r.axes == ("fsdp",)) / seconds(lambda r: r.axes == ("fsdp",)) / 1e9)
    # what the older measure reads beside them: `collective_exposed_share`'s seconds are the operations a
    # KIND names (0.867 s); the registry also holds the eight `fusion.N` that are the gradients'
    # all-reduces in the TPU's fused form and the compiler's `async-collective-*` fusions (0.329 s more)
    named = sum(s for name, s in trace["op_seconds"].items() if name.startswith(profiling.COLLECTIVE_KINDS))
    assert named == pytest.approx(trace["collective_exposed_s"], rel=1e-5)
    assert named < seconds(lambda r: True) == pytest.approx(named + 0.3287, abs=1e-3)
    # the recorded numbers themselves (my chip run, PR 53)
    assert [round(metric(name, registered), 3) for name in METRICS] == trace["recorded_metrics"]
    for name in METRICS:
        (entry,) = [m for m in benchmark_json["per_layer"] if m["name"] == name]
        assert (entry["layer"], entry["source"], entry["moves"]) == (
            "collectives", "device_trace", "train_tokens_per_s")
        assert "train-mistral7b-fsdp2tp2" in entry["workloads"]
        assert ("train-evabyte-fsdp4-32k" in entry["workloads"]) == ("_tp_" not in name)


def test_the_readers_select_by_kind_scope_and_pass(registered, recorded):
    trace, table, registry = recorded
    rows = profiling.collective_seconds(trace["op_seconds"], trace["op_counts"], table, registry)
    share = collective_busy_share.read

    def of(keep):
        return pytest.approx(100.0 * sum(row["seconds"] for row in rows if keep(row)) / trace["busy_s"])

    assert share(registered, kinds=["all-reduce"]) == of(lambda row: row["kind"] == "all-reduce")
    assert share(registered, axes=["tp"], kinds=["all-reduce"], scopes=["mlp"], passes=["bwd"]) == of(
        lambda row: (row["kind"], row["axes"], row["pass"]) == ("all-reduce", ("tp",), "bwd") and "mlp" in row["scopes"])
    assert share(registered, scopes=["attn.proj", "attn.out"]) == of(
        lambda row: {"attn.proj", "attn.out"} & set(row["scopes"]))
    by_pass = sum(share(registered, passes=[p]) for p in profiling.STEP_PASSES)
    assert by_pass == pytest.approx(share(registered))
    assert share(registered, kinds=["collective-broadcast"]) == 0.0
    assert collective_bandwidth.read(registered, kinds=["collective-broadcast"]) is None
    forward = [row for row in rows if row["axes"] == ("tp",) and row["pass"] == "fwd"]
    assert collective_bandwidth.read(registered, axes=["tp"], passes=["fwd"]) == pytest.approx(
        sum(row["bytes"] for row in forward) / sum(row["seconds"] for row in forward) / 1e9)


@pytest.mark.parametrize("reader", [collective_busy_share, collective_bandwidth], ids=["share", "bandwidth"])
def test_none_without_a_trace_a_table_or_the_registry(reader, recorded, monkeypatch):
    trace, table, registry = recorded
    monkeypatch.setattr(profiling, "_program_ops", {STEP: table})
    monkeypatch.setattr(profiling, "_program_collectives", {STEP: registry})
    assert reader.read({}, axes=["tp"]) is None
    assert reader.read({"trace": None}, axes=["tp"]) is None
    assert reader.read({"trace": dict(trace, program_seconds={})}, axes=["tp"]) is None
    assert reader.read({"trace": trace}, axes=["tp"]) > 0.0
    monkeypatch.setattr(profiling, "_program_collectives", {"jit_another_program": registry})
    assert reader.read({"trace": trace}, axes=["tp"]) is None
    monkeypatch.setattr(profiling, "_program_collectives", {STEP: registry})
    monkeypatch.setattr(profiling, "_program_ops", {})
    assert reader.read({"trace": trace}, axes=["tp"]) is None
    monkeypatch.setattr(profiling, "_program_ops", {STEP: table})
    monkeypatch.delattr(profiling, "program_collectives")       # the parent of this PR
    assert reader.read({"trace": trace}, axes=["tp"]) is None


def test_a_one_chip_step_reads_no_collective_time(recorded, monkeypatch):
    trace, table, _ = recorded
    monkeypatch.setattr(profiling, "_program_ops", {STEP: table})
    monkeypatch.setattr(profiling, "_program_collectives", {STEP: {}})
    assert collective_busy_share.read({"trace": trace}) == 0.0
    assert collective_bandwidth.read({"trace": trace}) is None


def test_the_record_is_json(recorded):
    trace, table, registry = recorded
    rows = profiling.collective_seconds(trace["op_seconds"], trace["op_counts"], table, registry)
    assert json.loads(json.dumps(rows)) and all(row["calls"] > 0 for row in rows)
