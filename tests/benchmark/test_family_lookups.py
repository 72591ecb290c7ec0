"""The four readers that price an expert layer or a window ask the family
(`model_config.expert_layer`, `model_config.attention_window`) and name no
model's key: the shipped cells read what the parent's readers read, to the
last bit; a foreign family that counts and names everything differently
reads the same through its own adapter; a family without the layer has
nothing to read."""

import os
import re
import sys
import types

import pytest

from bench_helpers import ROOT, load
from benchmark import model_config, moe_cost, roofline, window_cost
from benchmark import trace_reduce as tr
from benchmark.readers import (flash_win_fwd_roofline, moe_gmm_roofline, moe_held_gmm_roofline,
                               moe_held_rows_off_even)

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = {"moe_gmm_roofline": moe_gmm_roofline, "moe_held_gmm_roofline": moe_held_gmm_roofline,
           "moe_held_rows_off_even": moe_held_rows_off_even,
           "flash_win_fwd_roofline": flash_win_fwd_roofline}
KIND = "TPU v5 lite"
# a span record as `train-trinity-mini-8k` writes one: two reports inside the window
SPANS = [
    {"name": "train.init.step_fn", "start_mono": 1.0, "end_mono": 2.0,
     "attrs": {"moe_gmm_tile_rows": 256}},
    {"name": "train.report", "start_mono": 128.9, "end_mono": 129.0,            # set-up
     "attrs": {"moe_rows_held": 9e4, "moe_passes": 1.0, "moe_rows_held_share": 68.7}},
    {"name": "train.report", "start_mono": 133.9, "end_mono": 134.0,
     "attrs": {"moe_rows_held": 16526.25, "moe_passes": 1.0, "moe_rows_held_share": 12.6085}},
    {"name": "train.report", "start_mono": 138.9, "end_mono": 139.0,
     "attrs": {"moe_rows_held": 17391.5, "moe_passes": 1.0, "moe_rows_held_share": 13.2687}},
]


def _shipped(name):
    return model_config.load_config(os.path.join(ROOT, "benchmark", "configs", name + ".json"))


@pytest.fixture(scope="module")
def trace():
    """The recorded trace (16 ms of a chip, tests/benchmark/data), reduced,
    with its three measured `flash_fwd` calls also booked under the names
    the expert layer's and the window's kernels carry: the recording is of a
    program that has neither, and the readers find them by name."""
    reduced = tr.reduce_trace(load(os.path.join(HERE, "data", "recorded_trace.json")))
    (name,) = [k for k in reduced["op_seconds"] if k.startswith("flash_fwd")]
    more = ("moe_gmm_fwd custom-call", "moe_gmm_dlhs custom-call", "flash_win_fwd custom-call")
    return dict(reduced,
                op_seconds=dict(reduced["op_seconds"], **{k: reduced["op_seconds"][name] for k in more}),
                op_counts=dict(reduced["op_counts"], **{k: reduced["op_counts"][name] for k in more}))


@pytest.fixture
def span_record(monkeypatch):
    for reader in (moe_held_gmm_roofline, moe_held_rows_off_even):
        monkeypatch.setattr(reader, "program_spans", lambda: SPANS)


def _ctx(trace, conf, batch=2, seq=8192):
    return {"trace": trace, "conf": conf, "device": {"kind": KIND},
            "traffic": {"batch": batch, "seq": seq}, "t0": 130.0, "t1": 140.0}


def _parents(metric, ctx):
    """The parent's reader of that name, its arithmetic written out with the
    keys it read from the configuration file."""
    conf, traffic, trace = ctx["conf"], ctx["traffic"], ctx["trace"]
    in_window = [s["attrs"] for s in SPANS[2:]]
    if metric == "moe_held_rows_off_even":
        shares = [a["moe_rows_held_share"] for a in in_window]
        return abs(sum(shares) / len(shares) - 100.0 * conf["num_experts"] / conf["published"]["num_experts"])
    if metric == "flash_win_fwd_roofline":
        prefixes = ("flash_win_fwd",)
        shape = model_config.shape_numbers(conf)
        cost = window_cost.flash_win_fwd_cost(
            batch=traffic["batch"], seq=traffic["seq"], window=conf.get("sliding_window"),
            n_q_heads=shape["n_q_heads"], n_kv_heads=shape["n_kv_heads"], head_dim=shape["head_dim"])
    elif metric == "moe_gmm_roofline":
        prefixes = ("moe_gmm_",)
        cost = moe_cost.gmm_cost(
            rows=traffic["batch"] * traffic["seq"] * conf["num_experts_per_tok"],
            k=conf["hidden_size"], n=conf["intermediate_size"], groups=conf["num_experts"])
    else:
        prefixes = ("moe_gmm_",)
        rows = sum(a["moe_rows_held"] / max(a["moe_passes"], 1.0) for a in in_window) / len(in_window)
        cost = moe_cost.gmm_cost(rows=rows, k=conf["hidden_size"], n=conf["moe_intermediate_size"],
                                 groups=conf["num_experts"])
    least = roofline.roofline_seconds(cost, KIND)["seconds"]
    return 100.0 * tr.count_of(trace, prefixes) * least / tr.seconds_of(trace, prefixes)


@pytest.mark.parametrize("metric, config, batch, seq", [
    ("moe_gmm_roofline", "olmoe-1b-7b-train-1chip", 4, 4096),
    ("moe_held_gmm_roofline", "trinity-mini-train-1chip", 2, 8192),
    ("moe_held_rows_off_even", "trinity-mini-train-1chip", 2, 8192),
    ("flash_win_fwd_roofline", "trinity-mini-train-1chip", 2, 8192)])
def test_a_shipped_cell_reads_what_the_parents_reader_read_to_the_last_bit(
        trace, span_record, metric, config, batch, seq):
    ctx = _ctx(trace, _shipped(config), batch, seq)
    value = READERS[metric].read(ctx)
    assert value is not None and value == _parents(metric, ctx)


# Trinity-Mini's sizes as a family of other habits would write them down: every key the four
# readers used to read has another name here, and the adapter is the one place that knows
FOREIGN = {
    "model_type": "foreign_stub", "chips": 1, "hidden_size": 2048, "moe_ffn_hidden_size": 1024,
    "ffn_hidden_size": 6144, "moe_num_primary_experts": 16, "moe_num_active_primary_experts": 8,
    "sliding_window_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
    "head_dim": 128, "published": {"moe_num_primary_experts": 128},
    "reduced": ["moe_num_primary_experts"]}


def _stub_adapter(monkeypatch, *, experts=True, window=True):
    stub = types.ModuleType("benchmark.adapters.foreign_stub")
    stub.shapes = lambda conf: dict(
        n_q_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"])
    if experts:
        stub.expert_layer = lambda conf: {
            "held": conf["moe_num_primary_experts"],
            "published": conf["published"]["moe_num_primary_experts"],
            "per_token": conf["moe_num_active_primary_experts"], "hidden": conf["hidden_size"],
            "width": conf["moe_ffn_hidden_size"]}
    if window:
        stub.attention_window = lambda conf: conf["sliding_window_size"]
    monkeypatch.setitem(sys.modules, stub.__name__, stub)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_foreign_familys_names_read_the_same_through_its_adapter(
        monkeypatch, trace, span_record, metric):
    _stub_adapter(monkeypatch)
    own = _shipped("trinity-mini-train-1chip")
    assert model_config.expert_layer(FOREIGN) == model_config.expert_layer(own)
    assert model_config.attention_window(FOREIGN) == model_config.attention_window(own) == 2048
    value = READERS[metric].read(_ctx(trace, FOREIGN))
    assert value is not None and value == READERS[metric].read(_ctx(trace, own))
    if metric == "moe_gmm_roofline":
        # every routed row over the held experts, ONE expert's width (1,024, not the dense 6,144)
        cost = moe_cost.gmm_cost(rows=2 * 8192 * 8, k=2048, n=1024, groups=16)
        assert value == (100.0 * tr.count_of(trace, ("moe_gmm_",))
                         * roofline.roofline_seconds(cost, KIND)["seconds"]
                         / tr.seconds_of(trace, ("moe_gmm_",)))


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_family_without_the_layer_has_nothing_to_read(monkeypatch, trace, span_record, metric):
    """A stub that says nothing of an expert layer or a window, and the two
    shipped families that have neither."""
    _stub_adapter(monkeypatch, experts=metric == "flash_win_fwd_roofline",
                  window=metric != "flash_win_fwd_roofline")
    assert READERS[metric].read(_ctx(trace, FOREIGN)) is None
    for dense in ("gpt2-small-train-1chip", "mistral-7b-v0.3-train-4chip"):
        conf = _shipped(dense)
        assert model_config.expert_layer(conf) is None
        assert model_config.attention_window(conf) is None
        assert READERS[metric].read(_ctx(trace, conf)) is None
    # OLMoE has an expert layer, holds all of it, and no window
    olmoe = _shipped("olmoe-1b-7b-train-1chip")
    assert model_config.expert_layer(olmoe) == {
        "held": 64, "published": 64, "per_token": 8, "hidden": 2048, "width": 1024}
    assert model_config.attention_window(olmoe) is None
    assert moe_held_rows_off_even.read(_ctx(trace, olmoe)) is None


def test_no_reader_names_a_models_key():
    """What a reader takes from the configuration file itself is one of the
    benchmark's own groups; a model's sizes come through `model_config`."""
    own = {"chips", "trainer", "engine"}
    folder = os.path.join(ROOT, "benchmark", "readers")
    for name in sorted(os.listdir(folder)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(folder, name), encoding="utf-8") as f:
            text = f.read()
        keys = re.findall(r"""conf(?:"\])?(?:\[|\.get\()\s*["']([^"']+)["']""", text)
        assert set(keys) <= own, (name, keys)
