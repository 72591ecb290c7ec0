"""BENCHMARK.json against its contract's mechanical limits, and the
benchmark's tree: every data file is reachable from BENCHMARK.json or
from benchmark/spare.json (cells measured and not shipped)."""

import importlib
import os
import re

import pytest

from bench_helpers import ROOT, load, with_spare
from benchmark import model_config

BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def stems(folder):
    return {f[:-5] for f in os.listdir(os.path.join(BENCH, folder)) if f.endswith(".json")}


def test_exact_keys_and_limits(benchmark_json):
    b = benchmark_json
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert 1 <= len(b["workloads"]) <= 24 and 1 <= len(b["configs"]) <= 24
    assert 1 <= len(b["end_to_end"]) <= 16 and 1 <= len(b["per_layer"]) <= 128
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for entry in b["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert entry["file"].startswith(tuple(p + "/" for p in b["paths"]))
    for entry in b["workloads"]:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
        assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in b["end_to_end"] if "workloads" not in m}


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer(benchmark_json):
    b = benchmark_json
    for w in b["workloads"]:
        e2e = {m["name"] for m in b["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])}
        layers = [m for m in b["per_layer"] if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2 and layers
        # a per-layer metric moves an end-to-end metric its cells report
        assert {m["moves"] for m in layers} <= e2e


def cells_find_their_files(bench):
    """Every cell's configuration file and traffic file exist and agree with
    their entries; what an entry's `reduced` names is held to the rule of
    `model_config.check_reduced` (the depth or a count held as the chip's
    share, never a width), whatever the file itself lists."""
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    for w in bench["workloads"]:
        entry = configs[w["config"]]
        conf = model_config.load_config(os.path.join(ROOT, entry["file"]))
        model_config.check_reduced(dict(conf, reduced=entry["reduced"]), entry["name"])
        assert conf["chips"] == w["chips"]
        assert conf["source"] == entry["source"]
        assert conf["reduced"] == entry["reduced"]
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))


def test_cells_find_their_files(benchmark_json):
    cells_find_their_files(benchmark_json)


@pytest.mark.parametrize("key", ["hidden_size", "intermediate_size", "num_experts_per_tok",
                                 "head_dim", "kv_lora_rank", "moe_intermediate_size",
                                 "moe_ffn_hidden_size", "moe_num_active_primary_experts",
                                 "n_shared_experts", "zero_expert_num", "num_attention_heads",
                                 "sliding_window_size"])
def test_an_entry_whose_reduced_names_a_width_is_refused(benchmark_json, key):
    """Through BENCHMARK.json's own `reduced`: the shipped OLMoE file, with
    an entry that claims one more key."""
    bench = dict(benchmark_json, configs=[
        dict(c, reduced=c["reduced"] + [key]) if c["name"] == "olmoe-1b-7b-train-1chip" else c
        for c in benchmark_json["configs"]])
    with pytest.raises(ValueError, match=repr(key)):
        cells_find_their_files(bench)


def test_every_data_file_is_reachable_or_spare(benchmark_json):
    b = with_spare(benchmark_json)
    assert stems("configs") == {c["name"] for c in b["configs"]}
    assert stems("traffic") == {w["traffic"] for w in b["workloads"]}
    listed = {m["name"]: m for m in b["per_layer"]}
    assert stems("metrics") == set(listed)
    for stem, entry in listed.items():
        meta = load(os.path.join(BENCH, "metrics", stem + ".json"))
        assert meta["name"] == stem
        reader = importlib.import_module("benchmark.readers." + meta["reader"])
        assert callable(reader.read)
        for key in ("layer", "unit", "better", "source", "moves"):
            assert meta[key] == entry[key], (stem, key)
        # a metric's cells are listed in ONE place, its entry: a cell joins by one word there
        assert "workloads" not in meta, stem
    for w in b["workloads"]:
        kind = load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))["kind"]
        assert callable(importlib.import_module("benchmark.kinds." + kind).run)


def test_spare_entries_are_benchmark_entries_without_a_bound(benchmark_json):
    """What benchmark/spare.json keeps can be moved to BENCHMARK.json as it
    stands (plus a proved bound), and nothing is in both."""
    spare = load(os.path.join(BENCH, "spare.json"))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert not {e["name"] for e in spare[group]} & {e["name"] for e in benchmark_json[group]}
    cells = {w["name"] for w in spare["workloads"]}
    for entry in spare["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"} and len(entry["why"]) <= 200
        assert "not_shipped" in load(os.path.join(ROOT, entry["file"]))
    for entry in spare["workloads"]:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"} and len(entry["why"]) <= 200
        assert entry["config"] in {c["name"] for c in spare["configs"]}
        assert "not_shipped" in load(os.path.join(BENCH, "traffic", entry["traffic"] + ".json"))
    for m in spare["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "source", "workloads"}     # no bound was proved
    e2e = {m["name"] for m in spare["end_to_end"]}
    for m in spare["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and set(m["workloads"]) <= cells


def test_file_names_use_only_name_characters(benchmark_json):
    for path in benchmark_json["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", "_out")]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_serve_configuration_shows_its_arithmetic(benchmark_json):
    path = os.path.join(BENCH, "configs", "mistral-7b-v0.3-serve-1chip.json")
    conf = load(path)
    per_layer = (conf["hidden_size"] * conf["head_dim"] * 2 * (
        conf["num_attention_heads"] + conf["num_key_value_heads"])
        + 3 * conf["hidden_size"] * conf["intermediate_size"]) * 2 / 2**30
    assert per_layer == pytest.approx(conf["sizing"]["weights_per_layer_gib"], abs=1e-3)
    kv = 2 * conf["num_key_value_heads"] * conf["head_dim"] * 2
    assert kv == conf["sizing"]["kv_bytes_per_token_per_layer"]
    eng = conf["engine"]
    assert eng["max_pages_per_slot"] % eng["chunk_pages"] == 0
    weights = 0.5 + conf["num_hidden_layers"] * per_layer
    pool = eng["num_pages"] * eng["page_size"] * kv * conf["num_hidden_layers"] / 2**30
    assert weights + pool + conf["sizing"]["scratch_reserve_gib"] <= conf["sizing"]["bytes_limit_gib"]
    # both serving traffics fit the block-table width
    from benchmark.kinds.closed_loop import plan_for

    cells = [w for w in with_spare(benchmark_json)["workloads"] if w["config"] == conf["name"]]
    assert len(cells) == 2
    for w in cells:
        traffic = load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        plan = plan_for(traffic, conf, seed=1)
        assert plan.longest_context() <= eng["max_pages_per_slot"] * eng["page_size"]
