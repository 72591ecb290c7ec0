"""The benchmark's one command:

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in BENCHMARK.json, its configuration in
benchmark/configs/, its traffic in benchmark/traffic/, the generator of
the traffic's `kind` in benchmark/kinds/<kind>.py and each per-layer
metric's reader through benchmark/metrics/<metric>.json, and has no table
of its own. The last line of standard output is the result (one JSON
object); anything else goes to standard error.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
from typing import Any, Dict, List, Optional

from .harness import BENCH_DIR, ROOT, CompileCounter, device_record, load_benchmark, load_json, log


def cell_metrics(bench: Dict[str, Any], group: str, workload: str) -> List[Dict[str, Any]]:
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def kind_module(traffic: Dict[str, Any]):
    """benchmark/kinds/<kind>.py: the generator that reads this traffic
    file, with `run(ctx)`, one measured window of it."""
    kind = traffic.get("kind")
    try:
        return importlib.import_module(f"benchmark.kinds.{kind}")
    except ModuleNotFoundError:
        raise ValueError(f"unknown traffic kind {kind!r}: no benchmark/kinds/{kind}.py") from None


def run_cell(bench: Dict[str, Any], workload: str, seed: int, seconds: float,
             trace: bool, *, tree: str = BENCH_DIR, require_tpu: bool = True) -> Dict[str, Any]:
    """One run of one cell. `tree` is where configs/ and traffic/ are looked
    up (the tests point it at a tiny tree); `require_tpu=False` is the
    tests' CPU rehearsal and never the command's."""
    from ray_tpu.core.compile_cache import ensure_compile_cache

    from . import model_config

    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = model_config.load_config(os.path.join(tree, "configs", cell["config"] + ".json"))
    traffic = load_json(os.path.join(tree, "traffic", cell["traffic"] + ".json"))
    kind = kind_module(traffic)
    if conf["chips"] != cell["chips"]:
        raise SystemExit(f"{workload}: cell asks {cell['chips']} chips, its configuration {conf['chips']}")
    if require_tpu:
        ensure_compile_cache()
    device = device_record()
    if require_tpu and (device["platform"] != "tpu" or device["count"] < cell["chips"]):
        log(f"needs {cell['chips']} TPU chip(s); JAX found {device['count']} "
            f"{device['platform']} device(s)")
        raise SystemExit(1)
    compiles = CompileCounter()
    ctx: Dict[str, Any] = dict(
        cell=cell, conf=conf, traffic=traffic, seed=int(seed), seconds=float(seconds),
        trace=bool(trace), tree=tree, device=device, problems=[],
    )
    kind.run(ctx)
    ctx["compiles_in_window"] = compiles.between(ctx["t0"], ctx["t1"])
    if ctx["compiles_in_window"]:
        ctx["problems"].append(f"{ctx['compiles_in_window']} program(s) compiled or loaded inside the window")

    values: Dict[str, Optional[float]] = dict(ctx["end_to_end"], setup_s=ctx["setup_s"])
    group = "per_layer" if trace else "end_to_end"
    metrics: Dict[str, Dict[str, Any]] = {}
    for spec in cell_metrics(bench, group, workload):
        if trace:
            meta = load_json(os.path.join(BENCH_DIR, "metrics", spec["name"] + ".json"))
            reader = importlib.import_module(f"benchmark.readers.{meta['reader']}")
            value = reader.read(ctx, **meta.get("args", {}))
        else:
            value = values.get(spec["name"])
        if value is not None:
            metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
        elif not trace:
            ctx["problems"].append(f"end-to-end metric {spec['name']} has no value")
    for problem in ctx["problems"]:
        log("not correct:", problem)
    log("compared (each number beside its limit):", json.dumps(ctx.get("checks")))
    result: Dict[str, Any] = {
        "correct": not ctx["problems"], "attempted": ctx["attempted"],
        "failed": ctx["failed"], "metrics": metrics,
        "device": dict(device, memory_peak_bytes=ctx["memory"]["peak_bytes"]),
    }
    if trace and ctx.get("trace"):
        result["device"]["busy_s"] = ctx["trace"]["busy_s"]
        result["device"]["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = ctx["trace"]["breakdown"]
    result["info"] = {"checks": ctx.get("checks")}
    return result


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        log("the program (ray_tpu/) is not in this directory: nothing to measure")
        return 3
    bench = load_benchmark()
    result = run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
