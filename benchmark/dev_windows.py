"""The one-process window study (not the driver's command).

    python -m benchmark.dev_windows --workload serve-chat-sessions \
        --clients 4,6,8 --seeds 1,2 --run-seconds 150 --windows 15,30,45

One set-up, then for each client count and each traffic seed one long run
of the closed loop; the run is cut afterwards into back-to-back windows of
each length, and every window is reduced under each counting rule:

  event       the benchmark's rule: tokens, first tokens and finished
              requests count by the time they reached the client
  completed   the rule this replaced: tokens and TTFTs of the requests that
              COMPLETED in the window, over the window's length

The spread (quartile distance over median, statistics.quantiles) of each
metric over the windows says how short a window may be, which rule
repeats, and where the closed loop's knee is. It writes one JSON file
under chiprun_out/dev_windows/ (copied to benchmark/records/ by hand).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
now = time.perf_counter


def spread(values: List[float]) -> Optional[float]:
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def window_metrics(records, t0: float, t1: float) -> Dict[str, Optional[float]]:
    from . import counting

    event = counting.serving_end_to_end(records, t0, t1)
    done = [r for r in counting.finished_in_window(records, t0, t1) if r.error is None]
    return {
        "event.serve_tokens_per_s": event["serve_tokens_per_s"],
        "event.ttft_mean_ms": event["ttft_mean_ms"],
        "event.tpot_mean_ms": event["tpot_mean_ms"],
        "completed.serve_tokens_per_s": sum(r.n_out for r in done) / (t1 - t0),
        "completed.ttft_mean_ms": (
            1e3 * statistics.fmean(r.t_first - r.t_submit for r in done) if done else None),
        "first_tokens": len(counting.ttfts_in_window(records, t0, t1)),
        "finished": len(done),
    }


def study(system, conf, traffic, clients: int, seed: int, run_seconds: float,
          windows: List[int]) -> Dict[str, Any]:
    from .kinds import closed_loop

    plan = closed_loop.plan_for(traffic, conf, seed, clients=clients)
    loop = closed_loop.ClosedLoop(system, plan)
    gc.collect()
    gc.freeze()
    t_start = now()
    loop.start()
    loop.wait_steady()
    c0 = system.counters()
    t_open = now()
    time.sleep(run_seconds)
    t_close = t_open + run_seconds
    c1 = system.counters()
    loop.stop()
    gc.unfreeze()
    system.wait_idle()
    out: Dict[str, Any] = {
        "clients": clients, "seed": seed, "lead_in_s": t_open - t_start,
        "run_seconds": run_seconds,
        "requests_per_s": len([r for r in loop.records if r.t_done and t_open <= r.t_done < t_close]) / run_seconds,
        "errors": [r.error for r in loop.records if r.error][:3],
        "counters": {k: c1[k] - c0[k] for k in
                     ("prefill_tokens", "decode_tokens", "decode_steps", "mixed_ticks",
                      "decode_blocks", "page_stalls") if k in c0},
        "whole_run": window_metrics(loop.records, t_open, t_close),
        "windows": {},
        # enough of every request to cut any window afterwards, relative to
        # the moment the window would have opened
        "requests": [
            {"client": r.client, "prompt_tokens": r.prompt_tokens,
             "submit": round(r.t_submit - t_open, 4),
             "done": None if r.t_done is None else round(r.t_done - t_open, 4),
             "tokens": [round(t - t_open, 4) for t in r.token_times]}
            for r in loop.records],
    }
    for w in windows:
        cuts = []
        k = 0
        while t_open + (k + 1) * w <= t_close:
            cuts.append(window_metrics(loop.records, t_open + k * w, t_open + (k + 1) * w))
            k += 1
        out["windows"][str(w)] = cuts
    return out


def summarise(runs: List[Dict[str, Any]], windows: List[int]) -> List[Dict[str, Any]]:
    """Per client count and window length: the median and the spread of
    every metric over all windows of all seeds."""
    rows = []
    for clients in sorted({r["clients"] for r in runs}):
        for w in windows:
            cuts = [c for r in runs if r["clients"] == clients for c in r["windows"][str(w)]]
            row: Dict[str, Any] = {"clients": clients, "window_s": w, "n_windows": len(cuts)}
            for key in cuts[0] if cuts else ():
                values = [c[key] for c in cuts if c[key] is not None]
                if values:
                    row[key] = {"median": statistics.median(values), "spread": spread(values)}
            rows.append(row)
    return rows


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--clients", default="")
    parser.add_argument("--seeds", default="1,2")
    parser.add_argument("--weights-seed", type=int, default=0)
    parser.add_argument("--run-seconds", type=float, default=150.0)
    parser.add_argument("--windows", default="15,30,45")
    parser.add_argument("--out", default="")
    parser.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                        help="override a key of the traffic file for this study")
    args = parser.parse_args(argv)

    from ray_tpu.core.compile_cache import ensure_compile_cache

    from . import harness, model_config
    from .kinds import closed_loop

    bench = harness.load_benchmark(spare=True)
    (cell,) = [w for w in bench["workloads"] if w["name"] == args.workload]
    conf = model_config.load_config(os.path.join(harness.BENCH_DIR, "configs", cell["config"] + ".json"))
    traffic = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    for item in args.set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)
    ensure_compile_cache()
    device = harness.device_record()
    if device["platform"] != "tpu":
        harness.log(f"needs a TPU chip; JAX found {device}")
        return 1
    windows = [int(w) for w in args.windows.split(",")]
    clients = [int(c) for c in args.clients.split(",")] if args.clients else [traffic["clients"]]
    t_begin = now()
    system = closed_loop.ServeSystem(conf, args.weights_seed)
    setup_s = now() - t_begin
    harness.log(f"set-up {setup_s:.1f}s")
    runs = []
    try:
        for c in clients:
            for seed in (int(s) for s in args.seeds.split(",")):
                result = study(system, conf, traffic, c, seed, args.run_seconds, windows)
                harness.log(json.dumps({k: result[k] for k in
                                    ("clients", "seed", "lead_in_s", "requests_per_s",
                                     "counters", "whole_run", "errors")}))
                runs.append(result)
    finally:
        system.shutdown()
    record = {
        "workload": args.workload, "device": device, "setup_s": setup_s,
        "engine": conf["engine"], "num_hidden_layers": conf.get("num_hidden_layers"),
        "traffic": {k: v for k, v in traffic.items() if not k.endswith("why")},
        "summary": summarise(runs, windows), "runs": runs,
    }
    out = args.out or os.path.join(ROOT, "chiprun_out", "dev_windows", args.workload + ".json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w", encoding="utf-8") as f:
        json.dump(record, f)
    for row in record["summary"]:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
