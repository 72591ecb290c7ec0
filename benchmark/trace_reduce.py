"""Profiler trace (.xplane.pb) -> device busy/idle, time by operation name,
time by program, exposed collective time, idle gaps named by what the host
was doing.

The reduction works on a plain structure, so that it can be checked against
a small recorded trace without the profiler:

    planes = [{"name": str, "lines": [{"name": str,
               "events": [[name, start_ns, duration_ns], ...]}]}]

What a v5e trace looks like (looked at by hand, PERF.md): each chip is a
plane "/device:TPU:<n>" with the lines "XLA Modules" (one event per program
run, named "jit_<fn>(<hash>)"), "XLA Ops" (one event per HLO operation,
named by its HLO text; operations inside a `while` are listed as well as
the `while` itself) and "Async XLA Ops". The host is the plane "/host:CPU"
with one line per thread.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
# operations that only contain other listed operations
CONTAINERS = frozenset({"while", "conditional", "call"})
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"\b(?:pred|[suf]\d+|bf16)\[[\d,]*\]")
_SUFFIX = re.compile(r"(\.\d+)+$")

Interval = Tuple[float, float]


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load_xplane(path: str) -> List[Dict[str, Any]]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return [
        {"name": plane.name, "lines": [
            {"name": line.name,
             "events": [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]}
            for line in plane.lines]}
        for plane in data.planes
    ]


def parse_op(event_name: str) -> Dict[str, str]:
    """HLO text of an "XLA Ops" event -> its name, opcode and first result
    shape. Kernel calls (custom-call) lose their instance suffix, so that
    the per-layer copies of one kernel add up under one key."""
    name, _, rest = event_name.partition(" = ")
    name = name.lstrip("%").strip()
    opcode = _OPCODE.search(" " + rest) if rest else None
    shape = _SHAPE.search(rest) if rest else None
    op = {"name": name, "opcode": opcode.group(1) if opcode else "",
          "shape": shape.group(0) if shape else ""}
    if op["opcode"] == "custom-call":
        op["name"] = _SUFFIX.sub("", name)
    return op


def _key(op: Dict[str, str]) -> str:
    return " ".join(part for part in (op["name"], op["opcode"], op["shape"]) if part)


def _collective(op: Dict[str, str]) -> bool:
    return any(c in op["opcode"] or op["name"].startswith(c) for c in COLLECTIVES)


def op_key(event_name: str) -> str:
    return _key(parse_op(event_name))


def is_collective(event_name: str) -> bool:
    return _collective(parse_op(event_name))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of the (merged) intervals `a` that no interval of `b` covers."""
    out: List[Interval] = []
    b = list(b)
    j = 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def _line(plane: Dict[str, Any], name: str) -> List[List[Any]]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _iv(events: Iterable[Sequence[Any]]) -> List[Interval]:
    return [(e[1], e[1] + e[2]) for e in events]


def _host_events(planes: Sequence[Dict[str, Any]], skip: Sequence[str]) -> List[List[Any]]:
    out = []
    for plane in planes:
        if plane["name"] == HOST_PLANE:
            for line in plane["lines"]:
                out.extend(e for e in line["events"] if e[2] > 0 and e[0] not in skip)
    return out


def _name_gap(gap: Interval, host: Sequence[Sequence[Any]]) -> str:
    """The host event that covers most of the gap; among equals the
    shortest, which is the most specific."""
    best, best_rank = "(no host event)", (0.0, 0.0)
    for name, start, dur in host:
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap <= 0:
            continue
        rank = (overlap, -dur)
        if rank > best_rank:
            best, best_rank = name, rank
    return best


def reduce_trace(planes: Sequence[Dict[str, Any]], *,
                 skip_host: Sequence[str] = ("bench_window",),
                 top: int = 10) -> Dict[str, Any]:
    """All numbers in seconds. Busy, collective and per-name times are
    averaged over the chips that ran anything; idle gaps are chip 0's."""
    devices = sorted(
        (p for p in planes if DEVICE_PLANE.match(p["name"])),
        key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)),
    )
    host = _host_events(planes, skip_host)
    per_device = []
    for plane in devices:
        ops = _line(plane, OPS_LINE)
        if not ops:
            continue
        busy = union(_iv(ops))
        by_key: Dict[str, float] = {}
        by_name: Dict[str, float] = {}
        counts: Dict[str, int] = {}
        compute, collective = [], []
        for e in ops:
            op = parse_op(e[0])
            if op["opcode"] in CONTAINERS:
                continue
            key = _key(op)
            by_key[key] = by_key.get(key, 0.0) + e[2]
            by_name[op["name"]] = by_name.get(op["name"], 0.0) + e[2]
            counts[op["name"]] = counts.get(op["name"], 0) + 1
            (collective if _collective(op) else compute).append((e[1], e[1] + e[2]))
        collective.extend(_iv(e for e in _line(plane, ASYNC_LINE) if is_collective(e[0])))
        collective_u, compute_u = union(collective), union(compute)
        programs: Dict[str, List[float]] = {}
        for e in _line(plane, MODULES_LINE):
            programs.setdefault(e[0].split("(")[0], []).append(e[2])
        per_device.append({
            "busy": busy, "by_key": by_key, "by_name": by_name, "counts": counts,
            "collective_s": length(collective_u) / 1e9,
            "collective_exposed_s": length(subtract(collective_u, compute_u)) / 1e9,
            "programs": programs,
        })
    if not per_device:
        return {"devices": 0, "busy_s": 0.0}
    n = len(per_device)

    def mean_map(field: str) -> Dict[str, float]:
        keys = set().union(*(d[field] for d in per_device))
        return {k: sum(d[field].get(k, 0.0) for d in per_device) / n / 1e9 for k in keys}

    first = per_device[0]
    gaps = [(a, b) for (_, a), (b, _) in zip(first["busy"], first["busy"][1:])]
    gaps.sort(key=lambda g: g[0] - g[1])
    by_key = mean_map("by_key")
    return {
        "devices": n,
        "busy_s": sum(length(d["busy"]) for d in per_device) / n / 1e9,
        "span_s": (first["busy"][-1][1] - first["busy"][0][0]) / 1e9,
        "op_seconds": mean_map("by_name"),
        "op_counts": {k: sum(d["counts"].get(k, 0) for d in per_device) / n
                      for k in set().union(*(d["counts"] for d in per_device))},
        "program_seconds": {
            name: [x / 1e9 for x in runs] for name, runs in first["programs"].items()},
        "collective_s": sum(d["collective_s"] for d in per_device) / n,
        "collective_exposed_s": sum(d["collective_exposed_s"] for d in per_device) / n,
        "breakdown": {
            "device_ops": [[k, v] for k, v in
                           sorted(by_key.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_name_gap(g, host), (g[1] - g[0]) / 1e9] for g in gaps[:top]],
        },
    }


def seconds_of(reduced: Dict[str, Any], prefixes: Sequence[str]) -> float:
    """Device seconds of every operation whose name starts with a prefix."""
    return sum(v for k, v in reduced.get("op_seconds", {}).items()
               if k.startswith(tuple(prefixes)))


def count_of(reduced: Dict[str, Any], prefixes: Sequence[str]) -> float:
    return sum(v for k, v in reduced.get("op_counts", {}).items()
               if k.startswith(tuple(prefixes)))
