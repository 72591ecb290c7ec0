"""Command-line interface: `python -m ray_tpu <command>`.

Reference parity: `ray` CLI (/root/reference/python/ray/scripts/
scripts.py — `ray start` :706, `ray status`, `ray job submit` :1787,
`ray timeline`). TPU inversion: the runtime is in-process, so commands
that need a live cluster start one, act, and report — there is no
daemon to attach to. Job commands supervise real subprocesses; `doctor`
checks the JAX/TPU environment; `dashboard` serves the live view.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _cmd_doctor(args) -> int:
    """Environment sanity: devices, backend, config flags."""
    import jax

    print(f"python: {sys.version.split()[0]}")
    print(f"jax: {jax.__version__}")
    print(f"backend: {jax.default_backend()}")
    for d in jax.devices():
        print(f"device: {d} (kind={getattr(d, 'device_kind', '?')})")
    import ray_tpu

    rt = ray_tpu.init(detect_accelerators=not args.no_tpu)
    print(f"cluster resources: {rt.cluster_resources()}")

    @ray_tpu.remote
    def probe():
        return "ok"

    assert ray_tpu.get(probe.remote(), timeout=60) == "ok"
    print("task round-trip: ok")
    ray_tpu.shutdown()
    return 0


def _cmd_start(args) -> int:
    """Start a cluster head or join an existing cluster as a node agent
    (reference: `ray start --head` / `ray start --address=...`,
    /root/reference/python/ray/scripts/scripts.py:706). Blocks until
    SIGTERM/SIGINT or a shutdown_node RPC."""
    import ray_tpu

    if bool(args.head) == bool(args.address):
        print("pass exactly one of --head or --address", file=sys.stderr)
        return 2
    if args.snapshot_path:
        from .core.config import cfg

        cfg.set(gcs_snapshot_path=args.snapshot_path)
    if args.restore:
        from .core.config import cfg

        path = cfg.gcs_snapshot_path
        if not args.head:
            print("--restore only applies to --head", file=sys.stderr)
            return 2
        if not path or not (os.path.exists(path)
                            or os.path.exists(path + ".wal")):
            # the WAL alone is restorable: a head that died before its
            # first snapshot still replays every acknowledged write
            print(f"--restore: no snapshot or WAL at {path!r}",
                  file=sys.stderr)
            return 2
    rt = ray_tpu.init(
        num_cpus=args.num_cpus,
        resources=json.loads(args.resources) if args.resources else None,
        labels=json.loads(args.labels) if args.labels else None,
        detect_accelerators=not args.no_tpu,
        head=args.head,
        address=args.address,
        cluster_token=args.token,
        gcs_port=args.port,
    )
    ctx = rt.cluster
    if args.head:
        print(f"head up: gcs at {ctx.gcs_address}, node agent at {ctx.address}",
              flush=True)
        print(f"join with: python -m ray_tpu start --address {ctx.gcs_address}",
              flush=True)
    else:
        print(f"node {ctx.node_id.hex()[:12]} joined {args.address}, "
              f"agent at {ctx.address}", flush=True)
    # SIGTERM = announced preemption (cloud spot/maintenance semantics):
    # announce + drain for the warning window, then shut down gracefully.
    from .core.health import install_preemption_signal_handler

    install_preemption_signal_handler(ctx)
    try:
        while not ctx.shutdown_requested.wait(0.5):
            pass
    except KeyboardInterrupt:
        pass
    ray_tpu.shutdown()
    return 0


def _cmd_config(args) -> int:
    from .core.config import cfg

    print(cfg.describe())
    return 0


def _cmd_status(args) -> int:
    """Autoscaler-style cluster debug summary (reference `ray status`):
    per-node resources/usage/telemetry, pending demand, actors, PG
    states, object-store totals, recent warnings. --address joins an
    existing cluster as an observer; otherwise an in-process runtime is
    inspected. --json emits the machine shape instead."""
    import ray_tpu
    from .util import state

    if args.address:
        _observer_init(args)
        time.sleep(1.0)  # let the cluster view + node table populate
    else:
        ray_tpu.init(detect_accelerators=not args.no_tpu)
    if getattr(args, "autoscaler", False):
        # capacity-plane view only: managed nodes by type/class, pending
        # demand by origin, scale/replace/blocked counters
        scaler = state.autoscaler_summary()
        print(json.dumps(scaler if scaler is not None
                         else {"autoscaler": "not running"},
                         indent=2, default=str))
    elif args.json:
        print(json.dumps(state.summary(), indent=2, default=str))
    else:
        print(state.status_report(verbose=args.verbose))
    ray_tpu.shutdown()
    return 0


def _observer_init(args):
    import ray_tpu

    return ray_tpu.init(
        num_cpus=0, detect_accelerators=not args.no_tpu,
        address=args.address, cluster_token=args.token,
    )


def _cmd_up(args) -> int:
    """`ray up` equivalent over the launcher's provider abstraction
    (reference: python/ray/autoscaler/_private/commands.py)."""
    from .launcher import up_from_cli

    info = up_from_cli(args.config, no_tpu=args.no_tpu)
    print(f"cluster up: {len(info['nodes'])} nodes, head at {info['address']}")
    print(f"connect with: ray_tpu.init(address={info['address']!r})")
    return 0


def _cmd_down(args) -> int:
    from .launcher import down_from_cli

    stopped = down_from_cli(args.config)
    print(f"stopped {stopped} nodes")
    return 0


def _cmd_logs(args) -> int:
    """Aggregate log tails across the cluster (reference: `ray logs`
    routed through the per-node dashboard agents)."""
    import ray_tpu
    from .util import state

    _observer_init(args)
    time.sleep(1.0)  # let the cluster view populate
    for node_hex, lines in state.cluster_logs(tail=args.tail).items():
        print(f"=== node {node_hex[:12]} ===")
        for line in lines:
            print(line)
        print()
    ray_tpu.shutdown()
    return 0


def _fmt_event(e) -> str:
    ts = time.strftime("%H:%M:%S", time.localtime(e.get("ts", 0)))
    node = str(e.get("node") or "-")[:8]
    kind = e.get("kind") or "-"
    extra = f" {e['extra']}" if e.get("extra") else ""
    return (f"{ts} {e['severity']:7s} {node:8s} {kind:22s} "
            f"[{e['source']}] {e['message']}{extra}")


def _cmd_events(args) -> int:
    """The cluster-wide flight-recorder tail (merged + sorted by wall
    time), filterable by --kind/--node/--severity/--since; --follow
    keeps polling for new events until interrupted."""
    import ray_tpu
    from .util import state

    _observer_init(args)
    time.sleep(1.0)
    filters = dict(kind=args.kind, node=args.node, severity=args.severity)
    cursor = float(args.since or 0.0)
    try:
        while True:
            evs = state.events(limit=args.limit, since=cursor, **filters)
            # strictly-after cursor: events() is >=, so skip the boundary
            evs = [e for e in evs if e.get("ts", 0.0) > cursor or cursor == 0.0]
            for e in evs:
                print(_fmt_event(e), flush=True)
            if evs:
                cursor = max(e.get("ts", 0.0) for e in evs)
            if not args.follow:
                break
            time.sleep(args.poll)
    except KeyboardInterrupt:
        pass
    ray_tpu.shutdown()
    return 0


def _cmd_request(args) -> int:
    """Request forensics: `ray_tpu request <id>` renders the causally
    ordered phase waterfall of one request (cluster-wide marks joined on
    the shared request id); `ray_tpu request --list [--tenant t]
    [--slow]` prints the summary table the on-call triages from."""
    import ray_tpu
    from .serve import reqlog
    from .util import state

    _observer_init(args)
    time.sleep(1.0)  # let the federated _requests table populate
    try:
        if args.list or not args.request_id:
            rows = state.list_requests(
                tenant=args.tenant, slow_only=args.slow, limit=args.limit
            )
            if not rows:
                print("(no requests recorded)")
                return 0
            print(f"{'request_id':<22} {'tenant':<10} {'ttft_s':>8} "
                  f"{'marks':>5} {'last_phase':<21} terminal")
            for s in rows:
                ttft = s.get("ttft_s")
                ttft_txt = f"{ttft:.4f}" if ttft is not None else "-"
                print(f"{s['request_id']:<22} "
                      f"{str(s.get('tenant') or '-'):<10} "
                      f"{ttft_txt:>8} "
                      f"{s.get('marks', 0):>5} "
                      f"{s.get('last_phase', '-'):<21} "
                      f"{s.get('terminal') or '-'}")
            return 0
        marks = state.request_timeline(args.request_id)
        print(reqlog.render_waterfall(marks))
        return 0 if marks else 1
    finally:
        ray_tpu.shutdown()


def _cmd_steps(args) -> int:
    """Training forensics: `ray_tpu steps <run>` renders the per-rank
    step-phase waterfall of one run's sampled steps (buckets sum to step
    wall time, skew footers name the straggler rank and its dominant
    bucket); `ray_tpu steps --list` prints the cluster-wide sampled-step
    table."""
    import ray_tpu
    from .train import steplog
    from .util import state

    _observer_init(args)
    time.sleep(1.0)  # let the federated _steps table populate
    try:
        if args.list or not args.run:
            rows = state.list_steps(run=args.run, limit=args.limit)
            if not rows:
                print("(no sampled steps recorded)")
                return 0
            print(f"{'run':<18} {'step':>7} {'rank':>4} {'wall_s':>9} "
                  f"dominant_bucket")
            for s in rows:
                buckets = s.get("buckets") or {}
                top = max(buckets, key=buckets.get) if buckets else "-"
                wall = s.get("wall_s")
                wall_txt = f"{wall:.4f}" if wall is not None else "-"
                print(f"{str(s.get('run', '-')):<18} "
                      f"{s.get('step', 0):>7} "
                      f"{s.get('rank', 0):>4} "
                      f"{wall_txt:>9} "
                      f"{top}")
            return 0
        summaries = state.step_timeline(args.run, rank=args.rank)
        print(steplog.render_waterfall(summaries))
        return 0 if summaries else 1
    finally:
        ray_tpu.shutdown()


def _cmd_postmortem(args) -> int:
    """Snapshot events + spans + metrics + node stats + profile metas
    into one bundle archive with a reconstructed Perfetto episode
    timeline (util/postmortem)."""
    import ray_tpu
    from .util import state

    if args.address:
        _observer_init(args)
        time.sleep(1.0)  # let the cluster view + event table populate
    else:
        ray_tpu.init(detect_accelerators=not args.no_tpu)
    manifest = state.postmortem(args.output, note=args.note or "")
    counts = manifest["counts"]
    print(f"wrote {args.output}: {counts['events']} event(s), "
          f"{counts['spans']} span(s), {counts['nodes']} node(s), "
          f"{counts['profiles']} profile meta(s)")
    for name, meta in sorted(manifest["files"].items()):
        print(f"  {name}: {meta['bytes']} bytes sha256={meta['sha256'][:12]}")
    if manifest.get("errors"):
        print(f"  (degraded planes: {sorted(manifest['errors'])})")
    print("open the bundle's timeline.json in ui.perfetto.dev")
    ray_tpu.shutdown()
    return 0


def _cmd_job(args) -> int:
    from .jobs import default_job_manager

    mgr = default_job_manager()
    if args.job_cmd == "submit":
        jid = mgr.submit(args.entrypoint, job_id=args.job_id)
        print(f"submitted {jid}")
        if args.wait:
            status = mgr.wait(jid)
            print(mgr.logs(jid), end="")
            print(f"job {jid}: {status.value}")
            return 0 if status.value == "SUCCEEDED" else 1
        return 0
    if args.job_cmd == "list":
        for info in mgr.list():
            print(f"{info.job_id}  {info.status.value:9}  {info.entrypoint}")
        return 0
    if args.job_cmd == "logs":
        print(mgr.logs(args.job_id), end="")
        return 0
    if args.job_cmd == "status":
        print(mgr.status(args.job_id).value)
        return 0
    if args.job_cmd == "stop":
        print("stopped" if mgr.stop(args.job_id) else "not running")
        return 0
    raise SystemExit(f"unknown job command {args.job_cmd!r}")


def _collective_lines(rows) -> list:
    """A profile record's `collective_seconds` rows of one program (util/
    profiling.collective_seconds: by kind, mesh axes, scope and pass) as one
    line a (kind, axes), most device time first."""
    moved: dict = {}
    for row in rows:
        calls, nbytes, seconds = moved.get((row["kind"], row["axes"]), (0.0, 0.0, 0.0))
        moved[row["kind"], row["axes"]] = (
            calls + row["calls"], nbytes + row["bytes"], seconds + row["seconds"])
    return [f"{kind} over {axes or '(unplaced)'}: {calls:.0f} calls, "
            f"{nbytes / max(calls, 1) / 1e6:.2f} MB a call, {1e3 * seconds:.1f} ms, "
            f"{nbytes / max(seconds, 1e-12) / 1e9:.1f} GB/s"
            for (kind, axes), (calls, nbytes, seconds) in sorted(moved.items(), key=lambda kv: -kv[1][2])]


def _cmd_profile(args) -> int:
    """Coordinated cluster profile capture (reference: per-worker
    profiling behind `ray timeline`/the dashboard profiler buttons):
    fan a time-boxed device trace + host sampling profile out to the
    selected nodes, register the artifacts, optionally write them to
    --output, and print where everything landed."""
    import ray_tpu
    from .util import state

    if args.address:
        _observer_init(args)
        time.sleep(1.0)  # let the cluster view populate
    else:
        ray_tpu.init(detect_accelerators=not args.no_tpu)
    nodes = args.nodes.split(",") if args.nodes else None
    record = state.profile(
        nodes=nodes, duration_s=args.duration,
        device=not args.no_device, host=not args.no_host,
    )
    print(f"profile {record['profile_id']}: {len(record['nodes'])} node(s), "
          f"{record['duration_s']:.1f}s, {record['total_bytes']} bytes")
    for node_hex, meta in sorted(record["nodes"].items()):
        status = meta.get("error") or (
            f"device={meta.get('device')} host={meta.get('host')}"
        )
        print(f"  node {node_hex[:12]}: {status}")
        for name in meta.get("artifact_names", ()):
            print(f"    {name}")
        # a train step's device time by pass (util/profiling.scope_seconds)
        for program, split in sorted(meta.get("scope_seconds", {}).items()):
            total = max(split["total_s"], 1e-12)
            shares = dict(split["by_pass"], unscoped=split["unscoped_s"],
                          unmatched=split["unmatched_s"])
            print(f"    {program}: {split['total_s']:.3f} device s: " + ", ".join(
                f"{name} {100 * s / total:.1f}%" for name, s in shares.items()))
            for line in _collective_lines(meta.get("collective_seconds", {}).get(program, ())):
                print("      " + line)
    if args.output:
        from .core.runtime import get_runtime

        runtime = get_runtime()
        written = 0
        for key, data in runtime.profiles.artifacts_for(
            record["profile_id"]
        ).items():
            dest = os.path.join(args.output, record["profile_id"], key)
            os.makedirs(os.path.dirname(dest), exist_ok=True)
            with open(dest, "wb") as f:  # atomic-ok: export copy, not state
                f.write(data)
            written += 1
        print(f"wrote {written} artifact(s) under "
              f"{os.path.join(args.output, record['profile_id'])}")
    print("merge into a timeline with: ray_tpu timeline --profile-id "
          f"{record['profile_id']} (same session)")
    ray_tpu.shutdown()
    return 0


def _cmd_timeline(args) -> int:
    import ray_tpu
    from .util import state

    if not ray_tpu.is_initialized():
        print("no live runtime in this process; timeline covers the "
              "current session only", file=sys.stderr)
        ray_tpu.init(detect_accelerators=False)
    # span-based distributed trace (util/tracing): nested
    # submit→queue→dispatch→execute→result causality, stitched across
    # nodes. --trace is the historical opt-in; chrome_tracing_dump is a
    # deprecated alias of trace_dump now, so both paths export spans.
    # --profile-id merges a registered capture's device tracks in.
    state.trace_dump(args.output, trace_id=args.trace_id,
                     profile_id=args.profile_id)
    print(f"wrote {args.output} (open in chrome://tracing or Perfetto)")
    return 0


def _cmd_dashboard(args) -> int:
    import ray_tpu
    from .dashboard import start_dashboard

    ray_tpu.init(detect_accelerators=not args.no_tpu)
    url = start_dashboard(port=args.port)
    print(f"dashboard live at {url} (ctrl-c to stop)")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ray_tpu", description="ray_tpu cluster/runtime CLI"
    )
    p.add_argument("--no-tpu", action="store_true",
                   help="skip accelerator detection")
    sub = p.add_subparsers(dest="command", required=True)

    sub.add_parser("doctor", help="check the JAX/TPU environment")
    sub.add_parser("config", help="print all config flags")
    sp = sub.add_parser(
        "status", help="cluster debug summary: nodes, usage, telemetry"
    )
    sp.add_argument("--address", help="head GCS address to join as observer")
    sp.add_argument("--token", default=None)
    sp.add_argument("--verbose", "-v", action="store_true",
                    help="also show per-node log tails")
    sp.add_argument("--json", action="store_true",
                    help="emit state.summary() JSON instead of the report")
    sp.add_argument("--autoscaler", action="store_true",
                    help="emit only the capacity-plane (autoscaler) "
                         "status as JSON")

    st = sub.add_parser("start", help="start a cluster head or join one")
    st.add_argument("--head", action="store_true",
                    help="serve the GCS and become the head node")
    st.add_argument("--address", help="head GCS address (host:port) to join")
    st.add_argument("--port", type=int, default=0,
                    help="GCS port for --head (0 = ephemeral)")
    st.add_argument("--num-cpus", type=int, default=None)
    st.add_argument("--resources", default=None,
                    help='extra custom resources as JSON, e.g. \'{"GPU": 2}\'')
    st.add_argument("--labels", default=None,
                    help='node labels as JSON, e.g. \'{"zone": "us-a"}\'')
    st.add_argument("--token", default=None,
                    help="cluster auth token (required off-localhost)")
    st.add_argument("--launch-tag", default=None,
                    help="opaque tag embedded in the cmdline so the "
                         "launcher's `down` can target this cluster only")
    st.add_argument("--snapshot-path", default=None,
                    help="GCS snapshot file: the head persists its tables "
                         "here (same as RAY_TPU_GCS_SNAPSHOT_PATH)")
    st.add_argument("--restore", action="store_true",
                    help="with --head: require + replay the snapshot at "
                         "--snapshot-path so surviving agents re-register "
                         "(reference: Redis-backed GCS restart)")

    jp = sub.add_parser("job", help="submit/inspect driver jobs")
    jsub = jp.add_subparsers(dest="job_cmd", required=True)
    js = jsub.add_parser("submit")
    js.add_argument("entrypoint")
    js.add_argument("--job-id")
    js.add_argument("--wait", action="store_true",
                    help="block until the job finishes; tail its logs")
    jsub.add_parser("list")
    for name in ("logs", "status", "stop"):
        jx = jsub.add_parser(name)
        jx.add_argument("job_id")

    up = sub.add_parser("up", help="launch a cluster from a config file")
    up.add_argument("config", help="cluster YAML/JSON (see ray_tpu/launcher.py)")
    dn = sub.add_parser("down", help="terminate a cluster started with `up`")
    dn.add_argument("config")

    lp = sub.add_parser("logs", help="tail logs from every cluster node")
    lp.add_argument("--address", help="head GCS address to join as observer")
    lp.add_argument("--tail", type=int, default=50)
    lp.add_argument("--token", default=None)

    ep = sub.add_parser("events", help="typed cluster flight-recorder events")
    ep.add_argument("--address", help="head GCS address to join as observer")
    ep.add_argument("--limit", type=int, default=50)
    ep.add_argument("--token", default=None)
    ep.add_argument("--kind", default=None,
                    help="only events of this registered kind "
                         "(e.g. preempt.announced, ckpt.saved)")
    ep.add_argument("--node", default=None,
                    help="only events attributed to this node id hex prefix")
    ep.add_argument("--severity", default=None,
                    help="only events at this severity (case-insensitive)")
    ep.add_argument("--since", type=float, default=None,
                    help="only events with wall ts >= this epoch-seconds value")
    ep.add_argument("--follow", "-f", action="store_true",
                    help="keep polling and printing new events (ctrl-c stops)")
    ep.add_argument("--poll", type=float, default=1.0,
                    help="poll interval for --follow, seconds")

    rq = sub.add_parser(
        "request",
        help="per-request forensics: timeline waterfall or request list",
    )
    rq.add_argument("request_id", nargs="?", default=None,
                    help="request id to render (x-request-id / the "
                         "request_id echoed in responses); omit with "
                         "--list")
    rq.add_argument("--list", action="store_true",
                    help="list request summaries instead of one timeline")
    rq.add_argument("--tenant", default=None,
                    help="with --list: only this tenant's requests")
    rq.add_argument("--slow", action="store_true",
                    help="with --list: only SLO-violating or timed-out "
                         "requests")
    rq.add_argument("--limit", type=int, default=50)
    rq.add_argument("--address", help="head GCS address to join as observer")
    rq.add_argument("--token", default=None)

    st = sub.add_parser(
        "steps",
        help="training forensics: per-rank step waterfall or step list",
    )
    st.add_argument("run", nargs="?", default=None,
                    help="run name to render (RunConfig.name); omit with "
                         "--list")
    st.add_argument("--list", action="store_true",
                    help="list sampled-step summaries instead of one run's "
                         "waterfall")
    st.add_argument("--rank", type=int, default=None,
                    help="only this world rank's steps")
    st.add_argument("--limit", type=int, default=50)
    st.add_argument("--address", help="head GCS address to join as observer")
    st.add_argument("--token", default=None)

    pm = sub.add_parser(
        "postmortem", help="snapshot a causal postmortem bundle (.tgz)"
    )
    pm.add_argument("--output", default="postmortem.tgz",
                    help="bundle archive path")
    pm.add_argument("--note", default=None,
                    help="free-text note recorded in the bundle manifest")
    pm.add_argument("--address", help="head GCS address to join as observer")
    pm.add_argument("--token", default=None)

    tp = sub.add_parser("timeline", help="dump a chrome-trace of this session")
    tp.add_argument("output", nargs="?", default="timeline.json")
    tp.add_argument("--trace", action="store_true",
                    help="export runtime spans (distributed trace, nested "
                         "causality) instead of the legacy task timeline")
    tp.add_argument("--trace-id", default=None,
                    help="with --trace: export only this trace (stitched "
                         "cluster-wide)")
    tp.add_argument("--profile-id", default=None,
                    help="merge this registered capture's device-trace "
                         "events in as per-device tracks")

    pf = sub.add_parser(
        "profile", help="coordinated device/host profile capture"
    )
    pf.add_argument("--nodes", default=None,
                    help="comma-separated node id hex prefixes (default: "
                         "every alive node)")
    pf.add_argument("--duration", type=float, default=None,
                    help="capture window in seconds "
                         "(default: profile_default_duration_s)")
    pf.add_argument("--no-device", action="store_true",
                    help="skip the jax device trace")
    pf.add_argument("--no-host", action="store_true",
                    help="skip the host sampling profile")
    pf.add_argument("--output", default=None,
                    help="directory to write the captured artifacts into")
    pf.add_argument("--address", help="head GCS address to join as observer")
    pf.add_argument("--token", default=None)

    dp = sub.add_parser("dashboard", help="serve the cluster dashboard")
    dp.add_argument("--port", type=int, default=8265)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    handler = {
        "start": _cmd_start,
        "doctor": _cmd_doctor,
        "config": _cmd_config,
        "status": _cmd_status,
        "job": _cmd_job,
        "up": _cmd_up,
        "down": _cmd_down,
        "logs": _cmd_logs,
        "events": _cmd_events,
        "request": _cmd_request,
        "steps": _cmd_steps,
        "postmortem": _cmd_postmortem,
        "timeline": _cmd_timeline,
        "profile": _cmd_profile,
        "dashboard": _cmd_dashboard,
    }[args.command]
    return handler(args)


if __name__ == "__main__":
    raise SystemExit(main())
