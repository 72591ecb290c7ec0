"""Spot-fleet cluster drill: the capacity plane closing the loop.

A 2-worker training gang and a serve deployment share ONE autoscaled
spot cluster: every worker node exists because the CapacityAutoscaler
aggregated demand (gang bundles, replica actors) and launched it.
Scheduled preemptions with warning windows then reclaim BOTH fleets'
nodes, one after the other:

- the training gang emergency-checkpoints inside the warning window and
  re-meshes onto replacement capacity that was pre-provisioned BEFORE
  the old node died, finishing with `max_failures=0` (only the
  preemption budget is consumed);
- serve rides its node's reclaim through replica restarts on the
  replacement, surfacing only TYPED errors to the open client loop;
- the whole episode reconstructs from one `state.postmortem()` bundle:
  `preempt.announced` -> `autoscaler.replace` -> `node.dead` per victim,
  and the run's wall time fully attributed to goodput buckets.

One JSON line reports the episode. No process of this drill opens the
chip: the driver runs with `detect_accelerators=False` and every agent it
spawns is held to JAX_PLATFORMS=cpu.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time


def _emit_result(payload: dict) -> None:
    """Print the ONE result line. These drills check cluster BEHAVIOUR on
    CPU processes (`detect_accelerators=False`, agents under
    JAX_PLATFORMS=cpu): the line says so, carries counts and wall-clock
    readings of this host, and nothing is written into the checkout."""
    print(json.dumps({**payload, "platform": "cpu",
                      "note": "behaviour drill on CPU processes; no "
                              "device metric"}))


def _first_ts(evs, kind, **match):
    for e in evs:
        if e.get("kind") != kind:
            continue
        extra = e.get("extra") or {}
        if all(extra.get(k) == v for k, v in match.items()):
            return e["ts"]
    return None


def _ordered(evs, victim_hex):
    """preempt.announced -> autoscaler.replace -> node.dead for one
    reclaimed node, on the bundle's shared wall clock."""
    announced = _first_ts(
        [e for e in evs if e.get("node") == victim_hex], "preempt.announced"
    )
    replace = _first_ts(evs, "autoscaler.replace", replaces=victim_hex)
    dead = _first_ts(
        [e for e in evs if e.get("node") == victim_hex], "node.dead"
    )
    if None in (announced, replace, dead):
        return False
    return announced <= replace <= dead


def run_head_outage(args) -> None:
    """Head fault-tolerance drill: chaos SIGKILLs the HEAD out of its own
    snapshot loop while (a) a KV writer keeps committing state, (b) task
    traffic keeps dispatching to a worker agent, and (c) a stateful
    "trainer" actor keeps stepping on that agent. The head restarts with
    --restore on the same port; the drill passes when every ACKNOWLEDGED
    write is still readable, no client surfaced an untyped error, a
    pre-restart writer is epoch-fenced, and the agent (and the actor in
    it) rode through without a process restart. Reports
    recovery-time-to-ready: head death -> first acknowledged write
    against the restored head."""
    import signal
    import socket
    import subprocess

    from ray_tpu.core.exceptions import RayTpuError, StaleEpochError
    from ray_tpu.core.gcs_service import GcsClient

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    address = f"127.0.0.1:{port}"
    workdir = tempfile.mkdtemp(prefix="bench_head_outage_")
    snap = os.path.join(workdir, "gcs.snap")
    base_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                "RAY_TPU_NODE_HEARTBEAT_S": "0.2",
                "RAY_TPU_NODE_STALE_S": "2.5",
                "RAY_TPU_GCS_SNAPSHOT_INTERVAL_S": "0.5"}
    base_env.pop("RAY_TPU_CHAOS", None)
    chaos_env = {**base_env, "RAY_TPU_CHAOS":
                 f"kill_head=1,delay_s={args.outage_delay_s},"
                 "max_injections=1"}

    def spawn(cmd, log_path, env, mode="w"):
        return subprocess.Popen(cmd, env=env, stdout=open(log_path, mode),
                                stderr=subprocess.STDOUT, text=True)

    def wait_line(log_path, needle, timeout=90, proc=None):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if proc is not None and proc.poll() is not None:
                break
            with open(log_path) as f:
                if needle in f.read():
                    return
            time.sleep(0.2)
        with open(log_path) as f:
            raise AssertionError(f"never saw {needle!r} in:\n{f.read()}")

    head_cmd = [sys.executable, "-m", "ray_tpu", "--no-tpu", "start",
                "--head", "--port", str(port), "--num-cpus", "1",
                "--snapshot-path", snap]
    head = spawn(head_cmd, os.path.join(workdir, "head.log"), chaos_env)
    agent = None
    rc = 1
    acked: list = []
    writer_errors: list = []
    traffic_ok = [0]
    traffic_ok_during_outage = [0]
    traffic_typed: list = []
    traffic_untyped: list = []
    stop = threading.Event()
    outage = threading.Event()

    import ray_tpu

    try:
        wait_line(os.path.join(workdir, "head.log"), "head up", proc=head)
        agent = spawn(
            [sys.executable, "-m", "ray_tpu", "--no-tpu", "start",
             "--address", address, "--num-cpus", "2",
             "--resources", '{"drill": 2}'],
            os.path.join(workdir, "agent.log"), base_env)
        wait_line(os.path.join(workdir, "agent.log"), "joined", proc=agent)

        ray_tpu.init(address=address, num_cpus=0, detect_accelerators=False)
        deadline = time.monotonic() + 60
        while ray_tpu.cluster_resources().get("drill", 0) < 2:
            assert time.monotonic() < deadline, (
                f"agent resources never appeared: "
                f"{ray_tpu.cluster_resources()}")
            time.sleep(0.2)

        @ray_tpu.remote(num_cpus=0, resources={"drill": 1})
        def echo(x):
            return f"ok-{x}"

        @ray_tpu.remote(num_cpus=0, resources={"drill": 1})
        class Trainer:
            def __init__(self):
                self.step_count = 0

            def step(self):
                import os as _os
                self.step_count += 1
                return {"step": self.step_count, "pid": _os.getpid()}

        trainer = Trainer.remote()
        pre = ray_tpu.get(trainer.step.remote(), timeout=60)
        assert ray_tpu.get(echo.remote(0), timeout=60) == "ok-0"

        def writer():
            # the retry window spans kill + restore: every put either
            # acks or retries invisibly; ANY surfaced error fails the
            # drill (acked writes are the durability ledger)
            c = GcsClient(address, retry_window_s=90.0)
            c.adopt_epoch()
            i = 0
            while not stop.is_set():
                try:
                    if c.kv_put(f"w{i}", {"i": i}, namespace="bench"):
                        acked.append(i)
                except Exception as exc:  # noqa: BLE001 - the verdict
                    writer_errors.append(exc)
                i += 1
                time.sleep(0.05)

        def traffic():
            # data-plane traffic: dispatch goes DIRECT to the node agent,
            # so requests should keep succeeding while the head is down;
            # any failure must at least be TYPED
            i = 1
            while not stop.is_set():
                try:
                    assert ray_tpu.get(echo.remote(i), timeout=20) == f"ok-{i}"
                    traffic_ok[0] += 1
                    if outage.is_set():
                        traffic_ok_during_outage[0] += 1
                except RayTpuError as exc:
                    traffic_typed.append(exc)
                except Exception as exc:  # noqa: BLE001 - the verdict
                    traffic_untyped.append(exc)
                i += 1
                time.sleep(0.05)

        threads = [threading.Thread(target=writer, daemon=True),
                   threading.Thread(target=traffic, daemon=True)]
        for t in threads:
            t.start()

        zombie = GcsClient(address, retry_window_s=45.0)
        epoch_before = zombie.adopt_epoch()
        zombie.pin_epoch(epoch_before)

        # chaos fires outage_delay_s after the head armed it at init
        head.wait(timeout=120)
        assert head.returncode == 137, \
            f"head should die by chaos, got rc={head.returncode}"
        t_dead = time.monotonic()
        outage.set()
        acked_at_death = len(acked)
        assert agent.poll() is None, "agent must survive the head kill"

        head = spawn(head_cmd + ["--restore"],
                     os.path.join(workdir, "head2.log"), base_env)
        wait_line(os.path.join(workdir, "head2.log"), "head up", proc=head)

        probe = GcsClient(address, retry_window_s=45.0)
        ready_deadline = time.monotonic() + 60
        while probe.kv_get("w0", namespace="bench") is None:
            assert time.monotonic() < ready_deadline, "restore never ready"
            time.sleep(0.05)
        recovery_ready_s = time.monotonic() - t_dead
        outage.clear()

        # let post-recovery traffic accumulate, then settle the ledger
        deadline = time.monotonic() + 30
        while len(acked) <= acked_at_death + 10 and \
                time.monotonic() < deadline:
            time.sleep(0.1)
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=30)

        missing = [i for i in acked
                   if probe.kv_get(f"w{i}", namespace="bench") is None]
        fenced = False
        try:
            zombie.kv_put("zombie", 1, namespace="bench")
        except StaleEpochError:
            fenced = True
        epoch_after = probe.head_info()["epoch"]

        post = ray_tpu.get(trainer.step.remote(), timeout=60)
        trainer_rode_through = (post["pid"] == pre["pid"]
                                and post["step"] > pre["step"])

        ok = (
            not missing
            and not writer_errors
            and not traffic_untyped
            and len(acked) > acked_at_death + 10
            and fenced and epoch_after > epoch_before
            and trainer_rode_through
            and agent.poll() is None
        )
        rc = 0 if ok else 1
        _emit_result({
            "metric": "head_outage_recovery_ready_s",
            "value": round(recovery_ready_s, 3),
            "unit": "seconds",
            "vs_baseline": 0.0,
            "passed": ok,
            "drill": "head_outage",
            "acked_writes": len(acked),
            "acked_writes_at_death": acked_at_death,
            "acked_writes_lost": len(missing),
            "writer_errors": len(writer_errors),
            "traffic_ok": traffic_ok[0],
            "traffic_ok_during_outage": traffic_ok_during_outage[0],
            "traffic_typed_errors": len(traffic_typed),
            "traffic_untyped_errors": len(traffic_untyped),
            "stale_writer_fenced": fenced,
            "epoch_before": epoch_before,
            "epoch_after": epoch_after,
            "trainer_rode_through": trainer_rode_through,
            "trainer_steps": post["step"],
            "agent_survived": agent.poll() is None,
            "wal": probe.head_info().get("wal"),
        }, rc)
    finally:
        stop.set()
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass
        for proc in (head, agent):
            if proc is not None and proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
    sys.exit(rc)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--drill", choices=("spot_fleet", "head_outage"),
                    default="spot_fleet",
                    help="spot_fleet: autoscaled preemption episode; "
                    "head_outage: chaos head SIGKILL + WAL restore")
    ap.add_argument("--steps", type=int, default=60,
                    help="training steps per run")
    ap.add_argument("--workers", type=int, default=2,
                    help="training gang size (one spot node per worker)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="serve replicas (they share one spot node)")
    ap.add_argument("--warning-s", type=float, default=2.0,
                    help="preemption warning window")
    ap.add_argument("--outage-delay-s", type=float, default=8.0,
                    help="head_outage: seconds after head start when "
                    "chaos kills it")
    args = ap.parse_args()

    if args.drill == "head_outage":
        run_head_outage(args)
        return

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.capacity import (
        CapacityAutoscaler, FakeNodeProvider, NodeType, SpotNodeProvider,
    )
    from ray_tpu.core.exceptions import RayTpuError
    from ray_tpu.train import (
        FailureConfig, RunConfig, RunStatus, ScalingConfig, TrainController,
    )
    from ray_tpu.util import state
    from ray_tpu.util.events import events
    from ray_tpu.util.postmortem import load_bundle

    workdir = tempfile.mkdtemp(prefix="bench_cluster_")
    rt = ray_tpu.init(num_cpus=1, detect_accelerators=False)
    scaler = None
    rc = 1
    try:
        events().clear()
        provider = SpotNodeProvider(FakeNodeProvider(rt.scheduler),
                                    warning_s=args.warning_s)
        scaler = CapacityAutoscaler(
            rt.scheduler, provider,
            [
                NodeType("spot-train", {"CPU": 1.0, "trainer": 1.0},
                         capacity_class="spot"),
                NodeType("spot-serve",
                         {"CPU": float(args.replicas),
                          "serve_slot": float(args.replicas)},
                         capacity_class="spot"),
            ],
            poll_interval_s=0.05, idle_timeout_s=60.0, runtime=rt,
        )
        scaler.start()

        @serve.deployment(num_replicas=args.replicas,
                          resources_per_replica={"CPU": 1.0,
                                                 "serve_slot": 1.0})
        class Echo:
            def __call__(self, x):
                return f"ok-{x}"

        handle = serve.run(Echo.bind(), name="fleet-echo")
        assert ray_tpu.get(handle.remote(0), timeout=60) == "ok-0"

        total_steps = args.steps

        def train_fn(config):
            from ray_tpu import train

            ctx = train.get_context()
            ckpt = train.get_checkpoint()
            start = int(ckpt["step"]) + 1 if ckpt is not None else 0
            for step in range(start, total_steps):
                time.sleep(0.02)
                if ctx.world_rank != 0:
                    if train.is_preempted():
                        return "preempted"
                    continue
                if train.should_checkpoint():
                    train.report({"step": step}, checkpoint={"step": step},
                                 checkpoint_step=step)
                elif train.is_preempted():
                    return "preempted"
                elif step % 10 == 9:
                    train.report({"step": step}, checkpoint={"step": step},
                                 checkpoint_step=step)
                else:
                    train.report({"step": step})
            return "done"

        controller = TrainController(
            train_fn,
            ScalingConfig(num_workers=args.workers,
                          resources_per_worker={"CPU": 1.0, "trainer": 1.0}),
            RunConfig(name="fleet-train",
                      storage_path=os.path.join(workdir, "trial"),
                      failure=FailureConfig(max_failures=0)),
            train_config={},
            restart_backoff_s=0.0,
        )
        box = {}
        thread = threading.Thread(
            target=lambda: box.update(result=controller.run()), daemon=True
        )
        thread.start()

        serve_ok = [0]
        serve_errors: list = []
        stop_serving = threading.Event()

        def client_loop():
            i = 1
            while not stop_serving.is_set():
                try:
                    out = ray_tpu.get(handle.remote(i), timeout=30)
                    assert out == f"ok-{i}"
                    serve_ok[0] += 1
                except Exception as exc:  # noqa: BLE001 - tallied, typedness checked below
                    serve_errors.append(exc)
                i += 1
                time.sleep(0.05)

        client = threading.Thread(target=client_loop, daemon=True)
        client.start()

        deadline = time.monotonic() + 60
        while not controller.metrics_history and time.monotonic() < deadline:
            time.sleep(0.02)
        assert controller.metrics_history, "gang never started reporting"

        # ---- preemption 1: a gang-hosting train node
        train_victim = next(
            n for n in rt.scheduler.nodes()
            if n.labels.get("node_type") == "spot-train"
            and rt.scheduler.resident_bundles(n.node_id.hex())
        )
        provider.preempt_after(train_victim, 0.01, warning_s=args.warning_s)

        thread.join(timeout=180)
        assert not thread.is_alive(), "controller never finished"
        result = box["result"]

        # ---- preemption 2: the serve node; replicas must come back
        serve_victim = next(
            n for n in rt.scheduler.nodes()
            if n.labels.get("node_type") == "spot-serve" and n.alive
        )
        provider.preempt_after(serve_victim, 0.01, warning_s=args.warning_s)
        deadline = time.monotonic() + 30
        while serve_victim.alive and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not serve_victim.alive, "serve node never reclaimed"
        # recovered = replicas live again AND a fresh request round-trips
        recovered = False
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            status = serve.status().get("fleet-echo", {})
            if status.get("live_replicas", 0) >= args.replicas:
                try:
                    if ray_tpu.get(handle.remote("post"),
                                   timeout=10) == "ok-post":
                        recovered = True
                        break
                except RayTpuError:
                    pass
            time.sleep(0.1)
        stop_serving.set()
        client.join(timeout=30)

        # the train victim's reclaim also has to land before we bundle
        deadline = time.monotonic() + 30
        while train_victim.alive and time.monotonic() < deadline:
            time.sleep(0.05)

        untyped = [e for e in serve_errors if not isinstance(e, RayTpuError)]

        # ---- one bundle reconstructs the whole episode
        bundle_path = os.path.join(workdir, "episode.tgz")
        state.postmortem(bundle_path, note="spot-fleet bench drill")
        evs = load_bundle(bundle_path)["events.jsonl"]
        train_order_ok = _ordered(evs, train_victim.node_id.hex())
        serve_order_ok = _ordered(evs, serve_victim.node_id.hex())

        goodput = result.goodput or {}
        buckets = goodput.get("buckets", {})
        ok = (
            result.status == RunStatus.FINISHED
            and result.num_preempt_restarts == 1
            and scaler.stats["replacements"] >= 2
            and train_order_ok and serve_order_ok
            and recovered and not untyped
        )
        rc = 0 if ok else 1
        _emit_result({
            "metric": "cluster_spot_fleet_goodput_fraction",
            "value": round(goodput.get("goodput_fraction", 0.0), 3),
            "unit": "fraction",
            "vs_baseline": 0.0,
            "passed": ok,
            "train_status": str(result.status),
            "steps": total_steps,
            "workers": args.workers,
            "num_preempt_restarts": result.num_preempt_restarts,
            "max_failures_burned": 0 if result.status == RunStatus.FINISHED
            else 1,
            "preemptions": provider.num_preemptions(),
            "warning_s": args.warning_s,
            "scale_ups": scaler.stats["scale_ups"],
            "scale_downs": scaler.stats["scale_downs"],
            "replacements": scaler.stats["replacements"],
            "train_event_order_ok": train_order_ok,
            "serve_event_order_ok": serve_order_ok,
            "serve_recovered": recovered,
            "serve_requests_ok": serve_ok[0],
            "serve_typed_errors": len(serve_errors) - len(untyped),
            "serve_untyped_errors": len(untyped),
            "wall_time_s": round(goodput.get("wall_time_s", 0.0), 3),
            "goodput_buckets": {k: round(v, 3) for k, v in buckets.items()},
            "postmortem_bundle": bundle_path,
        }, rc)
        serve.shutdown()
    finally:
        if scaler is not None:
            scaler.stop()
        ray_tpu.shutdown()
    sys.exit(rc)


if __name__ == "__main__":
    main()
