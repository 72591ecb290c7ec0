"""The two longest multi-process drills of tests/test_cluster_pg.py and
tests/test_head_restart.py (a gang re-meshed after a node's death; a head
restarted under a surviving agent): 35-70 s each, so they live in a file of
few tests (the rule in tests/conftest.py). Clusters and helpers are the
origins'."""

import os
import signal
import subprocess
import sys
import tempfile
import time

import ray_tpu
from tests.test_cluster_pg import (  # noqa: F401 - `failover_cluster` is a fixture
    _HOST_ENV, _chaos_kill_node, _make_step_train_fn, _pg_event_states, failover_cluster)
from tests.test_head_restart import _ENV, _OBSERVER, _free_port, _spawn, _terminate, _wait_line


def test_cluster_gang_remesh_on_node_death(failover_cluster):
    """THE failover capstone: kill the agent hosting bundle 1 mid-train.
    The PG re-reserves on the spare node, the controller re-meshes the
    gang there with a freshly elected coordinator, training resumes from
    the latest checkpoint (steps never replay), and the loss curve
    continues to the end."""
    import threading

    from ray_tpu.train import (
        ClusterWorkerGroup,
        FailureConfig,
        RunConfig,
        RunStatus,
        ScalingConfig,
        TrainController,
    )

    pg = ray_tpu.placement_group(
        [{"CPU": 1, "gang": 1}, {"CPU": 1, "gang": 1}],
        strategy="STRICT_SPREAD",
    )
    assert pg.ready(timeout=10)
    victim_hex = pg.bundles[1].node.node_id.hex()

    groups = []

    def factory():
        group = ClusterWorkerGroup(
            num_workers=2,
            resources_per_worker={"CPU": 1, "gang": 1},
            run_name="failover-gang",
            env_per_worker=[dict(_HOST_ENV) for _ in range(2)],
            pg=pg,
            init_distributed=False,  # recovery paths under test, not SPMD
            pg_wait_s=60,
        )
        groups.append(group)
        return group

    total_steps = 40
    controller = TrainController(
        _make_step_train_fn(),
        ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 1, "gang": 1}
        ),
        RunConfig(name="failover-gang", failure=FailureConfig(max_failures=10)),
        train_config={"total_steps": total_steps, "step_s": 0.25},
        group_factory=factory,
        restart_backoff_s=0.5,
    )
    box = {}
    runner = threading.Thread(
        target=lambda: box.update(result=controller.run()), daemon=True
    )
    runner.start()

    # let training produce a few checkpointed steps, then kill bundle
    # 1's host mid-train
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and len(controller.metrics_history) < 3:
        time.sleep(0.1)
    assert controller.metrics_history, "gang never reported"
    _chaos_kill_node(pg.bundles[1].node.node_id)

    runner.join(timeout=240)
    assert not runner.is_alive(), "controller never finished after failover"
    result = box["result"]
    assert result.status == RunStatus.FINISHED, result.error
    assert result.error is None
    assert result.num_restarts >= 1

    # resumed from the latest checkpoint: steps strictly increase (no
    # replay, no gap) and reach the end; the loss curve continues
    steps = [m["step"] for m in result.metrics_history]
    assert steps[0] == 0
    assert steps[-1] == total_steps - 1
    assert steps == sorted(set(steps)), "steps replayed or reordered"
    losses = [m["loss"] for m in result.metrics_history]
    assert losses == sorted(losses, reverse=True), "loss curve broke"
    assert result.checkpoint_step == total_steps - 1

    # the PG re-reserved off the dead node...
    assert pg.state == "RESERVED"
    survivors = {b.node.node_id.hex() for b in pg.bundles}
    assert victim_hex not in survivors
    assert pg.reschedules_used >= 1
    # ...the re-meshed gang elected a NEW coordinator...
    assert len(groups) >= 2
    assert groups[-1]._coordinator != groups[0]._coordinator
    # ...and the event stream recorded the full transition sequence
    states = _pg_event_states(pg)
    assert states[0] == "RESERVED"
    assert "RESCHEDULING" in states
    assert states[-1] == "RESERVED"
    ray_tpu.remove_placement_group(pg)


def test_head_restart_restores_surviving_agent():
    tmp = tempfile.mkdtemp(prefix="ray_tpu_headrestart_")
    snap = os.path.join(tmp, "gcs.snap")
    port = _free_port()
    address = f"127.0.0.1:{port}"
    head_log = os.path.join(tmp, "head.log")
    agent_log = os.path.join(tmp, "agent.log")

    head_cmd = [
        sys.executable, "-m", "ray_tpu", "--no-tpu", "start", "--head",
        "--port", str(port), "--num-cpus", "1", "--snapshot-path", snap,
    ]
    head = _spawn(head_cmd, open(head_log, "w"))
    agent = None
    try:
        _wait_line(head_log, "head up", proc=head)
        agent = _spawn(
            [sys.executable, "-m", "ray_tpu", "--no-tpu", "start",
             "--address", address, "--num-cpus", "2",
             "--resources", '{"pet": 3}'],
            open(agent_log, "w"),
        )
        _wait_line(agent_log, "joined", proc=agent)

        # observer 1: the agent's resources are visible pre-kill
        out = subprocess.run(
            [sys.executable, "-c", _OBSERVER, address, "pet", "3"],
            env=_ENV, capture_output=True, text=True, timeout=120,
        )
        assert "OBSERVER-OK" in out.stdout, out.stdout + out.stderr
        agent_pid_1 = int(out.stdout.split("OBSERVER-OK")[1].strip())
        assert agent_pid_1 == agent.pid

        # give the snapshot loop a beat to persist the node table
        time.sleep(2.0)

        # kill the head hard; the agent keeps running (heartbeats warn)
        head.send_signal(signal.SIGKILL)
        head.wait(timeout=30)
        time.sleep(1.0)
        assert agent.poll() is None, "agent must survive head death"

        # restart the head from the snapshot, same port
        head = _spawn(head_cmd + ["--restore"], open(head_log, "a"))
        _wait_line(head_log, "head up", proc=head)

        # observer 2: the surviving agent (same pid!) re-registered and
        # still executes work — no agent restart happened
        out = subprocess.run(
            [sys.executable, "-c", _OBSERVER, address, "pet", "3"],
            env=_ENV, capture_output=True, text=True, timeout=120,
        )
        assert "OBSERVER-OK" in out.stdout, out.stdout + out.stderr
        agent_pid_2 = int(out.stdout.split("OBSERVER-OK")[1].strip())
        assert agent_pid_2 == agent.pid == agent_pid_1
    finally:
        _terminate(head, agent)
