"""Head fault-tolerance suite (round-4 verdict #10, grown into the head
fault-tolerance plane): WAL durability beats snapshot-only restore, torn
journal tails are quarantined, epoch fencing rejects stale writers,
clients degrade with typed errors through an outage, the serve router
keeps dispatching on cached membership, a restarted head reconciles
restored-but-gone state, and the chaos kill_head capstone drives serve
traffic and KV writes through a head SIGKILL + restore with zero
acknowledged-write loss.

Reference: Redis-backed GCS restart (gcs_table_storage.h:275,
gcs_redis_failure_detector.h:35) where raylets outlive the GCS.
"""

import logging
import os
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

import pytest

from ray_tpu.core.exceptions import HeadUnavailableError, StaleEpochError
from ray_tpu.core.gcs import GcsWal, GlobalControlStore
from ray_tpu.core.gcs_service import GcsClient, serve_gcs


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
        "RAY_TPU_NODE_HEARTBEAT_S": "0.2", "RAY_TPU_NODE_STALE_S": "2.5",
        "RAY_TPU_GCS_SNAPSHOT_INTERVAL_S": "0.5"}
_ENV.pop("RAY_TPU_CHAOS", None)

_OBSERVER = textwrap.dedent(
    """
    import sys, time
    import ray_tpu

    address, resource, want = sys.argv[1], sys.argv[2], float(sys.argv[3])
    ray_tpu.init(address=address, num_cpus=0, detect_accelerators=False)
    deadline = time.monotonic() + 60
    while ray_tpu.cluster_resources().get(resource, 0) < want:
        assert time.monotonic() < deadline, (
            f"never saw {resource}>={want}: {ray_tpu.cluster_resources()}"
        )
        time.sleep(0.2)

    @ray_tpu.remote(num_cpus=0, resources={resource: 1})
    def where():
        import os
        return os.getpid()

    pid = ray_tpu.get(where.remote(), timeout=60)
    ray_tpu.shutdown()
    print(f"OBSERVER-OK {pid}")
    """
)


def _spawn(cmd, log, env=None):
    return subprocess.Popen(
        cmd, env=env or _ENV, stdout=log, stderr=subprocess.STDOUT, text=True
    )


def _wait_line(path, needle, timeout=90, proc=None):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            break
        with open(path) as f:
            if needle in f.read():
                return
        time.sleep(0.2)
    with open(path) as f:
        raise AssertionError(f"never saw {needle!r} in:\n{f.read()}")


def _terminate(*procs):
    for proc in procs:
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()


# --------------------------------------------------------------------------
# durability: WAL + snapshot
# --------------------------------------------------------------------------


def test_wal_replay_beats_snapshot_only(tmp_path):
    """Every acknowledged mutation AFTER the last snapshot comes back from
    the journal; a snapshot-only restore provably loses them."""
    snap = str(tmp_path / "gcs.snap")
    wal = snap + ".wal"

    a = GlobalControlStore()
    a.attach_wal(wal)
    a.kv.put("pre", 1)
    a.snapshot(snap)
    # mutations the snapshot never saw
    a.kv.put("post", {"x": 2})
    a.kv.put("pre", "rewritten")
    a.kv.delete("pre")
    a.register_named_actor("late-actor", object())

    snap_only = GlobalControlStore()
    snap_only.restore(snap, wal_path=None)
    assert snap_only.kv.get("pre") == 1  # stale: the crash would lose data
    assert snap_only.kv.get("post") is None

    b = GlobalControlStore()
    b.restore(snap, wal_path=wal)
    assert b.kv.get("post") == {"x": 2}
    assert b.kv.get("pre") is None  # the delete replayed too
    # named-actor registrations journal as placeholders: the NAME survives
    # (handles are process-local and must be re-created)
    assert "late-actor" in b.list_named_actors()
    assert b.last_restore["wal_records_applied"] >= 3


def test_wal_only_restart_without_snapshot(tmp_path):
    """A head that dies before its first snapshot still recovers every
    acknowledged write from the journal alone."""
    wal = str(tmp_path / "gcs.snap.wal")
    a = GlobalControlStore()
    a.attach_wal(wal)
    for i in range(20):
        a.kv.put(f"k{i}", i, namespace="drill")
    a.kv.delete("k3", namespace="drill")

    b = GlobalControlStore()
    applied = b.replay_wal(wal, -1)
    assert applied == 21
    assert b.kv.get("k7", namespace="drill") == 7
    assert b.kv.get("k3", namespace="drill") is None


def test_torn_wal_tail_is_quarantined(tmp_path):
    """A torn tail (head died mid-append) must not poison replay: the
    valid prefix is applied, the garbage is moved aside for postmortem,
    and the journal keeps accepting appends with continuous seqs."""
    wal = str(tmp_path / "gcs.snap.wal")
    a = GlobalControlStore()
    a.attach_wal(wal)
    a.kv.put("good", 1)
    a.kv.put("also-good", 2)
    a.detach_wal()
    with open(wal, "ab") as f:
        f.write(b"\x00\x00\x00\x09torn-mid-append")

    # replay of the torn file applies the valid prefix and reports the tail
    b = GlobalControlStore()
    assert b.replay_wal(wal, -1) == 2
    assert b.kv.get("good") == 1 and b.kv.get("also-good") == 2
    assert b.last_restore["wal_quarantined_bytes"] > 0

    # REOPENING the journal (the restarted head attaching it) moves the
    # garbage aside — never silently discarded — and truncates
    reopened = GcsWal(wal)
    assert reopened.quarantined_bytes > 0
    assert os.path.exists(wal + ".quarantine")
    assert reopened.last_seq == 2  # seq resumes after the valid prefix
    reopened.close()


def test_snapshot_compacts_wal(tmp_path):
    """Snapshots are the WAL's compaction point: records the snapshot
    covers are dropped, and snapshot + compacted journal still restores
    everything."""
    snap = str(tmp_path / "gcs.snap")
    wal = snap + ".wal"
    a = GlobalControlStore()
    a.attach_wal(wal)
    for i in range(50):
        a.kv.put(f"bulk{i}", "x" * 200)
    size_before = os.path.getsize(wal)
    a.snapshot(snap)
    assert os.path.getsize(wal) < size_before
    a.kv.put("after-compact", 1)

    b = GlobalControlStore()
    b.restore(snap, wal_path=wal)
    assert b.kv.get("bulk49") == "x" * 200
    assert b.kv.get("after-compact") == 1
    # only the post-snapshot record should have replayed
    assert b.last_restore["wal_records_applied"] == 1


def test_unpicklable_keys_warn_once(tmp_path, caplog):
    """Process-local values (locks, sockets) are legitimately not durable;
    the snapshot and the journal each say so exactly ONCE per key instead
    of spamming every interval."""
    snap = str(tmp_path / "gcs.snap")
    store = GlobalControlStore()
    store.attach_wal(snap + ".wal")
    with caplog.at_level(logging.WARNING, logger="ray_tpu.core.gcs"):
        store.kv.put("lockref", threading.Lock())
        store.kv.put("lockref", threading.Lock())  # journal warn: once
        store.kv.put("plain", 1)
        store.snapshot(snap)
        store.snapshot(snap)  # snapshot warn: once
    snap_warns = [r for r in caplog.records
                  if "skipping unpicklable" in r.message]
    wal_warns = [r for r in caplog.records
                 if "cannot journal" in r.message]
    assert len(snap_warns) == 1, caplog.text
    assert len(wal_warns) == 1, caplog.text
    # the durable keys still made it
    b = GlobalControlStore()
    b.restore(snap, wal_path=snap + ".wal")
    assert b.kv.get("plain") == 1


# --------------------------------------------------------------------------
# epoch fencing + typed degraded mode (real RPC)
# --------------------------------------------------------------------------


def test_epoch_fence_rejects_stale_writer():
    """A writer carrying a pre-restart epoch is rejected with the typed,
    NON-retryable StaleEpochError; a live client re-adopts and proceeds."""
    store = GlobalControlStore()
    server = serve_gcs(store, port=0)
    try:
        zombie = GcsClient(server.url, retry_window_s=1.0)
        zombie.adopt_epoch()
        zombie.pin_epoch(zombie.epoch)  # simulate a pre-restart process

        store.bump_epoch()  # the head restarted underneath it

        with pytest.raises(StaleEpochError) as exc_info:
            zombie.kv_put("fenced", 1)
        # fencing must NOT look like a transient outage, or retry loops
        # would hammer the head with doomed writes
        assert not isinstance(exc_info.value, OSError)
        assert store.kv.get("fenced") is None

        fresh = GcsClient(server.url, retry_window_s=1.0)
        fresh.adopt_epoch()
        assert fresh.epoch == store.current_epoch()
        assert fresh.kv_put("fenced", 2)
        assert store.kv.get("fenced") == 2
    finally:
        server.stop()


def test_head_outage_is_typed_and_transitions_fire():
    """While the head is down every client call fails with the typed
    HeadUnavailableError (an OSError, so legacy handlers still catch it),
    and the client fires exactly one unreachable + one reconnected
    transition across the outage."""
    port = _free_port()
    store = GlobalControlStore()
    server = serve_gcs(store, port=port)
    states = []
    client = GcsClient(f"127.0.0.1:{port}", retry_window_s=0.5)
    client.on_head_state(lambda state, outage_s: states.append(state))
    try:
        assert client.kv_put("before", 1)
        server.stop()
        for _ in range(2):  # repeated failures: still ONE transition
            with pytest.raises(HeadUnavailableError) as exc_info:
                client.kv_get("before")
            assert isinstance(exc_info.value, ConnectionError)
        assert client.outage_s() > 0.0

        server = _rebind(store, port)
        deadline = time.monotonic() + 10
        while True:
            try:
                assert client.kv_get("before") == 1
                break
            except HeadUnavailableError:
                assert time.monotonic() < deadline
        assert client.outage_s() == 0.0
        assert states == ["unreachable", "reconnected"]
    finally:
        server.stop()


def _rebind(store, port, attempts=50):
    """Restart a GCS server on the SAME port (the restore contract: agents
    reconnect to the address they already hold)."""
    for i in range(attempts):
        try:
            return serve_gcs(store, port=port)
        except OSError:
            time.sleep(0.1)
    raise AssertionError(f"could not rebind port {port}")


def test_subscribe_poll_loop_survives_head_outage():
    """The long-poll subscription loop must ride through a head restart:
    keep the thread alive on transient RPC errors, back off, and resume
    from the SAME cursor so no message is dropped (regression: the loop
    previously died on the first transient error)."""
    port = _free_port()
    store = GlobalControlStore()
    server = serve_gcs(store, port=port)
    got = []
    stop = threading.Event()
    sub = GcsClient(f"127.0.0.1:{port}", retry_window_s=0.3)
    thread = threading.Thread(
        target=sub.subscribe_poll_loop,
        args=("drill", got.append),
        kwargs={"period_s": 0.05, "stop_event": stop},
        daemon=True,
    )
    thread.start()
    try:
        store.pubsub.publish("drill", "m1")
        _wait_until(lambda: "m1" in got)

        server.stop()
        time.sleep(1.0)  # several failed polls worth of outage
        assert thread.is_alive(), "poll loop died during the outage"
        store.pubsub.publish("drill", "m2")  # published while subscriber was cut off
        server = _rebind(store, port)
        store.pubsub.publish("drill", "m3")

        _wait_until(lambda: "m3" in got)
        assert got == ["m1", "m2", "m3"]  # cursor resumed: nothing dropped
        assert thread.is_alive()
    finally:
        stop.set()
        thread.join(timeout=5)
        server.stop()


def _wait_until(predicate, timeout=15.0):
    deadline = time.monotonic() + timeout
    while not predicate():
        assert time.monotonic() < deadline, "condition never became true"
        time.sleep(0.05)


def test_router_grace_window_keeps_cached_replicas(monkeypatch):
    """During a head outage the controller computes EMPTY membership
    (control-plane blindness, not replica death); inside the grace window
    the router must keep serving on cached handles, and past it the empty
    set is believed."""
    from ray_tpu.serve import router as router_mod
    from ray_tpu.core.config import cfg

    class _FakeActorId:
        def __init__(self, h):
            self._h = h

        def hex(self):
            return self._h

    class _FakeReplica:
        def __init__(self, h):
            self._actor_id = _FakeActorId(h)

    rset = router_mod.ReplicaSet("drill-deploy")
    r1 = _FakeReplica("aa" * 16)
    rset.set_replicas([r1])

    # head down 5s: inside the grace window -> cached membership survives
    monkeypatch.setattr(router_mod, "_head_outage_s", lambda: 5.0)
    rset.set_replicas([])
    assert rset.pick() is r1

    # outage exceeded the grace window -> the empty set is believed
    monkeypatch.setattr(
        router_mod, "_head_outage_s",
        lambda: float(cfg.head_outage_grace_s) + 1.0)
    rset.set_replicas([])
    with rset._lock:
        assert rset._replicas == []


# --------------------------------------------------------------------------
# multi-process drills
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_head_restart_reconciles_lost_state():
    """Restore brings back a node that died DURING the outage plus actor
    and placement-group records it owned. After the reconcile grace the
    head must purge the dead node, release its actor records, and fail
    its placement groups — WITHOUT touching the survivor, whose process
    never restarts."""
    tmp = tempfile.mkdtemp(prefix="ray_tpu_reconcile_")
    snap = os.path.join(tmp, "gcs.snap")
    port = _free_port()
    address = f"127.0.0.1:{port}"
    env = {**_ENV, "RAY_TPU_HEAD_RECONCILE_GRACE_S": "3"}
    head_log = os.path.join(tmp, "head.log")

    head_cmd = [
        sys.executable, "-m", "ray_tpu", "--no-tpu", "start", "--head",
        "--port", str(port), "--num-cpus", "1", "--snapshot-path", snap,
    ]
    head = _spawn(head_cmd, open(head_log, "w"), env=env)
    survivor = doomed = None
    try:
        _wait_line(head_log, "head up", proc=head)
        survivor_log = os.path.join(tmp, "survivor.log")
        doomed_log = os.path.join(tmp, "doomed.log")
        survivor = _spawn(
            [sys.executable, "-m", "ray_tpu", "--no-tpu", "start",
             "--address", address, "--num-cpus", "1",
             "--resources", '{"pet": 1}'],
            open(survivor_log, "w"), env=env)
        doomed = _spawn(
            [sys.executable, "-m", "ray_tpu", "--no-tpu", "start",
             "--address", address, "--num-cpus", "1",
             "--resources", '{"gone": 1}'],
            open(doomed_log, "w"), env=env)
        _wait_line(survivor_log, "joined", proc=survivor)
        _wait_line(doomed_log, "joined", proc=doomed)

        client = GcsClient(address, retry_window_s=5.0)
        nodes = {
            h: client.kv_get(h, namespace="_nodes")
            for h in client.kv_keys("*", namespace="_nodes")
        }
        doomed_hex = next(
            h for h, info in nodes.items()
            if info and info.get("resources", {}).get("gone"))
        survivor_hex = next(
            h for h, info in nodes.items()
            if info and info.get("resources", {}).get("pet"))

        # records the doomed node owns: an actor registration and a
        # placement group — reconciliation must release both
        client.kv_put("drill/ghost",
                      {"node_hex": doomed_hex, "actor_hex": "00" * 16},
                      namespace="_cluster_actors")
        client.kv_put("ff" * 16, {"owner": doomed_hex, "state": "READY"},
                      namespace="_pgs")
        time.sleep(1.5)  # let a snapshot/WAL interval persist it all

        # the node and the head die together (rack loss)
        doomed.send_signal(signal.SIGKILL)
        head.send_signal(signal.SIGKILL)
        doomed.wait(timeout=30)
        head.wait(timeout=30)

        head2_log = os.path.join(tmp, "head2.log")
        head = _spawn(head_cmd + ["--restore"], open(head2_log, "w"), env=env)
        _wait_line(head2_log, "head up", proc=head)

        client = GcsClient(address, retry_window_s=10.0)
        # the doomed node's restored record is purged — either by the
        # reconcile grace sweep or by the head's own staleness detector,
        # whichever notices first (both are "existing death paths")
        _wait_until(
            lambda: client.kv_get(doomed_hex, namespace="_nodes") is None,
            timeout=30)
        # the reconcile sweep (grace 3s) releases what the node owned
        _wait_until(
            lambda: client.kv_get("drill/ghost",
                                  namespace="_cluster_actors") is None,
            timeout=30)
        _wait_until(
            lambda: (client.kv_get("ff" * 16, namespace="_pgs")
                     or {}).get("state") == "FAILED",
            timeout=30)
        # the survivor was NOT purged and NOT restarted
        info = client.kv_get(survivor_hex, namespace="_nodes")
        assert info and info["pid"] == survivor.pid
        assert survivor.poll() is None
    finally:
        _terminate(head, survivor, doomed)


@pytest.mark.slow
def test_kill_head_chaos_drill():
    """Capstone: chaos SIGKILLs the head from its own snapshot loop while
    a writer keeps committing KV state and an agent keeps heartbeating.
    After --restore on the same port: every ACKNOWLEDGED write is still
    readable (zero acknowledged-write loss), the writer saw zero errors
    of any kind (its retry window spans the outage), a pre-restart writer
    is fenced by epoch, and the surviving agent re-registers without a
    process restart."""
    tmp = tempfile.mkdtemp(prefix="ray_tpu_chaos_head_")
    snap = os.path.join(tmp, "gcs.snap")
    port = _free_port()
    address = f"127.0.0.1:{port}"
    head_log = os.path.join(tmp, "head.log")
    agent_log = os.path.join(tmp, "agent.log")
    chaos_env = {**_ENV, "RAY_TPU_CHAOS":
                 "kill_head=1,delay_s=4.0,max_injections=1"}

    head_cmd = [
        sys.executable, "-m", "ray_tpu", "--no-tpu", "start", "--head",
        "--port", str(port), "--num-cpus", "1", "--snapshot-path", snap,
    ]
    head = _spawn(head_cmd, open(head_log, "w"), env=chaos_env)
    agent = None
    acked, errors = [], []
    stop_writer = threading.Event()

    def writer():
        # the retry window spans kill + restart: every put either acks or
        # retries invisibly — ANY surfaced exception fails the drill
        c = GcsClient(address, retry_window_s=60.0)
        c.adopt_epoch()  # exercise the re-adopt-on-fence recovery path
        i = 0
        while not stop_writer.is_set():
            try:
                if c.kv_put(f"w{i}", {"i": i}, namespace="drill"):
                    acked.append(i)
            except Exception as exc:  # noqa: BLE001 — the drill's verdict
                errors.append(exc)
            i += 1
            time.sleep(0.05)

    writer_thread = threading.Thread(target=writer, daemon=True)
    try:
        _wait_line(head_log, "head up", proc=head)
        agent = _spawn(
            [sys.executable, "-m", "ray_tpu", "--no-tpu", "start",
             "--address", address, "--num-cpus", "1",
             "--resources", '{"pet": 1}'],
            open(agent_log, "w"))
        _wait_line(agent_log, "joined", proc=agent)

        # a zombie writer from the pre-kill era: pinned to the old epoch
        zombie = GcsClient(address, retry_window_s=30.0)
        pre_epoch = zombie.adopt_epoch()
        zombie.pin_epoch(pre_epoch)

        writer_thread.start()

        # chaos fires ~4s after the head armed it at init
        head.wait(timeout=60)
        assert head.returncode == 137, (
            f"head should die by chaos os._exit(137), got {head.returncode}")
        t_dead = time.monotonic()
        acked_at_death = len(acked)
        assert agent.poll() is None, "agent must survive the head kill"

        # restart WITHOUT the chaos env (a restarted head re-reading the
        # injection env must not be re-armed anyway, but the drill
        # measures recovery, not a crash loop)
        head2_log = os.path.join(tmp, "head2.log")
        head = _spawn(head_cmd + ["--restore"], open(head2_log, "w"))
        _wait_line(head2_log, "head up", proc=head)

        # recovery-time-to-ready: first successful write after restore
        probe = GcsClient(address, retry_window_s=30.0)
        _wait_until(lambda: probe.kv_get("w0", namespace="drill") is not None,
                    timeout=30)
        recovery_s = time.monotonic() - t_dead

        # traffic rode THROUGH the outage: more acks accumulated after
        # death than existed at death
        _wait_until(lambda: len(acked) > acked_at_death + 5, timeout=30)
        stop_writer.set()
        writer_thread.join(timeout=10)

        assert not errors, f"writer surfaced errors during the drill: {errors}"

        # zero acknowledged-write loss, spot-checked across the whole run
        # (writes acked pre-kill came back via snapshot+WAL; writes acked
        # post-restore are simply present)
        missing = [i for i in acked
                   if probe.kv_get(f"w{i}", namespace="drill") is None]
        assert not missing, f"acknowledged writes lost: {missing[:10]}"

        # the restart bumped the epoch and the zombie is fenced
        assert probe.head_info()["epoch"] > pre_epoch
        with pytest.raises(StaleEpochError):
            zombie.kv_put("zombie-write", 1, namespace="drill")

        # the agent re-registered (same process) and serves work again
        out = subprocess.run(
            [sys.executable, "-c", _OBSERVER, address, "pet", "1"],
            env=_ENV, capture_output=True, text=True, timeout=120,
        )
        assert "OBSERVER-OK" in out.stdout, out.stdout + out.stderr
        assert int(out.stdout.split("OBSERVER-OK")[1].strip()) == agent.pid

        assert recovery_s < 30, f"recovery took {recovery_s:.1f}s"
    finally:
        stop_writer.set()
        _terminate(head, agent)
