"""The per-process runtime: object refs, task submission, actor management.

This is the equivalent of the reference's CoreWorker + driver singleton
(/root/reference/src/ray/core_worker/core_worker.h:166 and
python/ray/_private/worker.py:426): it owns the object store, the scheduler,
the control store, and the actor registry, and implements put/get/wait/
submit_task/create_actor on top of them.
"""

from __future__ import annotations

import atexit
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from .actors import ActorMethodCall, ActorRuntime, ActorState
from .exceptions import GetTimeoutError, RuntimeNotInitializedError
from .gcs import GlobalControlStore
from .ids import ActorID, JobID, NodeID, ObjectID, TaskID
from .object_store import ObjectStore
from .resources import ResourceDict, default_node_resources
from .scheduler import ClusterScheduler, Node, PlacementGroup, TaskSpec
from .streaming import ObjectRefGenerator


class ObjectRef:
    """A future handle to an object in the store (reference: ObjectRef in
    python/ray/_raylet.pyx; ownership semantics reference_count.h:72).

    Handles are counted: construction increfs, __del__ decrefs, and when
    the last handle dies the store releases the value (auto-GC — manual
    free() stays available for eager release). A GC'd object with recorded
    lineage is reconstructed on a later get()."""

    __slots__ = ("object_id", "_runtime", "__weakref__")

    def __init__(self, object_id: ObjectID, runtime: "Runtime"):
        self.object_id = object_id
        self._runtime = runtime
        runtime.object_store.incref(object_id)

    def __del__(self):
        try:
            self._runtime.object_store.decref(self.object_id)
        except Exception:
            pass  # interpreter teardown: modules may already be gone

    def hex(self) -> str:
        return self.object_id.hex()

    def is_ready(self) -> bool:
        return self._runtime.object_store.is_ready(self.object_id)

    def task_id(self) -> TaskID:
        return self.object_id.task_id()

    def __hash__(self):
        return hash(self.object_id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other.object_id == self.object_id

    def __repr__(self):
        return f"ObjectRef({self.object_id.hex()})"

    def __reduce__(self):
        # Refs may be passed through pickled task args between processes.
        # In cluster mode the ref carries its OWNER's node address, so the
        # receiving process becomes a registered BORROWER: it pins the
        # value at the owner until its copy of the ref dies (reference:
        # borrower protocol, reference_count.h:72).
        ctx = getattr(self._runtime, "cluster", None)
        if ctx is not None:
            from .object_store import Tier

            entry = self._runtime.object_store.entry(self.object_id)
            owner = (
                entry.owner_addr
                if entry is not None and entry.owner_addr  # chained borrow
                else ctx.address
            )
            # Arg locality (reference: pull_manager.h:57 pulls from any
            # holder): when the value physically lives on ANOTHER node
            # (REMOTE placeholder), ship that location so the receiver
            # pulls peer-to-peer instead of routing the bytes through
            # the owner (which would materialize a value it never needed).
            location = None
            if (
                entry is not None
                and entry.tier == Tier.REMOTE
                and isinstance(entry.value, str)
            ):
                location = entry.value
            return (
                _rebind_cluster_ref,
                (self.object_id.hex(), owner, location),
            )
        return (_rebind_object_ref, (self.object_id.hex(),))


def _rebind_object_ref(hex_id: str) -> "ObjectRef":
    rt = get_runtime()
    return ObjectRef(ObjectID(hex_id), rt)


def _rebind_cluster_ref(hex_id: str, owner_addr: str,
                        location: "Optional[str]" = None) -> "ObjectRef":
    rt = get_runtime()
    oid = ObjectID(hex_id)
    ctx = rt.cluster
    if ctx is not None and owner_addr != ctx.address:
        store = rt.object_store
        entry = store.entry(oid)
        if entry is None:
            entry = store.create(oid)
            entry.foreign = True
        # Register the borrow even when a sealed LOCAL copy exists (e.g.
        # this agent parked the task's result): without the pin, the
        # owner's last handle dying would free_object our copy while this
        # ref still lives. One borrow per (process, object).
        if entry.owner_addr is None:
            entry.owner_addr = owner_addr
            # pull from where the bytes ARE (maybe a peer node), while
            # the borrow protocol still runs against the owner
            if location and location != ctx.address:
                entry.fetch_addr = location
            ctx.enqueue_borrow(oid, owner_addr)
    return ObjectRef(oid, rt)


class Runtime:
    """A single in-process 'cluster': nodes, scheduler, store, control plane."""

    def __init__(
        self,
        num_cpus: Optional[int] = None,
        num_tpus: Optional[int] = None,
        resources: Optional[ResourceDict] = None,
        num_nodes: int = 1,
        object_store_capacity: Optional[int] = None,
        spill_dir: Optional[str] = None,
        detect_accelerators: bool = True,
        labels: "Optional[Dict[str, str]]" = None,
        head: bool = False,
        address: Optional[str] = None,
        cluster_token: Optional[str] = None,
        gcs_port: int = 0,
    ):
        from .config import cfg

        if head and address:
            raise ValueError("pass either head=True or address=..., not both")

        if object_store_capacity is None:
            object_store_capacity = cfg.object_store_capacity_bytes
        if spill_dir is None:
            spill_dir = cfg.spill_dir or None
        self.job_id = JobID.next()
        self.gcs = GlobalControlStore()
        self.object_store = ObjectStore(object_store_capacity, spill_dir=spill_dir)
        self.scheduler = ClusterScheduler(self.object_store, self._on_task_done)
        # lineage: a get() of a LOST object re-executes its creating task
        self.object_store.set_resubmit(self.scheduler.submit)
        self._actors: Dict[ActorID, ActorRuntime] = {}
        self._lock = threading.Lock()
        # completion log appended by scheduler worker threads and
        # scanned by the data plane (locality hints / hit accounting);
        # its own lock so readers never contend with the actor table
        self._task_events_lock = threading.Lock()
        self._task_events: List[Dict[str, Any]] = []  # guarded-by: _task_events_lock
        node_res = default_node_resources(
            num_cpus=num_cpus, num_tpus=num_tpus, resources=resources,
            detect_accelerators=detect_accelerators,
        )
        for i in range(num_nodes):
            self.scheduler.add_node(
                Node(NodeID.from_random(), dict(node_res), is_head=(i == 0),
                     labels=dict(labels or {}))
            )
        # failure detection + OOM policy + GCS durability (all flag-driven)
        from .health import HealthCheckManager, MemoryMonitor

        self.health = HealthCheckManager(
            cfg.health_check_period_s, cfg.health_check_failures
        )
        self.health.start()
        self.memory_monitor = MemoryMonitor(
            cfg.memory_usage_threshold,
            cfg.memory_monitor_interval_s,
            cfg.oom_policy,
        )
        self.memory_monitor.start()
        # log capture: the tail of this process's logging stream is
        # servable over the node RPC (cross-node `ray_tpu logs`)
        from ..util import logs as _logs

        _logs.install()
        _logs.set_node_id(self.scheduler.head_node().node_id.hex())
        # flight recorder: durable bounded event segments for this node
        # (cfg.events_dir; the in-memory ring always runs)
        if cfg.events_dir:
            import os as _os

            from ..util.events import events as _events

            _events().configure_segments(_os.path.join(
                cfg.events_dir,
                self.scheduler.head_node().node_id.hex()[:12],
            ))
        # telemetry plane: per-node stats sampling + node-local gauges
        # (core/stats.py); the cluster heartbeat piggybacks snapshots
        # into the GCS node table and /metrics federates head-side
        from . import stats as _stats
        from ..util.metrics import register_runtime_gauges

        self.node_stats = _stats.NodeStatsCollector(self)
        _stats.register_node_gauges()
        register_runtime_gauges()
        # profiling plane: driver-side registry of coordinated captures
        # (util/profiling ProfileStore; filled by profile_capture below)
        from ..util import profiling as _profiling

        self.profiles = _profiling.ProfileStore()
        # GCS durability: restore (newest snapshot + WAL replay) BEFORE
        # the head serves its GCS over RPC, so joining agents only ever
        # observe the fully recovered tables and the post-restart epoch —
        # never a half-restored store.
        self._snapshot_stop = threading.Event()
        self._snapshot_path = cfg.gcs_snapshot_path or None
        self._wal_path = (
            self._snapshot_path + ".wal"
            if self._snapshot_path and cfg.gcs_wal else None
        )
        self._gcs_restored = False
        self._restored_nodes: set = set()
        self._reconcile_state: Dict[str, Any] = {}
        if self._snapshot_path:
            import os as _os

            if _os.path.exists(self._snapshot_path):
                self._restore_gcs(self._snapshot_path, self._wal_path)
            elif self._wal_path and _os.path.exists(self._wal_path):
                # died before the first snapshot ever committed: the
                # journal alone holds everything that was acknowledged
                try:
                    self.gcs.replay_wal(self._wal_path, -1)
                    self._gcs_restored = True
                except Exception:  # noqa: BLE001 - a bad WAL must not brick init
                    import logging

                    logging.getLogger(__name__).exception(
                        "gcs WAL %s is unreadable; starting fresh",
                        self._wal_path,
                    )
            if self._wal_path:
                self.gcs.attach_wal(self._wal_path, fsync=cfg.gcs_wal_fsync)
            if self._gcs_restored:
                from .cluster import NODE_NS as _node_ns
                from ..util.events import emit as _emit

                # fence every pre-crash writer: the bump is journaled (and
                # snapshotted) so it survives the NEXT crash too. Capture
                # the restored node table first — reconciliation compares
                # it against who actually re-announces.
                self._restored_nodes = set(
                    self.gcs.kv.keys(namespace=_node_ns))
                new_epoch = self.gcs.bump_epoch()
                _emit("INFO", "gcs",
                      f"cluster epoch bumped to {new_epoch} after restore",
                      kind="gcs.restored", phase="epoch_bump",
                      epoch=new_epoch,
                      restored_nodes=len(self._restored_nodes))
            interval = cfg.gcs_snapshot_interval_s
            threading.Thread(
                target=self._snapshot_loop, args=(interval,), daemon=True,
                name="gcs-snapshot",
            ).start()
        # multi-process cluster membership (core/cluster.py): the head
        # serves its GCS over RPC; workers join an existing head. Either
        # way this process gains a node server + remote dispatch.
        self.cluster = None
        if head:
            from .cluster import start_head

            self.cluster = start_head(self, port=gcs_port, token=cluster_token)
        elif address:
            from .cluster import join_cluster

            self.cluster = join_cluster(self, address, token=cluster_token)
        # Announced-preemption plumbing: chaos (preempt_node mode) and the
        # agent SIGTERM hook pull the trigger; this runtime drains the
        # node, announces on the GCS pubsub, and kills it after the window.
        from . import chaos as _chaos

        self._preempt_timers: List[threading.Timer] = []
        _chaos.set_preemption_hook(self._chaos_preempt)
        # epoch-fenced reconciliation: restored tables name nodes, actors
        # and placement groups that may not have survived the outage.
        # Give the survivors one grace window to re-announce themselves
        # against the new epoch, then declare whatever never returned
        # dead — through the SAME failure paths ordinary deaths use.
        if self._gcs_restored and head and self.cluster is not None:
            threading.Thread(
                target=self._reconcile_after_restore, daemon=True,
                name="gcs-reconcile",
            ).start()

    # ------------------------------------------------------------ persistence

    def _snapshot_gcs(self) -> None:
        import dataclasses

        from .. import jobs as jobs_mod

        extra = {}
        if jobs_mod._default_manager is not None:
            with jobs_mod._default_manager._lock:
                # deep-ish copies UNDER the lock: the watcher thread mutates
                # live JobInfo objects, and pickling a mutating object can
                # tear or raise mid-snapshot
                extra["jobs"] = [
                    dataclasses.replace(j, metadata=dict(j.metadata))
                    for j in jobs_mod._default_manager._jobs.values()
                ]
        self.gcs.snapshot(self._snapshot_path, extra=extra)

    def _restore_gcs(self, path: str, wal_path: Optional[str] = None) -> None:
        from .. import jobs as jobs_mod
        from ..jobs import JobStatus, default_job_manager

        try:
            extra = self.gcs.restore(path, wal_path=wal_path)
        except Exception:  # noqa: BLE001 - a bad snapshot must not brick init
            import logging

            logging.getLogger(__name__).exception(
                "gcs snapshot %s is unreadable; starting fresh", path
            )
            return
        self._gcs_restored = True
        from ..util.events import emit

        emit("INFO", "gcs", f"restored GCS snapshot from {path}",
             kind="gcs.restored",
             wal_records_applied=self.gcs.last_restore.get(
                 "wal_records_applied", 0))
        for info in extra.get("jobs", ()):  # job records survive restarts
            if info.status in (JobStatus.PENDING, JobStatus.RUNNING):
                # the driver process died with the old control plane
                info.status = JobStatus.FAILED
            mgr = default_job_manager()
            with mgr._lock:
                mgr._jobs.setdefault(info.job_id, info)

    def _snapshot_loop(self, interval: float) -> None:
        from . import chaos as _chaos

        while not self._snapshot_stop.wait(interval):
            if getattr(self.cluster, "is_head", False):
                # head chaos drill trigger: a `kill_head` injection dies
                # HERE — between persistence ticks, so the WAL (not the
                # snapshot) is what carries the most recent writes
                _chaos.maybe_kill_head()
            try:
                self._snapshot_gcs()
            except Exception:  # noqa: BLE001 - persistence must not kill the runtime
                import logging

                logging.getLogger(__name__).exception("gcs snapshot failed")

    def _reconcile_after_restore(self) -> None:
        """Head-only post-restore convergence. Restored tables are a
        snapshot of the PAST: some of the nodes, actors and placement
        groups they name died during the head outage. Wait one grace
        window for survivors to re-announce (registration + heartbeats
        repopulate the live view), then purge whatever never returned —
        feeding the purges into the same node-death paths an ordinary
        heartbeat timeout uses, so surviving processes are never
        restarted and genuinely-dead state is reclaimed exactly once."""
        from .config import cfg
        from .cluster import ACTOR_NS, NODE_NS
        from .gcs_service import PG_NS
        from ..util.events import emit

        grace = float(cfg.head_reconcile_grace_s) or 3.0 * float(
            cfg.node_stale_s)
        self._reconcile_state = {
            "phase": "waiting", "grace_s": grace,
            "restored_nodes": len(self._restored_nodes),
        }
        if self._snapshot_stop.wait(grace):
            return  # runtime shut down before the grace window closed
        my_hex = self.scheduler.head_node().node_id.hex()
        syncer = getattr(getattr(self.cluster, "gcs_server", None),
                         "syncer", None)
        live = set()
        if syncer is not None:
            try:
                live = set(syncer.cluster_view().get("nodes", {}))
            except Exception:  # noqa: BLE001 - view read must not abort reconcile
                pass
        purged = []
        for node_hex in sorted(self._restored_nodes):
            if node_hex == my_hex or node_hex in live:
                continue
            try:
                self.gcs.kv.delete(node_hex, namespace=NODE_NS)
            except Exception:  # noqa: BLE001
                pass
            purged.append(node_hex)
            emit("WARNING", "cluster",
                 f"node {node_hex[:12]} never re-announced within "
                 f"{grace:.0f}s of head restart; purged",
                 kind="node.purged", node=node_hex, grace_s=grace)
        purged_set = set(purged)
        actors_purged = 0
        pgs_failed = 0
        if purged_set:
            # named-actor directory entries hosted on purged nodes: the
            # process died with its node — release the name so recreate
            # paths (get_if_exists / options(name=...)) can reclaim it
            for key in list(self.gcs.kv.keys(namespace=ACTOR_NS)):
                rec = self.gcs.kv.get(key, namespace=ACTOR_NS) or {}
                if rec.get("node_hex") not in purged_set:
                    continue
                try:
                    self.gcs.kv.delete(key, namespace=ACTOR_NS)
                except Exception:  # noqa: BLE001
                    pass
                ns, _, name = key.partition("/")
                if name:
                    self.gcs.unregister_named_actor(name, ns)
                actors_purged += 1
            # placement groups OWNED by a purged node: the owner's FSM
            # died with it, so nobody will ever drive these records again
            # — mark them failed so dependents stop waiting
            for key in list(self.gcs.kv.keys(namespace=PG_NS)):
                rec = self.gcs.kv.get(key, namespace=PG_NS) or {}
                if rec.get("owner") not in purged_set:
                    continue
                if rec.get("state") in ("FAILED", "REMOVED"):
                    continue
                rec = dict(rec)
                rec["state"] = "FAILED"
                rec["failure_reason"] = (
                    "owner node lost during head outage")
                try:
                    self.gcs.kv.put(key, rec, namespace=PG_NS)
                except Exception:  # noqa: BLE001
                    pass
                pgs_failed += 1
        self._reconcile_state = {
            "phase": "done", "grace_s": grace,
            "restored_nodes": len(self._restored_nodes),
            "survivors": len(self._restored_nodes) - len(purged)
            - (1 if my_hex in self._restored_nodes else 0),
            "nodes_purged": len(purged),
            "actors_purged": actors_purged,
            "pgs_failed": pgs_failed,
            "completed_ts": time.time(),
        }
        emit("INFO", "gcs",
             f"head reconciliation complete: {len(purged)} node(s) purged, "
             f"{actors_purged} actor record(s) released, "
             f"{pgs_failed} placement group(s) failed",
             kind="head.reconciled", **{
                 k: v for k, v in self._reconcile_state.items()
                 if k != "phase"
             })
        try:
            # persist the converged tables immediately: a crash right
            # after reconciliation must not resurrect the purged state
            self._snapshot_gcs()
        except Exception:  # noqa: BLE001
            pass

    # ------------------------------------------------------------------ store

    def put(self, value: Any) -> ObjectRef:
        oid = ObjectID.for_put(self.job_id)
        self.object_store.put(oid, value)
        return ObjectRef(oid, self)

    def get(
        self,
        refs: Union[ObjectRef, Sequence[ObjectRef]],
        timeout: Optional[float] = None,
    ) -> Any:
        if isinstance(refs, ObjectRef):
            return self.object_store.get(refs.object_id, timeout)
        deadline = None if timeout is None else time.monotonic() + timeout
        out = []
        for ref in refs:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            out.append(self.object_store.get(ref.object_id, remaining))
        return out

    def wait(
        self,
        refs: Sequence[ObjectRef],
        num_returns: int = 1,
        timeout: Optional[float] = None,
    ) -> Tuple[List[ObjectRef], List[ObjectRef]]:
        if num_returns > len(refs):
            raise ValueError("num_returns exceeds number of refs")
        if num_returns == 0:
            # nothing to wait for: the per-ref ready callbacks below are
            # the only thing that sets the event, so an empty wait would
            # otherwise block forever
            return [], list(refs)
        done_event = threading.Event()
        ready_count = [0]
        lock = threading.Lock()

        def _cb(_entry):
            with lock:
                ready_count[0] += 1
                if ready_count[0] >= num_returns:
                    done_event.set()

        for ref in refs:
            self.object_store.add_ready_callback(ref.object_id, _cb)
        done_event.wait(timeout)
        for ref in refs:
            self.object_store.remove_ready_callback(ref.object_id, _cb)
        # Set-based bookkeeping: the reference envelope is 10k+ refs in
        # flight (release/benchmarks/README.md:29) — membership scans over
        # lists would make this quadratic.
        ready_all: List[ObjectRef] = []
        not_ready: List[ObjectRef] = []
        for r in refs:
            (ready_all if self.object_store.is_ready(r.object_id) else not_ready).append(r)
        # ray.wait contract: at most num_returns refs in the ready list;
        # surplus ready refs stay in the second list, order preserved.
        ready = ready_all[:num_returns]
        second_ids = {r.object_id for r in ready_all[num_returns:]}
        second_ids.update(r.object_id for r in not_ready)
        return ready, [r for r in refs if r.object_id in second_ids]

    # ------------------------------------------------------------------ tasks

    def submit_task(
        self,
        func,
        args: Tuple[Any, ...],
        kwargs: Dict[str, Any],
        name: str = "",
        num_returns: Union[int, str] = 1,
        resources: Optional[ResourceDict] = None,
        max_retries: int = 0,
        retry_exceptions: Any = False,
        scheduling_strategy: Any = "DEFAULT",
        runtime_env: Any = None,
        executor: str = "thread",
        stream_max_backlog: Optional[int] = None,
        locality_hint: Any = None,
    ) -> Union[ObjectRef, List[ObjectRef], "ObjectRefGenerator"]:
        from . import runtime_env as _renv

        streaming = num_returns == "streaming"
        if streaming and executor == "process":
            raise ValueError(
                'num_returns="streaming" requires the thread executor: a '
                "process worker returns one pickled result, not a live stream"
            )
        renv = _renv.normalize(runtime_env)
        if renv and renv.get("working_dir") and executor != "process":
            raise ValueError(
                'runtime_env["working_dir"] requires executor="process": a '
                "thread task cannot change the process-global cwd safely"
            )
        task_id = TaskID.of(self.job_id)
        n_static = 0 if streaming else num_returns
        return_ids = [ObjectID.for_task_return(task_id, i) for i in range(n_static)]
        spec = TaskSpec(
            task_id=task_id,
            name=name or getattr(func, "__name__", "task"),
            func=func,
            args=args,
            kwargs=kwargs,
            num_returns=n_static,
            resources=dict(resources or {"CPU": 1.0}),
            max_retries=max_retries,
            retry_exceptions=retry_exceptions,
            scheduling_strategy=scheduling_strategy,
            return_ids=return_ids,
            runtime_env=renv,
            executor=executor,
            streaming=streaming,
            stream_max_backlog=stream_max_backlog,
            locality_hint=locality_hint,
        )
        if streaming:
            import weakref

            gen = ObjectRefGenerator(task_id, self)
            spec.stream = weakref.ref(gen)
        # Tracing root (or child, when submitted from inside a traced
        # region — another task, a serve request): every downstream
        # queue/dispatch/execute/result span shares this trace_id, across
        # processes for remote dispatch.
        from ..util import tracing

        submit_span = tracing.tracer().start_span(
            "task.submit",
            attrs={"task": spec.name, "task_id": task_id.hex()},
        )
        spec.trace_ctx = submit_span.context
        for oid in return_ids:
            self.object_store.create(oid, owner_task=spec)
        self.scheduler.submit(spec)
        submit_span.end()
        if streaming:
            return gen
        refs = [ObjectRef(oid, self) for oid in return_ids]
        return refs[0] if num_returns == 1 else refs

    def cancel(self, ref: ObjectRef) -> bool:
        return self.scheduler.cancel(ref.object_id.task_id())

    def _on_task_done(self, spec: TaskSpec, error: Optional[BaseException]) -> None:
        event = {
            "task_id": spec.task_id.hex(),
            "name": spec.name,
            "ok": error is None,
            "attempt": spec.attempt,
            "ts": time.time(),
            "start_ts": spec.start_ts,
            "end_ts": spec.end_ts or time.time(),
            "node": spec.node_hex,
        }
        with self._task_events_lock:
            self._task_events.append(event)
            if len(self._task_events) > 100_000:
                del self._task_events[:50_000]

    # ----------------------------------------------------------------- actors

    def create_actor(
        self,
        cls: type,
        args: Tuple[Any, ...],
        kwargs: Dict[str, Any],
        resources: Optional[ResourceDict] = None,
        max_restarts: int = 0,
        max_concurrency: int = 1,
        name: Optional[str] = None,
        namespace: str = "default",
        scheduling_strategy: Any = "DEFAULT",
        lifetime: Optional[str] = None,
        executor: str = "thread",
        runtime_env: Any = None,
        placement_pool: Any = None,
    ) -> "ActorHandle":
        from . import runtime_env as _renv

        renv = _renv.normalize(runtime_env)
        if renv and executor != "process":
            raise ValueError(
                "actor runtime_env requires executor='process' (thread "
                "actors share the driver's process environment)"
            )
        # Cluster placement: NodeAffinity to a remote node, a placement
        # group bundle reserved on one, or default spillover when only a
        # remote node can satisfy the resources — the agent hosts the
        # actor, this process keeps a proxy handle
        # (core/cluster.py RemoteActorProxy).
        if self.cluster is not None and placement_pool is None:
            res = dict(resources or {"CPU": 1.0})
            placed = self.cluster.can_place_actor_remotely(scheduling_strategy, res)
            if placed is not None:
                node, pool, bundle = placed
                actor_id, proxy = self.cluster.create_remote_actor(
                    node, cls, args, kwargs, resources=res,
                    max_restarts=max_restarts, max_concurrency=max_concurrency,
                    name=name, namespace=namespace, executor=executor,
                    runtime_env=renv, pool=pool, bundle=bundle,
                )
                handle = ActorHandle(actor_id, self)
                if name:
                    # reserve BEFORE creation proceeds (duplicate raises
                    # without leaking a live remote actor); proxy.die
                    # releases the name when the actor goes away
                    try:
                        self.gcs.register_named_actor(name, handle, namespace=namespace)
                    except BaseException:
                        self.cluster.kill_remote_actor(proxy)
                        raise
                    proxy.registered_name = name
                    proxy.registered_namespace = namespace
                return handle
        actor_id = ActorID.of(self.job_id)
        handle = ActorHandle(actor_id, self)
        # Reserve the name BEFORE spawning the actor so a duplicate name
        # raises without leaking a live, resource-holding actor.
        if name:
            self.gcs.register_named_actor(name, handle, namespace=namespace)
        def _on_death(rt: ActorRuntime) -> None:
            # Release the name when the actor dies on its own (init failure,
            # unschedulable, restarts exhausted) — not just on explicit kill.
            if rt.registered_name:
                self.gcs.unregister_named_actor(rt.registered_name, rt.registered_namespace)
            # stop probing a dead actor (and drop the closure pinning it)
            target = getattr(rt, "_health_target", None)
            if target is not None:
                self.health.unregister(target)

        try:
            runtime = ActorRuntime(
                actor_id=actor_id,
                cls=cls,
                init_args=args,
                init_kwargs=kwargs,
                resources=dict(resources or {"CPU": 1.0}),
                scheduler=self.scheduler,
                object_store=self.object_store,
                scheduling_strategy=scheduling_strategy,
                max_restarts=max_restarts,
                max_concurrency=max_concurrency,
                name=name or cls.__name__,
                on_death=_on_death,
                registered_name=name,
                registered_namespace=namespace,
                executor=executor,
                runtime_env=renv,
                placement_pool=placement_pool,
            )
        except BaseException:
            if name:
                self.gcs.unregister_named_actor(name, namespace=namespace)
            raise
        with self._lock:
            self._actors[actor_id] = runtime
        if executor == "process":
            self._register_actor_health(actor_id, runtime)
        return handle

    def _register_actor_health(self, actor_id: ActorID, rt: ActorRuntime) -> None:
        """Probe a process actor's worker so a killed/crashed process is
        detected and restarted WITHOUT waiting for the next method call
        (reference: GcsHealthCheckManager pings every raylet,
        gcs_health_check_manager.h:45)."""
        from .actors import _RestartSignal

        target = f"actor:{actor_id.hex()[:12]}:{rt.name}"
        rt._health_target = target  # unregistered by the on_death hook

        def probe() -> bool:
            if rt.state != ActorState.ALIVE:
                return True  # pending/restarting/dead: nothing to detect
            worker = rt._worker
            return worker is None or worker.alive()

        def on_dead(_tid: str) -> None:
            with rt._lock:
                dead = rt.state == ActorState.DEAD
            if not dead:
                rt._mailbox.put(
                    _RestartSignal(
                        "health check: worker process died", rt._incarnation
                    )
                )
                # re-arm: the restarted incarnation gets probed too
                self.health.register(target, probe, on_dead)

        self.health.register(target, probe, on_dead)

    def actor_runtime(self, actor_id: ActorID) -> ActorRuntime:
        with self._lock:
            return self._actors[actor_id]

    def _remote_actor_proxy(self, actor_id: ActorID):
        if self.cluster is None:
            return None
        return self.cluster.remote_actors.get(actor_id)

    def actor_state(self, actor_id: ActorID) -> ActorState:
        """State of a local actor or a cluster-hosted one (proxied over
        RPC to the hosting agent)."""
        with self._lock:
            rt = self._actors.get(actor_id)
        if rt is not None:
            return rt.state
        proxy = self._remote_actor_proxy(actor_id)
        if proxy is None:
            raise KeyError(actor_id)
        if proxy.state == "DEAD":
            return ActorState.DEAD
        if proxy.state == "PENDING":
            return ActorState.PENDING
        try:
            return ActorState(proxy.node.client.call("actor_state", actor_id.hex()))
        except Exception:
            return ActorState.DEAD

    def submit_actor_task(
        self,
        actor_id: ActorID,
        method_name: str,
        args: Tuple[Any, ...],
        kwargs: Dict[str, Any],
        num_returns: Union[int, str] = 1,
    ) -> Union[ObjectRef, List[ObjectRef], "ObjectRefGenerator"]:
        from ..util import tracing

        proxy = self._remote_actor_proxy(actor_id)
        if proxy is not None:
            if num_returns == "streaming":
                raise ValueError(
                    'num_returns="streaming" is not supported on cluster-'
                    "hosted actors (streams need a live in-process queue)"
                )
            r_task_id = TaskID.of(self.job_id)
            return_ids = [
                ObjectID.for_task_return(r_task_id, i) for i in range(num_returns)
            ]
            for oid in return_ids:
                self.object_store.create(oid)
            call_span = tracing.tracer().start_span(
                "actor.call",
                attrs={"actor": proxy.display_name, "method": method_name,
                       "task_id": r_task_id.hex(), "remote": True},
            )
            self.cluster.submit_remote_actor_call(
                proxy, method_name, args, kwargs, return_ids,
                trace_ctx=call_span.context,
            )
            call_span.end()
            refs = [ObjectRef(oid, self) for oid in return_ids]
            return refs[0] if num_returns == 1 else refs
        task_id = TaskID.of(self.job_id)
        streaming = num_returns == "streaming"
        if streaming and self.actor_runtime(actor_id).executor == "process":
            raise ValueError(
                'num_returns="streaming" requires a thread-executor actor: a '
                "process worker returns one pickled result, not a live stream"
            )
        n_static = 0 if streaming else num_returns
        return_ids = [ObjectID.for_task_return(task_id, i) for i in range(n_static)]
        for oid in return_ids:
            self.object_store.create(oid)
        rt = self.actor_runtime(actor_id)
        call_span = tracing.tracer().start_span(
            "actor.call",
            attrs={"actor": rt.name, "method": method_name,
                   "task_id": task_id.hex()},
        )
        call = ActorMethodCall(
            task_id=task_id,
            method_name=method_name,
            args=self._materialize_args(args),
            kwargs=self._materialize_kwargs(kwargs),
            return_ids=return_ids,
            num_returns=n_static,
            streaming=streaming,
            stream=ObjectRefGenerator(task_id, self) if streaming else None,
            trace_ctx=call_span.context,
        )
        rt.submit(call)
        call_span.end()
        if streaming:
            return call.stream
        refs = [ObjectRef(oid, self) for oid in return_ids]
        return refs[0] if num_returns == 1 else refs

    def _materialize_args(self, args):
        # Actor calls resolve ObjectRef args lazily inside the actor thread to
        # preserve submission ordering; we wrap them so the executor resolves.
        return tuple(_LazyRef(a, self) if isinstance(a, ObjectRef) else a for a in args)

    def _materialize_kwargs(self, kwargs):
        return {
            k: _LazyRef(v, self) if isinstance(v, ObjectRef) else v
            for k, v in kwargs.items()
        }

    def kill_actor(self, handle: "ActorHandle", no_restart: bool = True) -> None:
        proxy = self._remote_actor_proxy(handle._actor_id)
        if proxy is not None:
            self.cluster.kill_remote_actor(proxy)
            return
        rt = self.actor_runtime(handle._actor_id)
        rt.kill(no_restart=no_restart)
        if no_restart and getattr(rt, "registered_name", None):
            self.gcs.unregister_named_actor(rt.registered_name, rt.registered_namespace)

    def get_actor(self, name: str, namespace: str = "default") -> "ActorHandle":
        handle = self.gcs.get_named_actor(name, namespace)
        if handle is not None:
            return handle
        if self.cluster is not None:
            # cluster-wide directory: an actor named by ANY driver on ANY
            # node resolves to a proxy handle here
            proxy = self.cluster.lookup_named_actor(name, namespace)
            if proxy is not None:
                return ActorHandle(proxy.actor_id, self)
        raise ValueError(f"No actor named {name!r} in namespace {namespace!r}")

    def list_actors(self) -> List[Dict[str, Any]]:
        with self._lock:
            out = [
                {
                    "actor_id": aid.hex(),
                    "name": rt.name,
                    "state": rt.state.value,
                    "restarts": rt.num_restarts,
                }
                for aid, rt in self._actors.items()
            ]
        if self.cluster is not None:
            for aid, proxy in list(self.cluster.remote_actors.items()):
                out.append({
                    "actor_id": aid.hex(),
                    "name": proxy.display_name,
                    "state": proxy.state,
                    "restarts": 0,
                    "node": proxy.node.node_id.hex() if proxy.node else None,
                })
        return out

    # ------------------------------------------------------------- placement

    def create_placement_group(self, bundles, strategy="PACK", name="",
                               max_reschedules=None) -> PlacementGroup:
        return self.scheduler.create_placement_group(
            bundles, strategy, name, max_reschedules=max_reschedules
        )

    def remove_placement_group(self, pg: PlacementGroup) -> None:
        self.scheduler.remove_placement_group(pg)

    # ---------------------------------------------------------------- cluster

    def cluster_resources(self) -> ResourceDict:
        return self.scheduler.cluster_resources()

    def available_resources(self) -> ResourceDict:
        return self.scheduler.available_resources()

    def task_events(self) -> List[Dict[str, Any]]:
        with self._task_events_lock:
            return list(self._task_events)

    def node_of_task(self, task_id_hex: str) -> Optional[str]:
        """node_hex that executed a task (latest attempt wins), or None.
        The data plane uses this to learn which node produced a block
        (locality hints) and which node ran a map task (hit accounting).
        The snapshot is taken under the log's lock: a concurrent append
        or truncation must not shift entries under the reverse scan."""
        with self._task_events_lock:
            events = list(self._task_events)
        for ev in reversed(events):
            if ev["task_id"] == task_id_hex:
                return ev["node"] or None
        return None

    # -------------------------------------------------------------- profiling

    def profile_capture(
        self,
        nodes: Optional[Sequence[str]] = None,
        duration_s: Optional[float] = None,
        device: bool = True,
        host: bool = True,
    ) -> Dict[str, Any]:
        """Coordinated cluster capture: fan a time-boxed device-trace +
        host-profile request out to the selected nodes (hex prefixes;
        None = every alive node), run them CONCURRENTLY so the windows
        overlap, collect the bounded artifacts back here, and register
        the capture in the profile store + GCS `_profiles` table so
        `state.list_profiles()`, `ray_tpu profile`, and the dashboard can
        reach it. On the in-process runtime the logical nodes share one
        process, so one local capture covers every selected node (the
        non-head entries reference the head's artifacts)."""
        import os as _os

        from ..util import profiling as _profiling
        from .config import cfg
        from .gcs import PROFILE_NS

        if duration_s is None:
            duration_s = cfg.profile_default_duration_s
        profile_id = _os.urandom(6).hex()
        spec = {
            "profile_id": profile_id, "duration_s": duration_s,
            "device": device, "host": host,
        }

        def selected(node_hex: str) -> bool:
            if not nodes:
                return True
            return any(node_hex.startswith(p) for p in nodes)

        started_at = time.time()
        node_metas: Dict[str, Dict[str, Any]] = {}
        blobs: Dict[Tuple[str, str], bytes] = {}
        ctx = self.cluster
        if ctx is None:
            head_hex = self.scheduler.head_node().node_id.hex()
            chosen = [
                n.node_id.hex() for n in self.scheduler.nodes()
                if n.alive and selected(n.node_id.hex())
            ]
            if not chosen:
                raise ValueError(
                    f"no alive node matches the capture selector {nodes!r}"
                )
            local = _profiling.capture_local_profile(
                duration_s, device=device, host=host, profile_id=profile_id
            )
            artifact_hex = head_hex if head_hex in chosen else chosen[0]
            for name, data in local["artifacts"].items():
                blobs[(artifact_hex, name)] = data
            for node_hex in chosen:
                meta = dict(local["meta"])
                if node_hex != artifact_hex:
                    meta["artifacts_at"] = artifact_hex
                    meta["artifact_names"] = []
                node_metas[node_hex] = meta
        else:
            local_hex = ctx.node_id.hex()
            results: Dict[str, Dict[str, Any]] = {}
            workers: List[threading.Thread] = []
            if selected(local_hex):
                workers.append(threading.Thread(
                    target=lambda: results.__setitem__(
                        local_hex,
                        _profiling.capture_local_profile(
                            duration_s, device=device, host=host,
                            profile_id=profile_id,
                        ),
                    ),
                    daemon=True, name="ray_tpu-profile-local",
                ))

            def run_remote(node_hex: str, addr: str) -> None:
                # dedicated client: the capture blocks for the whole
                # window, which can exceed the shared agent client's
                # timeout — and must not head-of-line block dispatches
                from .rpc import RpcClient

                client = RpcClient(
                    addr, timeout=duration_s + 30.0, retries=0,
                    token=ctx.token,
                )
                try:
                    results[node_hex] = client.call("profile_capture", spec)
                except Exception as exc:  # noqa: BLE001 - partial captures are fine
                    results[node_hex] = {
                        "meta": {"error": repr(exc)}, "artifacts": {},
                    }
                finally:
                    client.close()

            for info in ctx.nodes():
                node_hex = info.get("node_id")
                if (
                    not node_hex or node_hex == local_hex
                    or not selected(node_hex) or not info.get("address")
                ):
                    continue
                workers.append(threading.Thread(
                    target=run_remote, args=(node_hex, info["address"]),
                    daemon=True,
                    name=f"ray_tpu-profile-{node_hex[:8]}",
                ))
            if not workers:
                raise ValueError(
                    f"no cluster node matches the capture selector {nodes!r}"
                )
            for w in workers:
                w.start()
            for w in workers:
                w.join(timeout=duration_s + 60.0)
            for node_hex, res in results.items():
                node_metas[node_hex] = res.get("meta", {})
                for name, data in (res.get("artifacts") or {}).items():
                    blobs[(node_hex, name)] = data
        record = {
            "profile_id": profile_id,
            "started_at": started_at,
            "duration_s": duration_s,
            "device": device,
            "host": host,
            "nodes": node_metas,
            "total_bytes": sum(len(b) for b in blobs.values()),
        }
        self.profiles.add(record, blobs)
        # register the record (meta only) in the GCS profile table so
        # other drivers/status observers see the capture happened
        try:
            if ctx is not None:
                ctx.gcs.kv_put(profile_id, record, namespace=PROFILE_NS)
            else:
                self.gcs.kv.put(profile_id, record, namespace=PROFILE_NS)
        except Exception:  # noqa: BLE001 - registration is observability
            pass
        return record

    # ------------------------------------------------------------- preemption

    def _chaos_preempt(self, node, warning_s: float, reason: str) -> None:
        """Chaos preempt_node trigger. `node` is the logical node the
        matching task ran on; None means the injection fired at an agent
        boundary and the whole PROCESS is being preempted."""
        if node is None or getattr(node, "is_remote", False):
            if self.cluster is not None:
                # a cluster member: announce through the head GCS, drain,
                # and hard-exit after the window (spot-VM semantics)
                self.cluster.begin_preemption(reason, warning_s, fate="exit")
                return
            node = self.scheduler.head_node()
        self.preempt_node(node, warning_s=warning_s, reason=reason)

    def preempt_node(self, node: Node, warning_s: Optional[float] = None,
                     reason: str = "preempted") -> None:
        """Put an in-process logical node into the PREEMPTING state:
        placement stops immediately, the preemption is published on the
        GCS pubsub (PREEMPT_CHANNEL) for train controllers et al., and
        after `warning_s` the node actually dies — running work gets the
        window to checkpoint and evacuate. Preempting the only node of a
        single-node runtime kills the whole runtime's capacity; drills
        should target a non-head node."""
        from .config import cfg
        from .gcs import PREEMPT_CHANNEL

        if warning_s is None:
            warning_s = cfg.preempt_warning_s
        deadline = time.time() + warning_s
        marked = self.scheduler.mark_node_draining(
            node.node_id.hex(), reason, deadline
        )
        if marked is None or not node.alive:
            return  # unknown or already gone
        from ..util.events import emit

        emit("WARNING", "cluster",
             f"node {node.node_id.hex()[:12]} preempting: {reason} "
             f"({warning_s:.1f}s warning)",
             kind="preempt.announced", node=node.node_id.hex(),
             deadline=deadline, warning_s=warning_s)
        self.gcs.pubsub.publish(PREEMPT_CHANNEL, {
            "node_hex": node.node_id.hex(),
            "reason": reason,
            "warning_s": warning_s,
            "deadline": deadline,
        })
        timer = threading.Timer(
            warning_s, self._kill_local_node, args=(node, reason)
        )
        timer.daemon = True
        timer.start()
        self._preempt_timers.append(timer)

    def _kill_local_node(self, node: Node, reason: str) -> None:
        """The warning window expired: the preempted node is gone. Actors
        hosted there die (restart elsewhere when budgeted — the node is
        already out of every placement path), and placement groups with
        bundles there reschedule."""
        if not node.alive:
            return
        from ..util.events import emit

        node_hex = node.node_id.hex()
        emit("WARNING", "cluster",
             f"preempted node {node_hex[:12]} died after its warning "
             f"window", kind="node.preempt_expired", node=node_hex,
             reason=reason)
        # the logical node IS dead now — record it as such so in-process
        # drills share the cluster-path timeline (announce → replace →
        # dead), not just the preempt-specific breadcrumb above
        emit("ERROR", "cluster",
             f"node {node_hex[:12]} is dead (preempted: {reason})",
             kind="node.dead", node=node_hex, reason=reason)
        self.scheduler.remove_node(node.node_id)
        with self._lock:
            doomed = [
                ar for ar in self._actors.values() if ar._node is node
            ]
        for ar in doomed:
            ar.kill(
                no_restart=False,
                reason=f"node {node_hex[:12]} preempted: {reason}",
            )
        self.scheduler.handle_node_death(node_hex, f"preempted: {reason}")

    def node_pinned(self, node: Node) -> bool:
        """Whether retiring `node` would destroy live state: an actor
        hosted there that is not DEAD, or (remote nodes) an object whose
        primary copy lives in that node's store. The capacity plane
        consults this before selecting a node for scale-down."""
        from .actors import ActorState

        with self._lock:
            actors = list(self._actors.values())
        for ar in actors:
            if ar._node is node and ar.state != ActorState.DEAD:
                return True
        agent_addr = getattr(node, "agent_addr", None)
        if agent_addr:
            return self.object_store.has_primary_copy_at(agent_addr)
        return False

    def shutdown(self) -> None:
        from . import chaos as _chaos

        _chaos.set_preemption_hook(None)
        for timer in self._preempt_timers:
            timer.cancel()
        self._preempt_timers = []
        if self.cluster is not None:
            self.cluster.stop()
            gcs_server = getattr(self.cluster, "gcs_server", None)
            if gcs_server is not None:
                gcs_server.stop()
            self.cluster = None
        self.health.stop()
        self.memory_monitor.stop()
        self._snapshot_stop.set()
        if self._snapshot_path:
            try:
                self._snapshot_gcs()  # final snapshot: durable state survives
            except Exception:
                pass
        try:
            self.gcs.detach_wal()  # flush + close the journal cleanly
        except Exception:
            pass
        with self._lock:
            actors = list(self._actors.values())
        for rt in actors:
            rt.kill(no_restart=True, reason="runtime shutdown")
        # a dead runtime can outlive shutdown (a stray handle, a gauge
        # callback): it must not pin its actors' constructor arguments,
        # which for a model server are the weights on the device
        with self._lock:
            self._actors.clear()
        self.scheduler.shutdown()
        from .worker_pool import shutdown_worker_pool

        shutdown_worker_pool()


class _LazyRef:
    """Marker for an ObjectRef arg of an actor call, resolved at execution.
    Holds the originating ObjectRef so the arg cannot be GC'd between
    submission and execution."""

    __slots__ = ("object_id", "_runtime", "_pin")
    __ray_tpu_lazy__ = True

    def __init__(self, ref: "ObjectRef", runtime: Runtime):
        self.object_id = ref.object_id
        self._runtime = runtime
        self._pin = ref

    def resolve(self):
        return self._runtime.object_store.get(self.object_id)


class ActorHandle:
    """Client-side handle; `handle.method.remote(...)` submits a mailbox call
    (reference: python/ray/actor.py ActorHandle/ActorMethod)."""

    def __init__(self, actor_id: ActorID, runtime: Runtime):
        self._actor_id = actor_id
        self._runtime = runtime

    def __getattr__(self, item: str) -> "ActorMethod":
        if item.startswith("_"):
            raise AttributeError(item)
        return ActorMethod(self, item)

    @property
    def __ray_ready__(self) -> "ActorMethod":
        return ActorMethod(self, "__ray_ready__")

    @property
    def __ray_pid__(self) -> "ActorMethod":
        """OS pid of the process executing this actor's methods."""
        return ActorMethod(self, "__ray_pid__")

    @property
    def __ray_apply__(self) -> "ActorMethod":
        """Run fn(instance, *args) inside the actor (reference
        __ray_call__): the compiled-DAG loop entry point."""
        return ActorMethod(self, "__ray_apply__")

    def state(self) -> ActorState:
        return self._runtime.actor_state(self._actor_id)

    def __repr__(self):
        return f"ActorHandle({self._actor_id.hex()[:12]})"


class ActorMethod:
    def __init__(self, handle: ActorHandle, name: str, num_returns: int = 1):
        self._handle = handle
        self._name = name
        self._num_returns = num_returns

    def options(self, num_returns: int = 1) -> "ActorMethod":
        return ActorMethod(self._handle, self._name, num_returns)

    def remote(self, *args, **kwargs):
        return self._handle._runtime.submit_actor_task(
            self._handle._actor_id, self._name, args, kwargs, self._num_returns
        )

    def bind(self, *args, **kwargs):
        """Bind this method into a DAG graph (reference dag_node.py bind);
        compile with .experimental_compile() on the leaf node."""
        from ..experimental.dag import ClassMethodNode

        return ClassMethodNode(self._handle, self._name, args, kwargs)


# --------------------------------------------------------------------- globals

_global_runtime: Optional[Runtime] = None
_global_lock = threading.Lock()


def init_runtime(**kwargs) -> Runtime:
    global _global_runtime
    with _global_lock:
        if _global_runtime is None:
            # Env-driven chaos (RAY_TPU_CHAOS) activates at process
            # start, so spawned node agents can be armed with e.g.
            # kill_node injections before any task reaches them.
            from . import chaos
            from .compile_cache import ensure_compile_cache

            chaos.load_from_env()
            # before the first compile of this process or its children
            ensure_compile_cache()
            _global_runtime = Runtime(**kwargs)
        return _global_runtime


def get_runtime() -> Runtime:
    if _global_runtime is None:
        raise RuntimeNotInitializedError(
            "ray_tpu.init() has not been called (and auto-init is disabled here)"
        )
    return _global_runtime


def get_or_init_runtime() -> Runtime:
    if _global_runtime is None:
        return init_runtime()
    return _global_runtime


def is_initialized() -> bool:
    return _global_runtime is not None


def head_outage_s() -> float:
    """Seconds the GCS head has currently been unreachable from this
    process (0.0 = reachable, no cluster, or no runtime). Control loops
    (serve controller/router, capacity autoscaler, SLO monitor) key
    their degraded-mode behavior off this probe."""
    cluster = getattr(_global_runtime, "cluster", None)
    if cluster is None:
        return 0.0
    try:
        return cluster.gcs.outage_s()
    except Exception:  # noqa: BLE001 - a liveness probe must never throw
        return 0.0


def shutdown_runtime() -> None:
    global _global_runtime
    with _global_lock:
        if _global_runtime is not None:
            _global_runtime.shutdown()
            _global_runtime = None


atexit.register(shutdown_runtime)
