"""Process worker pool: GIL-free tasks, process actors, crash recovery.

Reference behaviors modeled: worker reuse (worker_pool.h:228 prestarted
workers + lease reuse normal_task_submitter.cc:108), worker-death detection
and actor restart (gcs_actor_manager.h:328), runtime-env isolation in the
worker's own environment.
"""

import os
import time

import pytest

import ray_tpu as api
from ray_tpu.core.worker_pool import (
    ProcessWorkerPool,
    WorkerCrashedError,
    get_worker_pool,
)


def _square(x):
    return x * x


def _getpid():
    return os.getpid()


def _read_env(name):
    return os.environ.get(name)


def _crash():
    os._exit(42)


class Counter:
    def __init__(self, start=0):
        self.n = start

    def incr(self, k=1):
        self.n += k
        return self.n

    def pid(self):
        return os.getpid()

    def die(self):
        os._exit(1)


# ------------------------------------------------------------------ pool unit


def test_pool_executes_and_reuses_workers():
    pool = ProcessWorkerPool(max_workers=2)
    try:
        assert pool.execute(_square, (7,), {}) == 49
        pid1 = pool.execute(_getpid, (), {})
        pid2 = pool.execute(_getpid, (), {})
        assert pid1 == pid2  # same idle worker reused
        assert pid1 != os.getpid()  # and it is NOT this process
        assert pool.stats["spawned"] == 1
        assert pool.stats["reused"] >= 1
    finally:
        pool.shutdown()


def test_pool_env_isolation():
    pool = ProcessWorkerPool(max_workers=2)
    try:
        v = pool.execute(_read_env, ("RAY_TPU_TEST_ENV",), {},
                         env_vars={"RAY_TPU_TEST_ENV": "inside"})
        assert v == "inside"
        assert os.environ.get("RAY_TPU_TEST_ENV") is None  # parent untouched
    finally:
        pool.shutdown()


def test_pool_worker_crash_raises_and_recovers():
    pool = ProcessWorkerPool(max_workers=2)
    try:
        with pytest.raises(WorkerCrashedError):
            pool.execute(_crash, (), {})
        # pool recovers with a fresh worker
        assert pool.execute(_square, (3,), {}) == 9
        assert pool.stats["crashed"] == 1
    finally:
        pool.shutdown()


# ------------------------------------------------------------- task executor


def test_process_task_runs_in_separate_pid(runtime):
    pid_task = api.remote(_getpid).options(executor="process")
    child = api.get(pid_task.remote())
    assert child != os.getpid()


def test_process_task_gil_free_parallelism(runtime, tmp_path):
    """Two CPU-burn tasks run at the same time, each in a process of its
    own: each starts, waits until the other has started too and only then
    burns. Were they run one after the other, the first would wait out its
    deadline (a ratio of wall times said the same on an idle host and
    answered to the host's load on a busy one)."""

    def burn(n, mine, other, deadline_s=120.0):
        open(mine, "w").close()
        waited = time.monotonic()
        while not os.path.exists(other):
            if time.monotonic() - waited > deadline_s:
                return None
            time.sleep(0.01)
        acc = 0
        for i in range(n):
            acc += i * i
        return os.getpid(), acc

    marks = [str(tmp_path / "a"), str(tmp_path / "b")]
    refs = [
        api.remote(burn).options(executor="process").remote(2_000_000, mine, other)
        for mine, other in (marks, marks[::-1])
    ]
    done = api.get(refs)
    assert all(done), "a task never saw the other start: they ran one after the other"
    (pid_a, acc_a), (pid_b, acc_b) = done
    assert acc_a == acc_b and len({pid_a, pid_b, os.getpid()}) == 3


def test_process_task_error_propagates(runtime):
    def boom():
        raise ValueError("process boom")

    from ray_tpu.core.exceptions import TaskError

    with pytest.raises(TaskError, match="process boom"):
        api.get(api.remote(boom).options(executor="process").remote())


def test_process_task_crash_retries(runtime):
    marker = os.path.join("/tmp", f"ray_tpu_crash_{os.getpid()}")
    if os.path.exists(marker):
        os.unlink(marker)

    def crash_once(path):
        if not os.path.exists(path):
            open(path, "w").close()
            os._exit(3)
        return "recovered"

    f = api.remote(crash_once).options(executor="process", max_retries=2,
                                       retry_exceptions=True)
    try:
        assert api.get(f.remote(marker)) == "recovered"
    finally:
        if os.path.exists(marker):
            os.unlink(marker)


# ------------------------------------------------------------ process actors


def test_process_actor_state_and_pid(runtime):
    A = api.remote(Counter).options(executor="process")
    a = A.remote(10)
    assert api.get(a.incr.remote()) == 11
    assert api.get(a.incr.remote(5)) == 16  # state persists in the child
    child_pid = api.get(a.pid.remote())
    assert child_pid != os.getpid()
    assert api.get(a.__ray_pid__.remote()) == child_pid


def test_thread_actor_pid_is_parent(runtime):
    A = api.remote(Counter)
    a = A.remote()
    assert api.get(a.__ray_pid__.remote()) == os.getpid()


def test_process_actor_crash_restarts(runtime):
    A = api.remote(Counter).options(executor="process", max_restarts=1)
    a = A.remote(0)
    assert api.get(a.incr.remote()) == 1
    from ray_tpu.core.exceptions import ActorDiedError

    with pytest.raises(ActorDiedError):
        api.get(a.die.remote())
    # restarted: fresh state, new process
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            assert api.get(a.incr.remote()) == 1
            break
        except ActorDiedError:
            time.sleep(0.1)
    else:
        raise AssertionError("actor did not restart")


def test_working_dir_runtime_env(tmp_path, runtime):
    """runtime_env working_dir (reference runtime_env plugin): the process
    worker runs with cwd = working_dir and can import files there."""
    (tmp_path / "localmod.py").write_text("MAGIC = 'from-working-dir'\n")

    @api.remote(executor="process", runtime_env={"working_dir": str(tmp_path)})
    def probe():
        import os

        import localmod  # resolvable only via the working_dir

        return os.getcwd(), localmod.MAGIC

    cwd, magic = api.get(probe.remote(), timeout=60)
    assert cwd == str(tmp_path)
    assert magic == "from-working-dir"

    # workers are keyed by working_dir: a different dir gets a fresh worker
    other = tmp_path / "other"
    other.mkdir()

    @api.remote(executor="process", runtime_env={"working_dir": str(other)})
    def where():
        import os

        return os.getcwd()

    assert api.get(where.remote(), timeout=60) == str(other)

    # thread tasks must reject working_dir loudly (process-global cwd)
    @api.remote(runtime_env={"working_dir": str(tmp_path)})
    def threaded():
        return 1

    with pytest.raises(ValueError, match="process"):
        threaded.remote()

    with pytest.raises(ValueError, match="not a directory"):
        @api.remote(executor="process",
                        runtime_env={"working_dir": "/definitely/missing"})
        def bad():
            return 1

        bad.remote()


def test_working_dir_reasserted_on_reuse(tmp_path, runtime):
    """A task's os.chdir must not leak into the next task on a reused
    worker — cwd is part of the pool's reuse contract."""
    wd = tmp_path / "wd"
    wd.mkdir()

    @api.remote(executor="process", runtime_env={"working_dir": str(wd)})
    def chdir_away():
        import os

        os.chdir("/tmp")
        return os.getcwd()

    @api.remote(executor="process", runtime_env={"working_dir": str(wd)})
    def where():
        import os

        return os.getcwd()

    assert api.get(chdir_away.remote(), timeout=60) == "/tmp"
    assert api.get(where.remote(), timeout=60) == str(wd)


def test_process_actor_runtime_env(tmp_path, runtime):
    """Process actors get env_vars + working_dir isolation (reference:
    actor-level runtime_env)."""
    wd = tmp_path / "actor_wd"
    wd.mkdir()
    (wd / "cfgmod.py").write_text("NAME = 'actor-env'\n")

    @api.remote(executor="process", max_restarts=0,
                runtime_env={"env_vars": {"MY_TOKEN": "s3cr3t"},
                             "working_dir": str(wd)})
    class Svc:
        def probe(self):
            import os

            import cfgmod

            return os.getcwd(), os.environ["MY_TOKEN"], cfgmod.NAME

    svc = Svc.remote()
    cwd, token, name = api.get(svc.probe.remote(), timeout=60)
    assert cwd == str(wd)
    assert token == "s3cr3t"
    assert name == "actor-env"
    # the driver's environment is untouched
    import os

    assert "MY_TOKEN" not in os.environ

    # thread actors reject runtime_env loudly
    @api.remote(runtime_env={"env_vars": {"X": "1"}})
    class Threaded:
        pass

    with pytest.raises(ValueError, match="process"):
        Threaded.remote()


def test_process_actor_py_modules(tmp_path, runtime):
    lib = tmp_path / "lib"
    lib.mkdir()
    (lib / "shippedmod.py").write_text("VALUE = 123\n")

    @api.remote(executor="process",
                runtime_env={"py_modules": [str(lib)]})
    class Uses:
        def val(self):
            import shippedmod

            return shippedmod.VALUE

    assert api.get(Uses.remote().val.remote(), timeout=60) == 123
