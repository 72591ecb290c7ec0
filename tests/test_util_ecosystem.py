"""ActorPool + distributed Queue (reference ray.util.actor_pool/queue)."""

import threading
import time

import pytest

import ray_tpu
from ray_tpu.util import ActorPool, Empty, Full, Queue


@pytest.fixture(autouse=True)
def _cluster():
    ray_tpu.init(num_cpus=8, detect_accelerators=False)
    yield
    ray_tpu.shutdown()


@ray_tpu.remote
class Doubler:
    def work(self, x):
        return x * 2


def test_actor_pool_map_ordered():
    pool = ActorPool([Doubler.remote() for _ in range(3)])
    out = list(pool.map(lambda a, v: a.work.remote(v), range(10)))
    assert out == [x * 2 for x in range(10)]
    assert pool.num_idle == 3  # all actors returned to the pool


def test_actor_pool_map_unordered():
    pool = ActorPool([Doubler.remote() for _ in range(2)])
    out = sorted(pool.map_unordered(lambda a, v: a.work.remote(v), range(8)))
    assert out == [x * 2 for x in range(8)]


@ray_tpu.remote
class SlowOnZero:
    def work(self, x):
        if x == 0:
            time.sleep(1.0)
        return x * 2


def test_actor_pool_map_unordered_when_the_second_submission_finishes_first():
    """Results that finish out of submission order: map_unordered yields
    each once and stops (has_next is false once nothing is pending), and
    an ordered get after an unordered one skips what was returned."""
    pool = ActorPool([SlowOnZero.remote() for _ in range(2)])
    out = []
    worker = threading.Thread(
        target=lambda: out.extend(
            pool.map_unordered(lambda a, v: a.work.remote(v), [0, 1])),
        daemon=True)
    worker.start()
    worker.join(30)
    assert not worker.is_alive(), "map_unordered did not stop after the last result"
    assert out == [2, 0]
    assert not pool.has_next() and pool.num_idle == 2

    for v in (0, 1):
        pool.submit(lambda a, v: a.work.remote(v), v)
    assert pool.get_next_unordered(timeout=30) == 2     # the later submission's index...
    pool.submit(lambda a, v: a.work.remote(v), 2)
    assert pool.get_next(timeout=30) == 0
    assert pool.get_next(timeout=30) == 4               # ...is skipped here
    assert not pool.has_next()


def test_actor_pool_submit_get_next():
    pool = ActorPool([Doubler.remote()])
    pool.submit(lambda a, v: a.work.remote(v), 10)
    pool.submit(lambda a, v: a.work.remote(v), 20)  # blocks until actor frees
    assert pool.has_next()
    assert pool.get_next(timeout=30) == 20
    assert pool.get_next(timeout=30) == 40
    assert not pool.has_next()
    with pytest.raises(StopIteration):
        pool.get_next()


def test_queue_roundtrip_and_sharing():
    q = Queue()
    try:
        q.put("a")
        q.put("b")
        assert q.qsize() == 2
        assert q.get() == "a"

        # shared across tasks: a producer task feeds a consumer here
        @ray_tpu.remote
        def producer(queue, n):
            for i in range(n):
                queue.put(i)
            return "done"

        ref = producer.remote(q, 5)
        got = [q.get(timeout=30) for _ in range(6)]  # "b" + 5 produced
        assert got == ["b", 0, 1, 2, 3, 4]
        assert ray_tpu.get(ref) == "done"
    finally:
        q.shutdown()


def test_queue_bounds_and_timeouts():
    q = Queue(maxsize=2)
    try:
        q.put(1)
        q.put(2)
        with pytest.raises(Full):
            q.put_nowait(3)
        with pytest.raises(Full):
            q.put(3, timeout=0.1)
        assert q.full()
        assert q.get_nowait() == 1
        q.put(3)  # space again
        assert q.get() == 2 and q.get() == 3
        with pytest.raises(Empty):
            q.get_nowait()
        with pytest.raises(Empty):
            q.get(timeout=0.1)
    finally:
        q.shutdown()


def test_queue_blocking_get_wakes_on_put():
    q = Queue()
    try:
        result = []

        def consumer():
            result.append(q.get(timeout=30))

        t = threading.Thread(target=consumer)
        t.start()
        q.put("wake")
        t.join(timeout=30)
        assert result == ["wake"]
    finally:
        q.shutdown()


def _square(x):
    return x * x


def _addmul(a, b):
    return a + b, a * b


def test_multiprocessing_pool_map():
    from ray_tpu.util.multiprocessing import Pool

    with Pool(processes=3) as pool:
        assert pool.map(_square, range(8)) == [x * x for x in range(8)]
        assert pool.starmap(_addmul, [(1, 2), (3, 4)]) == [(3, 2), (7, 12)]
        assert pool.apply(_square, (9,)) == 81
        async_res = pool.map_async(_square, [2, 3])
        assert async_res.get(timeout=60) == [4, 9]
        # process executor = real OS processes, not the driver
        import os

        pids = pool.map(lambda _: os.getpid(), range(3))
        assert all(p != os.getpid() for p in pids)
    with pytest.raises(ValueError, match="closed"):
        pool.map(_square, [1])


def test_dataset_iter_torch_batches():
    import torch

    from ray_tpu import data

    ds = data.range(16, num_blocks=2).map_batches(
        lambda b: {"x": b["item"], "y": b["item"] * 2.0}
    )
    batches = list(ds.iter_torch_batches(4, dtypes={"y": torch.float32}))
    assert len(batches) == 4
    assert isinstance(batches[0]["x"], torch.Tensor)
    assert batches[0]["y"].dtype == torch.float32
    assert batches[1]["x"].tolist() == [4, 5, 6, 7]


def test_empty_waits_do_not_hang():
    from ray_tpu.util.multiprocessing import Pool

    with Pool(2) as pool:
        res = pool.map_async(_square, [])
        res.wait(timeout=5)  # must return immediately, not deadlock
        assert res.get(timeout=5) == []
        assert res.ready()
    # the underlying primitive: wait over zero refs returns at once
    ready, rest = ray_tpu.wait([], num_returns=0, timeout=5)
    assert ready == [] and rest == []
