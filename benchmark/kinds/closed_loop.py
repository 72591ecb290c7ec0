"""Traffic kind `closed_loop`: N clients, each sends its next request when
the answer to the last one is back (sessions of `turns` turns that carry
their history, or single unshared prompts when turns == 1), against the
paged LLM server behind the router. Clients are threads of this process
(the one that holds the chip).

`ServeSystem` is the set-up (weights from the seed, the app through
`serve.run`, every program warmed). `ClosedLoop` is the generator. `run`
is one measured window (run.py); dev_windows.py runs many windows after
one set-up.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, Optional

from .. import model_config
from ..counting import RequestRecord
from ..harness import Tracer, checked, log, memory_peak, now, seconds_since_process_start
from ..traffic import ClientSession, ClosedLoopPlan

GIB = float(1 << 30)


def weights_key(seed: int):
    """A PRNG key for any whole seed (the driver's exceed 32 signed bits)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_params(mc, seed: int):
    """Weights on the device in one jitted call, in the type they are
    served or trained in."""
    import jax

    from ray_tpu.models import init_params

    params = jax.jit(lambda key: init_params(mc, key))(weights_key(seed))
    return jax.block_until_ready(params)


class ServeSystem:
    """The system under test, as a user deploys it: an `LLMServer`
    deployment with a paged engine, reached through the router handle."""

    APP = "bench-llm"

    def __init__(self, conf: Dict[str, Any], seed: int):
        import jax

        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.serve.deployment import deployment
        from ray_tpu.serve.llm.paged import PagedConfig
        from ray_tpu.serve.llm.paged_engine import PagedEngineConfig
        from ray_tpu.serve.llm.server import LLMServer

        self.conf = conf
        self.mc = model_config.transformer_config(conf)
        eng = conf["engine"]
        self.paged = PagedConfig(
            page_size=eng["page_size"], num_pages=eng["num_pages"],
            max_pages_per_slot=eng["max_pages_per_slot"],
            chunk_pages=eng["chunk_pages"],
        )
        self.engine_config = PagedEngineConfig(
            max_slots=eng["max_slots"], decode_block_steps=eng["decode_block_steps"],
            precompile=True, paged=self.paged,
        )
        t0 = now()
        self._check_fits(jax.devices()[0])
        self.params = make_params(self.mc, seed)
        t1 = now()
        ray_tpu.init()
        self._ray, self._serve = ray_tpu, serve
        dep = deployment(LLMServer, name=self.APP, num_replicas=1,
                         max_ongoing_requests=2 * eng["max_slots"])
        self.handle = serve.run(dep.bind(self.mc, self.params, self.engine_config, 0, 1))
        self.stream = self.handle.options(stream=True)
        # the replica, and with it the engine and its precompiled programs,
        # is built by the first request
        self.generate(list(range(1, 9)), 2)
        self._warm_bucket_helpers()
        self.setup_seconds = {"weights": t1 - t0, "app_engine_and_programs": now() - t1}

    def _check_fits(self, device) -> None:
        """weights + pool + the reserve for the tick programs' scratch must
        fit the device, by the arithmetic the configuration file shows."""
        import jax

        from ray_tpu.models import init_params
        from ray_tpu.serve.llm.paged import init_paged_cache

        def nbytes(tree) -> int:
            return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

        weights = nbytes(jax.eval_shape(lambda: init_params(self.mc, jax.random.PRNGKey(0))))
        pool = nbytes(jax.eval_shape(lambda: init_paged_cache(self.mc, self.paged)))
        stats = device.memory_stats() or {}
        limit = stats.get("bytes_limit")
        reserve = self.conf["sizing"]["scratch_reserve_gib"] * GIB
        if limit is not None and weights + pool + reserve > limit:
            raise RuntimeError(
                f"configuration does not fit: weights {weights / GIB:.2f} + pool "
                f"{pool / GIB:.2f} + scratch {reserve / GIB:.2f} GiB > {limit / GIB:.2f} GiB"
            )

    def engine(self):
        from ray_tpu.serve.llm import engine as llm_engine

        engines = list(llm_engine._ENGINES.values())
        if len(engines) != 1:
            raise RuntimeError(f"expected one engine in this process, found {len(engines)}")
        return engines[0]

    def _warm_bucket_helpers(self) -> None:
        """The engine's `precompile` warms the tick programs of every
        prefill bucket but not the small jitted helper that scatters a
        bucket's first tokens (one shape per bucket), which would then
        compile inside a window the first time a bucket finishes a prompt.
        Warm it here on dummies of the same shapes (PERF.md lists the
        program fix that makes this unnecessary)."""
        import jax.numpy as jnp

        engine = self.engine()
        # private names: if the program renames them this fails here, in
        # set-up, and not as a compile inside somebody's window
        scatter, tokens_dev = engine._scatter_tokens, engine._tokens_dev
        ms = self.engine_config.max_slots
        b = 1
        while True:
            scatter(jnp.zeros_like(tokens_dev), jnp.full((b,), ms, jnp.int32),
                    jnp.zeros((b,), jnp.int32))
            if b >= ms:
                break
            b = min(2 * b, ms)

    def generate(self, prompt: List[int], max_tokens: int) -> List[int]:
        out = self._ray.get(self.handle.generate.remote({
            "prompt_tokens": prompt, "max_tokens": max_tokens, "temperature": 0.0,
        }), timeout=1200)
        return [int(t) for t in out["tokens"]]

    def counters(self) -> Dict[str, float]:
        return dict(self._ray.get(self.handle.metrics.remote(), timeout=60))

    def pool(self) -> Dict[str, int]:
        """{"total", "free", "in_use"} pages, read without stalling the loop."""
        from ray_tpu.util import state

        (snap,) = state.engine_snapshot().values()
        return dict(snap["pages"])

    def idle(self) -> bool:
        from ray_tpu.util import state

        (snap,) = state.engine_snapshot().values()
        return (snap["queue_depth"] == 0 and snap["inflight_blocks"] == 0
                and all(lane["free"] for lane in snap["lanes"]))

    def wait_idle(self, timeout: float = 120.0) -> None:
        deadline = now() + timeout
        while not self.idle():
            if now() > deadline:
                raise RuntimeError("engine still busy after the generator stopped")
            time.sleep(0.05)

    def shutdown(self) -> None:
        """Tear the app down and wait for the engine (and its page pool)
        to go: its threads wind down a moment after shutdown returns."""
        from ray_tpu.util import state

        if self.handle is None:
            return
        self._serve.shutdown()
        self._ray.shutdown()
        self.handle = self.stream = None
        deadline = now() + 30
        while state.engine_snapshot() and now() < deadline:
            gc.collect()
            time.sleep(0.1)


class ClosedLoop:
    """`plan.clients` client threads; each sends its next request when the
    answer to the last one is back. Clients only block on streams."""

    def __init__(self, system: ServeSystem, plan: ClosedLoopPlan):
        self.system = system
        self.plan = plan
        self.records: List[RequestRecord] = []
        self.answers: Dict[tuple, List[int]] = {}
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._client, args=(i,), daemon=True,
                             name=f"bench-client-{i}")
            for i in range(plan.clients)
        ]
        self._answered = [0] * plan.clients

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def wait_steady(self, timeout: float = 600.0) -> None:
        """Rule 1: the window opens only when every client has had at least
        one request answered."""
        deadline = now() + timeout
        while min(self._answered) < 1:
            if now() > deadline:
                raise RuntimeError(
                    f"lead-in: after {timeout:.0f}s only "
                    f"{sum(1 for a in self._answered if a)} of {self.plan.clients} "
                    "clients had an answer")
            if any(r.error for r in list(self.records)):
                raise RuntimeError(f"lead-in request failed: "
                                   f"{[r.error for r in self.records if r.error][:3]}")
            time.sleep(0.01)

    def stop(self, timeout: float = 300.0) -> None:
        """No drain is measured: clients send nothing more, read their
        current stream to its end without it counting, and exit."""
        self._stop.set()
        deadline = now() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - now()))
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"clients did not stop: {alive}")

    def _client(self, i: int) -> None:
        ray = self.system._ray
        stream_handle = self.system.stream
        session = ClientSession(self.plan, i)
        t_due = now()
        while not self._stop.is_set():
            turn, prompt = session.next_prompt()
            payload = {"prompt_tokens": prompt, "max_tokens": turn.max_tokens,
                       "temperature": 0.0}
            rec = RequestRecord(
                client=i, ordinal=turn.ordinal, turn=turn.turn,
                prompt_tokens=turn.prompt_tokens, max_tokens=turn.max_tokens,
                t_due=t_due, t_submit=now(),
            )
            self.records.append(rec)
            answer: List[int] = []
            try:
                for ref in stream_handle.stream_generate.remote(payload):
                    item = ray.get(ref, timeout=600)
                    t = now()
                    if "token" in item:
                        rec.token_times.append(t)
                        answer.append(item["token"])
                    else:
                        rec.t_done = t
            except Exception as exc:  # noqa: BLE001 - counted as a failed request
                rec.error = f"{type(exc).__name__}: {exc}"[:300]
                rec.t_done = now()
            rec.n_out = len(answer)
            self.answers[(i, turn.ordinal)] = answer
            if rec.error is not None or len(answer) != turn.max_tokens:
                return  # the session cannot go on; the request counts as failed
            session.answered(answer)
            self._answered[i] += 1
            t_due = now()


class Sampler:
    """Traced runs only: every 100 ms the pool's pages in use, and the
    request log's new marks (its ring is bounded, so it is read as it
    fills)."""

    def __init__(self, system: ServeSystem, period_s: float = 0.1):
        from ray_tpu.serve import reqlog

        self._log = reqlog.log()
        self._cursor = self._log.stats()["seq"]
        self.system = system
        self.period_s = period_s
        self.pages: List[tuple] = []   # (t, pages_in_use)
        self.marks: List[dict] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True, name="bench-sampler")

    def start(self) -> None:
        self._thread.start()

    def _poll(self) -> None:
        self.pages.append((now(), self.system.pool()["in_use"]))
        while True:
            fresh = self._log.since(self._cursor, max_n=2000)
            if not fresh:
                break
            self.marks.extend(fresh)
            self._cursor = fresh[-1]["seq"]

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            self._poll()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(10)
        self._poll()


def plan_for(traffic: Dict[str, Any], conf: Dict[str, Any], seed: int,
             clients: Optional[int] = None) -> ClosedLoopPlan:
    spec = dict(traffic)
    if clients is not None:
        spec["clients"] = clients
    plan = ClosedLoopPlan(spec, seed, conf["vocab_size"])
    cap = conf["engine"]["max_pages_per_slot"] * conf["engine"]["page_size"]
    if plan.longest_context() > cap:
        raise ValueError(f"traffic's longest context {plan.longest_context()} "
                         f"exceeds the engine's per-slot capacity {cap}")
    return plan


def run(ctx: Dict[str, Any]) -> None:
    from .. import check, counting

    conf, traffic, seed, seconds = ctx["conf"], ctx["traffic"], ctx["seed"], ctx["seconds"]
    system = ServeSystem(conf, seed)
    log("serving system up", system.setup_seconds)
    try:
        plan = plan_for(traffic, conf, seed)
        loop = ClosedLoop(system, plan)
        sampler = Sampler(system) if ctx["trace"] else None
        tracer = Tracer(ctx["tree"]) if ctx["trace"] else None
        gc.collect()
        gc.freeze()
        loop.start()
        loop.wait_steady()
        if sampler:
            sampler.start()
        counters0 = system.counters()
        t0 = now()
        ctx["setup_s"] = seconds_since_process_start()
        log(f"window open after {ctx['setup_s']:.1f}s of set-up")
        if tracer:
            tracer.start()
            time.sleep(min(float(traffic.get("trace_seconds", 5)), seconds))
            tracer.stop()
        time.sleep(max(0.0, t0 + seconds - now()))
        t1 = t0 + seconds
        counters1 = system.counters()
        if sampler:
            sampler.stop()
        loop.stop()
        gc.unfreeze()
        system.wait_idle()
        ctx.update(
            t0=t0, t1=t1, records=loop.records, answers=loop.answers, plan=plan,
            counters0=counters0, counters1=counters1,
            pages=sampler.pages if sampler else [], marks=sampler.marks if sampler else [],
            pool_total=system.pool()["total"],
            trace=tracer.reduce() if tracer else None,
            trace_t0=tracer.t0 if tracer else None, trace_t1=tracer.t1 if tracer else None,
        )
        ctx["end_to_end"] = counting.serving_end_to_end(loop.records, t0, t1)
        finished = counting.finished_in_window(loop.records, t0, t1)
        failed = counting.failures(loop.records, t0, t1, conf["vocab_size"], loop.answers)
        ctx["attempted"], ctx["failed"] = len(finished), len(failed)
        ctx["problems"] = failed[:5]
        ctx["memory"] = memory_peak(1)
        ctx["problems"] += checked(check.serve_correct, system, conf, seed, ctx)
    finally:
        system.shutdown()
