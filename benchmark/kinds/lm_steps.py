"""Traffic kind `lm_steps`: seeded token batches for a training loop,
`LMTrainer.train` fed by a background host iterator.

The window is made of whole segments of `segment_steps` steps; each
segment ends in the report's host read of the step's metrics, so the
device has finished every step that is counted.
"""

from __future__ import annotations

import gc
import queue
import threading
from typing import Any, Callable, Dict, Iterator, Optional

from .. import model_config
from ..harness import Tracer, checked, log, memory_peak, now, seconds_since_process_start
from ..traffic import lm_batches


class HostBatches:
    """Seeded batches produced on the host by a background thread, a few
    ahead of the trainer, so that the trainer's input wait is real."""

    def __init__(self, spec: Dict[str, Any], seed: int, vocab: int):
        self._source = lm_batches(spec, seed, vocab)
        self.first = next(self._source)     # the batch the reference loss is taken on
        self._queue: "queue.Queue" = queue.Queue(maxsize=int(spec.get("prefetch", 4)))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True, name="bench-batches")
        self._thread.start()

    def _fill(self) -> None:
        batch = self.first
        while not self._stop.is_set():
            try:
                self._queue.put(batch, timeout=0.1)
            except queue.Full:
                continue
            batch = next(self._source)

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return self

    def __next__(self) -> Dict[str, Any]:
        return self._queue.get()

    def close(self) -> None:
        self._stop.set()
        self._thread.join(10)


class TrainSystem:
    """The trainer as a user builds it, warmed: the first step's loss on
    the first seeded batch is kept for the reference comparison."""

    def __init__(self, conf: Dict[str, Any], traffic: Dict[str, Any], seed: int):
        self.conf, self.traffic, self.seed = conf, traffic, int(seed)
        self.mc = model_config.transformer_config(conf)
        self.batches = HostBatches(traffic, seed, conf["vocab_size"])
        self.segment_steps = int(traffic["segment_steps"])
        self.tokens_per_step = int(traffic["batch"]) * int(traffic["seq"])
        t0 = now()
        self.trainer = self._build()
        t1 = now()
        self.loss_first = float(self._segment(1)["loss"])
        t2 = now()
        self._segment(self.segment_steps)   # the window's own call shape
        self.setup_seconds = {"build_state": t1 - t0, "first_step_and_report": t2 - t1,
                              "warm_segment": now() - t2}

    def _build(self):
        from ray_tpu.parallel import MeshSpec
        from ray_tpu.train.trainer import LMTrainer

        t = self.conf["trainer"]
        mesh = t.get("mesh")
        return LMTrainer(
            self.mc,
            mesh_spec=MeshSpec(**mesh) if mesh else None,
            learning_rate=t["learning_rate"], total_steps=t["total_steps"],
            seed=self.seed & 0x7FFFFFFF,
        )

    def _segment(self, steps: int) -> Dict[str, Any]:
        return self.trainer.train(self.batches, num_steps=steps, report_every=steps)

    def window(self, seconds: float,
               around_segment: Optional[Callable[[int], Any]] = None) -> Dict[str, Any]:
        """Whole segments until `seconds` have passed. `around_segment(k)`
        may return a context manager for segment k (the traced run wraps
        one segment in the profiler)."""
        import contextlib

        import jax

        steps, wait_s, losses = 0, 0.0, []
        t0 = now()
        k = 0
        while True:
            ctx = around_segment(k) if around_segment else None
            with ctx if ctx is not None else contextlib.nullcontext():
                metrics = self._segment(self.segment_steps)
            steps += self.segment_steps
            wait_s += float(metrics["input_wait_s"])
            losses.append(float(metrics["loss"]))
            k += 1
            if now() - t0 >= seconds:
                break
        jax.block_until_ready(self.trainer.state)
        t1 = now()
        return {
            "t0": t0, "t1": t1, "steps": steps, "segments": k,
            "tokens": steps * self.tokens_per_step,
            "input_wait_s": wait_s, "loss_last": losses[-1],
        }

    def initial_params(self):
        """The weights the first step saw: the trainer is rebuilt from the
        same seed (its state is a pure function of it) once the measured
        one is gone, so nothing is held through the window for this."""
        self.trainer = None
        gc.collect()
        self.trainer = self._build()
        return self.trainer.state.params

    def close(self) -> None:
        self.batches.close()
        self.trainer = None


def run(ctx: Dict[str, Any]) -> None:
    from .. import check

    conf, traffic, seed, seconds = ctx["conf"], ctx["traffic"], ctx["seed"], ctx["seconds"]
    system = TrainSystem(conf, traffic, seed)
    try:
        tracer = Tracer(ctx["tree"]) if ctx["trace"] else None
        gc.collect()
        gc.freeze()
        ctx["setup_s"] = seconds_since_process_start()
        log(f"window open after {ctx['setup_s']:.1f}s of set-up", system.setup_seconds)
        # the traced run wraps its second segment in the profiler
        window = system.window(
            seconds, (lambda k: tracer if k == 1 else None) if tracer else None)
        gc.unfreeze()
        ctx.update(
            t0=window["t0"], t1=window["t1"], window=window,
            trace=tracer.reduce() if tracer else None,
        )
        ctx["end_to_end"] = {
            "train_tokens_per_s": window["tokens"] / (window["t1"] - window["t0"])}
        ctx["attempted"], ctx["failed"] = window["steps"], 0
        ctx["memory"] = memory_peak(conf["chips"])
        ctx["problems"] = checked(check.train_correct, system, conf, ctx)
    finally:
        system.close()
