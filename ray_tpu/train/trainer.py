"""User-facing trainers.

- `Trainer`: generic gang trainer — run any train_loop_per_worker on N
  actors with failure handling (reference parity: DataParallelTrainer,
  train/data_parallel_trainer.py:26).
- `LMTrainer`: the flagship TPU path — one SPMD pjit program per step over
  a mesh, driven host-side; checkpoint/resume via orbax; metrics via
  session.report. On multi-host TPU each host runs this same loop
  (jax.distributed), with the controller gang providing per-host processes.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import numpy as np

from ..models.transformer import TransformerConfig, count_params
from ..parallel.mesh import MeshSpec, build_mesh
from ..parallel.sharding import default_rules
from .checkpoint import CheckpointManager
from .config import CheckpointConfig, RunConfig, ScalingConfig
from .controller import Result, TrainController
from .lm import CORE_STEP_METRICS, create_train_state, default_optimizer, make_train_step


class Trainer:
    """Generic gang trainer: `fit()` = start controller, return Result."""

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        train_loop_config: Optional[Dict[str, Any]] = None,
        datasets: Optional[Dict[str, Any]] = None,
    ):
        self.train_fn = train_loop_per_worker
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.train_config = train_loop_config
        # name -> data.Dataset: streaming_split across the gang at start;
        # workers read their per-rank split via train.get_dataset_shard
        self.datasets = datasets

    def fit(self) -> Result:
        controller = TrainController(
            self.train_fn, self.scaling, self.run_config, self.train_config,
            datasets=self.datasets,
        )
        return controller.run()


class LMTrainer:
    """Language-model trainer: jitted sharded step + data iterator + ckpt.

    This is deliberately a *host-side object*, not an actor: the hot loop is
    the XLA program; Python only feeds batches and drains metrics.
    """

    def __init__(
        self,
        config: TransformerConfig,
        *,
        mesh_spec: Optional[MeshSpec] = None,
        optimizer=None,
        learning_rate: float = 3e-4,
        total_steps: int = 1000,
        grad_accum: int = 1,
        z_loss_coeff: float = 0.0,
        checkpoint_config: Optional[CheckpointConfig] = None,
        rules=None,
        seed: int = 0,
        loss_chunk: Optional[int] = None,
    ):
        from ..util import tracing

        self.config = config
        # sampled steps are chosen on this count of dispatched steps, not
        # on a train() call's own: one step in step_log_sample_every
        # syncs whatever the length of the calls
        self._steps_dispatched = 0
        # tracing.compile_seconds() as of the last report: the first
        # report's compile_s then holds what building the trainer compiled
        self._compile_s_reported = tracing.compile_seconds()
        with tracing.span("train.init"):
            with tracing.span("train.init.mesh"):
                n_dev = len(jax.devices())
                self.mesh = build_mesh(
                    mesh_spec or MeshSpec().with_devices(n_dev))
            self.rules = rules or default_rules()
            self.total_steps = total_steps
            with tracing.span("train.init.state"):
                self.optimizer = optimizer or default_optimizer(
                    learning_rate, total_steps=total_steps)
                self.state, self.state_shardings = create_train_state(
                    self.config, self.optimizer, jax.random.PRNGKey(seed),
                    self.mesh, self.rules)
            with tracing.span("train.init.step_fn") as self._step_fn_span:
                self.step_fn = make_train_step(
                    self.config,
                    self.optimizer,
                    self.mesh,
                    state_shardings=self.state_shardings,
                    z_loss_coeff=z_loss_coeff,
                    grad_accum=grad_accum,
                    loss_chunk=loss_chunk,
                )
        # cost_analysis() of the compiled step (util/profiling), computed
        # once the first time a report needs it (one extra AOT compile;
        # disable with profile_cost_accounting=False). The compiled object
        # lives from `train.report.cost` to the `train.report.ops` that
        # follows it, which reads the step's operation table off it
        self._step_cost = None
        self._step_compiled = None
        # batch shape the step was last traced for: the attention
        # implementation and its sub-tile walk, the expert layer's form and
        # the head's chunk are known with it, and are then written on the
        # train.init.step_fn span
        self._plan_shape: Optional[tuple] = None
        self.ckpt_config = checkpoint_config
        self.ckpt_mgr: Optional[CheckpointManager] = None
        if checkpoint_config and checkpoint_config.checkpoint_dir:
            self.ckpt_mgr = CheckpointManager(
                checkpoint_config.checkpoint_dir,
                max_to_keep=checkpoint_config.max_to_keep,
                async_save=checkpoint_config.async_save,
            )

    @property
    def num_params(self) -> int:
        return count_params(self.state.params)

    def _note_step_plan(self, tokens_shape: tuple) -> None:
        """Attributes of `train.init.step_fn`: what the step's attention,
        its expert layer (a MoE model's), its head and what its blocks keep
        across the forward pass (`remat*`) resolve to for a
        (B, S + 1) batch (the jitted step is traced per shape, so only the
        first batch says)."""
        from ..models import model_family
        from ..ops.attention import attention_plan

        self._plan_shape = tuple(tokens_shape)
        batch, seq = tokens_shape[0], tokens_shape[1] - 1
        plan = attention_plan(
            seq, causal=self.config.causal, implementation=self.config.attn_impl,
            head_dim=self.config.head_dim)
        with jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh):  # as the step is traced
            plan.update(model_family(self.config).plan(self.config, batch, seq))
        plan["loss_chunk"] = self.step_fn.loss_chunk_for(tokens_shape, self.state)
        plan.update(self.step_fn.remat_plan_for(tokens_shape, self.state))
        for key, value in plan.items():
            self._step_fn_span.set_attribute(key, value)

    def restore(self, step: Optional[int] = None) -> int:
        """Resume from a checkpoint; returns the restored step."""
        if self.ckpt_mgr is None:
            raise RuntimeError("no checkpoint_dir configured")
        self.state = self.ckpt_mgr.restore(self.state, step)
        return int(self.state.step)

    def maybe_restore(self) -> Optional[int]:
        if self.ckpt_mgr is not None and self.ckpt_mgr.latest_step() is not None:
            return self.restore()
        return None

    def train(
        self,
        batches: Iterable[Dict[str, Any]],
        *,
        num_steps: Optional[int] = None,
        report_every: int = 10,
        report_fn: Optional[Callable[[Dict[str, Any]], None]] = None,
        run_name: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Drive the step over a batch iterator. Returns final metrics incl.
        tokens/sec. `report_fn` defaults to session.report when inside a
        worker, else a no-op. `run_name` keys the step-forensics records
        (default: the session's run name, else "local")."""
        from ..util import tracing
        from . import steplog
        from .session import _local

        session = getattr(_local, "session", None)
        if report_fn is None:
            report_fn = session.report if session is not None else (lambda m: None)
        if run_name is None:
            run_name = session.context.run_name if session is not None else "local"
        rank = session.context.world_rank if session is not None else 0

        ckpt_every = self.ckpt_config.checkpoint_every if self.ckpt_config else 0
        # step forensics (train/steplog): every sample_every-th dispatched
        # step is decomposed into typed phase buckets. ONLY sampled steps
        # sync (block_until_ready); the rest keep jax async dispatch rolling.
        sample_every = steplog.sample_every() if steplog.enabled() else 0
        pending_steps: list = []
        tokens_done = 0.0
        last_metrics: Dict[str, Any] = {}
        steps = 0
        window_steps = 0
        # per-window phase seconds: the goodput accountant (util/goodput)
        # re-attributes these out of the step_compute bucket when the
        # report reaches the controller
        window_input_wait = 0.0
        window_ckpt_save = 0.0
        batch_iter = iter(batches)
        # The spans are the loop's only clock. `train.loop`, `train.step`
        # and `train.report` are opened with start_span, which the profile
        # does not mirror: the host lane of a profile then holds the leaves
        # alone, and a device gap is named by the leaf the host was in,
        # not by a span that covers the whole step or call. Each leaf
        # starts on the stamp the one before it ended on.
        tracer = tracing.tracer()
        loop = tracer.start_span("train.loop", attrs={
            "run": run_name, "rank": rank, "num_steps": num_steps})
        loop_ctx = loop.context
        t0 = window_t0 = loop.start_mono
        try:
            while num_steps is None or steps < num_steps:
                sampled = (sample_every > 0
                           and self._steps_dispatched % sample_every == 0)
                if sampled:
                    # a sampled step is timed alone: the steps still in
                    # flight are waited out before it starts, or its
                    # `device` bucket would hold their time too
                    with tracing.span("train.drain", parent=loop_ctx):
                        jax.block_until_ready(self.state)
                step = tracer.start_span("train.step", parent=loop_ctx)
                ctx = step.context
                with tracing.span("train.step.data_wait", parent=ctx,
                                  start=step.started) as wait:
                    batch = next(batch_iter, None)  # input pipeline wait happens HERE
                if batch is None:
                    step.end(end_of_data=True)
                    break
                window_input_wait += wait.duration_s
                tokens = batch["tokens"]
                if tuple(tokens.shape) != self._plan_shape:
                    self._note_step_plan(tokens.shape)
                with tracing.span("train.step.h2d", parent=ctx,
                                  start=wait.ended) as h2d:
                    if isinstance(tokens, np.ndarray):
                        batch = {"tokens": jax.numpy.asarray(tokens)}
                    if sampled:
                        # the ONE deliberate sync before dispatch: land the
                        # batch so h2d separates from device compute
                        jax.block_until_ready(batch["tokens"])
                with tracing.span("train.step.dispatch", parent=ctx,
                                  start=h2d.ended) as dispatch:
                    self.state, metrics = self.step_fn(self.state, batch)
                self._steps_dispatched += 1
                device_s = dispatch.duration_s
                if sampled:
                    with tracing.span("train.step.sync", parent=ctx,
                                      start=dispatch.ended) as sync:
                        jax.block_until_ready(self.state)
                    device_s += sync.duration_s
                steps += 1
                window_steps += 1
                tokens_done += float(tokens.shape[0] * (tokens.shape[1] - 1))
                report_s = ckpt_s = 0.0
                if steps % report_every == 0 or steps == num_steps:
                    report = tracer.start_span("train.report", parent=ctx)
                    rctx = report.context
                    with tracing.span("train.report.read", parent=rctx,
                                      start=report.started) as read:
                        # the host read that waits for the device: every
                        # scalar and the step counter in ONE transfer, so the
                        # idle device waits for one host round trip, not one
                        # a scalar, before the next step is handed to it
                        metrics, step_now = jax.device_get((metrics, self.state.step))
                        metrics = {k: float(v) for k, v in metrics.items()}
                        # what the model's family adds to the step's scalars
                        # (a MoE model's routers) also rides the span
                        for key, value in metrics.items():
                            if key not in CORE_STEP_METRICS:
                                report.set_attribute(key, value)
                        metrics["step"] = int(step_now)
                    with tracing.span("train.report.cost", parent=rctx,
                                      start=read.ended) as cost:
                        now = read.end_mono
                        metrics["tokens_per_sec"] = tokens_done / max(now - t0, 1e-9)
                        metrics["input_wait_s"] = round(window_input_wait, 6)
                        metrics["ckpt_save_s"] = round(window_ckpt_save, 6)
                        window_input_wait = window_ckpt_save = 0.0
                        # MFU/roofline from the compiled step's cost_analysis()
                        # over this window's measured step time (the first
                        # window absorbs the compile, so its MFU reads low)
                        metrics.update(self.profiling_metrics(
                            batch, (now - window_t0) / max(window_steps, 1)
                        ))
                        window_t0, window_steps = now, 0
                    accounted = cost
                    if self._step_compiled is not None:  # the first report's
                        with tracing.span("train.report.ops", parent=rctx,
                                          start=cost.ended) as accounted:
                            self._register_step_ops(accounted)
                    with tracing.span("train.report.publish", parent=rctx,
                                      start=accounted.ended):
                        # after `cost`: what step_cost lowers or builds counts
                        compiled_s = tracing.compile_seconds()
                        metrics["compile_s"] = round(
                            compiled_s - self._compile_s_reported, 6)
                        self._compile_s_reported = compiled_s
                        last_metrics = metrics
                        # sampled-step records + the worker's monotonic clock
                        # ride the report on RESERVED keys (popped controller-
                        # side before any metric publication)
                        payload = dict(metrics)
                        payload["_mono"] = cost.end_mono
                        if pending_steps:
                            payload["_steplog"] = pending_steps
                            pending_steps = []
                        report_fn(payload)
                    report.end()
                    report_s = report.duration_s
                if ckpt_every and steps % ckpt_every == 0 and self.ckpt_mgr is not None:
                    with tracing.span("train.ckpt_save", parent=ctx) as ckpt:
                        self.save_checkpoint()
                    ckpt_s = ckpt.duration_s
                    window_ckpt_save += ckpt_s
                step.end()
                if sampled:
                    # the step's span durations as the typed steplog buckets:
                    # the fused XLA program is one opaque interval, `device`
                    # (dispatch and the wait for the state)
                    pending_steps.append(steplog.record_step(
                        run_name, rank, int(self.state.step),
                        {"data_wait": wait.duration_s, "h2d": h2d.duration_s,
                         "device": device_s, "ckpt_save": ckpt_s,
                         "report": report_s},
                        step.duration_s,
                    ))
                    del pending_steps[:-64]  # bounded if reports never drain
            if pending_steps and session is not None:
                # trailing sampled steps with no report behind them: ship a
                # reserved-keys-only report (the controller drops it from
                # metric publication after popping the steplog payload)
                report_fn({"_steplog": pending_steps,
                           "_mono": time.perf_counter()})
            if self.ckpt_mgr is not None and self.ckpt_config.checkpoint_every:
                with tracing.span("train.ckpt_save", parent=loop_ctx):
                    self.save_checkpoint()
                    self.ckpt_mgr.wait_until_finished()
        except BaseException as exc:
            loop.end(status="ERROR", error=repr(exc), steps=steps)
            raise
        loop.end(steps=steps)
        return last_metrics

    def step_cost(self, batch: Dict[str, Any]):
        """cost_analysis() of the compiled train step at this batch's
        shapes (util/profiling StepCost), cached after the first call."""
        if self._step_cost is None:
            from ..util import profiling

            self._step_compiled = profiling.compile_step(self.step_fn, self.state, batch)
            self._step_cost = profiling.step_cost(self._step_compiled)
        return self._step_cost

    def _register_step_ops(self, span) -> None:
        """The step's operation table (`profiling.program_ops`: which scope
        and which pass each operation of the compiled step belongs to) and
        its collectives (`profiling.program_collectives`: kind, the axes of
        this trainer's mesh, bytes), read off the object `step_cost`
        compiled, which is dropped here; what they hold goes on `span`.
        Like the cost, it never fails a run."""
        from ..util import profiling

        compiled, self._step_compiled = self._step_compiled, None
        try:
            for key, value in profiling.register_program_ops(compiled, self.mesh).items():
                span.set_attribute(key, value)
        except profiling.ProfilingError as exc:
            span.set_attribute("error", str(exc))

    def profiling_metrics(self, batch: Dict[str, Any],
                          step_time_s: float) -> Dict[str, Any]:
        """MFU + roofline fractions for one measured step time, from the
        compiled step's cost_analysis — NOT hand-derived 6ND constants.
        Empty dict when the backend can't answer (cost accounting must
        never fail a training run)."""
        try:
            from ..core.config import cfg
            from ..util import profiling

            if not cfg.profile_cost_accounting:
                return {"step_time_s": step_time_s}
            cost = self.step_cost(batch)
            roof = profiling.roofline(cost, max(step_time_s, 1e-9))
            return {
                "step_time_s": step_time_s,
                "mfu": roof["mfu"],
                "step_flops": cost.total_flops,
                "step_bytes": cost.total_bytes,
                "roofline_hbm": roof["hbm_fraction"],
                "roofline_bound": roof["bound"],
            }
        except Exception:  # noqa: BLE001 - accounting must not kill training
            return {}

    def save_checkpoint(self) -> int:
        step = int(jax.device_get(self.state.step))
        self.ckpt_mgr.save(step, self.state)
        return step
