"""Shared pieces of the benchmark's CPU tests (imported by name; the
directory is no package, so that it cannot shadow the `benchmark`
package): the tiny tree and a BENCHMARK.json in which each tiny cell
reports what its real twin does."""

import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")
# tiny cell -> the shipped (or spare) cell whose metrics it reports
TWINS = {
    "tiny-train": "train-gpt2s",
    "tiny-chat": "serve-chat-sessions",
    "tiny-docs": "serve-docs-batch",
    "tiny-queue": "serve-chat-sessions",
    "tiny-train-4dev": "train-gpt2s",
}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# the matrices of a block's MLP, dense (`w_*`) or one stack an expert layer (`we_*`)
MLP_WEIGHTS = ("w_up", "w_gate", "w_down", "we_up", "we_gate", "we_down")


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def with_spare(real):
    """BENCHMARK.json and, appended, the cells that benchmark/spare.json
    keeps (measured, not shipped): the harness is tested on both."""
    spare = load(os.path.join(ROOT, "benchmark", "spare.json"))
    return dict(real, **{group: real[group] + spare[group]
                         for group in ("configs", "workloads", "end_to_end", "per_layer")})


@contextlib.contextmanager
def float8_weights(names=None):
    """The control of `check.train_correct`: the program, with its weights
    rounded through float8_e4m3 (the nearest precision below the bfloat16
    the cells compute in) where the train step's forward pass reads them. `names`: the block matrices to
    round (`MLP_WEIGHTS`: the MLP or the experts alone); None rounds every
    parameter, the head included. Patched in from here, for as long as the
    context lasts: the program has no such switch."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import models
    from ray_tpu.models import transformer
    from ray_tpu.train import lm

    def rounded(w):
        # behind a barrier: inside a jitted program XLA (which allows excess precision) removes
        # a narrowing and widening pair of converts, and the TPU's compiler does (PR 31's first
        # chip run read the sound program's numbers under this patch). The backward pass takes
        # the gradient of the rounded weight for the weight's own, as a float8 step with float32
        # master weights does: a cotangent sent back through the converts would itself be rounded
        # to float8, where most of a gradient underflows to zero.
        low = jax.lax.optimization_barrier(w.astype(jnp.float8_e4m3fn)).astype(w.dtype)
        return w + jax.lax.stop_gradient(low - w)

    def low(forward_hidden):
        def forward_hidden_float8(params, tokens, config, **kw):
            if names is None:
                params = jax.tree.map(rounded, params)
            else:
                params = dict(params, blocks={
                    k: rounded(w) if k in names else w for k, w in params["blocks"].items()})
            return forward_hidden(params, tokens, config, **kw)
        return forward_hidden_float8

    families, head = models._FAMILIES, transformer.lm_head_weights
    models._FAMILIES = tuple(
        (kind, family._replace(forward_hidden=low(family.forward_hidden)))
        for kind, family in families)
    if names is None:
        # the head is read beside `forward_hidden`, by the step's objective
        lm.lm_head_weights = transformer.lm_head_weights = (
            lambda params, config: head(jax.tree.map(rounded, params), config))
    try:
        yield
    finally:
        models._FAMILIES = families
        lm.lm_head_weights = transformer.lm_head_weights = head


class BrokenStep:
    """The trainer's jitted step with a fault in it; everything else of the
    step (its `lower`, its plans) is the real one's."""

    def __init__(self, step, fault):
        self._step, self._fault = step, fault

    def __getattr__(self, name):
        return getattr(self._step, name)

    def __call__(self, state, batch):
        import jax
        import jax.numpy as jnp

        if self._fault == "state_unchanged":
            _, metrics = self._step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        if self._fault == "half_the_batch_left_out":
            tokens = batch["tokens"]
            half = tokens.shape[0] // 2
            batch = {"tokens": jnp.concatenate([tokens[:half], tokens[:half]])}
        state, metrics = self._step(state, batch)
        if self._fault == "loss_altered":
            metrics = dict(metrics, loss=metrics["loss"] + 0.01)
        return state, metrics


@contextlib.contextmanager
def broken_step(fault):
    """Every trainer `kinds/lm_steps.TrainSystem` builds, the measured one
    and the one `check.train_correct` has rebuilt, runs its step with `fault`
    in it (`BrokenStep`)."""
    from benchmark.kinds import lm_steps

    build = lm_steps.TrainSystem._build

    def broken_build(self):
        trainer = build(self)
        trainer.step_fn = BrokenStep(trainer.step_fn, fault)
        return trainer

    lm_steps.TrainSystem._build = broken_build
    try:
        yield
    finally:
        lm_steps.TrainSystem._build = build


def tiny_benchmark(real, cell):
    """BENCHMARK.json with the tiny cells in place of the real ones; `cell`
    reports every metric its twin reports."""
    twin = TWINS[cell]
    real = with_spare(real)
    assert twin in {w["name"] for w in real["workloads"]}
    bench = dict(real, workloads=load(os.path.join(TINY, "BENCHMARK.tiny.json"))["workloads"])
    for group in ("end_to_end", "per_layer"):
        bench[group] = [
            dict(m, workloads=[cell]) if twin in m.get("workloads", [twin]) else m
            for m in real[group]
        ]
    return bench


def rehearse(real, cell, trace, seed=7):
    from benchmark import run

    result = run.run_cell(tiny_benchmark(real, cell), cell, seed, 2.0, trace,
                          tree=TINY, require_tpu=False)
    # the last line of the command is this object, serialised
    return json.loads(json.dumps(result))


def expected_metrics(real, cell, group):
    twin = TWINS[cell]
    real = with_spare(real)
    return {m["name"] for m in real[group] if twin in m.get("workloads", [twin])}
