"""Plain reference for the first training steps: the gradient of a family's
reference objective in float32, a clip by the global norm and AdamW behind
a warm-up and cosine schedule, written out. Nothing imported from `ray_tpu`
and nothing from optax: the optimizer's numbers are the configuration
file's `trainer` group, which states what the program's trainer runs.

A family's reference brings `part(params, rows, stats) -> (its share of the
objective, the summed cross entropy of the rows)`: the shares of all the
blocks of rows add up to the whole batch's objective, so their gradients
add up to its gradient, and the float32 activations of a block are all that
is ever held. `stats(params, tokens)` (or None) is what a share needs of the
whole batch beforehand (a mixture of experts' load shares).

AdamW's moments after k steps are sums over the clipped gradients so far
(mu_k = (1 - b1) sum_j b1^(k-j) g_j, nu_k likewise of g_j^2): the reference
keeps those gradients and not the moments, one copy of the parameters' size
a step instead of two, which is what lets a 626 M-parameter model's two
steps fit beside their activations on one chip.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def learning_rate(count: int, t: Dict[str, Any]) -> float:
    """The rate of update number `count` (0 for the first): linear from 0 to
    the peak over `warmup_steps`, then a cosine down to `end_lr_ratio` of
    the peak at `total_steps`."""
    peak, warm = float(t["learning_rate"]), int(t["warmup_steps"])
    total = max(int(t["total_steps"]), warm + 1)
    if count < warm:
        return peak * count / warm
    end = peak * float(t["end_lr_ratio"])
    done = min((count - warm) / (total - warm), 1.0)
    return end + (peak - end) * 0.5 * (1.0 + math.cos(math.pi * done))


def leaf_norms(tree) -> List[float]:
    """The Euclidean norm of every leaf, in `jax.tree.leaves` order."""
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                               for x in jax.tree.leaves(t)])(tree)
    return [float(n) for n in jax.device_get(norms)]


class BatchGradient:
    """(mean cross entropy of a (B, S + 1) batch, the gradient of its
    objective), a block of rows at a time; one compiled block for all steps."""

    def __init__(self, part: Callable, stats: Optional[Callable], rows_at_a_time: int):
        self.stats, self.rows = stats, rows_at_a_time

        def block(params, acc, rows, whole):
            (_, ce_sum), grads = jax.value_and_grad(
                lambda p: part(p, rows, whole), has_aux=True)(params)
            return jax.tree.map(jnp.add, acc, grads), ce_sum

        self.block = jax.jit(block, donate_argnums=(1,))

    @staticmethod
    def zeros(params):
        # laid out over the devices as the parameters are
        return jax.jit(lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, F32), params),
                       out_shardings=jax.tree.map(lambda x: x.sharding, params))()

    def __call__(self, params, tokens):
        whole = self.stats(params, tokens) if self.stats else None
        acc, ce = self.zeros(params), 0.0
        for i in range(0, tokens.shape[0], self.rows):
            acc, ce_sum = self.block(params, acc, tokens[i: i + self.rows], whole)
            ce += float(ce_sum)
        return ce / (tokens.shape[0] * (tokens.shape[1] - 1)), acc


def _clipped(grads, max_norm: float):
    def clip(g):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        scale = jnp.where(norm > max_norm, max_norm / norm, 1.0)
        return jax.tree.map(lambda x: x * scale, g)

    return jax.jit(clip, donate_argnums=(0,))(grads)


def _adamw_update(params, kept: List[Any], rate: float, t: Dict[str, Any]):
    """The parameters after update number len(kept), from the clipped
    gradients of every step so far."""
    b1, b2, eps, decay = float(t["b1"]), float(t["b2"]), float(t["eps"]), float(t["weight_decay"])
    k = len(kept)

    def leaf(p, *gs):
        mu = (1 - b1) * sum(b1 ** (k - 1 - j) * g for j, g in enumerate(gs))
        nu = (1 - b2) * sum(b2 ** (k - 1 - j) * g * g for j, g in enumerate(gs))
        step = (mu / (1 - b1 ** k)) / (jnp.sqrt(nu / (1 - b2 ** k)) + eps)
        return p - rate * (step + decay * p)

    return jax.jit(lambda p, gs: jax.tree.map(leaf, p, *gs), donate_argnums=(0,))(params, kept)


def follow(make_params: Callable[[], Any], batches: List[Any], trainer: Dict[str, Any], *,
           part: Callable, stats: Optional[Callable], rows_at_a_time: int,
           first_gradient_seen: Optional[Callable[[Any], None]] = None) -> Dict[str, Any]:
    """The first len(batches) steps from the seeded parameters
    `make_params()` (called again at the end for the parameters' change, so
    that no copy is held through the steps): each step's mean cross entropy
    before its update, the norms a leaf of the first gradient as AdamW gets
    it (clipped), and the norms a leaf of the parameters' change.
    `first_gradient_seen` is shown that first gradient while it is held."""
    params = jax.tree.map(lambda x: x.astype(F32), make_params())
    batch_gradient = BatchGradient(part, stats, rows_at_a_time)
    losses, kept, first_gradient = [], [], None
    for count, tokens in enumerate(batches):
        loss, grads = batch_gradient(params, tokens)
        losses.append(loss)
        kept.append(_clipped(grads, float(trainer["grad_clip"])))
        del grads
        if first_gradient is None:
            first_gradient = leaf_norms(kept[0])
            if first_gradient_seen is not None:
                first_gradient_seen(kept[0])
        params = _adamw_update(params, kept, learning_rate(count, trainer), trainer)
    del kept
    change = jax.jit(lambda p, p0: jax.tree.map(lambda a, b: a - b.astype(F32), p, p0),
                     donate_argnums=(0,))(params, make_params())
    return {"losses": losses, "first_gradient_norms": first_gradient,
            "change_norms": leaf_norms(change)}
