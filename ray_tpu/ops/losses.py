"""Training losses: cross entropy with optional z-loss, computed in float32.

The einsum-free formulation (take_along_axis on log-softmax) avoids
materializing one-hot targets — at 50k-128k vocab the one-hot would dominate
HBM traffic in the loss.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def z_loss(logits: jax.Array) -> jax.Array:
    """Auxiliary z-loss (mean logsumexp^2) — stabilizes logit scale at scale."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(jnp.square(lse))


def cross_entropy_loss(
    logits: jax.Array,
    targets: jax.Array,
    *,
    mask: Optional[jax.Array] = None,
    z_loss_coeff: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Token-level CE. logits (..., V), targets (...) int. Returns
    (mean_loss, num_tokens). mask=0 drops a position (padding)."""
    logits32 = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits32, axis=-1, keepdims=True)
    # gather the target logit FIRST, then subtract: logz - logits[target]
    # never materializes the (B, S, V) f32 logprobs tensor (the full
    # subtract showed up as an 11 ms/step HBM-bound fusion on v5e)
    tgt = jnp.take_along_axis(logits32, targets[..., None], axis=-1)
    nll = (logz - tgt)[..., 0]
    if mask is not None:
        mask_f = mask.astype(jnp.float32)
        num = jnp.maximum(jnp.sum(mask_f), 1.0)
        loss = jnp.sum(nll * mask_f) / num
    else:
        num = jnp.asarray(nll.size, jnp.float32)
        loss = jnp.mean(nll)
    if z_loss_coeff:
        lse2 = jnp.square(logz[..., 0])
        if mask is not None:
            zl = jnp.sum(lse2 * mask.astype(jnp.float32)) / num
        else:
            zl = jnp.mean(lse2)
        loss = loss + z_loss_coeff * zl
    return loss, num


# bytes the dense loss path keeps live per logit element: the bf16 logits
# from the head matmul, their f32 upcast, and the f32 probs tensor the
# backward softmax materializes
_DENSE_LOSS_BYTES_PER_LOGIT = 2 + 4 + 4
# and the fused head, per logit of ONE chunk, beside what its caller counts
# as held: the narrow logits and what the chunk's three matmuls keep around
# them. Fitted to gpt2-small's peaks on a v5e (PERF.md section 6, PR 46):
# the peak less the estimate of state, gradients and activations, over the
# chunk's logits, read 2.25 (24 x 512 rows), 2.76 (24 x 1,024, the whole
# sequence) and 2.42 (32 x 512); under 512 rows a chunk it reads more (3.5
# at 256), which only ever picks between the two smallest candidates
_CHUNKED_LOSS_BYTES_PER_LOGIT = 3
# The share of the device that a step's largest moment leaves free, for this
# rule and for what a recomputing step keeps (train/lm.auto_remat_saved).
# The compiler itself works to a ceiling near 93%: a program that needs
# more is scheduled and rematerialised into it and pays in time (Trinity-Mini
# with the residual kept beside the attention outputs, estimate 15.86 GB of
# 16.91: 15.62 GB and 9 ms a step MORE, PR 34; gpt2-small at 32 x 1,024 with
# the whole sequence as the head's one chunk, estimate 17.8 GB: 94.8% and
# 5.6% slower than two chunks, PR 46)
HBM_FREE_FRACTION = 0.065
_CHUNK_CANDIDATES = (2048, 1024, 512, 256, 128)   # after the whole sequence


def auto_loss_chunk(
    batch_per_device: int,
    seq: int,
    vocab: int,
    hbm_bytes: Optional[int] = None,
    *,
    resident_bytes: int = 0,
    step_bytes: int = 0,
) -> int:
    """The chunk of the fused head (`fused_linear_cross_entropy`) for a
    device of known size; 0, the dense head, for one of unknown size.

    Where the size is known the head is the fused one, whether or not the
    dense logits would fit: it holds none of the (B_local, S, V) logits,
    and a step that fits only just pays for them elsewhere (gpt2-small at
    24 x 1,024 on a v5e, PR 46: dense 143,573 tokens/s at 94.5% of memory,
    the compiler rematerialising nine MLP activations a step to stay under
    its ceiling; chunks of 256 / 512 / 1,024 rows 147,923 / 149,090 /
    149,395 at 66.3 / 68.2 / 80.2%, nothing rematerialised). The
    chunk is the largest candidate (the whole sequence first, then those of
    `_CHUNK_CANDIDATES` that divide S; the smallest if none fits) with which
    the head's moment leaves `HBM_FREE_FRACTION` of the device free: what
    the caller counts as held then, `resident_bytes` all along (a device's
    share of the train state, from the shardings) plus `step_bytes` during
    the step (its gradients, and the activations the blocks hold when the
    head runs), plus `_CHUNKED_LOSS_BYTES_PER_LOGIT` a logit of one chunk.
    Each chunk reads and writes the head gradient's float32 accumulator
    once, so fewer, larger chunks are faster while memory is not short
    (OLMoE's one layer at 4 x 4,096, PR 46: 2,048 rows 85,961 tokens/s at
    76.3%, the whole 4,096 87,462 at 77.9%), and slower once the program no
    longer fits under the compiler's ceiling (gpt2-small at 32 x 1,024: 256 /
    512 / 1,024 rows 148,226 / 148,833 / 140,453 at 84.8 / 87.9 / 94.8%).
    The three answers those readings ask for (1,024, 4,096, 512) are what
    the one criterion gives. Nothing live is probed but the device's size,
    so the same model and batch always get the same program.

    hbm_bytes None = probe the local device (memory_stats().bytes_limit);
    an unknown limit (CPU backends) means no HBM cliff to dodge and no
    reading to go by -> dense, the plain form the fused head's tests
    compare with."""
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    if not hbm_bytes:
        return 0
    candidates = [seq] + [chunk for chunk in _CHUNK_CANDIDATES if chunk < seq and seq % chunk == 0]
    room = (1 - HBM_FREE_FRACTION) * hbm_bytes - resident_bytes - step_bytes
    return next((chunk for chunk in candidates
                 if loss_logits_bytes(batch_per_device, seq, vocab, chunk) <= room), candidates[-1])


def loss_logits_bytes(batch_per_device: int, seq: int, vocab: int, chunk: int = 0) -> int:
    """The estimate of what the head's logits hold on a device: all of them
    under the dense loss (chunk 0), one chunk's under the fused one."""
    if chunk:
        return batch_per_device * chunk * vocab * _CHUNKED_LOSS_BYTES_PER_LOGIT
    return batch_per_device * seq * vocab * _DENSE_LOSS_BYTES_PER_LOGIT


def device_hbm_bytes() -> int:
    try:
        device = jax.local_devices()[0]
        if getattr(device, "platform", "cpu") == "cpu":
            return 0
        stats = device.memory_stats() or {}
        return int(stats.get("bytes_limit", 0))
    except Exception:  # noqa: BLE001 - heuristic must never fail a trace
        return 0


def _chunks(chunk: int, *arrays: jax.Array) -> Tuple[jax.Array, ...]:
    """Each (B, S, ...) -> (S / chunk, B, chunk, ...): what the scan walks."""
    return tuple(
        a.reshape(a.shape[0], a.shape[1] // chunk, chunk, *a.shape[2:]).swapaxes(0, 1)
        for a in arrays)


def _chunk_logits(xc, head, tc):
    """One chunk's float32 logits, their logsumexp and the negative log
    likelihood of its targets: the one head matmul every pass shares."""
    logits = jnp.einsum("bce,ev->bcv", xc, head)
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    # gathered before the upcast (the same value): a gather from logits32
    # makes XLA write the chunk's float32 logits out beside the narrow ones
    tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0].astype(jnp.float32)
    return logits32, lse, lse - tgt


def _num_tokens(mask):
    return jnp.maximum(jnp.sum(mask), 1.0)


def _chunk_sums(lse, nll, mc):
    return jnp.stack([jnp.sum(nll * mc), jnp.sum(jnp.square(lse) * mc)])


def _loss_of_sums(sums, num, z_loss_coeff):
    loss = sums[0] / num
    if z_loss_coeff:
        loss = loss + z_loss_coeff * (sums[1] / num)
    return loss


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _chunked_cross_entropy(x, head, targets, mask, chunk, z_loss_coeff):
    """The loss alone (evaluation: no gradient asked): one matmul a chunk."""
    def body(sums, xtm):
        xc, tc, mc = xtm
        _, lse, nll = _chunk_logits(xc, head, tc)
        return sums + _chunk_sums(lse, nll, mc), None

    sums, _ = jax.lax.scan(body, jnp.zeros((2,)), _chunks(chunk, x, targets, mask))
    return _loss_of_sums(sums, _num_tokens(mask), z_loss_coeff)


def _chunked_cross_entropy_fwd(x, head, targets, mask, chunk, z_loss_coeff):
    """The loss and, while each chunk's logits are live, its share of
    d(loss)/dx and d(loss)/d(head): the loss is the last thing the forward
    computes and its cotangent is a scalar, so d(logits) = (softmax x (1 +
    2 z lse) - onehot) x mask / n needs nothing the backward brings.
    Residuals: dx (x's size) and dW (head's size, summed over the chunks
    in float32), nothing with rows x V elements."""
    num = _num_tokens(mask)
    vocab = head.shape[-1]

    def body(carry, xtm):
        sums, dw = carry
        xc, tc, mc = xtm
        logits32, lse, nll = _chunk_logits(xc, head, tc)
        scale = mc / num
        soft_scale = scale * (1.0 + 2.0 * z_loss_coeff * lse) if z_loss_coeff else scale
        onehot = tc[..., None] == jnp.arange(vocab, dtype=tc.dtype)
        dlogits32 = (jnp.exp(logits32 - lse[..., None]) * soft_scale[..., None]
                     - jnp.where(onehot, scale[..., None], 0.0))
        # narrowed where autodiff narrows it: the transpose of the logits' upcast
        dlogits = dlogits32.astype(jnp.result_type(xc.dtype, head.dtype))
        dxc = jnp.einsum("bcv,ev->bce", dlogits, head).astype(xc.dtype)
        dw = dw + jnp.einsum("bce,bcv->ev", xc, dlogits, preferred_element_type=dw.dtype)
        return (sums + _chunk_sums(lse, nll, mc), dw), dxc

    dw0 = jnp.zeros(head.shape, jnp.promote_types(head.dtype, jnp.float32))
    (sums, dw), dxs = jax.lax.scan(
        body, (jnp.zeros((2,)), dw0), _chunks(chunk, x, targets, mask))
    dx = dxs.swapaxes(0, 1).reshape(x.shape)
    return _loss_of_sums(sums, num, z_loss_coeff), (dx, dw.astype(head.dtype))


def _chunked_cross_entropy_bwd(chunk, z_loss_coeff, residuals, g):
    dx, dw = residuals
    rows = dx.shape[:2]
    return (
        (g * dx).astype(dx.dtype),
        (g * dw).astype(dw.dtype),
        np.zeros(rows, jax.dtypes.float0),  # integer targets
        jnp.zeros(rows, jnp.float32),  # the mask is not differentiated
    )


_chunked_cross_entropy.defvjp(_chunked_cross_entropy_fwd, _chunked_cross_entropy_bwd)


def fused_linear_cross_entropy(
    x: jax.Array,
    head: jax.Array,
    targets: jax.Array,
    *,
    chunk: int = 256,
    mask: Optional[jax.Array] = None,
    z_loss_coeff: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """lm_head matmul + CE fused over sequence chunks: the (B, S, V)
    logits — float32 copies of them are the peak-memory hog of LM training
    at 50k vocabularies — are never materialized. A `lax.scan` builds one
    chunk's logits at a time, ONCE: under differentiation the chunk's dx
    and its share of the head's gradient are computed in the same scan
    step (`_chunked_cross_entropy_fwd`), three matmuls a chunk, and the
    backward only scales them by the loss's cotangent; an undifferentiated
    call runs the one logits matmul. Inputs of the matmuls are in the
    callers' dtype, logits, softmax and every sum in float32, as in
    `cross_entropy_loss`.

    x: (B, S, E) pre-head hidden states; head: (E, V); targets: (B, S).
    Same return contract as cross_entropy_loss. S % chunk must be 0 (S
    here is a static shape)."""
    b, s, _ = x.shape
    if s % chunk:
        raise ValueError(f"seq len {s} not divisible by loss chunk {chunk}")
    mask = jnp.ones((b, s), jnp.float32) if mask is None else mask.astype(jnp.float32)
    loss = _chunked_cross_entropy(x, head, targets, mask, chunk, z_loss_coeff)
    return loss, _num_tokens(mask)


# ------------------------------------------------- several next-token heads


def multihead_targets(tokens: jax.Array, heads: int) -> Tuple[jax.Array, jax.Array]:
    """(targets, has_target), each (B, S, heads), of a (B, S + 1) batch for
    `heads` next-token heads: head n at position t is scored against token
    t + 1 + n, and the last n positions of a sequence have no such target
    (their target reads 0 and their `has_target` False)."""
    s = tokens.shape[1] - 1
    index = jnp.arange(s)[:, None] + 1 + jnp.arange(heads)[None, :]          # (S, heads)
    has_target = index <= s
    targets = jnp.where(has_target[None], tokens[:, jnp.minimum(index, s)], 0)
    return targets, jnp.broadcast_to(has_target[None], targets.shape)


def _head_weights(has_target):
    """(B, S, heads) float32: 1 / (a head's positions that have a target), 0
    where there is none, so that a weighted sum over positions is that head's
    mean."""
    mask = has_target.astype(jnp.float32)
    return mask / jnp.maximum(jnp.sum(mask, axis=(0, 1)), 1.0)


def multihead_cross_entropy(logits: jax.Array, targets: jax.Array,
                            has_target: jax.Array) -> jax.Array:
    """(heads,) mean cross entropy of each next-token head: logits (B, S,
    heads x V), head n's the n-th V columns, in float32; targets and
    `has_target` (B, S, heads) (`multihead_targets`). The plain form that
    the fused one's tests compare with."""
    b, s, heads = targets.shape
    logits32 = logits.astype(jnp.float32).reshape(b, s, heads, -1)
    logz = jax.nn.logsumexp(logits32, axis=-1)
    tgt = jnp.take_along_axis(logits32, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - tgt) * _head_weights(has_target), axis=(0, 1))


def _multihead_chunk(xc, head, tc, wc):
    """One chunk's float32 logits (b, c, heads, V) from ONE matmul over all
    the heads' columns, their logsumexp a head and the chunk's share of each
    head's mean cross entropy."""
    heads = tc.shape[-1]
    logits = jnp.einsum("bce,ev->bcv", xc, head, preferred_element_type=jnp.float32)
    logits = logits.reshape(*logits.shape[:2], heads, -1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
    return logits, lse, jnp.sum((lse - tgt) * wc, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_multihead_cross_entropy(x, head, targets, weights, chunk):
    """(the mean over the heads of their mean cross entropies, each head's
    own (heads,): reported, not differentiated). The loss alone: one matmul a
    chunk."""
    def body(sums, xtw):
        xc, tc, wc = xtw
        return sums + _multihead_chunk(xc, head, tc, wc)[2], None

    sums, _ = jax.lax.scan(body, jnp.zeros((targets.shape[-1],)), _chunks(chunk, x, targets, weights))
    return jnp.mean(sums), sums


def _chunked_multihead_cross_entropy_fwd(x, head, targets, weights, chunk):
    """As `_chunked_cross_entropy_fwd`: while a chunk's logits are live, its
    share of d(objective)/dx and d(objective)/d(head). d(logits) of head n =
    (softmax - onehot) x weight / heads: the objective's cotangent is a
    scalar, and the heads' own losses carry none."""
    heads, vocab = targets.shape[-1], head.shape[-1] // targets.shape[-1]

    def body(carry, xtw):
        sums, dw = carry
        xc, tc, wc = xtw
        logits, lse, share = _multihead_chunk(xc, head, tc, wc)
        onehot = tc[..., None] == jnp.arange(vocab, dtype=tc.dtype)
        dlogits32 = (jnp.exp(logits - lse[..., None]) - onehot) * (wc / heads)[..., None]
        dlogits = dlogits32.reshape(*dlogits32.shape[:2], -1).astype(
            jnp.result_type(xc.dtype, head.dtype))
        dxc = jnp.einsum("bcv,ev->bce", dlogits, head).astype(xc.dtype)
        dw = dw + jnp.einsum("bce,bcv->ev", xc, dlogits, preferred_element_type=dw.dtype)
        return (sums + share, dw), dxc

    dw0 = jnp.zeros(head.shape, jnp.promote_types(head.dtype, jnp.float32))
    (sums, dw), dxs = jax.lax.scan(
        body, (jnp.zeros((heads,)), dw0), _chunks(chunk, x, targets, weights))
    dx = dxs.swapaxes(0, 1).reshape(x.shape)
    return (jnp.mean(sums), sums), (dx, dw.astype(head.dtype))


def _chunked_multihead_cross_entropy_bwd(chunk, residuals, cotangents):
    dx, dw = residuals
    g, _ = cotangents
    # the integer targets and the weights take no cotangent
    return (g * dx).astype(dx.dtype), (g * dw).astype(dw.dtype), None, None


_chunked_multihead_cross_entropy.defvjp(
    _chunked_multihead_cross_entropy_fwd, _chunked_multihead_cross_entropy_bwd)


def fused_multihead_cross_entropy(x: jax.Array, head: jax.Array, targets: jax.Array,
                                  has_target: jax.Array, *, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """`fused_linear_cross_entropy` for several next-token heads that share
    ONE head matrix (E, heads x V): a chunk's logits of all the heads are
    built once, by one matmul, in float32. x (B, S, E); targets and
    `has_target` (B, S, heads) (`multihead_targets`). Returns (the mean over
    the heads of each head's mean cross entropy over the positions that have
    its target, the heads' own means (heads,), which carry no gradient)."""
    if x.shape[1] % chunk:
        raise ValueError(f"seq len {x.shape[1]} not divisible by loss chunk {chunk}")
    objective, per_head = _chunked_multihead_cross_entropy(
        x, head, targets, _head_weights(has_target), chunk)
    return objective, jax.lax.stop_gradient(per_head)
