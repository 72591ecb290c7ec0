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
# and the chunked path, per logit of ONE chunk: the bf16 logits, and room
# for a float32 pass over them (the compiled v5e program keeps only the
# narrow ones: 1.7 bytes a logit by its memory analysis)
_CHUNKED_LOSS_BYTES_PER_LOGIT = 2 + 4
# the share of the room beside state and gradients that one chunk's logits
# may take: the activations live there too and are not counted. Measured on
# a v5e (PERF.md section 6, PR 28): chunks at 36-37% of the room were the
# fastest that fit (OLMoE 4 x 4,096: 2,048; gpt2-small at batch 32: 512),
# and at 73% gpt2-small's 1,024 lost 6% to 512 with the chip 95% full
_CHUNK_ROOM_FRACTION = 0.5
_AUTO_CHUNK_HEADROOM_FRACTION = 0.2  # the least kept for params/opt/activations
_CHUNK_CANDIDATES = (2048, 1024, 512, 256, 128)


def auto_loss_chunk(
    batch_per_device: int,
    seq: int,
    vocab: int,
    hbm_bytes: Optional[int] = None,
    *,
    resident_bytes: int = 0,
    step_bytes: int = 0,
) -> int:
    """Pick the fused-linear-CE chunk size (0 = dense) from the logits HBM
    working-set estimate vs what the device has left.

    Where the (B_local, S, V) logits fit, the head is dense. (On a v5e,
    gpt2-small at batch 24, the chunked head reads +2.8% over the dense one
    with 4.4 GB less memory, PERF.md section 6, PR 28: whether the dense
    head should stay at all is ROADMAP Design 2's question; this function
    only says where the logits stop fitting.) The logits have the device
    less a headroom: `resident_bytes` the caller knows a
    device holds all along (its share of the train state, counted from the
    shardings) plus `step_bytes` it will hold during the step (its
    gradients), and never less than 20% of the device — so a small model
    crosses over at estimate > 80% of HBM (gpt2-small on a 16G v5e: dense
    at batch 24, chunked at 32), and a model whose state fills most of the
    chip (one OLMoE layer: 10 GB of 16.9) chunks though its logits alone
    would fit. The chunk is then the largest of `_CHUNK_CANDIDATES` that
    divides S and whose own logits fit half of the same room (the smallest
    that divides S if none does): each chunk reads and writes the head
    gradient's float32 accumulator once, so fewer, larger chunks are
    faster while memory is not short (train-olmoe-64e-4k on a v5e, PR 28:
    2,048 over 512 +2.1%, peak memory 75.0% -> 76.3%). Nothing live is
    probed but the device's size, so the same model and batch always get
    the same program.

    hbm_bytes None = probe the local device (memory_stats().bytes_limit);
    an unknown limit (CPU backends) means no HBM cliff to dodge -> dense."""
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    if not hbm_bytes:
        return 0
    if loss_logits_bytes(batch_per_device, seq, vocab) <= _logits_room(
            hbm_bytes, resident_bytes, step_bytes):
        return 0
    return chunk_of_a_chunked_head(
        batch_per_device, seq, vocab, hbm_bytes,
        resident_bytes=resident_bytes, step_bytes=step_bytes)


def _logits_room(hbm_bytes: int, resident_bytes: int, step_bytes: int) -> float:
    return hbm_bytes - max(resident_bytes + step_bytes, _AUTO_CHUNK_HEADROOM_FRACTION * hbm_bytes)


def chunk_of_a_chunked_head(
    batch_per_device: int, seq: int, vocab: int, hbm_bytes: int, *,
    resident_bytes: int = 0, step_bytes: int = 0,
) -> int:
    """The chunk `auto_loss_chunk` gives a head that is chunked, whoever
    decided that it is: the largest of `_CHUNK_CANDIDATES` that divides S and
    whose own logits fit half the logits' room (the smallest that divides S
    if none does; 0, the dense head, if none divides S)."""
    room = _logits_room(hbm_bytes, resident_bytes, step_bytes)
    dividing = [chunk for chunk in _CHUNK_CANDIDATES if seq % chunk == 0]
    for chunk in dividing:
        if loss_logits_bytes(batch_per_device, seq, vocab, chunk) <= _CHUNK_ROOM_FRACTION * room:
            return chunk
    return dividing[-1] if dividing else 0


def loss_logits_bytes(batch_per_device: int, seq: int, vocab: int, chunk: int = 0) -> int:
    """The estimate of what the head's logits hold on a device: all of them
    under the dense loss (chunk 0), one chunk's under the chunked one."""
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
