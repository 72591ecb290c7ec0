"""Training losses: cross entropy with optional z-loss, computed in float32.

The einsum-free formulation (take_along_axis on log-softmax) avoids
materializing one-hot targets — at 50k-128k vocab the one-hot would dominate
HBM traffic in the loss.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


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
# backward softmax materializes (PERF_NOTES.md: the b24->b32 regression)
_DENSE_LOSS_BYTES_PER_LOGIT = 2 + 4 + 4
_AUTO_CHUNK_HEADROOM_FRACTION = 0.2  # the least kept for params/opt/activations
_CHUNK_CANDIDATES = (512, 256, 128)


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

    The dense path is ~8% faster when it fits (PERF_NOTES.md: its extra
    recomputed head matmul + scan overhead), so dense wins until the
    (B_local, S, V) logits working set crowds the HBM — measured on v5e
    16G: batch 24 dense 118.5k tok/s, batch 32 REGRESSES to 111k while
    fused holds 110.3k flat. The logits have the device less a headroom:
    `resident_bytes` the caller knows a device holds all along (its share
    of the train state, counted from the shardings) plus `step_bytes` it
    will hold during the step (its gradients), and never less than 20% of
    the device — so a small model crosses over where it always did
    (estimate > 80% of HBM), and a model whose state fills most of the
    chip (one OLMoE layer: 10 GB of 16.9) chunks though its logits alone
    would fit. Nothing live is probed but the device's size, so the same
    model and batch always get the same program.

    hbm_bytes None = probe the local device (memory_stats().bytes_limit);
    an unknown limit (CPU backends) means no HBM cliff to dodge -> dense."""
    if hbm_bytes is None:
        hbm_bytes = _device_hbm_bytes()
    if not hbm_bytes:
        return 0
    headroom = max(resident_bytes + step_bytes, _AUTO_CHUNK_HEADROOM_FRACTION * hbm_bytes)
    est = batch_per_device * seq * vocab * _DENSE_LOSS_BYTES_PER_LOGIT
    if est <= hbm_bytes - headroom:
        return 0
    for chunk in _CHUNK_CANDIDATES:
        if seq % chunk == 0:
            return chunk
    return 0


def _device_hbm_bytes() -> int:
    try:
        device = jax.local_devices()[0]
        if getattr(device, "platform", "cpu") == "cpu":
            return 0
        stats = device.memory_stats() or {}
        return int(stats.get("bytes_limit", 0))
    except Exception:  # noqa: BLE001 - heuristic must never fail a trace
        return 0


def fused_linear_cross_entropy(
    x: jax.Array,
    head: jax.Array,
    targets: jax.Array,
    *,
    chunk: int = 256,
    mask: Optional[jax.Array] = None,
    z_loss_coeff: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """lm_head matmul + CE fused over sequence chunks: the full
    (B, S, V) logits tensor — the peak-HBM hog of LM training (f32
    copies of it dominate the working set at 50k vocab; measured on
    v5e: batch 24→32 REGRESSES 118.5k→111k tok/s without this) — is
    never materialized. Each chunk's logits live only inside a
    rematerialized scan body (forward AND backward), trading one extra
    head matmul per chunk in the backward (~+10% head flops) for
    O(S/chunk) less loss memory.

    x: (B, S, E) pre-head hidden states; head: (E, V); targets: (B, S).
    Same return contract as cross_entropy_loss. S % chunk must be 0
    (pick chunk from {128, 256, 512}; S here is a static shape).
    """
    b, s, _ = x.shape
    if s % chunk:
        raise ValueError(f"seq len {s} not divisible by loss chunk {chunk}")
    nc = s // chunk
    xs = x.reshape(b, nc, chunk, x.shape[-1]).swapaxes(0, 1)
    ts = targets.reshape(b, nc, chunk).swapaxes(0, 1)
    if mask is not None:
        ms = mask.reshape(b, nc, chunk).swapaxes(0, 1).astype(jnp.float32)
    else:
        ms = jnp.ones((nc, b, chunk), jnp.float32)

    def chunk_loss(xc, tc, mc):
        logits32 = jnp.einsum("bce,ev->bcv", xc, head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits32, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logits32 - logz, tc[..., None], axis=-1)[..., 0]
        return (
            jnp.sum(nll * mc),
            jnp.sum(jnp.square(logz[..., 0]) * mc),
            jnp.sum(mc),
        )

    chunk_loss = jax.checkpoint(chunk_loss)

    def body(carry, xtm):
        xc, tc, mc = xtm
        nll, zl, n = chunk_loss(xc, tc, mc)
        return (carry[0] + nll, carry[1] + zl, carry[2] + n), None

    (total_nll, total_zl, num), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), (xs, ts, ms)
    )
    num = jnp.maximum(num, 1.0)
    loss = total_nll / num
    if z_loss_coeff:
        loss = loss + z_loss_coeff * (total_zl / num)
    return loss, num
