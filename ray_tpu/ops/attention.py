"""Flash attention for TPU: Pallas forward/backward kernels + XLA reference.

The reference framework has no attention kernel of its own — it rides on
vLLM/torch CUDA kernels (/root/reference/python/ray/llm/_internal/serve/
deployments/llm/vllm/vllm_engine.py:254). This module is the TPU-native
replacement: a blockwise online-softmax kernel (Dao et al.) tiled so the
score/accumulate matmuls land on the MXU and the running max/sum stay in
VMEM scratch across the kv-block grid dimension.

Layout convention: q (B, Hq, Sq, D); k, v (B, Hkv, Skv, D) with
Hq % Hkv == 0 (grouped-query attention — kv blocks are index-mapped onto
query-head groups, no materialized repeat on the forward path).

All shapes are static; padding to block multiples happens in the wrapper and
is masked inside the kernel, so XLA never sees dynamic shapes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30  # finite "minus infinity": keeps exp() at exactly 0.0 without NaNs
_LOG2E = 1.4426950408889634  # kernels fold log2(e) into sm_scale and use
# exp2/log2 internally: one VPU transcendental per element instead of
# exp's extra multiply (the standard TPU flash trick); the stored lse
# stays in NATURAL log so the backward contract is unchanged


# ------------------------------------------------------------------ reference


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    kv_len: Optional[int] = None,
) -> jax.Array:
    """Pure-XLA multi-head attention. Ground truth for the Pallas kernels and
    the CPU-backend fallback. Supports GQA and right-padding via `kv_len`."""
    _, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if hq != hkv:
        groups = hq // hkv
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * sm_scale
    mask = None
    if kv_len is not None:
        mask = jnp.arange(skv)[None, :] < kv_len
    if causal:
        causal_mask = jnp.arange(skv)[None, :] <= jnp.arange(sq)[:, None] + (skv - sq)
        mask = causal_mask if mask is None else (mask & causal_mask)
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


# -------------------------------------------------------------- pallas forward


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    kv_len: int,
    num_kv_blocks: int,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: kv blocks strictly above the diagonal band contribute nothing.
    needed = True
    if causal:
        needed = j * block_kv <= i * block_q + (block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]  # (block_q, d)
        k = k_ref[0, 0]  # (block_kv, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * (sm_scale * _LOG2E)  # base-2 log domain

        col = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < kv_len
        if causal:
            row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (col <= row)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # logsumexp residual for the backward pass; fully-masked rows get -inf.
        # Stored as (..., S, 1) — a (block_q, 1) block satisfies the Mosaic
        # last-two-dims tiling rule, a bare (block_q,) block does not.
        lse_ref[0, 0] = jnp.where(
            l == 0.0, _NEG_INF,
            (m_scr[:, :1] + jnp.log2(safe_l)) * (1.0 / _LOG2E),
        )


def _fwd_pallas(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    groups = hq // hkv
    nq = sq // block_q
    nk = skv // block_kv

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        kv_len=kv_len,
        num_kv_blocks=nk,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda b_, h, i, j, g=groups: (b_, h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda b_, h, i, j, g=groups: (b_, h // g, j, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ------------------------------------------------------------- pallas backward
#
# Standard flash backward (Dao et al. alg. 2), two kernels:
#   dkv kernel: grid kv-outer / q-inner, accumulates dK_j, dV_j across q blocks
#   dq  kernel: grid q-outer / kv-inner, accumulates dQ_i across kv blocks
# P is recomputed from (q, k, lse); delta = rowsum(dO * O) is cheap in XLA.
# GQA is handled in the wrapper (repeat kv, then segment-sum dk/dv) — the
# kernels always see Hq == Hkv.


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, sm_scale, causal, block_q, block_kv, kv_len, num_q_blocks,
):
    j = pl.program_id(2)  # kv block (outer)
    i = pl.program_id(3)  # q block (inner)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed = True
    if causal:
        needed = j * block_kv <= i * block_q + (block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (block_q, 1)
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (sm_scale * _LOG2E)
        col = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < kv_len
        if causal:
            row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (col <= row)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp2(s - lse * _LOG2E)  # (block_q, block_kv)

        # dV_j += P^T dO
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dP = dO V^T ; dS = P * (dP - delta)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        # dK_j += dS^T Q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_scr,
    *, sm_scale, causal, block_q, block_kv, kv_len, num_kv_blocks,
):
    i = pl.program_id(2)  # q block (outer)
    j = pl.program_id(3)  # kv block (inner)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    needed = True
    if causal:
        needed = j * block_kv <= i * block_q + (block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (block_q, 1)
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (sm_scale * _LOG2E)
        col = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < kv_len
        if causal:
            row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (col <= row)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp2(s - lse * _LOG2E)

        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, causal, sm_scale, block_q, block_kv, kv_len, interpret):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nq = sq // block_q
    nk = skv // block_kv

    # (b, h, sq, 1): the trailing singleton keeps row blocks 2D for Mosaic
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )

    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, j, i: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, j, i: (b_, h_, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, j, i: (b_, h_, i, 0))

    dkv_kernel = functools.partial(
        _dkv_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_kv=block_kv, kv_len=kv_len, num_q_blocks=nq,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    q_spec2 = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kv_spec2 = pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, i, j: (b_, h_, j, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0))

    dq_kernel = functools.partial(
        _dq_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_kv=block_kv, kv_len=kv_len, num_kv_blocks=nk,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, nq, nk),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=q_spec2,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------- custom_vjp plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret):
    out, _ = _fwd_pallas(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret):
    out, lse = _fwd_pallas(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_kv, kv_len, interpret, res, do):
    q, k, v, out, lse = res
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        groups = hq // hkv
        k_full = jnp.repeat(k, groups, axis=1)
        v_full = jnp.repeat(v, groups, axis=1)
    else:
        groups = 1
        k_full, v_full = k, v
    dq, dk, dv = _bwd_pallas(
        q, k_full, v_full, out, lse, do, causal, sm_scale, block_q, block_kv,
        kv_len, interpret,
    )
    if groups > 1:
        b, _, skv, d = dk.shape
        dk = dk.reshape(b, hkv, groups, skv, d).sum(axis=2)
        dv = dv.reshape(b, hkv, groups, skv, d).sum(axis=2)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------- pipelined forward kernel
#
# The classic forward above runs, per (q, kv) tile: QK^T (MXU) -> online
# softmax (VPU) -> PV (MXU) — a serial dependency chain that parks the MXU
# through the whole softmax. The pipelined forward breaks the chain with a
# one-step software skew over the kv-tile loop: inner step t issues tile
# t's QK^T while the online softmax/rescale for tile t-1 runs, so the two
# stages have no data dependency inside one step and Mosaic can overlap
# the MXU and VPU chains.
#
# On TPU the kv tiles stream HBM->VMEM through pltpu.emit_pipeline (explicit
# double buffering; q and the accumulators stay VMEM-resident across the
# whole row instead of being re-fetched per (i, j) grid step like the
# classic 4D grid does). Off-TPU an interpret-mode driver executes the SAME
# stage functions and slot arithmetic inside a fori_loop — the numerics of
# both drivers are identical by construction, and bit-identical to the
# classic kernel: tile math and accumulation order are unchanged, only the
# schedule moves. tests/test_ops.py pins that equality at f32.
#
# What the v5e compiler accepts (tests/test_tpu_compile.py): the streamed
# (1, 1, block_kv, D) tile must be lane-aligned, so D % 128 == 0 — at
# D = 64 Mosaic refuses the slice and `resolve_attention_impl` picks the
# classic kernel. There is no pipelined backward: its streamed
# (block_q, 1) lse/delta tiles were refused at every width, so the
# pipelined forward's residuals (same out/lse, bit for bit) feed the
# classic dkv/dq kernels.


def _fwd_stages(sm_scale, causal, block_q, block_kv, kv_len):
    """Per-tile forward stages. `scores` is the MXU stage (QK^T + mask),
    `online_update` the VPU-heavy stage (online softmax + PV rescale).
    Expressions mirror _fwd_kernel exactly — bit-compatibility depends on
    it."""

    def scores(q, k, i, t):
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * (sm_scale * _LOG2E)
        col = t * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < kv_len
        if causal:
            row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (col <= row)
        return jnp.where(mask, s, _NEG_INF)

    def online_update(s, v, m_scr, l_scr, acc_scr):
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    return scores, online_update


def _num_kv_tiles(i, causal, block_q, block_kv, nk):
    """kv tiles query block i touches (causal block skipping, same set the
    classic kernel's `needed` predicate admits)."""
    if not causal:
        return nk
    last = (i * block_q + block_q - 1) // block_kv
    return jnp.minimum(last + 1, nk)


def _fwd_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    l = l_scr[:, :1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(
        l == 0.0, _NEG_INF,
        (m_scr[:, :1] + jnp.log2(safe_l)) * (1.0 / _LOG2E),
    )


def _fwd_kernel_pipe_interp(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, s_scr,
    *, sm_scale, causal, block_q, block_kv, kv_len, num_kv_blocks,
):
    """Interpret-mode driver: the emit_pipeline schedule (skewed stages,
    double-buffered score slots) replayed in a fori_loop with whole-row k/v
    resident."""
    i = pl.program_id(2)
    scores, online_update = _fwd_stages(sm_scale, causal, block_q, block_kv, kv_len)
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0, 0]
    tiles = _num_kv_tiles(i, causal, block_q, block_kv, num_kv_blocks)

    def body(t, carry):
        @pl.when(t < tiles)
        def _stage_a():  # QK^T for tile t
            kt = k_ref[0, 0, pl.ds(t * block_kv, block_kv), :]
            s_scr[t % 2] = scores(q, kt, i, t)

        @pl.when(t > 0)
        def _stage_b():  # online softmax + PV for tile t-1
            vt = v_ref[0, 0, pl.ds((t - 1) * block_kv, block_kv), :]
            online_update(s_scr[(t - 1) % 2], vt, m_scr, l_scr, acc_scr)

        return carry

    jax.lax.fori_loop(0, tiles + 1, body, 0)
    _fwd_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _fwd_pipe_interp(q, k, v, causal, sm_scale, block_q, block_kv, kv_len):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    groups = hq // hkv
    nq = sq // block_q
    nk = skv // block_kv
    kernel = functools.partial(
        _fwd_kernel_pipe_interp, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_kv=block_kv, kv_len=kv_len, num_kv_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, skv, d), lambda b_, h, i, g=groups: (b_, h // g, 0, 0)),
            pl.BlockSpec((1, 1, skv, d), lambda b_, h, i, g=groups: (b_, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, i: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((2, block_q, block_kv), jnp.float32),
        ],
        interpret=True,
        name="flash_fwd_pipelined",
    )(q, k, v)


def _fwd_pipe_tpu(q, k, v, causal, sm_scale, block_q, block_kv, kv_len):
    """emit_pipeline driver: q/accumulators VMEM-resident per (b, h, i) row;
    kv tiles stream HBM->VMEM double-buffered, v delivered one step behind k
    so stage B always has the tile stage A scored on the previous step."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    groups = hq // hkv
    nq = sq // block_q
    nk = skv // block_kv

    def outer(q_ref, k_hbm, v_hbm, o_ref, lse_ref, m_scr, l_scr, acc_scr, s_scr):
        bi = pl.program_id(0)
        hi = pl.program_id(1)
        i = pl.program_id(2)
        hk = hi // groups
        scores, online_update = _fwd_stages(
            sm_scale, causal, block_q, block_kv, kv_len
        )
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q_blk = q_ref[0, 0]
        tiles = _num_kv_tiles(i, causal, block_q, block_kv, nk)

        def inner(k_ref, v_ref):
            t = pl.program_id(0)

            @pl.when(t < tiles)
            def _stage_a():
                s_scr[t % 2] = scores(q_blk, k_ref[0, 0], i, t)

            @pl.when(t > 0)
            def _stage_b():
                online_update(s_scr[(t - 1) % 2], v_ref[0, 0], m_scr, l_scr, acc_scr)

        pipeline = pltpu.emit_pipeline(
            inner,
            grid=(tiles + 1,),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, block_kv, d),
                    lambda t: (bi, hk, jnp.minimum(t, nk - 1), 0),
                ),
                pl.BlockSpec(
                    (1, 1, block_kv, d),
                    lambda t: (bi, hk, jnp.maximum(t - 1, 0), 0),
                ),
            ],
            out_specs=[],
        )
        pipeline(k_hbm, v_hbm)
        _fwd_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)

    return pl.pallas_call(
        outer,
        grid=(b, hq, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, i: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((2, block_q, block_kv), jnp.float32),
        ],
        name="flash_fwd_pipelined",
    )(q, k, v)


def _fwd_pipe(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret):
    if interpret:
        return _fwd_pipe_interp(q, k, v, causal, sm_scale, block_q, block_kv, kv_len)
    return _fwd_pipe_tpu(q, k, v, causal, sm_scale, block_q, block_kv, kv_len)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_pipelined(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret):
    out, _ = _fwd_pipe(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret)
    return out


def _flash_pipelined_fwd(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret):
    out, lse = _fwd_pipe(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret)
    return out, (q, k, v, out, lse)


_flash_pipelined.defvjp(_flash_pipelined_fwd, _flash_bwd)


# ------------------------------------------------------------------ public API


_PIPE_BLOCK_KV = 256  # stream tile: >=2 tiles in flight is what buys overlap
_IMPLEMENTATIONS = ("xla", "pallas", "pallas_pipelined")


def _pipe_blocks(sq: int, skv: int, block_q: Optional[int], block_kv: Optional[int]):
    """Pipelined defaults: whole-row q tiles (q stays VMEM-resident), small
    streaming kv tiles. Returns None if the shape leaves <2 kv tiles —
    nothing to overlap, the classic single-block kernel is the right tool."""
    bq = min(block_q or 1024, max(sq, 1))
    bkv = min(block_kv or _PIPE_BLOCK_KV, max(skv, 1))
    padded_skv = skv + ((-skv) % bkv)
    if padded_skv // bkv < 2:
        return None
    return bq, bkv


def _blocks(implementation: str, sq: int, skv: int,
            block_q: Optional[int], block_kv: Optional[int]):
    """(block_q, block_kv) of a resolved Pallas implementation."""
    if implementation == "pallas_pipelined":
        return _pipe_blocks(sq, skv, block_q, block_kv)
    return min(block_q or 1024, max(sq, 1)), min(block_kv or 1024, max(skv, 1))


def resolve_attention_impl(
    head_dim: int,
    sq: int,
    skv: int,
    *,
    implementation: Optional[str] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
) -> str:
    """The implementation `flash_attention` runs for a shape: "xla",
    "pallas" (classic kernel) or "pallas_pipelined". Every choice is a
    static rule on the backend and the shape — a kernel is never tried and
    swapped for another when it fails:

    - nothing requested, backend not "tpu": "xla". Off-TPU the Pallas
      kernels only run through the interpreter, for callers that ask.
    - nothing requested, backend "tpu": "pallas_pipelined" when
      `cfg.attn_pipeline` is set, else "pallas".
    - "pallas_pipelined" (requested or chosen) becomes "pallas" when the
      shape leaves < 2 kv tiles (nothing to overlap), and, compiled for a
      TPU, when head_dim % 128 != 0: emit_pipeline's HBM->VMEM kv tile
      must be lane-aligned and Mosaic refuses a 64-wide slice.

    Callers that report what ran (chip_smoke.py, bench.py) print this.
    """
    if implementation is not None and implementation not in _IMPLEMENTATIONS:
        raise ValueError(f"unknown attention implementation: {implementation!r}")
    on_tpu = jax.default_backend() == "tpu"
    if implementation is None:
        if not on_tpu:
            return "xla"
        from ..core.config import cfg

        implementation = "pallas_pipelined" if cfg.attn_pipeline else "pallas"
    if implementation == "pallas_pipelined" and (
        (on_tpu and head_dim % 128)
        or _pipe_blocks(sq, skv, block_q, block_kv) is None
    ):
        return "pallas"
    return implementation


def _per_shard(kernel_fn):
    """GSPMD cannot partition a Mosaic call ("wrap the call in a
    shard_map"), so a Pallas kernel traced under a context mesh
    (make_train_step traces its step inside `use_abstract_mesh`) runs
    once per shard: batch over the data axes, heads over tp — every
    shard is a whole (S, D) attention problem, no collective needed.
    With no context mesh, one device, or inside somebody else's
    shard_map (ring, Ulysses, pipeline, explicit-dp: the axes are already
    manual there) the kernel is called as it is. Axis names are
    parallel.mesh's (DATA_AXES, "tp")."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return kernel_fn
    batch = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    heads = "tp" if mesh.shape.get("tp", 1) > 1 else None
    if not batch and heads is None:
        return kernel_fn
    spec = P(batch or None, heads, None, None)
    return jax.shard_map(
        kernel_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )


def _pad_seq(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    length = x.shape[axis]
    pad = (-length) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    implementation: Optional[str] = None,
) -> jax.Array:
    """Blockwise flash attention. q (B,Hq,Sq,D); k,v (B,Hkv,Skv,D).

    implementation: "pallas_pipelined" (double-buffered emit_pipeline
    forward + classic backward; skewed-schedule interpret driver off-TPU),
    "pallas" (classic kernel; interpreted off-TPU), "xla" (reference), or
    None = the static rule of `resolve_attention_impl`.

    Block defaults: classic kernel 1024x1024 (clamped to the sequence) —
    at head_dim 64-128 it is grid-overhead-bound and big tiles measured
    3.1x faster than 128x128 on v5e while the f32 score tile (4 MB) still
    fits VMEM. Pipelined kernel 1024x256: q stays VMEM-resident so small
    kv tiles cost no revisit overhead, and >=4 tiles in flight is what
    lets the next tile's QK^T overlap the current tile's softmax.
    """
    sq, skv = q.shape[2], k.shape[2]
    implementation = resolve_attention_impl(
        q.shape[-1], sq, skv, implementation=implementation,
        block_q=block_q, block_kv=block_kv,
    )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if implementation == "xla":
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    if causal and sq != skv:
        raise NotImplementedError("causal flash kernel requires Sq == Skv")
    interpret = jax.default_backend() != "tpu"
    kernel = _flash_pipelined if implementation == "pallas_pipelined" else _flash
    bq, bkv = _blocks(implementation, sq, skv, block_q, block_kv)
    out = _per_shard(
        lambda q_, k_, v_: kernel(
            q_, k_, v_, causal, sm_scale, bq, bkv, skv, interpret
        )
    )(_pad_seq(q, 2, bq), _pad_seq(k, 2, bkv), _pad_seq(v, 2, bkv))
    if out.shape[2] != sq:
        out = out[:, :, :sq]
    return out


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    implementation: Optional[str] = None,
) -> "tuple[jax.Array, jax.Array]":
    """Like flash_attention but also returns the per-row logsumexp of the
    scaled scores, shape (B, Hq, Sq, 1) float32 — the carry blockwise
    consumers (ring attention) need to merge partial attentions exactly.

    FORWARD ONLY: no VJP is registered through the lse output; callers
    that need gradients wrap their own (ring_attention's custom_vjp
    recomputes through the einsum reference)."""
    sq, skv = q.shape[2], k.shape[2]
    implementation = resolve_attention_impl(
        q.shape[-1], sq, skv, implementation=implementation,
        block_q=block_q, block_kv=block_kv,
    )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if causal and sq != skv:
        raise NotImplementedError("causal requires Sq == Skv")
    if implementation == "xla":
        _, hq, _, _ = q.shape
        hkv = k.shape[1]
        if hq != hkv:
            groups = hq // hkv
            k = jnp.repeat(k, groups, axis=1)
            v = jnp.repeat(v, groups, axis=1)
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * sm_scale
        if causal:
            row = jnp.arange(sq)[:, None]
            col = jnp.arange(skv)[None, :]
            s = jnp.where(col <= row, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bhqk,bhkd->bhqd", p / l, v.astype(p.dtype))
        return out.astype(q.dtype), m + jnp.log(l)
    interpret = jax.default_backend() != "tpu"
    fwd = _fwd_pipe if implementation == "pallas_pipelined" else _fwd_pallas
    bq, bkv = _blocks(implementation, sq, skv, block_q, block_kv)
    out, lse = fwd(
        _pad_seq(q, 2, bq), _pad_seq(k, 2, bkv), _pad_seq(v, 2, bkv),
        causal, sm_scale, bq, bkv, skv, interpret,
    )
    if out.shape[2] != sq:
        out = out[:, :, :sq]
        lse = lse[:, :, :sq]
    return out, lse
