"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
ICLR 2023, in the form the EvaByte family trains): causal softmax attention
that is EXACT inside a window of `window` positions and reads everything
before the window as one learned summary a `chunk` positions, under one
softmax.

With s = D^-0.5, chunk j = positions [c j, c j + c) and a head's two learned
vectors mu, phi (D,):

    a_t  = softmax over the chunk's t of s (mu . k_t),   kbar_j = sum_t a_t k_t
    b_t  = softmax over the chunk's t of s (phi . k_t),  vbar_j = sum_t b_t v_t

Query i of window W = i // w scores s (q_i . k_t) for the t <= i of its own
window and s (q_i . kbar_j) for every chunk j of an EARLIER window (j < W w /
c; none of its own), and its output is the one softmax over both sets times
the v_t and the vbar_j. A query of the first window reads no summary.

On a TPU ("pallas") the local part IS the causal flash kernel of
ops/attention.py over B x S / w independent windows (a free reshape: a
window's rows are contiguous), and the far part is two kernels of this file,
`eva_far_fwd` and `eva_far_bwd`: a grid step is one window's queries of one
head with the head's summaries resident in VMEM, and walks the W w / c
summaries before its window as whole unmasked pieces (a summary is visible
to all of a window's queries or to none: no mask is ever built). The forward
kernel starts its online softmax from the local part's (o, lse), so what it
writes is the merged output and the merged lse; the backward of the local
part is `flash_bwd_dkv_dq` given the MERGED output and lse (p = exp(s - lse)
and delta = rowsum(dO O) are the whole row's), and the far backward kernel
adds its dQ to the local one and sums the summaries' cotangents over the
windows in resident float32 blocks. The pooling is plain XLA and
differentiated by JAX. Off a TPU ("xla") it is the plain masked form: every
score, one mask, one softmax.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attn
from .attention import _LOG2E, _NEG_INF

# Query rows a step of the far kernels' walk takes, and the windows whose
# summaries it takes at a time (the remainder one window at a time): pieces of
# 1,024 x 512 scores at the published sizes (window 2,048, chunk 16: 128
# summaries a window). One chip sweep at the cell's shard, 1 x 32 x 32,768 x
# 128 (PERF.md section 6, PR 51), ms a call of the whole op by the host's clock
# over 5 queued calls, forward / forward + backward (local part 6.3 / 16.7,
# pooling 5.1 forward); strip x group 1 / 2 / 4 / 8:
#    256:  35.3 / 75.2   30.2 / 61.0   23.8 / 54.7   25.1 / 57.6
#    512:  37.1 / 73.8   28.0 / 56.9   23.9 / 57.2   26.1 / 57.2
#  1,024:  29.7 / 60.8   24.5 / 52.1   20.4 / 47.7   22.3 / 50.8
# (a whole window a strip, 2,048, was not tried).
_FAR_STRIP = 1024
_FAR_GROUP = 4
_FAR_VMEM_BYTES = 64 * 1024 * 1024


def _check(q, k, v, mu, phi, window: int, chunk: int) -> None:
    b, h, s, d = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"eva_attention: q, k and v must have one shape (no grouped keys): "
                         f"{q.shape}, {k.shape}, {v.shape}")
    if mu.shape != (h, d) or phi.shape != (h, d):
        raise ValueError(f"eva_attention: mu and phi are (heads, head_dim) = {(h, d)}, "
                         f"got {mu.shape} and {phi.shape}")
    if window < 1 or chunk < 1 or window % chunk:
        raise ValueError(f"eva_attention: window {window} is no multiple of chunk {chunk}")
    if s % window:
        raise ValueError(f"eva_attention: sequence length {s} is no multiple of window {window}: "
                         "a window's queries and an earlier window's summaries are whole tiles")


def pool_chunks(k: jax.Array, v: jax.Array, mu: jax.Array, phi: jax.Array, *, chunk: int,
                sm_scale: float) -> Tuple[jax.Array, jax.Array]:
    """(kbar, vbar), each (B, H, S / chunk, D): a chunk's keys under the
    softmax of s (mu . k_t) over the chunk, its values under that of s (phi .
    k_t). Float32 inside, the operands' dtype out."""
    b, h, s, d = k.shape
    k32 = k.reshape(b, h, s // chunk, chunk, d).astype(jnp.float32)
    v32 = v.reshape(b, h, s // chunk, chunk, d).astype(jnp.float32)

    def weights(vector):
        logits = jnp.sum(k32 * vector.astype(jnp.float32)[None, :, None, None, :], axis=-1)
        return jax.nn.softmax(logits * sm_scale, axis=-1)[..., None]

    kbar = jnp.sum(weights(mu) * k32, axis=3)
    vbar = jnp.sum(weights(phi) * v32, axis=3)
    return kbar.astype(k.dtype), vbar.astype(v.dtype)


# ------------------------------------------------------------ plain masked form


def _eva_xla(q, k, v, kbar, vbar, window: int, chunk: int, sm_scale: float) -> jax.Array:
    """Every score against every key and every summary, the two masks, ONE
    softmax over both sets: the definition, for the CPU and the tests."""
    s = q.shape[2]
    row = jnp.arange(s)
    with jax.named_scope("attn.eva.local"):
        local = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * sm_scale
        same_window = (row[:, None] // window) == (row[None, :] // window)
        local = jnp.where(same_window & (row[None, :] <= row[:, None]), local, _NEG_INF)
    with jax.named_scope("attn.eva.far"):
        far = jnp.einsum("bhqd,bhjd->bhqj", q, kbar, preferred_element_type=jnp.float32) * sm_scale
        chunk_window = (jnp.arange(s // chunk) * chunk) // window
        far = jnp.where(chunk_window[None, :] < (row // window)[:, None], far, _NEG_INF)
    with jax.named_scope("attn.eva.merge"):
        probs = jax.nn.softmax(jnp.concatenate([local, far], axis=-1), axis=-1)
        out = (jnp.einsum("bhqk,bhkd->bhqd", probs[..., :s].astype(v.dtype), v,
                          preferred_element_type=jnp.float32)
               + jnp.einsum("bhqj,bhjd->bhqd", probs[..., s:].astype(vbar.dtype), vbar,
                            preferred_element_type=jnp.float32))
    return out.astype(q.dtype)


# ------------------------------------------------------------- the far kernels


def _walk_summaries(windows, per_window: int, group: int, piece) -> None:
    """`piece(first summary, how many)` over the summaries of the `windows`
    (a traced count) windows before this one: `group` windows' at a time, then
    the rest a window's at a time. Every piece is whole and unmasked."""
    wide = windows // group if group > 1 else 0

    def wide_piece(t, carry):
        piece(pl.multiple_of(t * (group * per_window), group * per_window), group * per_window)
        return carry

    def narrow_piece(j, carry):
        piece(pl.multiple_of(j * per_window, per_window), per_window)
        return carry

    if group > 1:
        jax.lax.fori_loop(0, wide, wide_piece, 0)
    jax.lax.fori_loop(wide * group, windows, narrow_piece, 0)


def _far_fwd_kernel(q_ref, kb_ref, vb_ref, o_loc_ref, lse_loc_ref, o_ref, lse_ref,
                    m_scr, l_scr, acc_scr, *, sm_scale, per_window, strip, group):
    """One window's queries of one head: the online softmax goes on from the
    local part's (o, lse) over the summaries of the windows before it."""
    windows = pl.program_id(2)
    for a in range(q_ref.shape[2] // strip):
        rows = pl.ds(a * strip, strip)
        q = q_ref[0, 0, rows, :]
        # the local softmax as a carry: its lse is the running maximum (base-2
        # domain), its sum 1 and its accumulator its normalised output
        m_scr[...] = jnp.broadcast_to(lse_loc_ref[0, 0, rows, :] * _LOG2E, m_scr.shape)
        l_scr[...] = jnp.ones_like(l_scr)
        acc_scr[...] = o_loc_ref[0, 0, rows, :].astype(jnp.float32)

        def piece(first, size, q=q):
            cols = pl.ds(first, size)
            scores = _attn._scores(q, kb_ref[0, 0, cols, :], sm_scale * _LOG2E, None, False, None)
            m, l, acc = _attn._softmax_pieces(
                [scores], [vb_ref[0, 0, cols, :]],
                (m_scr[:, :1], l_scr[:, :1], lambda: acc_scr[...]))
            acc_scr[...] = acc
            m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

        _walk_summaries(windows, per_window, group, piece)
        _attn._write_out(o_ref, lse_ref, rows, m_scr[:, :1], l_scr[:, :1], acc_scr[...])


def _far_bwd_kernel(q_ref, kb_ref, vb_ref, o_ref, lse_ref, do_ref, dq_loc_ref,
                    dq_ref, dkb_ref, dvb_ref, dq_scr, *, sm_scale, per_window, strip, group):
    """One window's queries of one head: dS of the summaries before it from
    the merged lse and output; dQ goes on from the local part's, the
    summaries' cotangents sum over the windows in their resident blocks."""
    windows = pl.program_id(2)

    @pl.when(windows == 0)
    def _():
        dkb_ref[...] = jnp.zeros_like(dkb_ref)
        dvb_ref[...] = jnp.zeros_like(dvb_ref)

    for a in range(q_ref.shape[2] // strip):
        rows = pl.ds(a * strip, strip)
        q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
        lse, o = lse_ref[0, 0, rows, :], o_ref[0, 0, rows, :]
        dq_scr[...] = dq_loc_ref[0, 0, rows, :].astype(jnp.float32)

        def piece(first, size, q=q, do=do, lse=lse, o=o):
            cols = pl.ds(first, size)
            kb, vb = kb_ref[0, 0, cols, :], vb_ref[0, 0, cols, :]
            p = _attn._probs(q, kb, lse, sm_scale, None, False, None)
            ds = _attn._ds(p, do, vb, o, sm_scale).astype(q.dtype)
            dvb_ref[0, 0, cols, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            dkb_ref[0, 0, cols, :] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            dq_scr[...] += jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        _walk_summaries(windows, per_window, group, piece)
        dq_ref[0, 0, rows, :] = dq_scr[...].astype(dq_ref.dtype)


def _far_shapes(q, window: int, chunk: int):
    """(B, H, S, D, windows, summaries a window, the walk's strip, its group:
    no more windows at a time than any query has before its own)."""
    b, h, s, d = q.shape
    strip = _FAR_STRIP if window % _FAR_STRIP == 0 else window
    return b, h, s, d, s // window, window // chunk, strip, max(1, min(_FAR_GROUP, s // window - 1))


def _far_specs(window: int, summaries: int, d: int):
    """Block specs of a (B, H, nW) grid: a window's rows of a (B, H, S, .)
    array, and a head's summaries whole (the same block for every window of a
    head, so they are copied once a head)."""
    rows = lambda width: pl.BlockSpec((1, 1, window, width), lambda b, h, w: (b, h, w, 0))  # noqa: E731
    return rows, pl.BlockSpec((1, 1, summaries, d), lambda b, h, w: (b, h, 0, 0))


def _far_fwd(q, kbar, vbar, o_loc, lse_loc, window: int, chunk: int, sm_scale: float,
             interpret: bool):
    """(merged output (B, H, S, D), merged lse (B, H, S, 1)) from the local
    part's, which the two outputs take the place of."""
    b, h, s, d, windows, per_window, strip, group = _far_shapes(q, window, chunk)
    rows, summaries = _far_specs(window, kbar.shape[2], d)
    kernel = functools.partial(_far_fwd_kernel, sm_scale=sm_scale, per_window=per_window,
                               strip=strip, group=group)
    return pl.pallas_call(
        kernel, grid=(b, h, windows),
        in_specs=[rows(d), summaries, summaries, rows(d), rows(1)],
        out_specs=[rows(d), rows(1)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((strip, 128), jnp.float32), pltpu.VMEM((strip, 128), jnp.float32),
                        pltpu.VMEM((strip, d), jnp.float32)],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_FAR_VMEM_BYTES),
        interpret=interpret, name="eva_far_fwd",
    )(q, kbar, vbar, o_loc, lse_loc)


def _far_bwd(q, kbar, vbar, out, lse, do, dq_loc, window: int, chunk: int, sm_scale: float,
             interpret: bool):
    """(dQ of both parts, d kbar, d vbar in float32) from the merged output
    and lse and the local part's dQ, which the first output takes the place of."""
    b, h, s, d, windows, per_window, strip, group = _far_shapes(q, window, chunk)
    rows, summaries = _far_specs(window, kbar.shape[2], d)
    kernel = functools.partial(_far_bwd_kernel, sm_scale=sm_scale, per_window=per_window,
                               strip=strip, group=group)
    return pl.pallas_call(
        kernel, grid=(b, h, windows),
        in_specs=[rows(d), summaries, summaries, rows(d), rows(1), rows(d), rows(d)],
        out_specs=[rows(d), summaries, summaries],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(kbar.shape, jnp.float32),
                   jax.ShapeDtypeStruct(vbar.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((strip, d), jnp.float32)],
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FAR_VMEM_BYTES),
        interpret=interpret, name="eva_far_bwd",
    )(q, kbar, vbar, out, lse, do, dq_loc)


# -------------------------------------------------- local + far, one custom_vjp


def _local_blocks(window: int, d: int):
    """(block, whether the local call's grid is the causal triangle of tiles)
    of the flash kernels over one window a head."""
    block, _ = _attn._blocks(window, window, None, None, None, d)
    if window % block:
        raise ValueError(f"eva_attention: window {window} is no multiple of the flash kernels' "
                         f"tile side {block}")
    tiles = window // block
    return block, _attn._live_grid(True, None, block, block, tiles, tiles)


def _core_forward(q, k, v, kbar, vbar, window, chunk, sm_scale, interpret):
    b, h, s, d = q.shape
    block, triangle = _local_blocks(window, d)
    fold = lambda x: x.reshape(b, h * (s // window), window, x.shape[-1])  # noqa: E731
    with jax.named_scope("attn.eva.local"):
        o_loc, lse_loc = _attn._fwd_pallas(fold(q), fold(k), fold(v), True, sm_scale, block, block,
                                           window, interpret, None, triangle)
    with jax.named_scope("attn.eva.far"):
        return _far_fwd(q, kbar, vbar, o_loc.reshape(q.shape), lse_loc.reshape(b, h, s, 1),
                        window, chunk, sm_scale, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _core(q, k, v, kbar, vbar, window, chunk, sm_scale, interpret):
    return _core_forward(q, k, v, kbar, vbar, window, chunk, sm_scale, interpret)[0]


def _core_fwd(q, k, v, kbar, vbar, window, chunk, sm_scale, interpret):
    out, lse = _core_forward(q, k, v, kbar, vbar, window, chunk, sm_scale, interpret)
    # what a recomputing block may keep in place of this call, both or neither
    # (ops/attention._flash_fwd): the MERGED output and lse, the lse as (B, H, S)
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse[..., 0], "attn_lse")
    return out, (q, k, v, kbar, vbar, out, lse)


def _core_bwd(window, chunk, sm_scale, interpret, res, do):
    q, k, v, kbar, vbar, out, lse = res
    b, h, s, d = q.shape
    lse = lse[..., None]
    block, triangle = _local_blocks(window, d)
    fold = lambda x: x.reshape(b, h * (s // window), window, x.shape[-1])  # noqa: E731
    with jax.named_scope("attn.eva.local"):
        dq_loc, dk, dv = _attn._bwd_pallas(
            fold(q), fold(k), fold(v), fold(out), fold(lse), fold(do), True, sm_scale, block, block,
            window, interpret, None, triangle)
    with jax.named_scope("attn.eva.far"):
        dq, dkbar, dvbar = _far_bwd(q, kbar, vbar, out, lse, do, dq_loc.reshape(q.shape),
                                    window, chunk, sm_scale, interpret)
    return (dq, dk.reshape(k.shape), dv.reshape(v.shape),
            dkbar.astype(kbar.dtype), dvbar.astype(vbar.dtype))


_core.defvjp(_core_fwd, _core_bwd)


# ------------------------------------------------------------------ public API


def eva_plan(seq: int, *, window: int, chunk: int, head_dim: int,
             implementation: Optional[str] = None) -> dict:
    """What `eva_attention` runs for one head of an S-long sequence, for
    callers that report it (LMTrainer's `train.init.step_fn` span): the
    resolved implementation, the local part's live tiles (the flash kernels'
    grid steps over S / window windows) and the far part's (a window's queries
    against one earlier window's summaries), and the pallas calls of a
    differentiated call."""
    impl = _attn.resolve_attention_impl(implementation)
    windows = seq // window
    plan = {"eva_impl": impl, "eva_window": window, "eva_chunk": chunk,
            "eva_summaries": seq // chunk, "eva_local_tiles": 0,
            "eva_far_tiles": windows * (windows - 1) // 2, "eva_kernels": 0}
    if impl != "xla":
        block, _ = _local_blocks(window, head_dim)
        tiles = window // block
        plan.update(eva_local_tiles=windows * tiles * (tiles + 1) // 2, eva_kernels=4)
    return plan


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array, mu: jax.Array, phi: jax.Array, *,
                  window: int, chunk: int, sm_scale: Optional[float] = None,
                  implementation: Optional[str] = None) -> jax.Array:
    """EVA attention of q, k, v (B, H, S, D) with a head's learned pooling
    vectors mu, phi (H, D): the module docstring. S must be a multiple of
    `window`, `window` of `chunk`. implementation: "pallas" (the kernels;
    interpreted off a TPU), "xla" (the plain masked form) or None = the static
    rule of `ops/attention.resolve_attention_impl`. The scope `attn.eva`, with
    `attn.eva.pool`, `attn.eva.local`, `attn.eva.far` and (the plain form's one
    softmax; the kernels merge inside `eva_far_fwd`) `attn.eva.merge` in it.
    Under a context mesh the kernels run once a shard, as the flash kernels
    do (`ops/attention._per_shard`)."""
    _check(q, k, v, mu, phi, window, chunk)
    implementation = _attn.resolve_attention_impl(implementation)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    with jax.named_scope("attn.eva"):
        with jax.named_scope("attn.eva.pool"):
            kbar, vbar = pool_chunks(k, v, mu, phi, chunk=chunk, sm_scale=sm_scale)
        if implementation == "xla":
            return _eva_xla(q, k, v, kbar, vbar, window, chunk, sm_scale)
        interpret = jax.default_backend() != "tpu"
        return _attn._per_shard(
            lambda *operands: _core(*operands, window, chunk, sm_scale, interpret)
        )(q, k, v, kbar, vbar)
