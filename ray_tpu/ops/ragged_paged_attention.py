"""Ragged paged attention: ONE kernel launch for mixed prefill + decode.

The serve engine's former dispatch was split — `batched_chunk_prefill_step`
for prompt chunks, the Pallas paged-attention kernel (decode, q_len == 1)
for everything else — so a tick with both kinds of work paid two compiled
programs and two rounds of HBM traffic over the page pool. This module is
the ragged-paged-attention recipe from PAPERS.md (arxiv 2604.15464): the
batch is described RAGGED — per-sequence q lengths, kv lengths and
scalar-prefetched block tables — and one grid covers prefill chunks
(q_len up to chunk_tokens) and decode lanes (q_len == 1) together.

Layout:

- q is TOKEN-MAJOR with heads leading: (Hq, T, D). T is the concatenation
  of per-sequence q REGIONS, each a whole number of `block_q` rows
  (`q_starts`/`q_block_counts`, in block units). A sequence's real rows are
  the first `q_lens[s]` of its region; the rest are padding the kernel
  masks off and writes back as zeros.
- K/V come straight from the paged pool, (Hkv, P, ps, D); `block_tables`
  (S, maxP) holds absolute page ids (callers fold per-layer offsets in).
  Unused table entries must point at the scratch page 0.
- The query at region row r of sequence s sits at token position
  kv_lens[s] - q_lens[s] + r; causal masking and the kv-length bound both
  derive from that, so a prefill chunk at offset o (q_len = chunk tokens,
  kv_len = o + chunk tokens) and a decode lane (q_len = 1, kv_len =
  position + 1) are the same descriptor.

Numerics contract: the kernel uses plain exp (NOT the exp2 trick the dense
flash kernel uses) and the caller pre-scales q, so the XLA reference
`ragged_reference_attention` — a gather over block tables that replays the
kernel's block schedule op for op — is bit-exact vs the kernel at f32.
Which of the two runs is a static rule (`resolve_ragged_impl`): the kernel
on a TPU backend at Mosaic-tileable shapes, the reference elsewhere; the
interpret driver exists so CI can replay the exact kernel schedule without
hardware.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30  # finite "minus infinity": exp() lands at exactly 0.0


# ------------------------------------------------------------------ kernel


def _ragged_kernel(
    # scalar-prefetched descriptor (available before the body runs — they
    # drive the q/kv BlockSpec index maps)
    starts_ref,   # (S,)  region start, in block_q units
    counts_ref,   # (S,)  region size, in block_q units (>= 1)
    q_lens_ref,   # (S,)  real q rows in the region
    kv_lens_ref,  # (S,)  total kv length (includes this step's tokens)
    tables_ref,   # (S, maxP) absolute page ids (0 = scratch)
    # tensor refs
    q_ref,        # (1, block_q, D)
    k_ref,        # (1, 1, ps, D)
    v_ref,        # (1, 1, ps, D)
    o_ref,        # (1, block_q, D)
    m_scr,        # (block_q, 128) f32 running max
    l_scr,        # (block_q, 128) f32 running sum
    acc_scr,      # (block_q, D)  f32 running numerator
    *,
    block_q: int,
    page_size: int,
    num_kv_blocks: int,
):
    s = pl.program_id(0)
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    q_len = q_lens_ref[s]
    kv_len = kv_lens_ref[s]

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # A (qb, kb) tile contributes iff the q block holds a real row AND the
    # kv block starts at or before the block's last reachable position.
    # pos_hi is the causal frontier of the block's last REAL row.
    pos_hi = kv_len - q_len + jnp.minimum((qb + 1) * block_q, q_len) - 1
    work = (qb * block_q < q_len) & (kb * page_size <= pos_hi)

    @pl.when(work)
    def _compute():
        q = q_ref[0].astype(jnp.float32)      # (block_q, D) — pre-scaled
        k = k_ref[0, 0].astype(jnp.float32)   # (ps, D)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, ps)
        row = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        col = kb * page_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1
        )
        pos = kv_len - q_len + row
        mask = (row < q_len) & (col <= pos) & (col < kv_len)
        logits = jnp.where(mask, logits, _NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # Write every block the sequence OWNS (padding blocks flush zeros, so
    # no region row is ever left as undefined memory); overflow grid steps
    # past the region (qb >= counts) alias the region's last block in the
    # index map and must not touch o_ref — the buffer re-flushes its
    # already-correct content.
    @pl.when((kb == num_kv_blocks - 1) & (qb < counts_ref[s]))
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def _clamped_q_block(s, qb, starts_ref, counts_ref):
    # Overflow steps (qb beyond this sequence's region) pin to the region's
    # last block: the index never crosses into a neighbour's rows.
    return starts_ref[s] + jnp.minimum(qb, counts_ref[s] - 1)


def _ragged_pallas(
    q, k_pages, v_pages, starts, counts, q_lens, kv_lens, tables,
    *, block_q: int, max_q_blocks: int, interpret: bool,
):
    hq, t, d = q.shape
    hkv = k_pages.shape[0]
    ps = k_pages.shape[2]
    s_count, max_pages = tables.shape
    groups = hq // hkv
    grid = (s_count, hq, max_q_blocks, max_pages)

    def q_map(s, h, qb, kb, starts_ref, counts_ref, ql_ref, kl_ref, t_ref):
        return (h, _clamped_q_block(s, qb, starts_ref, counts_ref), 0)

    def kv_map(s, h, qb, kb, starts_ref, counts_ref, ql_ref, kl_ref, t_ref):
        return (h // groups, t_ref[s, kb], 0, 0)

    kernel = functools.partial(
        _ragged_kernel,
        block_q=block_q,
        page_size=ps,
        num_kv_blocks=max_pages,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
            pl.BlockSpec((1, 1, ps, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hq, t, d), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(starts, counts, q_lens, kv_lens, tables, q, k_pages, v_pages)


# --------------------------------------------------------------- reference


def ragged_reference_attention(
    q, k_pages, v_pages, starts, counts, q_lens, kv_lens, tables,
    *, block_q: int, max_q_blocks: int,
):
    """Gather-based XLA reference that REPLAYS the kernel's block schedule.

    Pages are gathered through the block tables exactly as the kernel's
    index maps fetch them, and the online-softmax update runs per kv block
    in the kernel's op order (same dot shapes, same mask constant, same
    plain exp), vectorized over (S, Hq, q-block). That makes it bit-exact
    vs the Pallas kernel at f32 — the parity drill asserts it — instead of
    merely allclose, so off-TPU runs pin the kernel's numerics.
    """
    hq, t, d = q.shape
    hkv = k_pages.shape[0]
    ps = k_pages.shape[2]
    s_count, max_pages = tables.shape
    groups = hq // hkv

    # (S, MAXQB) region-clamped block indices -> q blocks (Hq, S, MAXQB, bq, D)
    qb_idx = jnp.arange(max_q_blocks)[None, :]
    blk = starts[:, None] + jnp.minimum(qb_idx, counts[:, None] - 1)
    q_blocks = q.reshape(hq, t // block_q, block_q, d)[:, blk]
    # gathered pages: (Hkv, S, maxP, ps, D)
    k_seq = k_pages[:, tables]
    v_seq = v_pages[:, tables]
    if groups > 1:
        k_seq = jnp.repeat(k_seq, groups, axis=0)
        v_seq = jnp.repeat(v_seq, groups, axis=0)

    row = (
        qb_idx[:, :, None] * block_q
        + jnp.arange(block_q)[None, None, :]
    )  # (1, MAXQB, bq) -> broadcast over S
    pos = kv_lens[:, None, None] - q_lens[:, None, None] + row  # (S, MAXQB, bq)
    row_valid = row < q_lens[:, None, None]
    pos_hi = (
        kv_lens[:, None] - q_lens[:, None]
        + jnp.minimum((qb_idx + 1) * block_q, q_lens[:, None]) - 1
    )  # (S, MAXQB)

    def step(carry, kb):
        m_prev, l_prev, acc = carry
        k = k_seq[:, :, kb].astype(jnp.float32)  # (Hq, S, ps, D)
        v = v_seq[:, :, kb].astype(jnp.float32)
        # same contraction as the kernel's 2D dot, batched over (Hq, S, MAXQB)
        logits = jnp.einsum(
            "hsbqd,hskd->hsbqk",
            q_blocks.astype(jnp.float32),
            k,
            preferred_element_type=jnp.float32,
        )  # (Hq, S, MAXQB, bq, ps)
        col = kb * ps + jnp.arange(ps)
        mask = (
            row_valid[None, :, :, :, None]
            & (col[None, None, None, None, :] <= pos[None, :, :, :, None])
            & (col[None, None, None, None, :] < kv_lens[None, :, None, None, None])
        )
        logits = jnp.where(mask, logits, _NEG_INF)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "hsbqk,hskd->hsbqd", p, v, preferred_element_type=jnp.float32
        )
        # the kernel's pl.when(work) guard, replayed per (S, qb) block
        work = (
            (qb_idx * block_q < q_lens[:, None]) & (kb * ps <= pos_hi)
        )[None, :, :, None, None]
        m_new = jnp.where(work, m_new, m_prev)
        l_new = jnp.where(work, l_new, l_prev)
        acc_new = jnp.where(work, acc_new, acc)
        return (m_new, l_new, acc_new), None

    stat = (hq, s_count, max_q_blocks, block_q, 1)
    init = (
        jnp.full(stat, _NEG_INF, jnp.float32),
        jnp.zeros(stat, jnp.float32),
        jnp.zeros((hq, s_count, max_q_blocks, block_q, d), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(step, init, jnp.arange(max_pages))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out_blocks = (acc / safe_l).astype(q.dtype)  # (Hq, S, MAXQB, bq, D)

    # scatter region blocks back to token-major rows; padding blocks beyond
    # a region (qb >= counts) must NOT clobber the aliased last block
    flat_blk = blk.reshape(-1)  # (S*MAXQB,)
    valid = (qb_idx < counts[:, None]).reshape(-1)
    out = jnp.zeros((hq, t // block_q, block_q, d), q.dtype)
    out = out.at[:, jnp.where(valid, flat_blk, t // block_q)].set(
        out_blocks.reshape(hq, -1, block_q, d), mode="drop"
    )
    return out.reshape(hq, t, d)


# ----------------------------------------------------------------- dispatch

RAGGED_KERNEL = "ragged_pallas"
RAGGED_REFERENCE = "gather_reference"


def resolve_ragged_impl(
    head_dim: int,
    page_size: int,
    block_q: int,
    *,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> str:
    """Which implementation `ragged_paged_attention` runs — RAGGED_KERNEL
    or RAGGED_REFERENCE — as a static rule, so callers (the engine's
    `stats()`, chip_smoke.py) can name it before anything is compiled:

    - `interpret`: the kernel, through the Pallas interpreter (tests);
    - `use_kernel=False`: the reference (the oracle the kernel is
      compared with, and GSPMD-partitionable without shard_map);
    - otherwise the kernel iff the backend is "tpu" and the shape obeys
      Mosaic's (8, 128) rule on the kernel's (block_q, D) and
      (page_size, D) tiles (GPT-2's 64-wide heads and tiny test configs
      do not).
      `use_kernel=True` at a shape or backend that cannot run it is an
      error, not a quiet switch to the reference.
    """
    if interpret:
        return RAGGED_KERNEL
    if use_kernel is False:
        return RAGGED_REFERENCE
    can_run = (
        jax.default_backend() == "tpu"
        and head_dim % 128 == 0
        and page_size % 8 == 0
        and block_q % 8 == 0
    )
    if use_kernel and not can_run:
        raise ValueError(
            f"ragged Pallas kernel requested but cannot run here: backend "
            f"{jax.default_backend()!r}, head_dim {head_dim}, page_size "
            f"{page_size}, block_q {block_q} (needs a tpu backend, "
            "head_dim % 128 == 0, page_size % 8 == 0, block_q % 8 == 0)"
        )
    return RAGGED_KERNEL if can_run else RAGGED_REFERENCE


def ragged_paged_attention(
    q: jax.Array,           # (Hq, T, D) token-major, per-seq block regions
    k_pages: jax.Array,     # (Hkv, P, ps, D)
    v_pages: jax.Array,
    starts: jax.Array,      # (S,) int32 region starts, block_q units
    counts: jax.Array,      # (S,) int32 region sizes, block_q units (>= 1)
    q_lens: jax.Array,      # (S,) int32 real q rows (0 = inactive lane)
    kv_lens: jax.Array,     # (S,) int32 total kv length per sequence
    tables: jax.Array,      # (S, maxP) int32 absolute page ids
    *,
    block_q: int = 8,
    sm_scale: Optional[float] = None,
    max_q_blocks: Optional[int] = None,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
    mesh=None,
    tp_axis: str = "tp",
) -> jax.Array:
    """Causal ragged paged attention over a page pool; returns (Hq, T, D).

    Region semantics: query row r of a region sits at absolute position
    kv_len - q_len + r, so the SAME descriptor covers every region shape
    the engine dispatches — prefill chunks (q_len = chunk fill), plain
    decode lanes (q_len = 1), and speculative VERIFY regions (q_len = K:
    the pending token plus K-1 drafts scored causally in one launch, each
    draft row attending to the drafts before it plus the lane's whole
    paged history). Nothing kernel-side distinguishes a verify region
    from a short prefill chunk — speculation rides the existing grid.

    Dispatch: the static rule of `resolve_ragged_impl`. `interpret=True`
    forces the kernel through the Pallas interpreter (CI parity drills).
    Under a tensor-parallel mesh the kernel path is wrapped in `shard_map`
    over the head axes — GSPMD cannot partition a pallas_call, but both
    Hq and Hkv divide by tp, so each shard runs the kernel on its local
    head group with the descriptor replicated.
    """
    hq, t, d = q.shape
    ps = k_pages.shape[2]
    if t % block_q:
        raise ValueError(
            f"token rows ({t}) must divide by block_q ({block_q}): regions "
            "are dispatched in block_q-row units"
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if max_q_blocks is None:
        # static upper bound on region size: T is exactly the sum of the
        # regions, so T // block_q bounds any single one; callers with a
        # tighter bound (the engine: chunk blocks) pass it to shrink the grid
        max_q_blocks = t // block_q
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    impl = resolve_ragged_impl(
        d, ps, block_q, use_kernel=use_kernel, interpret=interpret
    )
    args = (starts, counts, q_lens, kv_lens, tables)
    if impl == RAGGED_REFERENCE:
        return ragged_reference_attention(
            q, k_pages, v_pages, *args,
            block_q=block_q, max_q_blocks=max_q_blocks,
        )
    # nb: keep this local's name distinct from any method name in the
    # repo — raylint's name-level reachability treats shard_map args
    # as hot roots project-wide
    ragged_kernel_fn = functools.partial(
        _ragged_pallas,
        block_q=block_q,
        max_q_blocks=max_q_blocks,
        interpret=interpret,
    )
    if mesh is not None and mesh.shape.get(tp_axis, 1) > 1:
        ragged_kernel_fn = shard_map(
            ragged_kernel_fn,
            mesh=mesh,
            in_specs=(
                P(tp_axis, None, None),        # q: shard heads
                P(tp_axis, None, None, None),  # k pages: shard kv heads
                P(tp_axis, None, None, None),  # v pages
                P(), P(), P(), P(), P(),       # descriptor: replicated
            ),
            out_specs=P(tp_axis, None, None),
            check_vma=False,
        )
    return ragged_kernel_fn(q, k_pages, v_pages, *args)
