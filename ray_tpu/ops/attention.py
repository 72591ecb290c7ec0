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

The grid, by what a call can observe of its own shape: one (S, S) tile a
head up to S = 1,024, walked in sub-tiles; past it a causal call steps
through the lower triangle of 1,024-wide tiles alone, a windowed one through
the band of tiles its window touches, and any other call through a dense
grid of blocks (the comment above `_SUB_TILE`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
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
    window: Optional[int] = None,
) -> jax.Array:
    """Pure-XLA multi-head attention. Ground truth for the Pallas kernels and
    the CPU-backend fallback. Supports GQA and right-padding via `kv_len`.
    `window` (causal only): key j is live for query i iff i - window < j <= i."""
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
        if window is not None:
            causal_mask &= jnp.arange(skv)[None, :] > jnp.arange(sq)[:, None] + (skv - sq) - window
        mask = causal_mask if mask is None else (mask & causal_mask)
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


# ------------------------------------------------- sub-tiles of a resident block
#
# The grid hands a kernel one (block_q, block_kv) tile of the score matrix
# whose q, k, v (and do, lse, delta) rows are resident in VMEM: at S <= 1,024
# that is the whole head, one grid step and one set of DMAs. The kernels do
# not compute that tile in one piece. They walk it in strips of (sub_q,
# sub_kv) sub-tiles read from the resident refs with `pl.ds`, and visit only
# the sub-tiles that hold a live (query, key) pair: one wholly above the
# causal diagonal or wholly beyond `kv_len` costs nothing, one wholly below
# the diagonal and inside `kv_len` runs without the iota/compare/select
# mask, and only the sub-tiles the diagonal or the `kv_len` edge crosses
# build it. A grid step costs ~0.9 us of DMA issue and pipeline bookkeeping
# (the reason small GRID blocks lost every sweep); a sub-tile costs its
# compute and nothing else, so skipping one saves all of it.
#
# Along a strip the sub-tiles of one kind are adjacent (`_kv_range`,
# `_q_range`: unmasked ones, then or before them the masked ones), so each
# kind is one piece: one wide matmul and, in the forward, one softmax
# update a strip, not one a sub-tile (a row-max and a row-sum are cross-lane
# reductions, and an update per 256-wide sub-tile made the forward slower
# than the unwalked block: PERF.md section 6, PR 26). `attention_subtiles`
# counts with the same two functions.
#
# The walk needs the strip's place against the diagonal while tracing, so
# it engages with one grid tile a head, and wherever a tile's place against
# the diagonal is static whatever its place in the sequence:
#
# A CAUSAL self-attention call past one tile a head (S > 1,024, square
# tiles, no window: `_live_grid`) has a grid of its LIVE tiles alone, the
# lower triangle: nq (nq + 1) / 2 steps a head on one flattened axis whose
# (q tile, kv tile) come from two prefetched tables (`_triangle`), so a tile
# above the diagonal is neither a step nor a copy. A live tile is of one of
# two classes, each emitted once (`_triangle_classes`): BELOW the diagonal
# every pair is live, one whole unmasked piece (one softmax step a tile in
# the forward); ON it the tile is what a one-tile call's is, walked in
# sub-tiles or computed whole with a static mask, a kernel
# (`_DIAGONAL_WALK`). The forward runs a q tile's kv tiles 0..i, the carry
# initialised at the first and written at the diagonal; the backward runs a
# kv tile's q tiles j..nq - 1 from the diagonal down. `kv_len` is not
# tested there: causality implies it on every real row, a padded row's
# output is sliced off and its cotangent is zero.
#
# Any other grid of tiles (not causal, explicit blocks that are not square,
# ring attention's blocks) is dense, and a grid tile is its own single
# sub-tile whose offsets are traced scalars: live or not is then decided per
# grid step (`_walk_strip`), and a live tile is computed masked.
#
# A WINDOWED call (key j live for query i iff i - window < j <= i) has a
# third edge, the window's lower one, and a grid of its own: its kv axis is
# not the sequence's tiles but the BAND of square tiles one q tile's window
# touches (`window_band`), the diagonal tile and those below it. A band tile
# lies at a static offset from the diagonal whatever its place in the
# sequence, and a pair is live by its row less its column alone, so which of
# a tile's pairs are live is known while tracing (`_band_tiles`). A band tile
# is of one of three classes (`_band_class`): INTERIOR, every pair live, one
# body for all of them; the DIAGONAL tile and the TRAILING one(s) the window's
# lower edge crosses, a body each. A kernel computes a class whole, one piece
# with the static mask of the edge that crosses it (none, interior), or walks
# it in sub-tiles, where a sub-tile outside the window is skipped, one inside
# it runs unmasked and one an edge crosses builds the mask (`_BAND_WALK`). A
# tile outside the band is never a grid step, so it is not copied either.

# (sub_q, sub_kv) of the walk. One chip sweep over {128, 256, 512} a side
# and kernel, at D = 64 and D = 128 (PERF.md section 6, PR 26): 256 x 256
# won, or lost by under 1%, in all three kernels at both widths, so the
# size depends on nothing a call can observe but its block. (The one backward
# kernel on one tile a head, PR 45, 24 x 12 x 1,024 x 64 and 12 x 16 x 1,024 x
# 128, ms a call: the parent's pair 4.04 and 1.96; 256 x 256 3.47 and 1.59,
# 128 x 256 3.46 and 1.58, 256 x 512 3.55 and 1.65, 512 x 256 3.66 and 1.74.)
_SUB_TILE = (256, 256)

# Whether a kernel walks a causal grid's DIAGONAL tile in those sub-tiles (10 of
# 16, 4 of them masked) or computes it whole and masked (`_triangle_classes`).
# One alternating chip sweep at the three cells' shapes, D = 128 (1 x 28/4 x
# 16,384, 2 x 32/4 x 8,192, 4 x 16/16 x 4,096; PERF.md section 6, PR 37), ms a
# call whole / walked: dK/dV 23.99 / 23.31, 15.17 / 14.38, 4.64 / 4.25 and dQ
# 18.25 / 17.61, 11.69 / 10.96, 3.68 / 3.31, the walk wins at all three; the
# forward 15.62 / 16.30, 9.69 / 10.46, 2.85 / 3.24, it loses at all three (a
# softmax step a 256-row strip, as in the windowed forward).
# The ONE backward kernel that took those two's place, swept again at the same
# shapes, alternating with the parent's pair (PERF.md section 6, PR 45; ms a
# call by the host's clock over 4 queued calls, the median of 5 rounds), the
# pair / whole / walked: 39.81 / 29.71 / 28.91, 24.29 / 18.78 / 17.87, 6.91 /
# 5.75 / 5.30: the walk still wins at all three, and the one kernel by 23-27%.
# (Swept with delta = rowsum(dO O) still an XLA input; computed in the kernel,
# a second sweep of the chosen table read 28.27, 17.11 and 4.94, the fusion
# that wrote delta gone: -29%, -30%, -29% against the pair.)
_DIAGONAL_WALK = {"flash_fwd": False, "flash_bwd_dkv_dq": True}

# A head WIDER than 128 features (latent attention's 256): every q, k, v, do, o
# and accumulator block of a tile is twice a 128-wide head's, and a 1,024 x 1,024
# tile's backward no longer fits the compiler's 16 MiB of scoped VMEM (dK/dV is
# refused for the described v5e). The side of a grid tile, the scoped VMEM the
# kernels ask for (None: the compiler's), the walk's sub-tile and the diagonal
# tile's walk are therefore chosen again for such a head. One alternating chip
# sweep at 2 x 20/20 x 8,192 x 256, causal (PERF.md section 6, PR 44), ms a
# call by the host's clock over 4 queued calls, the median of 5 rounds; a tile
# side of 512 asks for no VMEM, 1,024 for 64 MiB, 2,048 for 100 MiB; the
# diagonal tile whole, or walked in sub-tiles of 128 x 128 / 256 x 256 / 512 x 512:
#              512: whole   128    256   | 1,024: whole   128    256    512  | 2,048: 512 | 256: whole
#   forward         13.24  13.49  13.49  |        10.03  10.04   9.71   9.62  |       9.60 |      28.34
#   dK/dV           18.57  18.23  18.52  |        17.81  16.44  16.87  16.93  |      24.78 |      30.77
#   dQ              14.56  14.70  14.63  |        13.59  12.54  12.59  12.94  |      20.45 |      21.30
# (512 a side with sub-tiles of 256 x 128 and 128 x 256: within 0.4% of 256 x 256.)
# The 1,024 tile wins all three kernels (the forward by 27%: 136 steps a head
# become 36, a quarter of the copies and of the softmax's carried updates), so
# the scoped VMEM is asked for; 2,048 gains nothing more in the forward and
# loses a third in the backward. All three kernels WALK the diagonal tile at
# this width, the forward too (at D = 128 it computes it whole: the masked
# quarter of a tile is twice as many MXU passes here); 256 x 256 wins or loses
# by under 2.6% (dK/dV at 128 x 128), so the sub-tile stays the narrow heads'.
# (The one backward kernel since PR 45, at 1,024 and 256 x 256, the parent's
# dK/dV + dQ / diagonal whole / walked: 28.40 / 22.03 / 20.73 ms a call.)
# A call's head size is static, so the choice is one more rule on the shape a
# call can observe, not an option. The forward then reads 72% of its roofline
# (6.98 ms of required work a call).
_WIDE_HEAD = {
    "tile": 1024, "vmem_limit_bytes": 64 * 1024 * 1024, "sub_tile": (256, 256),
    "diagonal_walk": {"flash_fwd": True, "flash_bwd_dkv_dq": True},
}


# Scoped VMEM the compiler gives a kernel that asks for none, and what a v5e
# has: the backward's resident dQ (`_resident_dq_bytes`) is asked for on top of
# the first (or of a wide head's own), within the second.
_SCOPED_VMEM_BYTES = 16 * 1024 * 1024
_VMEM_BYTES = 128 * 1024 * 1024


def _head_choices(head_dim: Optional[int] = None) -> dict:
    """The grid tile's side, the walk's sub-tile, which kernels walk a causal
    grid's diagonal tile and the scoped VMEM asked for, by the head size (None:
    a head of up to 128 features, which every sweep before PR 44 was made at)."""
    if head_dim is None or head_dim <= 128:
        return {"tile": 1024, "vmem_limit_bytes": None, "sub_tile": _SUB_TILE,
                "diagonal_walk": _DIAGONAL_WALK}
    return _WIDE_HEAD

# The same question for each class of a windowed call's band tiles (`_band_class`):
# True walks the class in those sub-tiles, False computes it as one whole piece
# (an interior tile unmasked, a diagonal or trailing one with the static mask of
# the edge that crosses it). One alternating chip sweep of all eight tables a
# kernel at the two cells' shapes, D = 128 (1 x 28/4 x 16,384, window 4,096: 16
# diagonal + 42 interior + 12 trailing tiles a head; 2 x 32/4 x 8,192, window
# 2,048: 8 + 7 + 6; PERF.md section 6, PR 43), ms a call by the host's clock
# over 5 queued calls, the parent's kernel first, then all walked / interior
# whole / the chosen table / all whole:
#   forward  10.33, 10.30 /  9.33 /  8.28 /  8.28 and 7.49, 7.46 / 7.10 / 5.90 / 5.90
#   dK/dV    13.14, 13.05 / 12.16 / 12.16 / 13.25 and 8.78, 8.76 / 8.40 / 8.40 / 9.68
#   dQ        8.87,  8.82 /  8.81 /  8.81 /  9.93 and 6.10, 6.09 / 6.08 / 6.08 / 7.27
# The forward gains from every class it computes whole (a softmax step a tile,
# not one a 256-row strip: diagonal 0.71 / 0.77, interior 0.97 / 0.36, trailing
# 0.38 / 0.42 ms a call), each class's gain the same whatever the others do. The
# backward kernels gain from the interior class alone (dK/dV 0.89 / 0.36; dQ
# 0.005 / 0.011, a tie, broken for the smaller body) and LOSE on both edges
# computed whole (dK/dV 0.69 / 0.77 and 0.43 / 0.48, dQ 0.64 / 0.62 and 0.48 /
# 0.52): they skip the six dead sub-tiles of an edge tile, as on the causal
# grid's diagonal (`_DIAGONAL_WALK`).
# The ONE backward kernel (PR 45), all eight tables at the same two shapes,
# alternating with the parent's pair, ms a call (W walked, o whole; diagonal,
# interior, trailing):  pair    WWW    WWo    WoW    Woo    oWW    oWo    ooW    ooo
#   window 4,096 of 16,384  19.74  15.56  16.11  14.71  15.28  16.35  16.88  15.51  16.07
#   window 2,048 of 8,192   13.27  10.53  11.17  10.21  10.86  11.44  12.06  11.11  11.75
# the same table wins (WoW): each class moves the call by what it did in dK/dV.
_BAND_WALK = {
    "flash_fwd": {"diagonal": False, "interior": False, "trailing": False},
    "flash_bwd_dkv_dq": {"diagonal": True, "interior": False, "trailing": True},
}

# The share of a tile's sub-tiles that the walk visits on the tiles that sweep
# timed (10 of 16: the causal triangle, and its mirror where the window is a
# multiple of the tile side). A diagonal or trailing tile of which the walk
# visits less (a window narrower than a tile, the second of two trailing tiles)
# is no class of the table: it is walked, as every band tile was before.
_WALK_VISITS_SWEPT = 10 / 16


def _sub_tiles(block_q: int, block_kv: int, grid_tiles: int, head_dim: Optional[int] = None):
    """Sub-tile shape of the walk over a (block_q, block_kv) block, one of
    `grid_tiles` a head whose offsets are traced. A block the sub-tile does
    not divide (it is smaller, or an odd length) and a block of a larger grid
    are their own single sub-tile. (A windowed call's band of tiles has
    static offsets against the diagonal, so it passes 1.)"""
    sub_q, sub_kv = _head_choices(head_dim)["sub_tile"]
    if grid_tiles > 1:
        return block_q, block_kv
    return (sub_q if block_q % sub_q == 0 else block_q,
            sub_kv if block_kv % sub_kv == 0 else block_kv)


def _kv_range(row0: int, col0: int, n: int, sub_q: int, sub_kv: int,
              causal: bool, kv_len: Optional[int], window: Optional[int] = None):
    """For the queries [row0, row0 + sub_q) and the n kv sub-tiles
    [col0 + c * sub_kv, + sub_kv): (first, full_lo, full_hi, live).
    Sub-tiles full_lo <= c < full_hi hold only live pairs; first <= c <
    full_lo (the window's lower edge crosses them) and full_hi <= c < live
    (the diagonal or the kv_len edge does) hold live and masked ones; c <
    first and c >= live hold none. `kv_len` None: no such edge (a windowed
    call's offsets are relative, and causality implies it)."""
    live = full = n
    if kv_len is not None:
        live = -((col0 - kv_len) // sub_kv)  # ceil
        full = (kv_len - col0) // sub_kv
    if causal:
        live = min(live, (row0 + sub_q - 1 - col0) // sub_kv + 1)
        full = min(full, (row0 + 1 - col0) // sub_kv)
    live = max(0, min(live, n))
    full = max(0, min(full, live))
    first = full_lo = 0
    if window is not None:
        # key j is live iff j > i - window: some pair of the sub-tile, every pair
        first = (row0 - window + 1 - col0) // sub_kv
        full_lo = (row0 + sub_q - 1 - window - col0) // sub_kv + 1
        first = max(0, min(first, live))
        full_lo = max(first, min(full_lo, live))
        full = max(full, full_lo)
        if full_lo >= full:      # nothing whole: one masked run
            full_lo = full = first
    return first, full_lo, full, live


def _q_range(row0: int, col0: int, n: int, sub_q: int, sub_kv: int,
             causal: bool, kv_len: Optional[int], window: Optional[int] = None):
    """The same set seen from the keys [col0, col0 + sub_kv) over the n q
    sub-blocks [row0 + a * sub_q, + sub_q): (first_live, first_full,
    full_end, live_end). Sub-blocks first_full <= a < full_end hold only
    live pairs; first_live <= a < first_full (the diagonal or the kv_len
    edge) and full_end <= a < live_end (the window's edge) hold live and
    masked ones; the others none."""
    first_live = (col0 - row0) // sub_q if causal else 0
    first_full = -((row0 - col0 - sub_kv + 1) // sub_q) if causal else 0
    if kv_len is not None:
        if col0 >= kv_len:  # beyond kv_len: never live
            first_live = n
        if col0 + sub_kv > kv_len:  # the edge crosses it: masked for every query
            first_full = n
    first_live = max(0, min(first_live, n))
    first_full = max(first_live, min(first_full, n))
    full_end = live_end = n
    if window is not None:
        # query i sees key j iff i < j + window
        live_end = -((row0 - (col0 + sub_kv - 1 + window)) // sub_q)
        full_end = -((row0 - (col0 + window + 1)) // sub_q) - 1
        live_end = max(first_live, min(live_end, n))
        full_end = max(first_live, min(full_end, live_end))
        first_full = min(first_full, live_end)
        if first_full >= full_end:      # nothing whole: one masked run
            first_full = full_end = live_end
    return first_live, first_full, full_end, live_end


def _kv_runs(*args):
    """`_kv_range` as runs (lo, hi, is_masked) along the strip, the whole
    sub-tiles first."""
    first, full_lo, full_hi, live = _kv_range(*args)
    return ((full_lo, full_hi, False), (first, full_lo, True), (full_hi, live, True))


def _q_runs(*args):
    first_live, first_full, full_end, live_end = _q_range(*args)
    return ((first_full, full_end, False), (first_live, first_full, True),
            (full_end, live_end, True))


def window_band(window: int, block: int, tiles: int) -> int:
    """Grid tiles of side `block` that one q tile's window touches (the
    diagonal tile and those below it, back to the one the window's lower edge
    crosses), at most `tiles`: the kv extent of a windowed call's grid."""
    return min(tiles, -(-(window - 1) // block) + 1)


def _band_tile_walk(d: int, block: int, sub_q: int, sub_kv: int, window: int):
    """(visited, masked) sub-tiles of the band tile d tiles below the diagonal
    as the walk covers it, by the kernels' own loop bounds."""
    visited = masked = 0
    for a in range(block // sub_q):
        first, full_lo, full_hi, live = _kv_range(
            d * block + a * sub_q, 0, block // sub_kv, sub_q, sub_kv, True, None, window)
        visited += live - first
        masked += (live - first) - (full_hi - full_lo)
    return visited, masked


def _band_class(d: int, band: int, block: int, window: int) -> Optional[str]:
    """The class of the band tile d tiles below the diagonal, from its offset,
    the tile side and the window alone: "interior" (every pair live: the
    code is the same at any such offset), "diagonal" (d == 0), "trailing" (the
    window's lower edge crosses it), or None: a tile of which the walk visits
    under `_WALK_VISITS_SWEPT`, and the one tile of a band of 1 (nothing is
    carried there: a strip's softmax is final, as in a one-tile causal call,
    whose walk PR 26's sweep chose). None is walked whatever the kernel."""
    if band == 1:
        return None
    if d >= 1 and (d + 1) * block - 1 < window:
        return "interior"
    sub_q, sub_kv = _sub_tiles(block, block, 1)
    visited, _ = _band_tile_walk(d, block, sub_q, sub_kv, window)
    if visited < _WALK_VISITS_SWEPT * (block // sub_q) * (block // sub_kv):
        return None
    return "diagonal" if d == 0 else "trailing"


def _band_whole(kernel: str, d: int, band: int, block: int, window: int) -> bool:
    """Whether `kernel` computes that band tile as one whole piece."""
    tile_class = _band_class(d, band, block, window)
    return tile_class is not None and not _BAND_WALK[kernel][tile_class]


def _band_tile_subtiles(kernel: str, d: int, band: int, block: int, sub_q: int, sub_kv: int,
                        window: int):
    """(visited, masked) sub-tiles of that band tile as `kernel` computes it:
    the walk's, or whole: all of them, masked if any pair is dead."""
    visited, masked = _band_tile_walk(d, block, sub_q, sub_kv, window)
    if _band_whole(kernel, d, band, block, window):
        whole = (block // sub_q) * (block // sub_kv)
        return whole, whole * (masked > 0)
    return visited, masked


def attention_subtiles(sq: int, skv: int, causal: bool, kv_len: int,
                       block_q: int, block_kv: int, sub_q: int, sub_kv: int,
                       window: Optional[int] = None, kernel: str = "flash_bwd_dkv_dq",
                       head_dim: Optional[int] = None):
    """(visited, masked, total) sub-tiles of one head's (sq, skv) score
    matrix as the kernels walk it: `visited` run their matmuls, `masked`
    of those build the mask, `total` is what a dense walk would visit.
    Counted with the kernels' own loop bounds. The forward and the backward
    kernel walk alike but for a causal grid's diagonal tiles (`_DIAGONAL_WALK`, or a wide
    `head_dim`'s table) and a band's tiles by class (`_BAND_WALK`), which
    `kernel` decides: a tile computed whole visits all its sub-tiles, and masks
    all or (interior) none."""
    visited = masked = 0
    total = (sq // sub_q) * (skv // sub_kv)
    tiles = sq // block_q
    if window is not None:
        # a band of tiles at static offsets from the diagonal (`_band_tiles`):
        # the q tiles d .. tiles - 1 have a tile d below the diagonal
        band = window_band(window, block_q, skv // block_kv)
        for d in range(band):
            on_visited, on_masked = _band_tile_subtiles(kernel, d, band, block_q, sub_q, sub_kv, window)
            visited += (tiles - d) * on_visited
            masked += (tiles - d) * on_masked
        return visited, masked, total
    if _live_grid(causal, window, block_q, block_kv, tiles, skv // block_kv):
        # the lower triangle's tiles (`_triangle_classes`): those below the
        # diagonal whole and unmasked, the diagonal's as one tile a head is
        # walked, or whole and masked
        whole = (block_q // sub_q) * (block_kv // sub_kv)
        on_visited, on_masked = whole, whole
        if _head_choices(head_dim)["diagonal_walk"][kernel]:
            on_visited, on_masked, _ = attention_subtiles(
                block_q, block_kv, True, block_kv, block_q, block_kv, sub_q, sub_kv)
        return (tiles * (tiles - 1) // 2 * whole + tiles * on_visited, tiles * on_masked, total)
    for i in range(tiles):
        for j in range(skv // block_kv):
            for a in range(block_q // sub_q):
                _, _, full, live = _kv_range(
                    i * block_q + a * sub_q, j * block_kv, block_kv // sub_kv,
                    sub_q, sub_kv, causal, kv_len)
                visited += live
                masked += live - full
    if tiles * (skv // block_kv) > 1:
        masked = visited  # traced offsets: `_walk_strip` masks every live tile
    return visited, masked, total


def attention_grid_steps(sq: int, skv: int, causal: bool, kv_len: int, block_q: int,
                         block_kv: int, window: Optional[int] = None):
    """(steps, live) of one head's (q tile, kv tile) grid: the steps a kernel
    is run and copies its blocks for, and those of them whose tile holds a
    live pair. A band and a causal grid step through live tiles alone (but a
    band's first rows, whose window starts before the sequence)."""
    nq, nk = sq // block_q, skv // block_kv
    if window is not None:
        band = window_band(window, block_q, nk)
        return nq * band, sum(min(band, i + 1) for i in range(nq))
    if _live_grid(causal, window, block_q, block_kv, nq, nk):
        return (nq * (nq + 1) // 2,) * 2
    live = sum(1 for i in range(nq) for j in range(nk)
               if j * block_kv < kv_len and (not causal or j * block_kv <= (i + 1) * block_q - 1))
    return nq * nk, live


def _static(*xs) -> bool:
    return all(isinstance(x, int) for x in xs)


def _walk_strip(row0, col0, n, sub_q, sub_kv, causal, kv_len, along, visit,
                always, window=None):
    """`visit(pieces)` for the live sub-tiles of one strip, as runs (lo, hi,
    is_masked) of sub-tile indices along the strip: the n kv sub-tiles
    from col0 against the queries [row0, row0 + sub_q) (`along` "kv"), or
    the n q sub-blocks from row0 against the keys [col0, col0 + sub_kv)
    (`along` "q"). No live sub-tile: no call, unless `always` (the
    strip's output is then the visitor's to write).

    Static offsets (one grid tile a head, or a tile of a windowed call's
    band): one call with the non-empty runs. Traced offsets (the strip is
    the one sub-tile of a grid tile): live or not is a comparison per grid
    step, and a live tile is computed masked, as every grid tile was before
    the walk."""
    if _static(row0, col0):
        runs = (_kv_runs if along == "kv" else _q_runs)(
            row0, col0, n, sub_q, sub_kv, causal, kv_len, window)
        pieces = [run for run in runs if run[1] > run[0]]
        if pieces or always:
            visit(pieces)
        return
    assert n == 1 and window is None, "a strip with traced offsets is a single sub-tile"
    live = col0 < kv_len
    if causal:
        live = live & (col0 <= row0 + sub_q - 1)
    pl.when(live)(lambda: visit([(0, 1, True)]))
    if always:
        pl.when(jnp.logical_not(live))(lambda: visit([]))


def _grid_tile(num_q_blocks, num_kv_blocks, q_axis, kv_axis):
    """(i, j) of this grid step; the int 0 on an axis of one block, which
    is what makes the walk's bounds static."""
    i = pl.program_id(q_axis) if num_q_blocks > 1 else 0
    j = pl.program_id(kv_axis) if num_kv_blocks > 1 else 0
    return i, j


def _when(condition, run):
    """`run()` under a condition that is static (an axis of one block) or traced."""
    if isinstance(condition, bool):
        if condition:
            run()
    else:
        pl.when(condition)(run)


def _band_tiles(kernel, band, window, block, step, tile, tiles, toward_diagonal, strips):
    """A windowed call's grid step: `strips(d, 0, *piece)` for the one tile of
    the band this step holds, as the q tile d and kv tile 0 of a head, its
    STATIC offset from the diagonal (a pair is live by its row less its column
    alone), so that which pairs are live is known here whatever the length.
    `piece` is the whole tile or the walk's sub-tile, by the tile's class and
    `_BAND_WALK`; the interior tiles share ONE body, traced at d = 1 (every
    pair live at any of their offsets), the others have one each. `tile` is
    the fixed side's tile (a q tile for the forward, whose band runs
    `toward_diagonal` over kv tiles tile - band + 1 .. tile; a kv tile for
    the backward, whose band runs away from it over q tiles tile .. tile +
    band - 1), `step` the band's grid axis. A step whose tile falls off the
    sequence (before its start, or after the last of `tiles`) does nothing;
    its index map repeats a neighbour's block, so nothing is copied either."""
    bodies = {}     # (the offset a body is traced at, its piece) -> when it runs
    for t in range(band):
        d = band - 1 - t if toward_diagonal else t    # tiles below the diagonal
        inside = tile >= d if toward_diagonal else tile + d <= tiles - 1
        here = inside if band == 1 else inside & (step == t)
        tile_class = _band_class(d, band, block, window)
        walked = tile_class is None or _BAND_WALK[kernel][tile_class]
        body = (1 if tile_class == "interior" else d,
                _sub_tiles(block, block, 1) if walked else (block, block))
        bodies[body] = bodies[body] | here if body in bodies else here
    for (d, piece), here in bodies.items():
        _when(here, functools.partial(strips, d, 0, *piece))


def _live_grid(causal, window, block_q, block_kv, num_q_blocks, num_kv_blocks) -> bool:
    """Whether a call's grid holds its live tiles alone, in two classes: causal
    self-attention with no window, square tiles and more than one a head."""
    return bool(causal and window is None and block_q == block_kv
                and num_q_blocks == num_kv_blocks > 1)


def _triangle(tiles: int, by_kv: bool):
    """The steps of a causal grid's one tile axis, as the (q tile, kv tile)
    tables its index maps and kernels read (scalar prefetch): the lower
    triangle row by row, kv tiles 0..i of q tile i with the diagonal last
    (forward), or `by_kv` column by column, q tiles j..tiles - 1 of kv tile
    j with the diagonal first (backward)."""
    pairs = ([(i, j) for j in range(tiles) for i in range(j, tiles)] if by_kv
             else [(i, j) for i in range(tiles) for j in range(i + 1)])
    q_tiles, kv_tiles = zip(*pairs)
    return jnp.asarray(q_tiles, jnp.int32), jnp.asarray(kv_tiles, jnp.int32)


def _triangle_tile(q_tiles_ref, kv_tiles_ref):
    """(i, j) of a causal grid's step, read from `_triangle`'s tables."""
    step = pl.program_id(2)
    return q_tiles_ref[step], kv_tiles_ref[step]


def _triangle_classes(kernel, i, j, block_q, block_kv, strips, head_dim):
    """A causal grid's step: the code of its tile's class. Both classes have
    STATIC offsets against the diagonal, as a band's tiles have, so `strips`
    takes them as (q tile, kv tile) of a head: the diagonal tile is (0, 0),
    walked in sub-tiles where `_DIAGONAL_WALK` says so and else one masked
    piece; a tile below it is (1, 0) as ONE unmasked piece (a softmax step a
    256-row strip made the windowed forward a third dearer a tile than the
    whole one: PERF.md section 6, PR 33 and PR 37). Each is emitted once
    whatever the length."""
    whole = (block_q, block_kv)
    walked = _head_choices(head_dim)["diagonal_walk"][kernel]
    diagonal = _sub_tiles(block_q, block_kv, 1, head_dim) if walked else whole
    pl.when(i == j)(functools.partial(strips, 0, 0, *diagonal))
    pl.when(i != j)(functools.partial(strips, 1, 0, *whole))


def _scores(q, k, scale, mask_at, causal, kv_len, window=None):
    """QK^T of one piece in the base-2 log domain. `mask_at` is None for
    a piece of live pairs only, else its (row0, col0)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if mask_at is None:
        return s
    row0, col0 = mask_at
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = None if kv_len is None else col < kv_len
    if causal:
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        # a windowed call's offsets are static: of its two edges a piece
        # builds the one(s) that cross it (some pair above the diagonal, some
        # pair at or beyond the window's lower edge)
        if window is None or col0 + s.shape[1] - 1 > row0:
            upper = col <= row
            mask = upper if mask is None else mask & upper
        if window is not None and col0 <= row0 + s.shape[0] - 1 - window:
            lower = col > row - window
            mask = lower if mask is None else mask & lower
    return jnp.where(mask, s, _NEG_INF)


def _softmax_pieces(scores, values, carry=None):
    """(m, l, acc) of one softmax step over the score pieces `scores` (f32,
    base-2 domain; at least one) and their value rows `values`: from
    nothing, or online on top of `carry` = (m, l, read_acc) of earlier kv
    (`read_acc()` loads the accumulator, late: it then does not live
    across the matmuls)."""
    m = None if carry is None else carry[0]
    for s in scores:
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m = m_cur if m is None else jnp.maximum(m, m_cur)
    l = acc = None
    for s, v in zip(scores, values):
        p = jnp.exp2(s - m)
        l_cur = jnp.sum(p, axis=-1, keepdims=True)
        acc_cur = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        l = l_cur if l is None else l + l_cur
        acc = acc_cur if acc is None else acc + acc_cur
    if carry is not None:
        alpha = jnp.exp2(carry[0] - m)
        l, acc = alpha * carry[1] + l, carry[2]() * alpha + acc
    return m, l, acc


def _write_out(o_ref, lse_ref, rows, m, l, acc):
    """Rows `rows` of the output and of its natural-log lse residual."""
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0, rows, :] = (acc / safe_l).astype(o_ref.dtype)
    # rows no sub-tile reached (kv_len == 0) get -inf. Stored as (..., S, 1):
    # a (block_q, 1) block satisfies the Mosaic last-two-dims tiling rule,
    # a bare (block_q,) block does not.
    lse_ref[0, 0, rows, :] = jnp.where(
        l == 0.0, _NEG_INF, (m + jnp.log2(safe_l)) * (1.0 / _LOG2E))


# -------------------------------------------------------------- pallas forward


def _fwd_kernel(
    *refs,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    sub_q: int,
    sub_kv: int,
    kv_len: Optional[int],
    num_q_blocks: int,
    num_kv_blocks: int,
    window: Optional[int] = None,
    band: int = 0,
    triangle: bool = False,
):
    # `triangle` (a causal grid of live tiles): `_triangle`'s tables come first
    tiles, (q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch) = refs[:2 * triangle], refs[2 * triangle:]
    # `band` > 0 (a windowed call): the kv grid axis is the band's, not the
    # sequence's
    kv_steps = band or num_kv_blocks
    i, j = _triangle_tile(*tiles) if triangle else _grid_tile(num_q_blocks, kv_steps, 2, 3)
    # one kv block a row: a strip's softmax is whole and goes straight to
    # the output. More: (m, l, acc) ride in scratch across the kv axis.
    carried = kv_steps > 1
    if carried:
        m_scr, l_scr, acc_scr = scratch

        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        pl.when(j == 0)(_init)

    # per strip of queries: one softmax step over its live kv sub-tiles.
    # Causal: a strip (with a grid of tiles, the whole tile) above the
    # diagonal has none.
    def strips(i, j, sub_q=sub_q, sub_kv=sub_kv):
        for a in range(block_q // sub_q):
            row0 = i * block_q + a * sub_q
            rows = pl.ds(a * sub_q, sub_q)

            def visit(pieces, row0=row0, rows=rows):
                if not pieces:  # nothing live and nothing carried: kv_len == 0
                    o_ref[0, 0, rows, :] = jnp.zeros((sub_q, o_ref.shape[-1]), o_ref.dtype)
                    lse_ref[0, 0, rows, :] = jnp.full((sub_q, 1), _NEG_INF, jnp.float32)
                    return
                q = q_ref[0, 0, rows, :]
                scores, values = [], []
                for lo, hi, masked in pieces:
                    cols = pl.ds(lo * sub_kv, (hi - lo) * sub_kv)
                    scores.append(_scores(
                        q, k_ref[0, 0, cols, :], sm_scale * _LOG2E,
                        (row0, j * block_kv + lo * sub_kv) if masked else None,
                        causal, kv_len, window))
                    values.append(v_ref[0, 0, cols, :])
                if not carried:
                    _write_out(o_ref, lse_ref, rows, *_softmax_pieces(scores, values))
                    return
                m, l, acc = _softmax_pieces(
                    scores, values,
                    (m_scr[rows, :1], l_scr[rows, :1], lambda: acc_scr[rows, :]))
                acc_scr[rows, :] = acc
                m_scr[rows, :] = jnp.broadcast_to(m, (sub_q, m_scr.shape[1]))
                l_scr[rows, :] = jnp.broadcast_to(l, (sub_q, l_scr.shape[1]))

            _walk_strip(row0, j * block_kv, block_kv // sub_kv, sub_q, sub_kv, causal,
                        kv_len, "kv", visit, always=not carried, window=window)

    if band:
        _band_tiles("flash_fwd", band, window, block_q, j, i, num_q_blocks, True, strips)
    elif triangle:
        _triangle_classes("flash_fwd", i, j, block_q, block_kv, strips, q_ref.shape[-1])
    else:
        strips(i, j)

    if carried:
        # a row's last kv tile: the diagonal's, or the grid axis's
        pl.when(j == (i if triangle else kv_steps - 1))(lambda: _write_out(
            o_ref, lse_ref, slice(None), m_scr[:, :1], l_scr[:, :1], acc_scr[...]))


def _band_index(band: int, toward_diagonal: bool, tiles: int):
    """The moving side's tile of a band step, as an index map computes it:
    kv tile i - band + 1 + t of q tile i (never before the first), or q tile
    j + t of kv tile j (never after the last). A step past the sequence
    repeats its neighbour's block."""
    if toward_diagonal:
        return lambda i, t: jnp.maximum(i - (band - 1) + t, 0)
    return lambda j, t: jnp.minimum(j + t, tiles - 1)


def _grid_call(kernel, tables, grid, in_specs, out_specs, scratch_shapes, head_dim,
               resident_bytes=0, **how):
    """`pl.pallas_call` over `grid`; with a causal grid's `tables` as its
    scalar prefetch, which every index map and the kernel then take too; with
    the scoped VMEM a wide head's choices ask for, and `resident_bytes` (the
    backward's dQ of a head) on top of that or of the compiler's own."""
    spec = dict(grid=grid, in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch_shapes)
    vmem_limit_bytes = _head_choices(head_dim)["vmem_limit_bytes"]
    if resident_bytes:
        vmem_limit_bytes = (vmem_limit_bytes or _SCOPED_VMEM_BYTES) + resident_bytes
    if vmem_limit_bytes:
        how["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)
    if tables:
        spec = {"grid_spec": pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=len(tables), **spec)}
    return pl.pallas_call(kernel, **spec, **how)


def _fwd_pallas(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret, window=None,
                triangle=False):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    groups = hq // hkv
    nq = sq // block_q
    nk = skv // block_kv
    band = window_band(window, block_q, nk) if window is not None else 0
    sub_q, sub_kv = _sub_tiles(block_q, block_kv, 1 if band else nq * nk, d)
    kv_tile = _band_index(band, True, nk) if band else (lambda i, j: j)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        sub_q=sub_q,
        sub_kv=sub_kv,
        kv_len=None if band or triangle else kv_len,
        num_q_blocks=nq,
        num_kv_blocks=nk,
        **({"window": window, "band": band} if band else {}),
        triangle=triangle,
    )
    kv_steps = band or nk
    if triangle:
        tables = _triangle(nq, by_kv=False)
        grid = (b, hq, len(tables[0]))
        q_at = lambda b_, h, t, qt, kt: (b_, h, qt[t], 0)  # noqa: E731
        kv_at = lambda b_, h, t, qt, kt: (b_, h // groups, kt[t], 0)  # noqa: E731
    else:
        tables = ()
        grid = (b, hq, nq, kv_steps)
        q_at = lambda b_, h, i, j: (b_, h, i, 0)  # noqa: E731
        kv_at = lambda b_, h, i, j: (b_, h // groups, kv_tile(i, j), 0)  # noqa: E731
    out, lse = _grid_call(
        kernel, tables, grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_at),
            pl.BlockSpec((1, 1, block_kv, d), kv_at),
            pl.BlockSpec((1, 1, block_kv, d), kv_at),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_at),
            pl.BlockSpec((1, 1, block_q, 1), q_at),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ] if kv_steps > 1 else [],
        head_dim=d,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_win_fwd" if band else "flash_fwd",
    )(*tables, q, k, v)
    return out, lse


# ------------------------------------------------------------- pallas backward
#
# Flash backward (Dao et al. alg. 2) as ONE kernel, grid kv-outer / q-inner:
# a tile's scores, probabilities and dS are built once and feed all three
# gradients. dK_j and dV_j sum over the q blocks of a kv block in scratch, as
# a tile's worth of rows. dQ_i gets a term from EVERY kv block, and a TPU
# kernel has no atomic add: the head's whole dQ is a float32 scratch of
# (sq, d) that stays in VMEM from the head's first grid step to its last
# (8 MiB at 16,384 x 128 and at 8,192 x 256 of a v5e's 128: `_resident_dq_bytes`),
# zeroed at the first, and a q tile's rows leave it in q's dtype at the step
# that adds their last term (`_write_dq`), through an output block whose index
# map names that tile. kv tiles reach a q tile in ascending order, so dQ sums
# as a q-major kernel would.
# P is recomputed from (q, k, lse), and delta = rowsum(dO * O) from the rows of
# dO and O a piece holds: as a (B, H, S, 1) float32 input it is laid out a lane
# of 128 a row, 235 MB a call at 28 x 16,384 that XLA writes and the kernel
# reads back a tile at a time (one alternating chip sweep at the eight shapes
# of the tables above, PERF.md section 6, PR 45, ms a call with delta an input /
# in the kernel: 28.98 / 28.27, 14.72 / 14.07, 17.89 / 17.11, 10.20 / 9.45, D =
# 256 20.76 / 20.04, 4 x 16 x 4,096 5.34 / 4.94, one tile 3.49 / 3.37 and 1.60 /
# 1.32; the step's peak falls by that array).
# GQA is handled in the wrapper (repeat kv, then segment-sum dk/dv) — the
# kernel always sees Hq == Hkv. Inside a grid tile it walks sub-tiles as the
# forward does, per kv sub-tile over the q sub-blocks from the diagonal down.


def _resident_dq_bytes(sq: int, head_dim: int) -> int:
    """Bytes of the float32 dQ the backward kernel keeps in VMEM for a head of
    `sq` (padded) queries. A head it cannot keep beside a tile's blocks (64 k
    tokens at D = 256, 256 k at 128) is refused here, by name: no model, cell
    or test of the repo is within a factor of 4 of it."""
    resident = sq * head_dim * 4
    tile_bytes = _head_choices(head_dim)["vmem_limit_bytes"] or _SCOPED_VMEM_BYTES
    if tile_bytes + resident >= _VMEM_BYTES:
        raise ValueError(
            f"flash attention's backward keeps a head's float32 dQ in VMEM: {sq} queries x "
            f"{head_dim} features are {resident} bytes, which with the {tile_bytes} of a tile's "
            f"blocks is all of the chip's {_VMEM_BYTES} or more; shard the sequence (ring attention)")
    return resident


def _probs(q, k, lse, sm_scale, mask_at, causal, kv_len, window=None):
    """One piece's probabilities, rebuilt from the forward's lse."""
    s = _scores(q, k, sm_scale * _LOG2E, mask_at, causal, kv_len, window)
    return jnp.exp2(s - lse * _LOG2E)


def _ds(p, do, v, o, sm_scale):
    """dP = dO V^T ; dS = P * (dP - delta), delta = rowsum(dO * O)."""
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)
    return p * (dp - delta) * sm_scale


def _accumulate(out_ref, scr, carried, where, value):
    """A strip's gradient rows `where` (a pl.ds): straight to the output
    when this grid step is the only one that adds to them, else added in
    scratch. `value` is None for a strip with nothing live."""
    if carried:
        if value is not None:
            scr[where, :] += value
    elif value is None:
        out_ref[0, 0, where, :] = jnp.zeros(
            (where.size, out_ref.shape[-1]), out_ref.dtype)
    else:
        out_ref[0, 0, where, :] = value.astype(out_ref.dtype)


def _bwd_kernel(
    *refs,
    sm_scale, causal, block_q, block_kv, sub_q, sub_kv, kv_len,
    num_q_blocks, num_kv_blocks, window=None, band=0, triangle=False,
):
    tiles, (q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
            dk_ref, dv_ref, dq_ref, dq_scr, *scratch) = refs[:2 * triangle], refs[2 * triangle:]
    # grid: kv block outer (axis 2), q block inner (axis 3): the sequence's q
    # blocks, or a windowed call's band of them; a causal grid's one tile
    # axis runs a kv block's q blocks from the diagonal down
    q_steps = band or num_q_blocks
    i, j = _triangle_tile(*tiles) if triangle else _grid_tile(q_steps, num_kv_blocks, 3, 2)
    carried = q_steps > 1  # dK_j, dV_j summed over q blocks in scratch
    dk_scr, dv_scr = scratch if carried else (None, None)
    if carried:
        def _init():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        pl.when(i == (j if triangle else 0))(_init)

    # the head's dQ: zero at the head's first step; `dq_rows` are this step's q
    # tile's in it (a band's step is the tile's offset below the diagonal tile j)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    _when((i == 0) & (j == 0), _init_dq)
    q_row0 = (j + i if band else i) * block_q

    def dq_rows(first, size):
        start = q_row0 + first
        return pl.ds(start if _static(start) else pl.multiple_of(start, sub_q), size)

    def strips(i, j, sub_q=sub_q, sub_kv=sub_kv):
        for c in range(block_kv // sub_kv):
            col0 = j * block_kv + c * sub_kv
            cols = pl.ds(c * sub_kv, sub_kv)

            def visit(pieces, col0=col0, cols=cols):
                k = k_ref[0, 0, cols, :]
                v = v_ref[0, 0, cols, :]
                dk = dv = None
                for lo, hi, masked in pieces:
                    rows = pl.ds(lo * sub_q, (hi - lo) * sub_q)
                    q = q_ref[0, 0, rows, :]
                    do = do_ref[0, 0, rows, :]
                    p = _probs(
                        q, k, lse_ref[0, 0, rows, :], sm_scale,
                        (i * block_q + lo * sub_q, col0) if masked else None,
                        causal, kv_len, window)
                    # dV_j += P^T dO
                    dv_cur = jax.lax.dot_general(
                        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    ds = _ds(p, do, v, o_ref[0, 0, rows, :], sm_scale).astype(q.dtype)
                    # dK_j += dS^T Q
                    dk_cur = jax.lax.dot_general(
                        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                    # dQ_i += dS K_j, into the head's rows
                    dq_scr[dq_rows(lo * sub_q, rows.size), :] += jax.lax.dot_general(
                        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                    dv = dv_cur if dv is None else dv + dv_cur
                    dk = dk_cur if dk is None else dk + dk_cur
                _accumulate(dv_ref, dv_scr, carried, cols, dv)
                _accumulate(dk_ref, dk_scr, carried, cols, dk)

            _walk_strip(i * block_q, col0, block_q // sub_q, sub_q, sub_kv, causal, kv_len,
                        "q", visit, always=not carried, window=window)

    if band:
        # from the diagonal tile down: q tile j + t of kv tile j
        _band_tiles("flash_bwd_dkv_dq", band, window, block_q, i, j, num_q_blocks, False, strips)
    elif triangle:
        _triangle_classes("flash_bwd_dkv_dq", i, j, block_q, block_kv, strips, q_ref.shape[-1])
    else:
        strips(i, j)

    if carried:
        def _finalize():
            dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)

        pl.when(i == q_steps - 1)(_finalize)

    # a q tile's dQ is whole when its last kv tile has added to it: the diagonal
    # tile, a kv tile's FIRST step on a causal grid and on a band; the last kv
    # tile of a dense grid. `_bwd_pallas` maps the dQ block to that q tile.
    def _write_dq():
        dq_ref[0, 0] = dq_scr[dq_rows(0, block_q), :].astype(dq_ref.dtype)

    _when((i == j) if triangle else (i == 0) if band else (j == num_kv_blocks - 1), _write_dq)


def _bwd_pallas(q, k, v, out, lse, do, causal, sm_scale, block_q, block_kv, kv_len, interpret,
                window=None, triangle=False):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nq = sq // block_q
    nk = skv // block_kv

    band = window_band(window, block_q, nk) if window is not None else 0
    sub_q, sub_kv = _sub_tiles(block_q, block_kv, 1 if band else nq * nk, d)
    q_tile = _band_index(band, False, nq) if band else (lambda j, i: i)

    kernel = functools.partial(
        _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_kv=block_kv, sub_q=sub_q, sub_kv=sub_kv,
        kv_len=None if band or triangle else kv_len,
        num_q_blocks=nq, num_kv_blocks=nk,
        **({"window": window, "band": band} if band else {}),
        triangle=triangle,
    )
    # dQ leaves by q tile, at the step that completes it (`_write_dq`): on a
    # causal grid and a band (square tiles) that step is kv tile j's first and
    # the q tile is j; on a dense grid the last kv tile completes every q tile
    if triangle:
        tables = _triangle(nq, by_kv=True)
        grid = (b, h, len(tables[0]))
        q_at = lambda b_, h_, t, qt, kt: (b_, h_, qt[t], 0)  # noqa: E731
        kv_at = dq_at = lambda b_, h_, t, qt, kt: (b_, h_, kt[t], 0)  # noqa: E731
    else:
        tables = ()
        grid = (b, h, nk, band or nq)
        q_at = lambda b_, h_, j, i: (b_, h_, q_tile(j, i), 0)  # noqa: E731
        kv_at = lambda b_, h_, j, i: (b_, h_, j, 0)  # noqa: E731
        dq_at = kv_at if band else (
            lambda b_, h_, j, i: (b_, h_, jnp.where(j == nk - 1, i, 0), 0))  # noqa: E731
    q_spec = pl.BlockSpec((1, 1, block_q, d), q_at)
    kv_spec = pl.BlockSpec((1, 1, block_kv, d), kv_at)
    # (b, h, sq, 1): the trailing singleton keeps the lse's row blocks 2D for Mosaic
    row_spec = pl.BlockSpec((1, 1, block_q, 1), q_at)
    dk, dv, dq = _grid_call(
        kernel, tables, grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, q_spec],
        out_specs=[kv_spec, kv_spec, pl.BlockSpec((1, 1, block_q, d), dq_at)],
        scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32)] + ([
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ] if (band or nq) > 1 else []),
        head_dim=d,
        resident_bytes=_resident_dq_bytes(sq, d),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(q.shape, q.dtype),
        ],
        interpret=interpret,
        name="flash_win_bwd_dkv_dq" if band else "flash_bwd_dkv_dq",
    )(*tables, q, k, v, do, lse, out)
    return dq, dk, dv


# ----------------------------------------------------------- custom_vjp plumbing


def _live_grid_of(q, k, causal, window, block_q, block_kv) -> bool:
    """`_live_grid` of a padded self-attention call's operands."""
    return _live_grid(causal, window, block_q, block_kv,
                      q.shape[2] // block_q, k.shape[2] // block_kv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret, window=None,
           lse_first=False):
    out, _ = _fwd_pallas(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret, window,
                         _live_grid_of(q, k, causal, window, block_q, block_kv))
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret, window=None,
               lse_first=False):
    out, lse = _fwd_pallas(q, k, v, causal, sm_scale, block_q, block_kv, kv_len, interpret, window,
                           _live_grid_of(q, k, causal, window, block_q, block_kv))
    # What a recomputing block (jax.checkpoint) may keep in place of this
    # call: both or neither, the backward rebuilds the probabilities from the
    # lse, so the output alone spares nothing. The lse is held as (B, H, S):
    # a tiled layout pads the kernel's trailing axis of 1 to a lane's 128.
    # Where nothing is recomputed the two reshapes fold and the backward
    # reads the kernel's own lse, as it did.
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse[..., 0], "attn_lse")
    if lse_first:
        # the output is there when its lse is there in the shape that is kept:
        # left to itself the compiler reshapes the lse where it is next read,
        # in the backward pass, and holds the padded one until then (in a
        # scanned block the loop's own boundary does this)
        out, lse = jax.lax.optimization_barrier((out, lse))
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_kv, kv_len, interpret, window, lse_first, res, do):
    q, k, v, out, lse = res
    lse = lse[..., None]   # the row blocks' trailing axis (`_write_out`)
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
        kv_len, interpret, window, _live_grid_of(q, k, causal, window, block_q, block_kv),
    )
    if groups > 1:
        b, _, skv, d = dk.shape
        dk = dk.reshape(b, hkv, groups, skv, d).sum(axis=2)
        dv = dv.reshape(b, hkv, groups, skv, d).sum(axis=2)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------------------ public API


_IMPLEMENTATIONS = ("xla", "pallas")


# Side of a windowed call's square grid tiles. One chip sweep at S = 8,192,
# window 2,048, 32 query heads over 4 key-value heads of 128 (PERF.md section
# 6, PR 33) chose it; the band's static offsets give every tile its class
# whatever the side, so the side only trades grid steps against VMEM.
_WINDOW_BLOCK = 1024


def _blocks(sq: int, skv: int, block_q: Optional[int], block_kv: Optional[int],
            window: Optional[int] = None, head_dim: Optional[int] = None):
    """(block_q, block_kv) of the Pallas kernels: the whole sequence up to
    1,024 a side (a head wider than 128: `_WIDE_HEAD`'s side, the same), resident in
    VMEM for the sub-tile walk. A windowed call's tiles are square (its band
    of tiles then lies at static offsets from the diagonal)."""
    if window is not None:
        if block_q != block_kv:
            raise ValueError("a windowed call takes square blocks: block_q == block_kv")
        side = min(block_q or _WINDOW_BLOCK, max(sq, 1))
        return side, side
    tile = _head_choices(head_dim)["tile"]
    return min(block_q or tile, max(sq, 1)), min(block_kv or tile, max(skv, 1))


def resolve_attention_impl(implementation: Optional[str] = None) -> str:
    """The implementation `flash_attention` runs: "xla" or "pallas". A
    static rule on the backend: with nothing requested, "pallas" on a TPU
    and "xla" elsewhere (off-TPU the Pallas kernels only run through the
    interpreter, for callers that ask). The shape does not enter: the one
    kernel family compiles for the v5e at D = 64 and D = 128
    (tests/test_tpu_compile.py), and a kernel is never tried and swapped
    for another when it fails.

    Callers that report what ran (chip_smoke.py, LMTrainer) print this.
    """
    if implementation is not None and implementation not in _IMPLEMENTATIONS:
        raise ValueError(f"unknown attention implementation: {implementation!r}")
    if implementation is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return implementation


def attention_plan(seq: int, *, causal: bool = True,
                   implementation: Optional[str] = None,
                   window: Optional[int] = None, head_dim: Optional[int] = None) -> dict:
    """What `flash_attention` runs for one head of a (seq, seq)
    self-attention: the resolved implementation, how far the kernels'
    sub-tile walk engages (no sub-tiles for "xla"; the sub-tiles as the
    backward kernel counts them, `attention_subtiles`) and how many kernels
    the backward of a call is (`attn_bwd_kernels`: 1, `flash_bwd_dkv_dq`).
    With the `head_dim` of the call, also what was chosen by it: the grid
    tile's side, the sub-tile (`_head_choices`) and the bytes of a head's
    float32 dQ that the backward kernel keeps in VMEM (`_resident_dq_bytes`).
    For callers that report it: LMTrainer's `train.init.step_fn` span,
    chip_smoke.py."""
    impl = resolve_attention_impl(implementation)
    visited = masked = total = steps = live = whole = 0
    chosen = {}
    if impl != "xla":
        bq, bkv = _blocks(seq, seq, None, None, window, head_dim)
        padded_q, padded_kv = seq + (-seq) % bq, seq + (-seq) % bkv
        nq, nk = padded_q // bq, padded_kv // bkv
        # a band's and a causal grid's tiles lie at static offsets: walked
        static = window is not None or _live_grid(causal, window, bq, bkv, nq, nk)
        if head_dim is not None:
            chosen = {"attn_tile": bq, "attn_subtile": "{}x{}".format(*_sub_tiles(bq, bkv, 1, head_dim)),
                      "attn_bwd_resident_bytes": _resident_dq_bytes(padded_q, head_dim)}
        visited, masked, total = attention_subtiles(
            padded_q, padded_kv, causal, seq, bq, bkv,
            *_sub_tiles(bq, bkv, 1 if static else nq * nk, head_dim), window, head_dim=head_dim)
        steps, live = attention_grid_steps(padded_q, padded_kv, causal, seq, bq, bkv, window)
        if window is not None:
            band = window_band(window, bq, nk)
            whole = sum(nq - d for d in range(band) if _band_whole("flash_fwd", d, band, bq, window))
    return {
        "attention_impl": impl,
        "attn_subtiles_visited": visited,
        "attn_subtiles_masked": masked,
        "attn_subtiles_total": total,
        "attn_grid_steps": steps,
        "attn_grid_steps_live": live,
        "attn_bwd_kernels": int(impl != "xla"),
        # a windowed call's band tiles a head that the forward computes as one
        # piece (`_BAND_WALK`), of its `attn_grid_steps_live`
        **({} if window is None else {"attn_window_tiles_whole": whole}),
        **chosen,
    }


def _per_shard(kernel_fn):
    """GSPMD cannot partition a Mosaic call ("wrap the call in a
    shard_map"), so a Pallas kernel traced under a context mesh
    (make_train_step traces its step inside `use_abstract_mesh`) runs
    once per shard: batch over the data axes, heads over tp — every
    shard is a whole (S, D) attention problem, no collective needed.
    With no context mesh, one device, or inside somebody else's
    shard_map (ring, Ulysses, pipeline, explicit-dp: the axes are already
    manual there) the kernel is called as it is. Axis names are
    parallel.mesh's (DATA_AXES, "tp"). Every operand is a (B, H, ., .) array
    (q, k, v; ops/eva.py's summaries beside them)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return kernel_fn
    batch = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    heads = "tp" if mesh.shape.get("tp", 1) > 1 else None
    if not batch and heads is None:
        return kernel_fn
    spec = P(batch or None, heads, None, None)
    return lambda *operands: jax.shard_map(
        kernel_fn, mesh=mesh, in_specs=(spec,) * len(operands), out_specs=spec,
        check_vma=False,
    )(*operands)


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
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    implementation: Optional[str] = None,
) -> jax.Array:
    """Blockwise flash attention. q (B,Hq,Sq,D); k,v (B,Hkv,Skv,D).

    implementation: "pallas" (the kernels; interpreted off-TPU), "xla"
    (reference), or None = the static rule of `resolve_attention_impl`.

    Block defaults: 1024 x 1024, clamped to the sequence, so that up to
    S = 1,024 a head is ONE grid step whose q, k, v stay resident in VMEM
    (128 KB each at D = 64 in bf16, 256 KB at D = 128): a grid step costs
    ~0.9 us of DMA issue and bookkeeping, which is why smaller grid blocks
    lost every sweep. Inside the resident block the kernels walk 256 x 256
    sub-tiles and skip the dead ones (the comment above `_SUB_TILE`; the
    chip sweep is in PERF.md section 6, PR 26).

    Past one tile a head a causal call's grid is the lower triangle of
    tiles alone (136 steps a head at S = 16,384 where the square has 256):
    a tile above the diagonal is neither stepped through nor copied, one
    below it runs whole and unmasked, the diagonal's as a one-tile call's
    does (PERF.md section 6, PR 37). Explicit square `block_q` / `block_kv`
    make the same grid of smaller tiles; a call that is not causal, or whose
    blocks are not square, a dense grid of blocks, each its own masked
    sub-tile.

    window (causal self-attention only): key j is live for query i iff
    i - window < j <= i. The same kernels under the names `flash_win_*`:
    the kv axis of the grid is then the BAND of tiles a q tile's window
    touches (`window_band`; 3 of 8 at S = 8,192, window 2,048), so a tile
    outside the window is neither computed nor copied, and since a band
    tile lies at a static offset from the diagonal its class is known while
    tracing: an interior tile (every pair live) is one whole unmasked piece,
    the diagonal tile and the one the window's lower edge crosses are whole
    with that edge's static mask or walked in sub-tiles, a kernel
    (`_BAND_WALK`; PERF.md section 6, PR 43).

    The backward of a call is ONE kernel on any of those grids
    (`flash_bwd_dkv_dq`; PERF.md section 6, PR 45): a tile's scores,
    probabilities and dS are built once for dK, dV and dQ, and a head's dQ
    sums over kv tiles in a float32 scratch of its whole q sequence that
    stays in VMEM for the head (`_resident_dq_bytes`, which refuses a head
    that cannot: 64 k tokens at D = 256).
    """
    return _attend(q, k, v, False, causal, window, sm_scale, block_q, block_kv, implementation)


def flash_attention_kept(q: jax.Array, k: jax.Array, v: jax.Array, **how) -> jax.Array:
    """`flash_attention(q, k, v, **how)` for a block whose checkpoint keeps
    the kernel's output and lse ("attn_out", "attn_lse" of `_flash_fwd`):
    the same numbers, and the lse in the shape that is kept before anything
    reads the output (`lse_first` there)."""
    return _attend(q, k, v, True, **how)


def _attend(q, k, v, lse_first, causal=False, window=None, sm_scale=None, block_q=None,
            block_kv=None, implementation=None):
    sq, skv = q.shape[2], k.shape[2]
    implementation = resolve_attention_impl(implementation)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None and not (causal and sq == skv and window >= 1):
        raise ValueError("a window needs causal self-attention (Sq == Skv) and window >= 1")
    if implementation == "xla":
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale, window=window)
    if causal and sq != skv:
        raise NotImplementedError("causal flash kernel requires Sq == Skv")
    interpret = jax.default_backend() != "tpu"
    bq, bkv = _blocks(sq, skv, block_q, block_kv, window, q.shape[-1])
    out = _per_shard(
        lambda q_, k_, v_: _flash(
            q_, k_, v_, causal, sm_scale, bq, bkv, skv, interpret, window, lse_first
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
    consumers need to merge partial attentions exactly: ring attention, and
    (through `_fwd_pallas` itself) ops/eva.py, whose far kernel goes on from
    the local part's output and lse.

    FORWARD ONLY: no VJP is registered through the lse output; both
    consumers own theirs (ring_attention's custom_vjp recomputes through
    the einsum reference, ops/eva.py's hands the MERGED output and lse to
    `flash_bwd_dkv_dq`)."""
    sq, skv = q.shape[2], k.shape[2]
    implementation = resolve_attention_impl(implementation)
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
    bq, bkv = _blocks(sq, skv, block_q, block_kv, head_dim=q.shape[-1])
    out, lse = _fwd_pallas(
        _pad_seq(q, 2, bq), _pad_seq(k, 2, bkv), _pad_seq(v, 2, bkv),
        causal, sm_scale, bq, bkv, skv, interpret,
    )
    if out.shape[2] != sq:
        out = out[:, :, :sq]
        lse = lse[:, :, :sq]
    return out, lse
