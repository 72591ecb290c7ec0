"""Grouped matmul over ragged row groups: `out[r] = lhs[r] @ rhs[group(r)]`.

The expert matmuls of a dropless MoE layer (models/moe.py): the rows of
`lhs` are (token, choice) pairs sorted by expert, `rhs` holds one matrix
an expert, and `group_sizes[g]` rows in a row belong to group g. Two
implementations, chosen by the backend alone (`resolve_gmm_impl`, as
attention's): no flag, and a kernel is never tried and swapped.

- "xla": `jax.lax.ragged_dot`, which also differentiates itself. What the
  CPU tests run and the kernels are compared with.
- "pallas": three Mosaic kernels under one `custom_vjp`, every call named
  `moe_gmm_*` so that a device trace prints them: the forward
  `lhs[rows] @ rhs[group]`, the same kernel with `rhs` transposed for
  d(lhs), and `lhs[rows]^T @ dout[rows]` summed over a group's rows for
  d(rhs).

The kernels' contract is narrower than `ragged_dot`'s, and the layout in
models/moe.py (`dropless_layout`) is built to it: every group holds a
positive multiple of `tile_rows` rows (the rows a group lacks are zero
rows), so that a row tile belongs to exactly one group and the kernel is
a plain tiled matmul whose `rhs` block index is read from a prefetched
table. Rows after the last group are written as zeros.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_IMPLEMENTATIONS = ("xla", "pallas")
# the `checkpoint_name` of the kernels' two prefetched tables (each row tile's group, the tiles that hold
# rows): a few words that all three matmuls of a layer and their transposes read, found by a search
TILES = "moe_gmm_tiles"
# rows of one kernel tile: a multiple of the v5e MXU's 128 that keeps the
# zero rows a group is padded with (half a tile on average) a few percent
PALLAS_TILE_ROWS = 256
_COL_TILE = 512
_VMEM_LIMIT = 48 * 1024 * 1024


def resolve_gmm_impl(implementation: Optional[str] = None) -> str:
    """The implementation `grouped_matmul` runs: with nothing requested,
    "pallas" on a TPU and "xla" elsewhere."""
    if implementation is not None and implementation not in _IMPLEMENTATIONS:
        raise ValueError(f"unknown grouped-matmul implementation: {implementation!r}")
    if implementation is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return implementation


def gmm_tile_rows(implementation: Optional[str] = None) -> int:
    """Rows every group is padded to a multiple of, for the layout."""
    return PALLAS_TILE_ROWS if resolve_gmm_impl(implementation) == "pallas" else 1


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   tile_rows: int = 1, implementation: Optional[str] = None,
                   interpret: bool = False) -> jax.Array:
    """lhs (P, K) sorted by group, rhs (G, K, N), group_sizes (G,) int32
    -> (P, N) in lhs's dtype. For "pallas" every size is a positive
    multiple of `tile_rows` and P is one too.

    A row tile after the last group is never computed, and its copy is not
    made either (its block index repeats the last used tile's), so a buffer
    sized for more rows than it holds (a layer that holds a part of the
    experts, models/moe.py) costs what its rows cost: 0.45 ms a call of
    36,864 empty slots otherwise (PERF.md section 6, PR 33)."""
    if resolve_gmm_impl(implementation) == "xla":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
    if lhs.shape[0] % tile_rows:
        raise ValueError(f"{lhs.shape[0]} rows are no multiple of the tile's {tile_rows}")
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    tile_starts = jnp.arange(lhs.shape[0] // tile_rows, dtype=jnp.int32) * tile_rows
    # the group of each row tile; tiles after the last group read the last
    # group's matrix and are not computed
    tile_group = checkpoint_name(jnp.minimum(
        jnp.searchsorted(ends, tile_starts, side="right"), rhs.shape[0] - 1).astype(jnp.int32), TILES)
    tiles_used = checkpoint_name((ends[-1:] // tile_rows).astype(jnp.int32), TILES)
    return _gmm_pallas(lhs, rhs, tile_group, tiles_used, tile_rows, interpret)


# ------------------------------------------------------------------ kernels


def _gmm_kernel(tile_group_ref, tiles_used_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    del tile_group_ref
    used = pl.program_id(1) < tiles_used_ref[0]

    @pl.when(used)
    def _():
        contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], contract, preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(used))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _drhs_kernel(tile_group_ref, tiles_used_ref, lhs_ref, dout_ref, out_ref, acc_ref):
    i, last_tile = pl.program_id(1), pl.num_programs(1) - 1
    group = tile_group_ref[i]
    first = jnp.logical_or(i == 0, tile_group_ref[jnp.maximum(i - 1, 0)] != group)
    last = jnp.logical_or(i == last_tile, tile_group_ref[jnp.minimum(i + 1, last_tile)] != group)

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < tiles_used_ref[0])
    def _():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # the block stays resident while the group does; written once, at its end
    @pl.when(last)
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _col_tile(n: int) -> int:
    return _COL_TILE if n % _COL_TILE == 0 else n


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _row_tile(i, used):
    """The row tile a grid step reads: its own, or the last used one for the
    steps after it, whose copy is then skipped."""
    return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))


def _gmm_call(lhs, rhs, tile_group, tiles_used, tile_rows, interpret, transpose_rhs):
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _col_tile(n)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, tn, k), lambda j, i, tg, used: (tg[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec((1, k, tn), lambda j, i, tg, used: (tg[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tile_rows),
            in_specs=[pl.BlockSpec((tile_rows, k), lambda j, i, tg, used: (_row_tile(i, used), 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tile_rows, tn), lambda j, i, tg, used: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="moe_gmm_dlhs" if transpose_rhs else "moe_gmm_fwd",
    )(tile_group, tiles_used, lhs, rhs)


def _drhs_call(lhs, dout, tile_group, tiles_used, groups, tile_rows, interpret, dtype):
    rows, k = lhs.shape
    n = dout.shape[1]
    tn = _col_tile(n)
    return pl.pallas_call(
        _drhs_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tile_rows),
            in_specs=[
                pl.BlockSpec((tile_rows, k), lambda j, i, tg, used: (_row_tile(i, used), 0)),
                pl.BlockSpec((tile_rows, tn), lambda j, i, tg, used: (_row_tile(i, used), j)),
            ],
            out_specs=pl.BlockSpec((1, k, tn), lambda j, i, tg, used: (tg[i], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="moe_gmm_drhs",
    )(tile_group, tiles_used, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm_pallas(lhs, rhs, tile_group, tiles_used, tile_rows, interpret):
    return _gmm_call(lhs, rhs, tile_group, tiles_used, tile_rows, interpret, False)


def _gmm_fwd(lhs, rhs, tile_group, tiles_used, tile_rows, interpret):
    out = _gmm_pallas(lhs, rhs, tile_group, tiles_used, tile_rows, interpret)
    return out, (lhs, rhs, tile_group, tiles_used)


def _gmm_bwd(tile_rows, interpret, residuals, dout):
    lhs, rhs, tile_group, tiles_used = residuals
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm_call(dout, rhs, tile_group, tiles_used, tile_rows, interpret, True)
    drhs = _drhs_call(lhs, dout, tile_group, tiles_used, rhs.shape[0], tile_rows,
                      interpret, rhs.dtype)
    return dlhs, drhs, None, None


_gmm_pallas.defvjp(_gmm_fwd, _gmm_bwd)
