"""Rows of an expert-sorted buffer summed into their tokens' rows:
`out[t] = sum over the slots s with token[s] == t of weight[s] * x[s]`.

What a layer that holds a part of the experts does twice a pass
(models/moe._held_experts): the combine (`x` the experts' outputs, `weight`
the gates, float32 out) and the transpose of the dispatch's gather (`x` the
buffer's cotangent, no weight, the activations' dtype out). As a scatter-add
of wide rows a TPU walks the rows one at a time (PERF.md section 6, PR 40:
8.6-9.4 ms a call where the bytes are 0.4-0.5 ms). Here it is one Mosaic
kernel, `moe_rows_sum`, under one `custom_vjp` pair: `rows_sum` (its transpose
two gathers and a row dot, left to XLA) and `take_token_rows` (a gather whose
transpose is `rows_sum`). The plain form the tests compare with is the layer's
own "xla" path; the kernel runs interpreted off a TPU.

The contract is the layer's layout: the slots lie in groups (an expert's
rows), inside a group the tokens ascend with the slots that hold none
(`token[s] >= tokens`) last. Then the slots of group g that belong to a tile
of `token_tile` tokens are ONE contiguous range, and `token_tile_bounds` finds
every range with one search of a monotone key.

The kernel's grid is the token tiles. A step walks its tile's ranges, one a
group, in windows of `window` rows whose start is aligned down to `_ALIGN`
rows, copied from the buffer in HBM two windows deep, and places each window's
rows into the tile's (token_tile, M) float32 accumulator with a one-hot matmul:
`onehot[t, r] = (token[r] == tile's first + t)`, zero for a row outside the
range. It loops over as many windows as the tile's longest range takes, so a
tile that few rows reach costs little, and a token fetches the rows it holds
and no more. Rows that are not bfloat16 already (the gated product, float32
rows) go through the MXU as three bfloat16 pieces stacked along the
contraction, hi + mid + lo == the float32 value exactly, so a token's sum is
the float32 sum of its float32 products in slot order, written once.

Set-up: every call site enters through ONE primitive, `moe_rows_sum_p`,
whose lowering builds the kernel and is emitted out of line, so a program
traces the kernel and lowers it to a Mosaic module once a (shapes, dtypes,
parameters) signature and calls that one function from every layer, pass and
recomputation. A function under `jax.jit` does the same only while nobody
copies its jaxpr: partial evaluation does (the forward pass of a pass that is
not checkpointed beside one that is), and JAX keys a jitted function's lowering
by the jaxpr's identity, so the SmallThinker step held three bodies for two
signatures that way (PERF.md section 6, PR 41). A primitive is one equation to
every transformation, and its lowering is keyed by shapes and parameters.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive
from jax.interpreters import mlir

# a window starts on a multiple of this: the rows one (16, 128) bfloat16 tile packs
_ALIGN = 16
# a window's rows by the pieces a row is placed in: three pieces of 32 rows fill
# one pass of the MXU's 128-deep contraction; rows placed whole are cheapest 64
# at a time (PR 40's sweep on the chip, 16,384 tokens of 2,560: 1.52 ms a call
# in windows of 32, 1.30 of 64, 1.49 of 128; in three pieces 1.69, 1.85, 2.48)
_WINDOW = {3: 32, 1: 64}
_VMEM_LIMIT = 48 * 1024 * 1024


def rows_sum_tile(tokens: int, m: int) -> int:
    """Tokens a grid step of the kernel sums rows of width `m` into: the
    largest power of two up to 256 that divides `tokens` and whose float32
    accumulator and two output blocks leave the scoped VMEM half empty."""
    tile = 256
    while tile > 1 and (tokens % tile or 3 * tile * m * 4 > _VMEM_LIMIT // 2):
        tile //= 2
    if tile < _ALIGN and tile != tokens:
        raise ValueError(f"{tokens} tokens of width {m} have no tile of {_ALIGN} or more")
    return tile


def token_tile_bounds(token: jax.Array, group: jax.Array, groups: int, tokens: int,
                      token_tile: int) -> jax.Array:
    """token (P,) int32 and group (P,) int32 as the contract above has them
    (the slots after the last group in the last group, holding no token) ->
    bounds (groups, tokens / token_tile + 1) int32: group g's slots of token
    tile i are [bounds[g, i], bounds[g, i + 1]). The few hundred edges are
    each compared with every key in one fused pass (0.23-0.26 ms a call on
    the chip, PR 41) and not searched for through fifteen dependent gathers."""
    key = group * (tokens + 1) + jnp.minimum(token.astype(jnp.int32), tokens)
    edges = jnp.arange(tokens // token_tile + 1, dtype=jnp.int32) * token_tile
    queries = jnp.arange(groups, dtype=jnp.int32)[:, None] * (tokens + 1) + edges[None, :]
    return jnp.searchsorted(
        key, queries.reshape(-1), side="left", method="compare_all").astype(jnp.int32).reshape(
            groups, -1)


# ------------------------------------------------------------------- kernel


def _rows_sum_kernel(bounds_ref, tok_ref, *refs, token_tile, window, rows, groups, pieces, weighted):
    if weighted:
        gate_ref, refs = refs[0], refs[1:]
    x_hbm, out_ref, buf, sem, acc = refs[:5]
    i, tiles = pl.program_id(0), pl.num_programs(0)
    depth = pieces * window

    def range_of(group):
        at = group * (tiles + 1) + i
        return bounds_ref[at], bounds_ref[at + 1]

    def windows_of(group, most):
        lo, hi = range_of(group)
        reach = hi - jax.lax.div(lo, _ALIGN) * _ALIGN
        return jnp.maximum(most, jnp.where(hi > lo, jax.lax.div(reach + window - 1, window), 0))

    # step j is window j / groups of group j % groups: every group's first, then every second
    steps = jax.lax.fori_loop(0, groups, windows_of, jnp.int32(0)) * groups

    def window_of(j):
        """(range, the rows window j stands for, the rows copied for it,
        whether it holds a row of the range); no window past the last step does."""
        lo, hi = range_of(jax.lax.rem(j, groups))
        nominal = jax.lax.div(lo, _ALIGN) * _ALIGN + jax.lax.div(j, groups) * window
        live = jnp.logical_and(hi > lo, nominal < hi)
        # the buffer's last window is copied from where it still fits
        start = pl.multiple_of(jnp.minimum(nominal, rows - window), _ALIGN)
        return lo, hi, nominal, start, live

    def copy(j, start):
        at = jax.lax.rem(j, 2)
        return pltpu.make_async_copy(x_hbm.at[pl.ds(start, window), :], buf.at[at], sem.at[at])

    def start_copy(j):
        _, _, _, start, live = window_of(j)
        pl.when(live)(lambda: copy(j, start).start())

    acc[...] = jnp.zeros_like(acc)
    start_copy(jnp.int32(0))

    def step(j, carry):
        start_copy(j + 1)
        lo, hi, nominal, start, live = window_of(j)

        @pl.when(live)
        def _():
            copy(j, start).wait()
            table_row = jax.lax.div(start, _ALIGN)
            col = jax.lax.broadcasted_iota(jnp.int32, (1, depth), 1)
            slot = start + jnp.bitwise_and(col, window - 1)
            mine = jnp.logical_and(slot >= jnp.maximum(lo, nominal),
                                   slot < jnp.minimum(hi, nominal + window))
            tok = jnp.where(mine, tok_ref[pl.ds(table_row, 1), :], -1)
            tokens_at = i * token_tile + jax.lax.broadcasted_iota(
                jnp.int32, (token_tile, depth), 0)
            onehot = jnp.where(tok == tokens_at, 1.0, 0.0).astype(jnp.bfloat16)
            if pieces == 1:
                placed = buf[jax.lax.rem(j, 2)]
            else:
                parts = refs[5]
                value = buf[jax.lax.rem(j, 2)].astype(jnp.float32)
                if weighted:
                    # the window's gates, a row of lanes, as a column: the
                    # diagonal of their broadcast
                    gates = jnp.broadcast_to(gate_ref[pl.ds(table_row, 1), :], (window, window))
                    diagonal = (jax.lax.broadcasted_iota(jnp.int32, (window, window), 0)
                                == jax.lax.broadcasted_iota(jnp.int32, (window, window), 1))
                    value = value * jnp.sum(jnp.where(diagonal, gates, 0.0), axis=1, keepdims=True)
                for piece in range(pieces):
                    part = value.astype(jnp.bfloat16)
                    parts[piece * window:(piece + 1) * window, :] = part
                    value = value - part.astype(jnp.float32)
                placed = parts[...]
            acc[...] += jnp.dot(onehot, placed, preferred_element_type=jnp.float32)

        return carry

    jax.lax.fori_loop(0, steps, step, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _windows_table(values, window, repeats, fill):
    """values (P,) -> (P / _ALIGN, repeats * window): row a holds the values
    of the slots [a _ALIGN, a _ALIGN + window), `repeats` times over, so that
    a window's values are one row of lanes wherever it starts. Slices and
    reshapes, no gather."""
    slots = values.shape[0]
    padded = jax.lax.pad(values, jnp.asarray(fill, values.dtype), [(0, window, 0)])
    table = jnp.concatenate(
        [padded[a:a + slots].reshape(slots // _ALIGN, _ALIGN) for a in range(0, window, _ALIGN)],
        axis=1)
    return jnp.tile(table, (1, repeats))


def _rows_sum_build(x, *rest, tokens, out_dtype, interpret):
    """The one place the kernel is built: x (P, M), then weight (P,) if the
    rows are weighted, token (P,) int32, bounds from `token_tile_bounds` ->
    (tokens, M) `out_dtype`."""
    weight, token, bounds = rest if len(rest) == 3 else (None, *rest)
    rows, m = x.shape
    groups, tiles = bounds.shape[0], bounds.shape[1] - 1
    # bfloat16 rows with no weight are placed as they are: one exact pass
    pieces = 1 if (x.dtype == jnp.bfloat16 and weight is None) else 3
    token_tile, window = tokens // tiles, _WINDOW[pieces]
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    tables = [_windows_table(token.astype(jnp.int32), window, pieces, tokens)]
    if weight is not None:
        tables.append(_windows_table(weight.astype(jnp.float32), window, 1, 0.0))
    scratch = [pltpu.VMEM((2, window, m), x.dtype), pltpu.SemaphoreType.DMA((2,)),
               pltpu.VMEM((token_tile, m), jnp.float32)]
    if pieces > 1:
        scratch.append(pltpu.VMEM((pieces * window, m), jnp.bfloat16))
    return pl.pallas_call(
        functools.partial(
            _rows_sum_kernel, token_tile=token_tile, window=window, rows=rows, groups=groups,
            pieces=pieces, weighted=weight is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[whole] * len(tables) + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((token_tile, m), lambda i, bounds: (i, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, m), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_rows_sum",
    )(bounds.reshape(-1), *tables, x)


def _rows_sum_shape(x, *rest, tokens, out_dtype, interpret):
    del interpret
    rows, m = x.shape
    most = _WINDOW[1]
    if rows % _ALIGN or rows < most:
        raise ValueError(f"{rows} rows: a multiple of {_ALIGN}, and a window's {most} at least")
    if tokens % (rest[-1].shape[1] - 1):
        raise ValueError(f"{tokens} tokens in {rest[-1].shape[1] - 1} tiles")
    return x.update(shape=(tokens, m), dtype=out_dtype, weak_type=False)


moe_rows_sum_p = Primitive("moe_rows_sum")
moe_rows_sum_p.def_abstract_eval(_rows_sum_shape)
moe_rows_sum_p.def_impl(
    lambda *args, **params: jax.jit(functools.partial(moe_rows_sum_p.bind, **params))(*args))
# out of line: one function a signature, called from every site
mlir.register_lowering(
    moe_rows_sum_p, mlir.lower_fun(_rows_sum_build, multiple_results=False), inline=False)


# ------------------------------------------------------- the custom_vjp pair


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _rows_sum(x, weight, token, bounds, tokens, out_dtype, interpret):
    arrays = (x, token, bounds) if weight is None else (x, weight, token, bounds)
    return moe_rows_sum_p.bind(*arrays, tokens=tokens, out_dtype=out_dtype, interpret=interpret)


def _rows_sum_fwd(x, weight, token, bounds, tokens, out_dtype, interpret):
    return _rows_sum(x, weight, token, bounds, tokens, out_dtype, interpret), (x, weight, token)


def _rows_sum_bwd(tokens, out_dtype, interpret, residuals, dy):
    x, weight, token = residuals
    # a slot's token's cotangent row; zero where the slot holds none
    taken = jnp.take(dy, token, axis=0, mode="fill", fill_value=0).astype(jnp.float32)
    if weight is None:
        return taken.astype(x.dtype), None, None, None
    dx = (weight.astype(jnp.float32)[:, None] * taken).astype(x.dtype)
    dweight = jnp.sum(x.astype(jnp.float32) * taken, axis=-1).astype(weight.dtype)
    return dx, dweight, None, None


_rows_sum.defvjp(_rows_sum_fwd, _rows_sum_bwd)


def rows_sum(x: jax.Array, weight: Optional[jax.Array], token: jax.Array, bounds: jax.Array,
             tokens: int, *, out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """x (P, M), weight (P,) float32 or None, token (P,) int32, bounds as
    `token_tile_bounds` gives them (their shape carries the token tile) ->
    (tokens, M) `out_dtype`. Differentiable in `x` and `weight`:
    `dx[s] = weight[s] * dy[token[s]]`, `dweight[s] = <x[s], dy[token[s]]>`,
    zero where the slot holds no token."""
    return _rows_sum(x, weight, token, bounds, tokens, jnp.dtype(out_dtype), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _take_token_rows(h, token, bounds, tokens, dtype, interpret):
    return jnp.take(h, token, axis=0, mode="fill", fill_value=0)


def _take_token_rows_fwd(h, token, bounds, tokens, dtype, interpret):
    return _take_token_rows(h, token, bounds, tokens, dtype, interpret), (token, bounds)


def _take_token_rows_bwd(tokens, dtype, interpret, residuals, dy):
    token, bounds = residuals
    return _rows_sum(dy, None, token, bounds, tokens, dtype, interpret), None, None


_take_token_rows.defvjp(_take_token_rows_fwd, _take_token_rows_bwd)


def take_token_rows(h: jax.Array, token: jax.Array, bounds: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """`h[token]` (T, M) -> (P, M), a zero row where `token[s] >= T`; its
    transpose is `rows_sum` with no weight over the same layout, summed in
    float32 and rounded once to `h`'s dtype."""
    return _take_token_rows(h, token, bounds, h.shape[0], jnp.dtype(h.dtype), interpret)
