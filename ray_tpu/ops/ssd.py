"""The state-space mixer's core (Mamba-2): a causal depthwise convolution
(`causal_conv1d`), the selective scan in its chunked, state-space-duality
form (`ssd_scan`), and the gated norm over groups of features behind it
(`gated_group_norm`, at the end).

The recurrence, a head h of P features with a state of P x N, reading group
g = h // (H / G) of the G groups that B and C come in:

  a_t     = exp(dt_t A),                A = -exp(a_log) < 0, dt_t > 0
  H_t     = a_t H_{t-1} + dt_t x_t (x) B_t,        H_0 = 0
  y_t     = H_t C_t + d x_t

`ssd_scan` never holds a state a position. The sequence is cut into chunks
of `chunk` positions; with c_l the cumulative log-decay inside a chunk
(c_l = sum_{j <= l} dt_j A),

  inside a chunk    y_l += sum_{s <= l} exp(c_l - c_s) (C_l . B_s) dt_s x_s
  a chunk's state   S    = sum_s exp(c_last - c_s) dt_s x_s (x) B_s
  across chunks     H_k  = exp(c_last of chunk k) H_{k-1} + S_k
  the read-out      y_l += exp(c_l) C_l . H_{k-1}

so the products are matmuls (C B^T, the masked scores times x, x^T B, C H)
and the recurrence runs over S / chunk states. dt, the log-decays, their
exponentials (every exponent is <= 0: differences of one chunk's cumulative
sums under the causal mask, never the two factors exp(c_l) exp(-c_s)) and
the carried state are float32; the products take their operands in the
dtype x comes in and accumulate in float32.

Two forms compute it, chosen by a static rule on the backend, the shapes and
the context mesh (`resolve_scan_impl`; `scan_plan` reports it; no flag, and a
form is never tried and swapped for the other; `_resolve` is the one rule,
the gated norm's and the convolution's too):

- "pallas", on a TPU at the sizes the kernels tile: two Mosaic kernels under
  one `custom_vjp`, `ssd_fwd` and `ssd_bwd`, every call named so that a device
  trace prints it. A grid step computes ONE chunk of one group's heads in
  VMEM: C B^T once a group, exp(c_l - c_s) under the causal mask, the weighted
  sum over x, the read-out of the state, the state's update and + d x before
  the one write of y. The group's float32 state lives in VMEM scratch from a
  sequence's first chunk to its last; no decays, scores or per-chunk states
  cross HBM. Differentiated, the forward also writes the state that ENTERED
  each chunk, narrowed to x's dtype as the read-out takes it ((B, chunks, N,
  H P): 134 MB a layer of 2 x 8,192 tokens in bfloat16), and the backward
  walks the chunks in reverse with the state's cotangent in scratch, builds
  the chunk's scores and decays again in VMEM and writes dx, d(dt),
  d(log-decay) and dB, dC summed over the group's heads.
- "xla_chunked", everywhere else and what the kernels are compared with: XLA
  einsums (`_block`) over `BLOCK_CHUNKS` chunks at a time, walked by a
  `lax.scan` that carries the state from block to block, with a backward pass
  of its own (`_walk`, a `custom_vjp`): differentiated, the walk keeps its
  inputs and ONE float32 state a block (the state that entered it), and the
  backward pass computes a block again from that state before it transposes
  it. What a block builds and drops is (B, chunks, H, chunk, chunk) decays and
  scores and (B, chunks, H, P, N) states, 0.2 GB at 2 x 1,024 tokens of 64
  heads.

Either form's output and kept states carry the names `ssm_scan_out` and
`ssm_chunk_states` for a checkpoint policy around the caller: with both kept,
its backward pass does not run the scan forward a second time.

A sequence that is no multiple of the chunk is refused by name: padding at
the end would be silent work, and the cell's sequences are multiples.

The gated norm, y silu(z) through an RMS norm over each group's features, has
the same two forms by the same rule (`resolve_gate_norm_impl`,
`gate_norm_plan`): "pallas", two kernels under one `custom_vjp`,
`ssm_gate_norm_fwd` and `ssm_gate_norm_bwd`, a grid step a tile of rows with
every group a static slice of whole lane tiles; "xla", ops/layers.rmsnorm on
the view by groups. Its section says why.

So has the convolution in front of the scan, with its bias and silu
(`causal_conv1d`; `resolve_conv_impl`, `conv_plan`): "pallas", two kernels
under one `custom_vjp`, `ssm_conv_fwd` and `ssm_conv_bwd`, a grid step a tile
of rows of ONE sequence with every channel, read out of the in-projection's
output as it is at a column offset and written as x, B and C, an output each;
"xla", K shifted slices of a padded array. Its section says how.

What the three pairs of kernels tile, and nothing else (other sizes run the
XLA forms, by the rule; a kernel asked for by name there is refused by name):
the scan a chunk of 128, heads of 64 or 128 features, a group's heads and the
state whole 128-lane tiles, the group's float32 state within 1 MiB; the norm
groups of whole lane tiles and B S rows with a divisor that is a multiple of
16; the convolution at most 8,192 channels, they, their column offset and
every output whole lane tiles, a sequence with a divisor that is a multiple
of 16, and 2 to 17 taps (a tap reaches one strip of 16 rows back). All of
them one device, or a `shard_map` around them: under a context mesh of several
devices that nothing made manual the rule keeps the XLA form.
"""

from __future__ import annotations

import functools
import itertools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive
from jax.interpreters import mlir

from .layers import rmsnorm

F32 = jnp.float32
_IMPLEMENTATIONS = ("xla_chunked", "pallas")

# Chunks a step of the scan over the sequence computes at once (and a
# backward step computes again). Set by a sweep on the chip at the
# `train-nemotron3nano-8k` cell's shapes (PERF.md section 6, PR 48).
BLOCK_CHUNKS = 8


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array, *, offset: int = 0,
                  splits: Optional[Tuple[int, ...]] = None, implementation: Optional[str] = None):
    """silu(b[c] + sum_j w[c, j] x_{t - K + 1 + j}[offset + c]) on x (B, S, C
    or WIDER: the convolution reads the C features from `offset` of the array
    as it is, where a slice handed to the kernels would be a copy) with w (C,
    K), b (C,) -> (B, S, C) in x's dtype: a causal depthwise convolution with
    bias, zeros before the sequence. With `splits`, widths that add up to C,
    a tuple of arrays of those widths instead (the kernels write each as an
    output of its own and take each one's cotangent, where slices of one
    output are copies on both passes). Float32 inside, differentiable in x, w
    and b. `implementation` is `resolve_conv_impl`'s, for tests: with nothing
    asked, the kernels of the section "the convolution" on a TPU at the sizes
    they tile and, elsewhere, K shifted multiply-adds of a padded array that
    JAX differentiates."""
    channels, k = w.shape
    s = x.shape[1]
    widths = (channels,) if splits is None else tuple(splits)
    if sum(widths) != channels:
        raise ValueError(f"causal_conv1d: splits {widths} do not add up to the {channels} channels")
    impl = resolve_conv_impl(implementation, seq=s, channels=channels, taps=k, offset=offset, splits=widths)
    if impl == "pallas":
        out = _conv_kernels(x, w.astype(F32).T, b.astype(F32).reshape(1, channels),
                            (offset, _conv_rows(s), widths), jax.default_backend() != "tpu")
        return out[0] if splits is None else out
    x = x[..., offset:offset + channels]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(F32)
    out = b.astype(F32) + sum(w[:, j] * padded[:, j:j + s].astype(F32) for j in range(k))
    out = jax.nn.silu(out).astype(x.dtype)
    if splits is None:
        return out
    return tuple(out[..., end - width:end] for width, end in zip(widths, itertools.accumulate(widths)))


# The kernels' tiles: a chunk is the side of the (chunk, chunk) decays a grid
# step builds in VMEM, and a lane tile of the MXU holds 128 // P heads' features
_KERNEL_CHUNK = 128
_LANES = 128
# the float32 state (and its cotangent) a grid step may hold in VMEM scratch
_KERNEL_STATE_BYTES = 1024 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024


def _kernels_tile(chunk: int, heads: int, groups: int, head_dim: int, state: int) -> bool:
    """Whether `ssd_fwd` / `ssd_bwd` tile a scan of these sizes: the published
    chunk; heads of 64 or 128 features, so that a 128-lane tile holds whole
    heads; a group's heads and its state whole tiles of lanes; the group's
    float32 state within `_KERNEL_STATE_BYTES`."""
    per_group = heads // groups
    return (chunk == _KERNEL_CHUNK and head_dim in (64, 128)
            and (per_group * head_dim) % _LANES == 0 and state % _LANES == 0
            and per_group * head_dim * state * 4 <= _KERNEL_STATE_BYTES)


def _largest_tile(rows: int, most: int, strip: int) -> int:
    """The largest divisor of `rows` that is a multiple of `strip` and at most `most` (0: none)."""
    return next((n for n in range(min(most, rows) // strip * strip, 0, -strip) if rows % n == 0), 0)


def _resolve(implementation: Optional[str], forms: Tuple[str, str], tiles: bool, what: str,
             refusal: str) -> str:
    """The one rule of this module's kernels, the scan's and the gated norm's:
    of `forms` = (the XLA form, "pallas"), the kernels on a TPU at the sizes
    they tile and the XLA form elsewhere (off a TPU the kernels only run
    through the interpreter, for callers that ask). A kernel is never tried
    and swapped for the other form when it fails; one asked for by name at
    sizes it does not tile is refused.

    GSPMD cannot partition a Mosaic call: traced under a context mesh of more
    than one device with nothing around it that made the axes manual (a
    `shard_map`), the rule keeps the XLA form. No cell does this today: the
    one configuration with a state-space layer runs on one chip."""
    if implementation is not None and implementation not in forms:
        raise ValueError(f"unknown {what} implementation: {implementation!r}")
    if implementation == "pallas" and not tiles:
        raise ValueError(refusal)
    if implementation is not None:
        return implementation
    mesh = jax.sharding.get_abstract_mesh()
    spread = not mesh.empty and not mesh.manual_axes and mesh.size > 1
    return "pallas" if tiles and not spread and jax.default_backend() == "tpu" else forms[0]


def resolve_scan_impl(implementation: Optional[str] = None, *, chunk: int, heads: int, groups: int,
                      head_dim: int, state: int) -> str:
    """The implementation `ssd_scan` runs: "pallas" (the kernels `ssd_fwd` /
    `ssd_bwd`) or "xla_chunked" (the einsums of `_block`). A static rule on
    the backend, the shapes and the context mesh (`_resolve`, as
    `ops/attention.resolve_attention_impl` is): with nothing requested,
    "pallas" on a TPU for the sizes the kernels tile (`_kernels_tile`) and
    "xla_chunked" elsewhere."""
    return _resolve(implementation, _IMPLEMENTATIONS, _kernels_tile(chunk, heads, groups, head_dim, state),
                    "scan", f"ssd_scan: the kernels do not tile chunk {chunk}, {heads} heads of {head_dim} in "
                    f"{groups} groups, state {state}")


def scan_plan(seq: int, chunk: int, *, heads: int, groups: int, head_dim: int, state: int,
              implementation: Optional[str] = None) -> dict:
    """What `ssd_scan` resolves to for a sequence of `seq`, for callers that
    report it: the implementation's name, the chunk, the chunks a step over
    the sequence computes at once (a grid step of the kernels: one), the
    `pallas_call`s a differentiated scan makes and the float32 state a grid
    step holds in VMEM (none of either for the XLA form)."""
    impl = resolve_scan_impl(implementation, chunk=chunk, heads=heads, groups=groups,
                             head_dim=head_dim, state=state)
    kernels = impl == "pallas"
    return {"ssm_scan_impl": impl, "ssm_chunk": chunk,
            "ssm_scan_block_chunks": 1 if kernels else _block_chunks(seq // chunk),
            "ssm_scan_kernels": 2 if kernels else 0,
            "ssm_scan_state_bytes": heads // groups * head_dim * state * 4 if kernels else 0}


def _block_chunks(chunks: int) -> int:
    """The largest divisor of `chunks` that is at most BLOCK_CHUNKS."""
    return next(n for n in range(min(BLOCK_CHUNKS, chunks), 0, -1) if chunks % n == 0)


def _block(state, x, dt, da, b, c):
    """`BLOCK_CHUNKS` chunks from the state that enters them. state (B, H, P,
    N) float32; x (B, K, L, H, P); dt, da = dt A (B, K, L, H) float32; b, c (B,
    K, L, G, N) -> (the state that leaves them, y (B, K, L, H, P) float32)."""
    dtype = x.dtype
    bsz, k, l, h, p = x.shape
    g = b.shape[3]
    cum = jnp.cumsum(da, axis=2)                               # (B, K, L, H), <= 0 and falling
    last = cum[:, :, -1]                                       # (B, K, H)
    # inside a chunk: exp(c_l - c_s) (C_l . B_s) dt_s under the causal mask, heads before (l, s)
    scores = jnp.einsum("bklgn,bksgn->bkgls", c, b, preferred_element_type=F32)
    causal = jnp.tril(jnp.ones((l, l), bool))
    by_head = jnp.moveaxis(cum, 3, 2)                          # (B, K, H, L)
    decay = jnp.exp(jnp.where(causal, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    weights = decay * jnp.moveaxis(dt, 3, 2)[..., None, :]     # (B, K, H, l, s)
    weights = weights.reshape(bsz, k, g, h // g, l, l) * scores[:, :, :, None]
    y = jnp.einsum("bkhls,bkshp->bklhp", weights.reshape(bsz, k, h, l, l).astype(dtype), x,
                   preferred_element_type=F32)
    # a chunk's own state: sum_s exp(c_last - c_s) dt_s x_s (x) B_s
    into_state = (jnp.exp(last[:, :, None] - cum) * dt)[..., None] * x.astype(F32)
    into_state = into_state.astype(dtype).reshape(bsz, k, l, g, h // g, p)
    chunk_states = jnp.einsum("bklgrp,bklgn->bkgrpn", into_state, b,
                              preferred_element_type=F32).reshape(bsz, k, h, p, -1)
    # across the block's chunks, in float32: entering[j] = sum_{i < j} exp(sum of
    # the chunks' whole log-decays between them) S_i + exp(all before j) state
    total = jnp.cumsum(last, axis=1)                           # (B, K, H)
    before = total - last                                      # the log-decay of the chunks before j
    strictly = jnp.tril(jnp.ones((k, k), bool), -1)
    between = jnp.exp(jnp.where(strictly[None, :, :, None],
                                before[:, :, None] - total[:, None, :], -jnp.inf))  # (B, j, i, H)
    entering = (jnp.einsum("bjih,bihpn->bjhpn", between, chunk_states,
                           precision=jax.lax.Precision.HIGHEST)
                + jnp.exp(before)[..., None, None] * state[:, None])
    leaving = jnp.exp(last[:, -1])[..., None, None] * entering[:, -1] + chunk_states[:, -1]
    # the read-out of the state that entered the chunk: exp(c_l) C_l . H
    read = jnp.einsum("bklgn,bkgrpn->bklgrp", c,
                      entering.astype(dtype).reshape(bsz, k, g, h // g, p, -1),
                      preferred_element_type=F32).reshape(bsz, k, l, h, p)
    return leaving, y + jnp.exp(cum)[..., None] * read


@jax.custom_vjp
def _walk(x, dt, da, b, c):
    """`_block` over the blocks in order, each handed the state the one
    before it left: arguments cut (blocks, B, K, L, ...) -> y (blocks, B, K,
    L, H, P) in x's dtype."""
    return _walk_fwd(x, dt, da, b, c)[0]


def _walk_fwd(x, dt, da, b, c):
    bsz, h, p, n = x.shape[1], x.shape[4], x.shape[5], b.shape[-1]

    def step(state, block):
        leaving, y = _block(state, *block)
        return leaving, (y.astype(x.dtype), state)

    _, (y, entering) = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), F32), (x, dt, da, b, c))
    # what a checkpoint around the caller may keep: with both, its backward
    # pass starts from here and does not walk the sequence a second time
    y = checkpoint_name(y, "ssm_scan_out")
    entering = checkpoint_name(entering, "ssm_chunk_states")
    return y, (x, dt, da, b, c, entering)


def _walk_bwd(kept, dy):
    *inputs, entering = kept

    def step(d_leaving, block):     # the block again from the state that entered it, then its transpose
        state, block_inputs, dy_block = block
        _, transpose = jax.vjp(_block, state, *block_inputs)
        d_entering, *d_inputs = transpose((d_leaving, dy_block.astype(F32)))
        return d_entering, tuple(d_inputs)

    _, grads = jax.lax.scan(step, jnp.zeros(entering.shape[1:], F32),
                            (entering, tuple(inputs), dy), reverse=True)
    return grads


_walk.defvjp(_walk_fwd, _walk_bwd)


# ------------------------------------------------------------------ kernels
#
# `ssd_fwd` and `ssd_bwd`: a grid over (sequence of the batch, group of heads,
# chunk), the chunks innermost and in order (the backward's reversed), the
# group's float32 state (its cotangent) in VMEM scratch from a sequence's
# first chunk to its last. x, y and their cotangents are (B, S, H P) with a
# group's heads side by side on the lanes, B and C (B, S, G N): blocks of the
# arrays as they are, no copy into blocks. A (chunk, chunk) matrix has the
# read position l on the sublanes and the written one s on the lanes, so dt
# and the cumulative log-decay come a head a row (s on the lanes: (B, S, H)
# float32 transposed outside, where a head a column would be padded sixteen
# times in HBM) and the kernels transpose a step's (heads, chunk) rows for
# the heads' columns (l on the sublanes). What is shared by a group is
# computed once a step with its heads stacked (C B^T; the read-out C H^T and
# the state's update Xs^T B at the heads' whole width); a head's own (chunk,
# chunk) weights meet x a 128-lane tile at a time, 128 // P heads side by
# side, each keeping its own lanes of the product.

_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


def _chunk_masks(chunk: int, head_dim: int):
    """(l >= s on a (chunk, chunk) matrix, which of a lane tile's heads a lane is of)."""
    causal = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    return causal, jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1) // head_dim


def _as_columns(*rows):
    """(heads, chunk) arrays, a head a row -> (chunk, heads) each, a head a
    column: stacked on the sublanes, padded to a square and transposed ONCE
    (a (heads, chunk) transpose of its own costs Mosaic several times a
    square one's: PERF.md section 6, PR 49)."""
    heads, chunk = rows[0].shape
    stacked = jnp.concatenate([*rows, jnp.zeros((chunk - len(rows) * heads, chunk), F32)], axis=0).T
    return [stacked[:, i * heads:(i + 1) * heads] for i in range(len(rows))]


def _sum_over_lanes(matrix):
    """(chunk, 128) -> its sum over the lanes as a ROW (1, chunk): transposed
    and summed over the sublanes, which costs less than a lane reduction of
    every sublane and leaves the sum as dt and the log-decay are stored."""
    return jnp.sum(matrix.T, axis=0, keepdims=True)


def _position_factors(dt_rows, cum_rows):
    """Of a step's heads at once, a head a row: exp(c_l), by which a position
    reads the state that entered the chunk, and exp(c_last - c_s) dt_s, by
    which one writes into the state that leaves it, without dt and with it."""
    to_last = jnp.exp(cum_rows[:, -1:] - cum_rows)
    return jnp.exp(cum_rows), to_last, to_last * dt_rows


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, y_ref, *rest, head_dim: int):
    """A chunk of a group's heads from the state in `state_scr`, which it
    leaves updated: y, and where `rest` holds a block for it the state that
    entered the chunk, narrowed as the read-out takes it."""
    *states_ref, state_scr = rest
    dtype = x_ref.dtype
    chunk, width = x_ref.shape[1:]
    per_tile = _LANES // head_dim

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_scr[...] = jnp.zeros_like(state_scr)

    bm, cm = b_ref[0], c_ref[0]
    dt_rows, cum_rows = dt_ref[0, 0], cum_ref[0, 0]                 # (heads, chunk): s on the lanes
    out_rows, _, into_rows = _position_factors(dt_rows, cum_rows)
    cum_cols, out_cols, into_cols = _as_columns(cum_rows, out_rows, into_rows)  # (chunk, heads): l on the sublanes
    causal, lane_head = _chunk_masks(chunk, head_dim)
    scores = _dot(cm, bm, _NT)                                       # C_l . B_s
    entering = state_scr[...].astype(dtype)
    if states_ref:
        states_ref[0][0, 0] = entering
    read = _dot(cm, entering)                                        # C_l . H, every head
    scaled, whole = [], []
    for tile in range(width // _LANES):
        lanes = slice(tile * _LANES, (tile + 1) * _LANES)
        xt = x_ref[0, :, lanes]
        xf = xt.astype(F32)
        inside = out_decay = into_state = jnp.zeros((chunk, _LANES), F32)
        for j in range(per_tile):
            h = tile * per_tile + j
            decay = jnp.exp(jnp.where(causal, cum_cols[:, h:h + 1] - cum_rows[h:h + 1], -jnp.inf))
            weights = (decay * dt_rows[h:h + 1] * scores).astype(dtype)
            mine = lane_head == j
            inside = jnp.where(mine, _dot(weights, xt), inside)
            out_decay = jnp.where(mine, out_cols[:, h:h + 1], out_decay)
            into_state = jnp.where(mine, into_cols[:, h:h + 1], into_state)
        y_ref[0, :, lanes] = (inside + out_decay * read[:, lanes] + d_ref[:, lanes] * xf).astype(dtype)
        scaled.append((into_state * xf).astype(dtype))
        whole.append(out_decay[chunk - 1:])                         # exp(the chunk's whole log-decay)
    own = _dot(bm, jnp.concatenate(scaled, axis=1), _TN)            # the chunk's state, (N, width)
    state_scr[...] = jnp.concatenate(whole, axis=1) * state_scr[...] + own


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, states_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dd_ref, dstate_scr, *, head_dim: int):
    """The transpose of `_fwd_kernel`'s chunk, the chunks in reverse: the
    scores and decays again in VMEM, the cotangent of the state that leaves
    the chunk in `dstate_scr`, replaced by that of the state that entered it.
    dB and dC are summed over the group's heads; d's is summed over the
    sequence's chunks in its block. What reaches dt and the log-decay as the
    written position s is a sum over a matrix's sublanes, a head a row as
    they are stored; what reaches them as the read position l is a sum over
    its lanes (`_sum_over_lanes`)."""
    dtype = x_ref.dtype
    chunk, width = x_ref.shape[1:]
    per_tile = _LANES // head_dim
    heads = width // head_dim

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bm, cm = b_ref[0], c_ref[0]
    dt_rows, cum_rows = dt_ref[0, 0], cum_ref[0, 0]
    out_rows, to_last_rows, into_rows = _position_factors(dt_rows, cum_rows)
    cum_cols, out_cols, into_cols = _as_columns(cum_rows, out_rows, into_rows)
    causal, lane_head = _chunk_masks(chunk, head_dim)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0)
    scores = _dot(cm, bm, _NT)
    entering = states_ref[0, 0]
    d_leaving = dstate_scr[...]
    d_leaving_n = d_leaving.astype(dtype)
    read = _dot(cm, entering)                                        # (chunk, width)
    d_scaled = _dot(bm, d_leaving_n)                                 # d of (into_state x), (chunk, width)
    d_scores = jnp.zeros((chunk, chunk), F32)
    held = jnp.zeros((heads, 1), F32)
    scaled, d_reads, whole, down, as_read, d_into = [], [], [], [], [], []
    for tile in range(width // _LANES):
        lanes = slice(tile * _LANES, (tile + 1) * _LANES)
        xt, dyt = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        xf, dyf = xt.astype(F32), dyt.astype(F32)
        dx = d_ref[:, lanes] * dyf
        out_decay = into_state = jnp.zeros((chunk, _LANES), F32)
        through = []
        for j in range(per_tile):
            h = tile * per_tile + j
            dtr = dt_rows[h:h + 1]
            decay = jnp.exp(jnp.where(causal, cum_cols[:, h:h + 1] - cum_rows[h:h + 1], -jnp.inf))
            scored = decay * scores
            weights = (scored * dtr).astype(dtype)
            mine = lane_head == j
            d_weights = _dot(jnp.where(mine, dyt, jnp.zeros_like(dyt)), xt, _NT)
            dx = jnp.where(mine, dx + _dot(weights, dyt, _TN), dx)
            d_scores = d_scores + d_weights * (decay * dtr)
            before_dt = d_weights * scored                          # d of (decay scores dt) / d dt
            down.append(jnp.sum(before_dt, axis=0, keepdims=True))   # over l: what reached s
            out_decay = jnp.where(mine, out_cols[:, h:h + 1], out_decay)
            into_state = jnp.where(mine, into_cols[:, h:h + 1], into_state)
            through.append((mine, h, before_dt * dtr))
        d_read = out_decay * dyf                                     # d of C_l . H
        d_xs = d_scaled[:, lanes]
        from_state = d_xs * xf                                       # summed over a head's lanes: d of into_state
        # what reaches c_l: through the decays (a sum over s), the read-out and, as -c_s, the state's update
        to_cum = d_read * read[:, lanes] - from_state * into_state
        # d of exp(the whole log-decay) H, a lane: the sum over the state's N of dH' H
        kept = (jnp.sum(d_leaving[:, lanes] * entering[:, lanes].astype(F32), axis=0, keepdims=True)
                * out_decay[chunk - 1:])
        for mine, h, through_decay in through:
            as_read.append(_sum_over_lanes(through_decay + jnp.where(mine, to_cum, 0.0)))
            d_into.append(_sum_over_lanes(jnp.where(mine, from_state, 0.0)))
            held = jnp.where(head_row == h, jnp.sum(jnp.where(mine[:1], kept, 0.0), axis=1, keepdims=True), held)
        dx_ref[0, :, lanes] = (dx + d_xs * into_state).astype(dtype)
        dd_ref[0, :, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        scaled.append((into_state * xf).astype(dtype))
        d_reads.append(d_read.astype(dtype))
        whole.append(out_decay[chunk - 1:])
    down, as_read, d_into = (jnp.concatenate(rows, axis=0) for rows in (down, as_read, d_into))  # (heads, chunk)
    ddt_ref[0, 0] = down + d_into * to_last_rows
    # the chunk's whole log-decay is its last position's: d of exp(c_last - c_s) and of exp(c_last) H
    d_last = jnp.sum(d_into * into_rows, axis=1, keepdims=True) + held
    is_last = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk), 1) == chunk - 1
    dcum_ref[0, 0] = as_read - down * dt_rows + jnp.where(is_last, d_last, 0.0)
    d_scores_n, d_read = d_scores.astype(dtype), jnp.concatenate(d_reads, axis=1)
    dc_ref[0] = (_dot(d_scores_n, bm) + _dot(d_read, entering, _NT)).astype(dtype)
    db_ref[0] = (_dot(d_scores_n, cm, _TN)
                 + _dot(jnp.concatenate(scaled, axis=1), d_leaving_n, _NT)).astype(dtype)
    dstate_scr[...] = jnp.concatenate(whole, axis=1) * d_leaving + _dot(cm, d_read, _TN)


def _head_rows(t, groups: int):
    """(B, S, H) -> a head a row, a group's heads together: (B, G, H / G, S)."""
    bsz, s, h = t.shape
    return jnp.transpose(t.reshape(bsz, s, groups, h // groups), (0, 2, 3, 1))


def _grid(x, sizes, chunk_of):
    """(the grid over (sequence, group of heads, chunk), and the blocks of a
    step (b, g, k) whose chunk is `chunk_of(k)`: x's and y's; B's and C's; a
    head a row; d's; the entering states'; what is summed over a sequence's
    chunks), for x (B, S, H P). A step holds a whole group's heads, so that C
    B^T is computed once a group: fewer heads a step lost on the chip (PERF.md
    section 6, PR 49)."""
    chunk, groups, head_dim, state = sizes
    bsz, s, inner = x.shape
    width = inner // groups
    return (bsz, groups, s // chunk), (
        pl.BlockSpec((1, chunk, width), lambda b, g, k: (b, chunk_of(k), g)),
        pl.BlockSpec((1, chunk, state), lambda b, g, k: (b, chunk_of(k), g)),
        pl.BlockSpec((1, 1, width // head_dim, chunk), lambda b, g, k: (b, g, 0, chunk_of(k))),
        pl.BlockSpec((1, width), lambda b, g, k: (0, g)),
        pl.BlockSpec((1, 1, state, width), lambda b, g, k: (b, chunk_of(k), 0, g)),
        pl.BlockSpec((1, 1, width), lambda b, g, k: (b, 0, g)))


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _fwd_call(x, dt, cum, b, c, d, sizes, keep_states: bool, interpret: bool):
    """`ssd_fwd` on x (B, S, H P), dt and cum (B, S, H) float32, b and c (B,
    S, G N), d (1, H P) float32 -> (y, the state that entered each chunk (B,
    chunks, N, H P) in x's dtype, or None)."""
    _, _, head_dim, state = sizes
    grid, (wide, narrow, rows, skip, states, _) = _grid(x, sizes, lambda k: k)
    bsz, groups, chunks = grid
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, head_dim=head_dim),
        grid=grid,
        in_specs=[wide, narrow, narrow, rows, rows, skip],
        out_specs=[wide, states] if keep_states else [wide],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)]
        + [jax.ShapeDtypeStruct((bsz, chunks, state, x.shape[2]), x.dtype)] * keep_states,
        scratch_shapes=[pltpu.VMEM(states.block_shape[2:], F32)],
        compiler_params=_params(), interpret=interpret, name="ssd_fwd",
    )(x, b, c, _head_rows(dt, groups), _head_rows(cum, groups), d)
    return (out[0], out[1]) if keep_states else (out[0], None)


def _bwd_call(x, dt, cum, b, c, d, states, dy, sizes, interpret: bool):
    """`ssd_bwd`: the cotangents of `_fwd_call`'s six arguments from y's."""
    chunk, _, head_dim, _ = sizes
    chunks = x.shape[1] // chunk
    grid, (wide, narrow, rows, skip, kept, summed) = _grid(x, sizes, lambda k: chunks - 1 - k)
    bsz, groups, _ = grid
    by_head = jax.ShapeDtypeStruct((bsz, groups, dt.shape[2] // groups, dt.shape[1]), F32)
    dx, db, dc, ddt, dcum, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, head_dim=head_dim),
        grid=grid,
        in_specs=[wide, narrow, narrow, rows, rows, skip, kept, wide],
        out_specs=[wide, narrow, narrow, rows, rows, summed],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype), by_head, by_head,
                   jax.ShapeDtypeStruct((bsz, 1, x.shape[2]), F32)],
        scratch_shapes=[pltpu.VMEM(kept.block_shape[2:], F32)],
        compiler_params=_params(), interpret=interpret, name="ssd_bwd",
    )(x, b, c, _head_rows(dt, groups), _head_rows(cum, groups), d, states, dy)

    def of_tokens(t):                   # (B, G, H / G, S) -> (B, S, H)
        return jnp.transpose(t, (0, 3, 1, 2)).reshape(dt.shape)

    return dx, of_tokens(ddt), of_tokens(dcum), db, dc, jnp.sum(dd, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_kernels(x, dt, cum, b, c, d, sizes, interpret):
    return _fwd_call(x, dt, cum, b, c, d, sizes, False, interpret)[0]


def _scan_kernels_fwd(x, dt, cum, b, c, d, sizes, interpret):
    y, states = _fwd_call(x, dt, cum, b, c, d, sizes, True, interpret)
    # what a checkpoint around the caller may keep: with both, its backward
    # pass starts from here and does not run `ssd_fwd` a second time
    y = checkpoint_name(y, "ssm_scan_out")
    states = checkpoint_name(states, "ssm_chunk_states")
    return y, (x, dt, cum, b, c, d, states)


def _scan_kernels_bwd(sizes, interpret, kept, dy):
    return _bwd_call(*kept, dy, sizes, interpret)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array, c: jax.Array,
             d: jax.Array, *, chunk: int = 128, implementation: Optional[str] = None) -> jax.Array:
    """The selective scan of the module's docstring in its chunked form. x
    (B, S, H, P); dt (B, S, H), the positive step (float32: after its bias
    and softplus); a_log, d (H,); b, c (B, S, G, N) with H a multiple of G
    -> y (B, S, H, P) in x's dtype. Differentiable in every argument; what
    the backward pass keeps is the module's docstring's. `implementation`
    is `resolve_scan_impl`'s, for tests: with nothing asked, the kernels on a
    TPU at the sizes they tile and the XLA form elsewhere."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    if s % chunk:
        raise ValueError(f"ssd_scan: a sequence of {s} is no multiple of the chunk {chunk}")
    if h % g:
        raise ValueError(f"ssd_scan: {h} heads are no multiple of the {g} groups")
    impl = resolve_scan_impl(implementation, chunk=chunk, heads=h, groups=g, head_dim=p, state=n)
    dt = dt.astype(F32)
    da = dt * -jnp.exp(a_log.astype(F32))
    if impl == "pallas":
        cum = jnp.cumsum(da.reshape(bsz, s // chunk, chunk, h), axis=2).reshape(bsz, s, h)
        y = _scan_kernels(x.reshape(bsz, s, h * p), dt, cum, b.reshape(bsz, s, g * n),
                          c.reshape(bsz, s, g * n), jnp.repeat(d.astype(F32), p)[None],
                          (chunk, g, p, n), jax.default_backend() != "tpu")
        return y.reshape(bsz, s, h, p)
    per_block = _block_chunks(s // chunk)

    def cut(t):     # (B, S, ...) -> (blocks, B, per_block, chunk, ...)
        return jnp.moveaxis(t.reshape(bsz, -1, per_block, chunk, *t.shape[2:]), 1, 0)

    y = _walk(*(cut(t) for t in (x, dt, da, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, s, h, p)
    return (y.astype(F32) + d.astype(F32)[:, None] * x.astype(F32)).astype(x.dtype)


def log_decay_chunk_min(dt: jax.Array, a_log: jax.Array, chunk: int) -> jax.Array:
    """The most negative cumulative log-decay inside a chunk, over every
    chunk and head: sum over a chunk of dt A. What an implementation that
    split exp(c_l - c_s) into two factors would have to hold in its dtype."""
    bsz, s, h = dt.shape
    da = dt.astype(F32) * -jnp.exp(a_log.astype(F32))
    return jnp.min(jnp.sum(da.reshape(bsz, s // chunk, chunk, h), axis=2))


# ---------------------------------------------------------- the convolution
#
# `ssm_conv_fwd` and `ssm_conv_bwd`: the causal depthwise convolution, its
# bias and silu on the (B, S, F) array as it is, read at a column offset. A
# grid step is a tile of rows of ONE sequence with every channel, x in blocks
# of whole 128-lane tiles that the offset is a multiple of (the same array
# under one block map a block); the rows before the tile (and, backward, after
# it) come as further blocks of the same array one strip of `_CONV_STRIP` rows
# back (on): zeros at a sequence's first (last) tile, never the rows beside
# them in memory. x, B and C leave as an output each (`splits`). A tap's shift
# is a roll over the sublanes of a strip's float32 registers with the first
# rows taken from the strip before, which every strip leaves in VMEM scratch
# for the next: nothing is padded in HBM. The backward keeps nothing of its
# own: it builds the pre-activation again from x, d pre = dy silu'(pre), dx[t]
# = sum_j w[:, j] d pre[t + K - 1 - j], and sums dw and db over the grid in
# resident blocks.

_CONV_IMPLEMENTATIONS = ("xla", "pallas")
# The most rows of a sequence a grid step takes, the rows computed at once (a
# bfloat16 tile's 16 sublanes; a tap reaches at most one strip back) and their
# float32 sublanes a register, the widest block of columns, the most channels
# (a step takes them all) and the most lanes computed at once. On the chip at
# the `train-nemotron3nano-8k` cell's shapes, forward / backward ms a call
# (PERF.md section 6, PR 52; the bytes alone 0.68 / 0.95, the XLA form 1.28 /
# 5.99): 256 rows and 512 lanes 0.71 / 1.24 (0.71 / 1.33 with the ONE loop
# body a kernel that ships, below); 512 rows 0.70 / 1.23; 128 rows
# 0.83 / 1.35; column tiles of 2,048 a step 0.76 / 1.27; 256 and 128 lanes
# within 0.1 ms at 1.6 and 2.7 times the bodies; 1,024 lanes 0.73 / 1.38. The
# lane chunks are a static loop INSIDE the loop over strips: outside it, a
# chunk's strips in a loop of their own that carried the strip before in
# registers, the same work took 0.90 / 1.71 (1.85 / 2.60 at 128 lanes): a
# strip's chain of rolls and the sigmoid is long, Mosaic does not overlap a
# loop's iterations, and only other chunks' work fills it. What a strip hands
# the next (its rows, its d pre) goes through VMEM scratch, so the loop has
# ONE body: the bodies' equations are what set-up pays (`ssm_conv_p` below).
_CONV_ROWS = 256
_CONV_STRIP = 16
_CONV_HALF = 8
_CONV_COLUMNS = 2048
_CONV_CHANNELS = 8192
_CONV_LANES = 512


def _conv_rows(seq: int) -> int:
    """The rows of a sequence a grid step of the convolution's kernels takes:
    the largest divisor of `seq` that is a multiple of `_CONV_STRIP` and at
    most `_CONV_ROWS` (0: the sequence does not tile)."""
    return _largest_tile(seq, _CONV_ROWS, _CONV_STRIP)


def _conv_columns(most: int, *widths: int) -> int:
    """The most columns, whole lane tiles and at most `most`, that every one
    of `widths` (multiples of a lane tile, or 0) is a multiple of."""
    return next(n for n in range(most // _LANES * _LANES, 0, -_LANES) if all(width % n == 0 for width in widths))


def resolve_conv_impl(implementation: Optional[str] = None, *, seq: int, channels: int, taps: int,
                      offset: int = 0, splits: Optional[Tuple[int, ...]] = None) -> str:
    """The implementation `causal_conv1d` runs on sequences of `seq`, by the
    scan's rule (`_resolve`): "pallas" (the kernels `ssm_conv_fwd` /
    `ssm_conv_bwd`) on a TPU where the channels, at most `_CONV_CHANNELS`, and
    every one of the `splits` are whole numbers of 128-lane tiles from a
    lane-aligned offset, the sequence tiles (`_conv_rows`) and a tap reaches
    at most one strip back, "xla" (shifted slices of a padded array)
    elsewhere."""
    tiles = (all(width % _LANES == 0 for width in (channels, offset, *(splits or ())))
             and 0 < channels <= _CONV_CHANNELS and _conv_rows(seq) > 0 and 1 < taps <= _CONV_STRIP + 1)
    cut = f" in {tuple(splits)}" if splits and len(splits) > 1 else ""
    return _resolve(implementation, _CONV_IMPLEMENTATIONS, tiles, "convolution",
                    f"causal_conv1d: the kernels do not tile sequences of {seq} with {channels} channels{cut} "
                    f"at column {offset} under {taps} taps")


def conv_plan(seq: int, channels: int, taps: int, offset: int = 0, splits: Optional[Tuple[int, ...]] = None) -> dict:
    """What `causal_conv1d` resolves to, for callers that report it: the
    implementation's name and the rows of a sequence a grid step of the
    kernels takes (0 for the XLA form)."""
    impl = resolve_conv_impl(seq=seq, channels=channels, taps=taps, offset=offset, splits=splits)
    return {"ssm_conv_impl": impl, "ssm_conv_rows": _conv_rows(seq) if impl == "pallas" else 0}


def _later(before, piece, d: int, row):
    """An (8, lanes) piece's rows d later: [t] = piece[t - d], the first d the last of the piece before."""
    return pltpu.roll(jnp.where(row >= _CONV_HALF - d, before, piece), d, 0)


def _sooner(piece, after, d: int, row):
    """An (8, lanes) piece's rows d sooner: [t] = piece[t + d], the last d the first of the piece after."""
    return pltpu.roll(jnp.where(row < d, after, piece), _CONV_HALF - d, 0)


def _halves(strip):
    """A strip's float32 values as its two (8, lanes) pieces, a register a 128 lanes each."""
    return strip[:_CONV_HALF], strip[_CONV_HALF:]


def _lane_chunks(columns: int, splits: Tuple[int, ...], most: int):
    """Chunks of at most `most` lanes, each inside one block of `columns` and
    one of the `splits`: (the block, its lanes, the same lanes of all
    channels, the split, its lanes)."""
    lanes = _conv_columns(most, columns, *splits)
    ends = list(itertools.accumulate(splits))
    chunks = []
    for at in range(0, ends[-1], lanes):
        part = next(k for k, end in enumerate(ends) if at < end)
        inside = at - (ends[part] - splits[part])
        chunks.append((at // columns, slice(at % columns, at % columns + lanes), slice(at, at + lanes),
                       part, slice(inside, inside + lanes)))
    return chunks


def _strip_at(i):
    return pl.ds(pl.multiple_of(i * _CONV_STRIP, _CONV_STRIP), _CONV_STRIP)


def _taps_row(w_ref, dst):
    """The taps' weights on a chunk's lanes by d, the rows a tap reaches back: w[taps - 1 - d]."""
    taps = w_ref.shape[0]
    return [w_ref[taps - 1 - d:taps - d, dst] for d in range(taps)]


def _pre_activation(before, strip, weights, bias):
    """(bias + sum_d weights[d] (the strip d rows later), those shifted strips
    by d) for a strip (16, lanes) and the strip `before` it, of which the last
    rows are read."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_CONV_HALF, strip.shape[1]), 0)
    strip = strip.astype(F32)
    (_, last), (low, high) = _halves(before.astype(F32)), _halves(strip)
    shifted = [strip] + [jnp.concatenate([_later(last, low, d, row), _later(low, high, d, row)], axis=0)
                         for d in range(1, len(weights))]
    return bias + sum(weight * of for weight, of in zip(weights, shifted)), shifted


def _start_tile(before_refs, before_scr, columns: int):
    """The strip before a tile's first into `before_scr`, where every strip
    leaves itself for the next: zeros at a sequence's first tile."""
    first = pl.program_id(1) == 0
    for v, before_ref in enumerate(before_refs):
        before = before_ref[0].astype(F32)
        before_scr[:, v * columns:(v + 1) * columns] = jnp.where(first, 0.0, before).astype(before_scr.dtype)


def _conv_fwd_kernel(*refs, blocks: int, splits: Tuple[int, ...]):
    x_refs, before_refs = refs[:blocks], refs[blocks:2 * blocks]
    w_ref, b_ref, *out_refs, before_scr = refs[2 * blocks:]
    rows, columns = x_refs[0].shape[1:]
    _start_tile(before_refs, before_scr, columns)

    def step(i, carry):
        at = _strip_at(i)
        for v, src, dst, part, lanes in _lane_chunks(columns, splits, _CONV_LANES):
            strip = x_refs[v][0, at, src]
            pre, _ = _pre_activation(before_scr[:, dst], strip, _taps_row(w_ref, dst), b_ref[:, dst])
            before_scr[:, dst] = strip
            out_refs[part][0, at, lanes] = (pre * jax.nn.sigmoid(pre)).astype(out_refs[part].dtype)
        return carry

    jax.lax.fori_loop(0, rows // _CONV_STRIP, step, 0)


def _conv_bwd_kernel(*refs, blocks: int, splits: Tuple[int, ...]):
    """The transpose of `_conv_fwd_kernel`'s tile. A strip's d pre reaches the
    dx of the strip before it: d pre is left in `d_scr` for the next strip,
    which writes that dx (the first strip writes one over what `d_scr` held,
    and the second writes it again), and the last strip's dx waits for the d
    pre of the rows AFTER the tile (zeros at a sequence's last tile), which
    are not summed into dw and db: the next step's are. dw and db are summed
    over a tile's strips in `sums_scr`, 8 rows a tap, and over the grid in
    their resident blocks."""
    x_refs, before_refs, after_refs = refs[:blocks], refs[blocks:2 * blocks], refs[2 * blocks:3 * blocks]
    w_ref, b_ref, *rest = refs[3 * blocks:]
    dy_refs, dy_after_refs = rest[:len(splits)], rest[len(splits):2 * len(splits)]
    dx_ref, dw_ref, db_ref, before_scr, d_scr, sums_scr = rest[2 * len(splits):]
    taps, (rows, columns) = w_ref.shape[0], x_refs[0].shape[1:]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    _start_tile(before_refs, before_scr, columns)
    sums_scr[...] = jnp.zeros_like(sums_scr)

    def d_pre(before, strip, dy, weights, dst):
        pre, shifted = _pre_activation(before, strip, weights, b_ref[:, dst])
        gate = jax.nn.sigmoid(pre)
        return dy.astype(F32) * (gate * (1.0 + pre * (1.0 - gate))), shifted

    def write_dx(at, d_after, weights, dst):
        """dx of the strip at `at`, whose d pre `d_scr` holds, with the d pre of the strip after it."""
        row = jax.lax.broadcasted_iota(jnp.int32, (_CONV_HALF, d_after.shape[1]), 0)
        d_strip = d_scr[:, dst]
        (low, high), (after, _) = _halves(d_strip), _halves(d_after)
        sooner = [d_strip] + [jnp.concatenate([_sooner(low, high, d, row), _sooner(high, after, d, row)], axis=0)
                              for d in range(1, taps)]
        dx_ref[0, at, dst] = sum(weight * of for weight, of in zip(weights, sooner)).astype(dx_ref.dtype)

    def step(i, carry):
        at = _strip_at(i)
        for v, src, dst, part, lanes in _lane_chunks(columns, splits, _CONV_LANES):
            weights, strip = _taps_row(w_ref, dst), x_refs[v][0, at, src]
            d_strip, shifted = d_pre(before_scr[:, dst], strip, dy_refs[part][0, at, lanes], weights, dst)
            before_scr[:, dst] = strip
            write_dx(_strip_at(jnp.maximum(i - 1, 0)), d_strip, weights, dst)
            d_scr[:, dst] = d_strip
            for d, of in enumerate([*shifted, None]):       # the taps' sums, then the bias's
                low, high = _halves(d_strip if of is None else d_strip * of)
                sums_scr[d * _CONV_HALF:(d + 1) * _CONV_HALF, dst] += low + high
        return carry

    jax.lax.fori_loop(0, rows // _CONV_STRIP, step, 0)
    # once a tile, on lanes as wide as a block and a split allow: the last strip's dx
    last = pl.program_id(1) == pl.num_programs(1) - 1
    for v, src, dst, part, lanes in _lane_chunks(columns, splits, _CONV_COLUMNS):
        weights = _taps_row(w_ref, dst)
        d_after, _ = d_pre(before_scr[:, dst], after_refs[v][0, :, src], dy_after_refs[part][0, :, lanes], weights, dst)
        write_dx(pl.ds(rows - _CONV_STRIP, _CONV_STRIP), jnp.where(last, 0.0, d_after), weights, dst)
    for d in range(taps):
        dw_ref[taps - 1 - d:taps - d] += jnp.sum(sums_scr[d * _CONV_HALF:(d + 1) * _CONV_HALF], axis=0, keepdims=True)
    db_ref[...] += jnp.sum(sums_scr[taps * _CONV_HALF:], axis=0, keepdims=True)


def _conv_call(x, w, b, *dys, sizes, interpret: bool):
    """`ssm_conv_fwd` (no `dys`) -> the outputs, (B, S, a split) each, or
    `ssm_conv_bwd` -> [dx (B, S, C), dw (K, C) float32, db (1, C) float32]
    from the outputs' cotangents, on x (B, S, F) read at the columns
    `offset`..`offset` + C, w (K, C) and b (1, C) float32. The grid is
    (sequence, tile of rows), every channel a step: x comes as C / columns
    blocks of the one array, and dw's and db's blocks stay in VMEM over the
    grid."""
    offset, step_rows, splits = sizes
    bsz, s, _ = x.shape
    taps, channels = w.shape
    backward = bool(dys)
    columns = _conv_columns(_CONV_COLUMNS, channels, offset)
    blocks = channels // columns
    strips, last_strip = step_rows // _CONV_STRIP, s // _CONV_STRIP - 1
    tile = lambda v: (lambda b, i: (b, i, v))                                                   # noqa: E731
    before = lambda v: (lambda b, i: (b, jnp.maximum(i * strips - 1, 0), v))                    # noqa: E731
    after = lambda v: (lambda b, i: (b, jnp.minimum((i + 1) * strips, last_strip), v))          # noqa: E731

    def of_x(rows, index):          # the blocks of x's columns offset..offset + C
        return [pl.BlockSpec((1, rows, columns), index(offset // columns + v)) for v in range(blocks)]

    def of_parts(rows, index):      # an array a split, each all its columns
        return [pl.BlockSpec((1, rows, width), index(0)) for width in splits]

    whole = [pl.BlockSpec((taps, channels), lambda b, i: (0, 0)), pl.BlockSpec((1, channels), lambda b, i: (0, 0))]
    scratch = [pltpu.VMEM((_CONV_STRIP, channels), x.dtype)]
    if backward:
        scratch += [pltpu.VMEM((_CONV_STRIP, channels), F32), pltpu.VMEM(((taps + 1) * _CONV_HALF, channels), F32)]
    # every tile of a step twice (the pipeline's two buffers) and room for the strips' values
    vmem = 2 * (3 if backward else 2) * step_rows * channels * x.dtype.itemsize + 8 * 1024 * 1024
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel if backward else _conv_fwd_kernel, blocks=blocks, splits=splits),
        grid=(bsz, s // step_rows),
        in_specs=(of_x(step_rows, tile) + of_x(_CONV_STRIP, before)
                  + (of_x(_CONV_STRIP, after) + whole + of_parts(step_rows, tile) + of_parts(_CONV_STRIP, after)
                     if backward else whole)),
        out_specs=([pl.BlockSpec((1, step_rows, channels), tile(0))] + whole if backward
                   else of_parts(step_rows, tile)),
        out_shape=([jax.ShapeDtypeStruct((bsz, s, channels), x.dtype), jax.ShapeDtypeStruct(w.shape, F32),
                    jax.ShapeDtypeStruct(b.shape, F32)] if backward
                   else [jax.ShapeDtypeStruct((bsz, s, width), x.dtype) for width in splits]),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary" if backward else "parallel",) * 2,
                                             vmem_limit_bytes=vmem),
        interpret=interpret, name="ssm_conv_bwd" if backward else "ssm_conv_fwd",
    )(*([x] * (3 if backward else 2) * blocks), w, b, *(dys * 2))


def _conv_shapes(x, w, b, *dys, sizes, interpret):
    del interpret
    like = lambda shape, dtype: x.update(shape=shape, dtype=dtype, weak_type=False)     # noqa: E731
    if dys:
        return [like((*x.shape[:2], w.shape[1]), x.dtype), like(w.shape, F32), like(b.shape, F32)]
    return [like((*x.shape[:2], width), x.dtype) for width in sizes[2]]


# Every call site enters through ONE primitive whose lowering builds the
# kernel and is emitted out of line, as ops/moe_rows_sum's is: a program traces
# a kernel and lowers it to a Mosaic module once a signature and calls that one
# function from every layer, pass and recomputation. Traced in line, the
# bodies' ~2,000 equations were built again by every trace of the step (the
# plan's, the checkpoint's, the transpose's): +27 s of set-up on the chip
# (PERF.md section 6, PR 52).
ssm_conv_p = Primitive("ssm_conv")
ssm_conv_p.multiple_results = True
ssm_conv_p.def_abstract_eval(_conv_shapes)
ssm_conv_p.def_impl(lambda *args, **params: jax.jit(functools.partial(ssm_conv_p.bind, **params))(*args))
mlir.register_lowering(ssm_conv_p, mlir.lower_fun(_conv_call, multiple_results=True), inline=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_kernels(x, w, b, sizes, interpret):
    """x (B, S, F), w (K, C) and b (1, C) float32 -> silu of the convolution
    of x's columns offset..offset + C, cut into the splits: (B, S, a split)
    each, in x's dtype; `sizes` = (offset, the rows a step, the splits)."""
    return tuple(ssm_conv_p.bind(x, w, b, sizes=sizes, interpret=interpret))


def _conv_kernels_fwd(x, w, b, sizes, interpret):
    return _conv_kernels(x, w, b, sizes, interpret), (x, w, b)       # nothing kept but the arguments


def _conv_kernels_bwd(sizes, interpret, kept, dys):
    x, w, b = kept
    dx, dw, db = ssm_conv_p.bind(x, w, b, *dys, sizes=sizes, interpret=interpret)
    return jnp.pad(dx, ((0, 0), (0, 0), (sizes[0], x.shape[2] - sizes[0] - dx.shape[2]))), dw, db


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


# ----------------------------------------------------------- the gated norm
#
# `ssm_gate_norm_fwd` and `ssm_gate_norm_bwd`: y silu(z) through an RMS norm
# over each group's features, on the (B S, inner) arrays as they are. A grid
# step is a tile of rows with ALL groups; a group is a static slice of whole
# 128-lane tiles (512 lanes at the published sizes) and its sum of squares a
# lane reduction of that slice, so nothing moves: the view (.., groups, inner
# // groups) that the XLA form takes puts the groups on the sublanes, on the
# chip a shuffle of the float32 product, five a layer forward and backward
# (PERF.md section 6, PR 49 and PR 50). Float32 inside a kernel, the
# activations' dtype in HBM on both sides; the backward keeps nothing of its
# own and builds g and the inverse root again from y and z.

_NORM_IMPLEMENTATIONS = ("xla", "pallas")
# The most rows of (B S, inner) a grid step takes, and the rows of it computed
# at once: a bfloat16 tile's 16 sublanes, whose float32 values stay in vector
# registers; a step's rows are a multiple. The kernels move 3 and 5 arrays
# through VMEM and nothing else binds them: 128 / 256 / 512 rows a step and 8
# to 64 a strip read the same on the chip at the `train-nemotron3nano-8k`
# cell's shapes (PERF.md section 6, PR 50); these compile fastest.
_NORM_ROWS = 256
_NORM_STRIP = 16


def _norm_rows(rows: int) -> int:
    """The rows a grid step of the norm's kernels takes: the largest divisor
    of `rows` that is a multiple of `_NORM_STRIP` and at most `_NORM_ROWS`
    (0: the rows do not tile)."""
    return _largest_tile(rows, _NORM_ROWS, _NORM_STRIP)


def resolve_gate_norm_impl(implementation: Optional[str] = None, *, rows: int, inner: int, groups: int) -> str:
    """The implementation `gated_group_norm` runs on `rows` = B S rows, by the
    scan's rule (`_resolve`): "pallas" (the kernels `ssm_gate_norm_fwd` /
    `ssm_gate_norm_bwd`) on a TPU where a group's width is a whole number of
    128-lane tiles and the rows tile (`_norm_rows`), "xla" (ops/layers.rmsnorm
    on the view by groups) elsewhere."""
    tiles = inner % groups == 0 and (inner // groups) % _LANES == 0 and _norm_rows(rows) > 0
    return _resolve(implementation, _NORM_IMPLEMENTATIONS, tiles, "gated norm",
                    f"gated_group_norm: the kernels do not tile {rows} rows of {inner} features in {groups} groups")


def gate_norm_plan(rows: int, inner: int, groups: int) -> dict:
    """What `gated_group_norm` resolves to for B S = `rows`, for callers that
    report it: the implementation's name and the rows a grid step of the
    kernels takes (0 for the XLA form)."""
    impl = resolve_gate_norm_impl(rows=rows, inner=inner, groups=groups)
    return {"ssm_gate_norm_impl": impl, "ssm_gate_norm_rows": _norm_rows(rows) if impl == "pallas" else 0}


def _strips_of_groups(rows: int, inner: int, groups: int, body):
    """`body(rows, lanes)` for every strip of `_NORM_STRIP` rows of a tile and
    every group's lanes in it."""
    width = inner // groups

    def step(i, carry):
        at = pl.ds(pl.multiple_of(i * _NORM_STRIP, _NORM_STRIP), _NORM_STRIP)
        for group in range(groups):
            body(at, slice(group * width, (group + 1) * width))
        return carry

    jax.lax.fori_loop(0, rows // _NORM_STRIP, step, 0)


def _gated(y_ref, z_ref, at, lanes, eps: float):
    """Of a strip's rows and a group's lanes, in float32: (y, z, sigmoid(z), g
    = y silu(z), the inverse root of g's mean square + eps a row)."""
    y, z = y_ref[at, lanes].astype(F32), z_ref[at, lanes].astype(F32)
    gate = jax.nn.sigmoid(z)
    g = y * (z * gate)
    return y, z, gate, g, jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)


def _gate_norm_fwd_kernel(y_ref, z_ref, scale_ref, out_ref, *, groups: int, eps: float):
    def body(at, lanes):
        *_, g, inv = _gated(y_ref, z_ref, at, lanes, eps)
        out_ref[at, lanes] = (g * inv * scale_ref[:, lanes]).astype(out_ref.dtype)

    _strips_of_groups(*y_ref.shape, groups, body)


def _gate_norm_bwd_kernel(y_ref, z_ref, scale_ref, dout_ref, dy_ref, dz_ref, dscale_ref, *, groups: int,
                          eps: float):
    """The transpose of `_gate_norm_fwd_kernel`'s tile. With n = g inv and out
    = n scale: dn = dout scale, dg = inv (dn - n mean(dn n)) over the group's
    lanes, dy = dg silu(z), dz = dg y silu'(z); the scale's cotangent, the
    sum over rows of dout n, is accumulated over the grid in its block."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    def body(at, lanes):
        y, z, gate, g, inv = _gated(y_ref, z_ref, at, lanes, eps)
        dout = dout_ref[at, lanes].astype(F32)
        normed = g * inv
        dn = dout * scale_ref[:, lanes]
        dg = inv * (dn - normed * jnp.mean(dn * normed, axis=-1, keepdims=True))
        dy_ref[at, lanes] = (dg * (z * gate)).astype(dy_ref.dtype)
        dz_ref[at, lanes] = (dg * y * (gate * (1.0 + z * (1.0 - gate)))).astype(dz_ref.dtype)
        dscale_ref[:, lanes] += jnp.sum(dout * normed, axis=0, keepdims=True)

    _strips_of_groups(*y_ref.shape, groups, body)


def _norm_call(y, z, scale, dout, sizes, interpret: bool):
    """`ssm_gate_norm_fwd` (`dout` None) -> [out], or `ssm_gate_norm_bwd` ->
    [dy, dz, the scale's cotangent (1, inner) float32], over the row tiles of
    y, z and dout taken as (B S, inner): z may be wider, its first `inner`
    features are its block; `scale` (1, inner) float32 is whole every step
    and its cotangent's block stays in VMEM over the grid."""
    groups, eps, step_rows = sizes
    rows, inner = y.shape[0] * y.shape[1], y.shape[2]
    backward = dout is not None
    tile = pl.BlockSpec((step_rows, inner), lambda i: (i, 0))
    whole = pl.BlockSpec((1, inner), lambda i: (0, 0))
    like = jax.ShapeDtypeStruct((rows, inner), y.dtype)
    tiled = [t.reshape(rows, t.shape[2]) for t in ((y, z, dout) if backward else (y, z))]
    # every tile of a step twice (the pipeline's two buffers) and room for a strip's values
    vmem = 2 * (5 if backward else 3) * step_rows * inner * y.dtype.itemsize + 8 * 1024 * 1024
    out = pl.pallas_call(
        functools.partial(_gate_norm_bwd_kernel if backward else _gate_norm_fwd_kernel, groups=groups, eps=eps),
        grid=(rows // step_rows,),
        in_specs=[tile, tile, whole, tile] if backward else [tile, tile, whole],
        out_specs=[tile, tile, whole] if backward else [tile],
        out_shape=[like, like, jax.ShapeDtypeStruct((1, inner), F32)] if backward else [like],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary" if backward else "parallel",),
                                             vmem_limit_bytes=vmem),
        interpret=interpret, name="ssm_gate_norm_bwd" if backward else "ssm_gate_norm_fwd",
    )(*tiled[:2], scale, *tiled[2:])
    return [t.reshape(y.shape) for t in out[:2]] + list(out[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm_kernels(y, z, scale, sizes, interpret):
    """y (B, S, inner), z (B, S, inner or more: the gate is its first inner
    features), scale (1, inner) float32 -> the gated, normed (B, S, inner) in
    y's dtype; `sizes` = (groups, eps, the rows a step)."""
    return _norm_call(y, z, scale, None, sizes, interpret)[0]


def _norm_kernels_fwd(y, z, scale, sizes, interpret):
    return _norm_call(y, z, scale, None, sizes, interpret)[0], (y, z, scale)        # nothing kept but the arguments


def _norm_kernels_bwd(sizes, interpret, kept, dout):
    y, z, scale = kept
    dy, dz, dscale = _norm_call(y, z, scale, dout, sizes, interpret)
    return dy, jnp.pad(dz, ((0, 0), (0, 0), (0, z.shape[2] - y.shape[2]))), dscale


_norm_kernels.defvjp(_norm_kernels_fwd, _norm_kernels_bwd)


def gated_group_norm(y: jax.Array, z: jax.Array, scale: jax.Array, *, groups: int, eps: float,
                     implementation: Optional[str] = None) -> jax.Array:
    """The Mamba-2 mixer's gated norm: g = y silu(z) in float32 (the gate
    first), an RMS norm of g over each of the `groups` contiguous segments of
    inner // groups features (eps inside the root), times scale, in y's
    dtype. y (B, S, inner), scale (inner,) -> (B, S, inner); z (B, S, inner),
    or WIDER with the gate its first inner features: the kernels read those
    columns of the array as it is, where a slice handed to them would be a
    copy. Differentiable in y, z and scale. `implementation` is
    `resolve_gate_norm_impl`'s, for tests: with nothing asked, the kernels on
    a TPU at the sizes they tile and the XLA form elsewhere."""
    bsz, s, inner = y.shape
    impl = resolve_gate_norm_impl(implementation, rows=bsz * s, inner=inner, groups=groups)
    if impl == "pallas":
        return _norm_kernels(y, z, scale.astype(F32).reshape(1, inner), (groups, eps, _norm_rows(bsz * s)),
                             jax.default_backend() != "tpu")
    gated = y.astype(F32) * jax.nn.silu(z[..., :inner].astype(F32))
    normed = rmsnorm(gated.reshape(bsz, s, groups, inner // groups), scale.reshape(groups, inner // groups), eps=eps)
    return normed.astype(y.dtype).reshape(bsz, s, inner)


def ssd_reference(x, dt, a_log, b, c, d) -> Tuple[jax.Array, jax.Array]:
    """The recurrence one position at a time in float32 (the tests' oracle;
    no caller on the main path) -> (y (B, S, H, P), the last state (B, H, P, N))."""
    bsz, _, h, p = x.shape
    g, n = b.shape[2:]
    a = -jnp.exp(a_log.astype(F32))
    expand = lambda t: jnp.repeat(t.astype(F32), h // g, axis=2)      # noqa: E731  groups -> heads

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs                  # (B, H, P), (B, H), (B, H, N), (B, H, N)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=jax.lax.Precision.HIGHEST)

    time_first = lambda t: jnp.moveaxis(t, 1, 0)                       # noqa: E731
    state, y = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), F32),
                            (time_first(x.astype(F32)), time_first(dt.astype(F32)),
                             time_first(expand(b)), time_first(expand(c))))
    return jnp.moveaxis(y, 0, 1) + d.astype(F32)[:, None] * x.astype(F32), state
