"""Kimi Delta Attention's core (arXiv:2510.26692): the delta rule with a decay
a channel, in its chunked form, from a mixer's arguments as the mixer has them
and through the norm a head that follows it (`kda_rule`) or from the
recurrence's own (`kda_chunk`, the XLA form).

The recurrence, a head of D_k key and D_v value features with a state S of
D_k x D_v, position by position (`kda_reference`):

  alpha_t = exp(a_t),            a_t <= 0 a CHANNEL of the keys (D_k numbers)
  S_t     = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,   S_0 = 0
  o_t     = S_t^T q_t

which is S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T with the pseudo-value
u_t = beta_t (v_t - S_{t-1}^T (alpha_t * k_t)). `kda_chunk` never holds a state
a position. The sequence is cut into chunks of `chunk` positions; with A_i the
cumulative log-decay inside a chunk (A_i = sum_{j <= i} a_j, a vector of D_k)
and S the state that ENTERS the chunk,

  the corrections   (I + tril(beta_i <k_i e^{A_i}, k_j e^{-A_j}>, -1)) U
                        = beta * (V - (K e^{A}) S)           (the WY / UT form)
  the read-out      o_i = S^T (q_i e^{A_i}) + sum_{j <= i} <q_i e^{A_i}, k_j e^{-A_j}> u_j
  the state leaving S'  = Diag(e^{A_last}) S + sum_j (k_j e^{A_last - A_j}) u_j^T

With T the inverse of the unit lower-triangular matrix, U = T (beta V) - T
(beta K e^{A}) S: everything but S is a function of the chunk alone. So the
work is three stages: `_chunk_parts`, every chunk at once (the pairwise decays,
T, W = T (beta K e^{A}), U' = T (beta V), K e^{A_last - A}, Q e^{A}); a
`lax.scan` over the chunks that carries the float32 state and does two small
matmuls a step (u = U' - W S; S' = e^{A_last} S + (K e^{A_last - A})^T u); and
the read-out of every chunk at once from the states that entered them.

THE DECAYS. A falls to -`chunk` x 5 = -320 over a chunk of 64 at the gate's
lower bound, so no factor e^{-A_j} is ever formed over a whole chunk. The
decay between two positions, e^{A_i - A_j} (i >= j, so <= 1), is formed
relative to the cumulative log-decay R at the start of i's SUB-BLOCK of
`subchunk` = 16 positions: e^{A_i - R} (in [e^-80, 1]) times e^{R - A_j}, whose
exponent is <= 0 for every j before the sub-block (it underflows to 0 exactly
where the product would), and inside the diagonal sub-blocks the exponent
A_i - A_j is formed pairwise, exactly, under the causal mask. No exponent
formed here is positive, so nothing overflows whatever the gate; the bound
the configuration puts on it (-5 a position, `kda_lower_bound`: 16 x 5 = 80 <
88) is what keeps e^{A_i - R} a normal float32 number over a sub-block.

The inverse T of I + L (L strictly lower, chunk x chunk) is built exactly, not
by a series in L (whose powers cancel catastrophically where keys repeat): the
diagonal `subchunk` blocks by forward substitution, rows in order, then pairs
of blocks merged ([[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]])
until one block is the chunk. It, and the two products with T, are float32 at
"highest" precision; the other products take their operands in the dtype q
comes in and accumulate in float32; a, its cumulative sums, every exponential
and the carried state are float32.

WHAT THE MIXER HAS is not what the recurrence takes. `kda_rule` reads q, k, v
as the causal convolution writes them and the gate's input f, flat (B, S, H D)
in the activations' dtype, beta's logits (B, S, H), `A_log` a head and the
gate's bias a channel, and makes of them (`rule_arguments`): q and k a head
over their L2 norm, q over sqrt(D), rounded to the activations' dtype; the
log-decay a = `lower_bound` x sigmoid(exp(A_log) (f + bias)) in float32; beta
= sigmoid. WHAT THE MIXER READS is not o either: beside beta's logits
`kda_rule` takes the norm's gate logits, a head's ONE (the two as the one
small matmul writes them, (B, S, 2 H) float32, [beta | gate]), the norm's
scale (D_v,) and its eps, and returns y = o over its root mean square a head
x scale x sigmoid(gate), float32 throughout and rounded once, flat (B, S, H
D): what the out-projection reads (`gated_head_norm` is the same in `jnp`).
-> (y, the most negative cumulative log-decay a chunk reaches).

Differentiated, the op carries its own backward pass (`custom_vjp`): it keeps
its arguments, the state that entered each chunk and o in the activations'
dtype (what the norm's transpose reads), and its caller y (named
`kda_chunk_states`, `kda_chunk_o` and `kda_chunk_out` for a checkpoint policy
around the caller: with the three kept, its backward pass does not run the
rule forward a second time), builds the chunks' parts again, walks the chunks in reverse with
the state's cotangent and transposes the parts.

Two forms compute `kda_rule`, chosen by ops/ssd's one static rule on the
backend, the shapes and the context mesh (`resolve_kda_impl` through
`ssd._resolve`; `kda_plan` reports it; no flag, and a form is never tried and
swapped for the other). The form that tiles is the form that fuses:

- "pallas", on a TPU at the sizes the kernels tile: two Mosaic kernels under
  one `custom_vjp`, `kda_fwd` and `kda_bwd`, bound through ONE primitive
  lowered out of line (`kda_p`). A grid step is ONE chunk of a block of heads
  (`_KERNEL_HEADS`: at most 8, the sweep's winner): it builds the chunk's parts
  in VMEM (the cumulative log-decay as a triangular matmul, the two pairwise
  matrices, T, W, U'), then does one step of the walk against the block's
  float32 states, which live in VMEM scratch from a sequence's first chunk to
  its last. It reads the MIXER'S arguments, q, k, v and f as (B, S, H D), a
  head one 128-lane tile, beta's logits a (64, H) block, exp(A_log) and the
  bias a (1, H D) row each, and makes the recurrence's in VMEM first
  (`_kernel_arguments`: two lane reductions and a sigmoid a head block, each
  rounding where `rule_arguments` has it, the float32 log-decay never
  written: around the kernels XLA took as long as the kernels, PERF.md
  section 6, PR 58). It norms o where o is, the block's heads' (64, 128)
  float32 tiles side by side in VMEM: one more lane reduction a head beside
  the prologue's two, the scale's row, the sigmoid of the block's heads' gate
  logits picked out of the logits' lanes as beta's are (`_logit_columns`,
  `_kernel_gated_norm`: XLA's norm on the view a head, (.., 4,096) -> (..,
  32, 128), took thirteen times its bytes over three passes, PERF.md section
  6, PR 63). It writes y, the least cumulative log-decay a channel at
  a chunk's end (a (1, H D) block that stays over a sequence's chunks) and,
  differentiated, the float32 state that entered the chunk (134 MB a layer of
  4,096 tokens of 32 heads, what the XLA form keeps) and o rounded to the
  activations' dtype (33.5 MB a layer: forming o again in the backward from
  the parts it holds, two products a head, kept 0.20 GB fewer on the
  `train-ling3flash-4k` cell and its step was 0.78 ms longer, PERF.md section
  6, PR 63). The backward is ONE
  kernel, the same walk reversed with the states' cotangents in scratch: it
  makes the arguments and builds the parts again, reads o, makes the norm's
  statistics of it
  and turns y's cotangent into o's, the gate logits' and the scale's;
  transposes the chunk's
  step and its parts, takes a's reverse cumulative sum as a triangular
  matmul, goes on in float32 through the gate's, beta's and the norms'
  derivatives and writes the cotangents of the mixer's q, k, v, f (one
  rounding each) and of the two logits (one block, handed back as ONE (B, S,
  2 H) cotangent); the two gate parameters' gradients and the norm's scale's
  are summed in float32, a channel, over a sequence's chunks in three blocks
  that stay, and finished in XLA from those few KB. It keeps nothing of its
  own. Every array of a step carries the block's heads side by side, so the
  long chain of dependent steps of one head (cumulative sum, exponentials,
  sub-blocks, 15 eliminations, two merges, T's products, the state) is every
  head's at once: a head after a head, the same work took 1.7 times as long.
  THE DIAGONAL SUB-BLOCKS ARE FACTORED there, (x_i e^{A_i - R + 40}) . (k_j
  e^{R - A_j - 40}) with R the sub-block's own start, both factors float32
  and the product at "highest" precision: the pairwise form's (16, 16, 128)
  exponentials need a lane reduction a pair, which the vector unit pays 256
  times a head and chunk, where the factored form is one (2 C, D) x (D, C)
  product a head. Every term carries float32's relative error, as the
  pairwise form's does; the exponents A_i - R lie in [-80, 0] under the gate's
  bound and are centred (+-40), so that neither factor times a small feature
  leaves float32's normal numbers (e^{-80} q_d does: 0.004 of the output at
  the bound, measured). So the kernels count on the gate's lower bound, which
  they apply themselves (`lower_bound`, the configuration's
  `kda_gate_lower_bound`): bound x 16 under -87 does not tile. Everything
  else is float32 or bfloat16 exactly where the XLA form is.
- "xla_chunked", everywhere else (every CPU run) and what the kernels are
  compared with: `rule_arguments` in `jnp`, then `kda_chunk`, the einsums
  above, the diagonal sub-blocks pairwise, then `gated_head_norm` on o as
  `kda_chunk` rounds it (one rounding more than the kernels' forward), under
  the scope `kda.gate_norm`; its o carries the kernels' name for it.

What the kernels tile, and nothing else (other sizes run the XLA form, by the
rule; a kernel asked for by name there is refused by name): key and value
heads of 128 features, the chunk of 64 with its sub-block of 16, a gate whose
lower bound x 16 stays over -87, one device or a `shard_map` around them.

A sequence that is no multiple of the chunk is refused by name.
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
from jax.extend.core import Primitive
from jax.interpreters import mlir

from . import ssd
from .layers import rmsnorm

F32 = jnp.float32
_IMPLEMENTATIONS = ("xla_chunked", "pallas")
CHUNK = 64
# the sub-block inside which decays are formed pairwise and, across which,
# relative to the sub-block's start: `SUBCHUNK` x the gate's lower bound (5)
# stays inside float32's exponent (80 < 88)
SUBCHUNK = 16
LOWER_BOUND = -5.0
_HIGHEST = jax.lax.Precision.HIGHEST
# What the kernels tile: a head's key and value features one 128-lane tile, and
# at most this many heads a grid step (the sweep on the chip: PERF.md section 6,
# PR 56). The kernels' diagonal sub-blocks hold e^{R - A_j} over a sub-block, so
# the gate's lower bound x `SUBCHUNK` has to stay inside float32's exponent.
_LANES = ssd._LANES
_KERNEL_HEADS = 8
_KERNEL_EXPONENT = -87.0
_KERNEL_SHIFT = -LOWER_BOUND * SUBCHUNK / 2
_KERNEL_UP = math.exp(_KERNEL_SHIFT)
_VMEM_LIMIT = 64 * 1024 * 1024


def _kernels_tile(chunk: int, d_k: int, d_v: int, lower_bound: float) -> bool:
    return (chunk == CHUNK and d_k == _LANES and d_v == _LANES
            and lower_bound <= 0 and lower_bound * SUBCHUNK >= _KERNEL_EXPONENT)


def _heads_per_step(heads: int) -> int:
    """The heads a grid step of the kernels takes: the largest divisor of `heads` at most `_KERNEL_HEADS`."""
    return next(n for n in range(min(_KERNEL_HEADS, heads), 0, -1) if heads % n == 0)


def resolve_kda_impl(implementation: Optional[str] = None, *, chunk: int = CHUNK, d_k: int = 0, d_v: int = 0,
                     lower_bound: float = LOWER_BOUND) -> str:
    """The implementation `kda_rule` runs: "pallas" (the kernels `kda_fwd` /
    `kda_bwd`) or "xla_chunked" (the einsums of `_chunked`), by ops/ssd's one
    rule (`ssd._resolve`): with nothing asked, "pallas" on a TPU, on one
    device or inside a `shard_map`, at the sizes the kernels tile (a chunk of
    64, key and value heads of 128 features, a gate whose lower bound x the
    sub-block of 16 stays over -87) and "xla_chunked" elsewhere; a kernel
    asked for by name where it does not tile is refused by name."""
    return ssd._resolve(implementation, _IMPLEMENTATIONS, _kernels_tile(chunk, d_k, d_v, lower_bound), "kda",
                        f"kda_rule: the kernels do not tile a chunk of {chunk}, key heads of {d_k} and value "
                        f"heads of {d_v} under a gate whose lower bound is {lower_bound} a position")


def kda_plan(chunk: int = CHUNK, implementation: Optional[str] = None, *, heads: int = 0, d_k: int = 0,
             d_v: int = 0, lower_bound: float = LOWER_BOUND) -> dict:
    """What `kda_rule` resolves to for `heads` heads of `d_k` key and `d_v`
    value features, for callers that report it: the implementation's name, the
    chunk and its sub-block, the `pallas_call`s a differentiated rule makes,
    the heads a grid step takes and the float32 state a grid step holds in
    VMEM scratch (none of the three for the XLA form), and which form makes
    the recurrence's arguments (the L2 norms, the gate's log-decay, beta) from
    the mixer's, `kda_prologue`: "kernel" (in VMEM, `_kernel_arguments`) or
    "xla" (`rule_arguments`), and which norms o a head under the head's gate,
    `kda_epilogue`: "kernel" (`kda_fwd` where it has o, its transpose at
    `kda_bwd`'s head: `_kernel_gated_norm`) or "xla" (`gated_head_norm`)."""
    impl = resolve_kda_impl(implementation, chunk=chunk, d_k=d_k, d_v=d_v, lower_bound=lower_bound)
    per_step = _heads_per_step(heads) if impl == "pallas" else 0
    fused = "kernel" if impl == "pallas" else "xla"
    return {"kda_impl": impl, "kda_chunk": chunk, "kda_subchunk": _subchunk(chunk),
            "kda_kernels": 2 if impl == "pallas" else 0, "kda_heads_per_step": per_step,
            "kda_state_bytes": per_step * d_k * d_v * 4, "kda_prologue": fused, "kda_epilogue": fused}


def _subchunk(chunk: int) -> int:
    return SUBCHUNK if chunk % SUBCHUNK == 0 else chunk


def _unit_lower_inverse(lower: jax.Array, base: int) -> jax.Array:
    """(I + L)^-1 for L (..., n, n), of which the strictly lower triangle is
    read: `base`-sized diagonal blocks by forward substitution (row i of the
    inverse is e_i - sum_{j < i} L_ij row_j), then pairs of blocks merged."""
    n = lower.shape[-1]
    blocks = n // base
    diagonal = jnp.stack([lower[..., p * base:(p + 1) * base, p * base:(p + 1) * base]
                          for p in range(blocks)], axis=-3)                 # (..., blocks, base, base)
    eye = jnp.eye(base, dtype=F32)
    rows = [jnp.broadcast_to(eye[0], diagonal.shape[:-2] + (base,))]
    for i in range(1, base):
        before = jnp.stack(rows, axis=-2)                                    # (..., blocks, i, base)
        rows.append(eye[i] - jnp.einsum("...j,...jk->...k", diagonal[..., i, :i], before,
                                        precision=_HIGHEST))
    inverse, size = jnp.stack(rows, axis=-2), base
    while size < n:
        pairs = n // (2 * size)
        first, second = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        below = jnp.stack([lower[..., (2 * p + 1) * size:(2 * p + 2) * size, 2 * p * size:(2 * p + 1) * size]
                           for p in range(pairs)], axis=-3)
        corner = -jnp.einsum("...ij,...jk,...kl->...il", second, below, first, precision=_HIGHEST)
        inverse = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([corner, second], axis=-1)], axis=-2)
        size *= 2
    return inverse[..., 0, :, :]


def _pairwise_decays(q, k, cum, sub: int):
    """(<k_i e^{A_i}, k_j e^{-A_j}> for j < i, <q_i e^{A_i}, k_j e^{-A_j}> for
    j <= i), each (..., C, C) float32 and 0 above those triangles, from q, k
    (..., C, D) and the cumulative log-decay `cum` (..., C, D) float32."""
    dtype = q.dtype
    n = cum.shape[-2]
    qf, kf = q.astype(F32), k.astype(F32)
    causal = jnp.tril(jnp.ones((sub, sub), bool))
    rows_k, rows_q = [], []
    for lo in range(0, n, sub):
        hi = lo + sub
        at = slice(lo, hi)
        # the diagonal sub-block, the exponents pairwise and exact
        decay = jnp.exp(jnp.where(causal[:, :, None],
                                  cum[..., at, None, :] - cum[..., None, at, :], -jnp.inf))
        keyed = kf[..., None, at, :] * decay                                    # (..., i, j, D)
        pieces_k = [jnp.sum(kf[..., at, None, :] * keyed, axis=-1)]
        pieces_q = [jnp.sum(qf[..., at, None, :] * keyed, axis=-1)]
        if lo:
            # the sub-blocks before it, relative to the cumulative log-decay at its start
            start = cum[..., lo - 1:lo, :]
            here = jnp.exp(cum[..., at, :] - start)
            before = (kf[..., :lo, :] * jnp.exp(start - cum[..., :lo, :])).astype(dtype)
            for pieces, rows in ((pieces_k, kf), (pieces_q, qf)):
                pieces.insert(0, jnp.einsum("...ic,...jc->...ij", (rows[..., at, :] * here).astype(dtype),
                                            before, preferred_element_type=F32))
        after = jnp.zeros((*cum.shape[:-2], sub, n - hi), F32)
        rows_k.append(jnp.concatenate([*pieces_k, after], axis=-1))
        rows_q.append(jnp.concatenate([*pieces_q, after], axis=-1))
    strictly = jnp.tril(jnp.ones((n, n), bool), -1)
    return jnp.where(strictly, jnp.concatenate(rows_k, axis=-2), 0.0), jnp.concatenate(rows_q, axis=-2)


def _chunk_parts(q, k, v, a, beta, sub: int):
    """What every chunk computes without the state that enters it, all chunks
    at once: q, k (..., C, D_k), v (..., C, D_v), a (..., C, D_k) float32, beta
    (..., C) float32 -> float32 (W = T (beta K e^{A}) (..., C, D_k), U' = T
    (beta V) (..., C, D_v), K e^{A_last - A} (..., C, D_k), e^{A_last} (...,
    D_k), Q e^{A} (..., C, D_k), the q-k pairwise decays (..., C, C))."""
    cum = jnp.cumsum(a, axis=-2)
    last = cum[..., -1, :]
    keys, queries = _pairwise_decays(q, k, cum, sub)
    inverse = _unit_lower_inverse(beta[..., :, None] * keys, sub)
    kf = k.astype(F32)
    weighted = beta[..., None] * jnp.concatenate([kf * jnp.exp(cum), v.astype(F32)], axis=-1)
    solved = jnp.einsum("...ij,...jf->...if", inverse, weighted, precision=_HIGHEST)
    d_k = k.shape[-1]
    return (solved[..., :d_k], solved[..., d_k:], kf * jnp.exp(last[..., None, :] - cum),
            jnp.exp(last), q.astype(F32) * jnp.exp(cum), queries)


def _corrections(w, u_own, states, dtype):
    """u = U' - W S, a chunk's pseudo-values from the state that entered it."""
    return u_own - jnp.einsum("...ck,...kv->...cv", w.astype(dtype), states.astype(dtype),
                              preferred_element_type=F32)


def _read_out(decayed_q, queries, states, u, dtype):
    """o = (Q e^{A}) S + (the q-k pairwise decays) u."""
    return (jnp.einsum("...ck,...kv->...cv", decayed_q.astype(dtype), states.astype(dtype),
                       preferred_element_type=F32)
            + jnp.einsum("...ij,...jv->...iv", queries.astype(dtype), u.astype(dtype),
                         preferred_element_type=F32))


def _states(w, u_own, decayed_k, leaving, dtype):
    """The float32 state that ENTERS each chunk, (N, B, H, D_k, D_v), from the
    chunks' parts with the chunks leading."""
    def step(state, parts):
        w, u_own, decayed_k, leaving = parts
        u = _corrections(w, u_own, state, dtype)
        after = leaving[..., None] * state + jnp.einsum(
            "...ck,...cv->...kv", decayed_k.astype(dtype), u.astype(dtype), preferred_element_type=F32)
        return after, state

    zero = jnp.zeros((*leaving.shape[1:], u_own.shape[-1]), F32)
    return jax.lax.scan(step, zero, (w, u_own, decayed_k, leaving))[1]


@jax.custom_vjp
def _chunked(q, k, v, a, beta):
    """Arguments cut (N, B, H, C, ...) -> o (N, B, H, C, D_v) in v's dtype."""
    return _chunked_fwd(q, k, v, a, beta)[0]


def _chunked_fwd(q, k, v, a, beta):
    dtype = q.dtype
    w, u_own, decayed_k, leaving, decayed_q, queries = _chunk_parts(q, k, v, a, beta, _subchunk(q.shape[-2]))
    states = checkpoint_name(_states(w, u_own, decayed_k, leaving, dtype), "kda_chunk_states")
    out = _read_out(decayed_q, queries, states, _corrections(w, u_own, states, dtype), dtype)
    # what the norm after this reads, forward and backward (`kda_rule`): the kernels' second kept output
    out = checkpoint_name(out.astype(v.dtype), "kda_chunk_o")
    return out, (q, k, v, a, beta, states)


def _chunked_bwd(kept, d_out):
    q, k, v, a, beta, states = kept
    dtype = q.dtype
    parts, transpose_parts = jax.vjp(lambda *inputs: _chunk_parts(*inputs, _subchunk(q.shape[-2])),
                                     q, k, v, a, beta)
    w, u_own, decayed_k, leaving, decayed_q, queries = parts
    u, transpose_u = jax.vjp(lambda w, u_own: _corrections(w, u_own, states, dtype), w, u_own)
    _, transpose_out = jax.vjp(lambda *operands: _read_out(*operands, dtype), decayed_q, queries, states, u)
    d_decayed_q, d_queries, d_states_read, d_u_read = transpose_out(d_out.astype(F32))

    def step(d_after, chunk):
        # the cotangent of the state that LEFT the chunk -> of the one that entered it
        w, decayed_k, leaving, d_u_read, d_state_read = chunk
        d_u = d_u_read + jnp.einsum("...ck,...kv->...cv", decayed_k.astype(dtype), d_after.astype(dtype),
                                    preferred_element_type=F32)
        d_state = (d_state_read + leaving[..., None] * d_after
                   - jnp.einsum("...ck,...cv->...kv", w.astype(dtype), d_u.astype(dtype),
                                preferred_element_type=F32))
        return d_state, (d_u, d_after)

    _, (d_u, d_after) = jax.lax.scan(step, jnp.zeros(states.shape[1:], F32),
                                     (w, decayed_k, leaving, d_u_read, d_states_read), reverse=True)
    # u = U' - W S and S' = e^{A_last} S + (K e^{A_last - A})^T u, every chunk at once
    d_w, d_u_own = transpose_u(d_u)
    d_decayed_k = jnp.einsum("...cv,...kv->...ck", u.astype(dtype), d_after.astype(dtype),
                             preferred_element_type=F32)
    d_leaving = jnp.sum(states * d_after, axis=-1)
    return transpose_parts((d_w, d_u_own, d_decayed_k, d_leaving, d_decayed_q, d_queries))


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


# ------------------------------------------------------------------ kernels
#
# `kda_fwd` and `kda_bwd`: a grid over (sequence of the batch, block of heads,
# chunk), the chunks innermost and in order (the backward's reversed), the
# block's float32 states (their cotangents) in VMEM scratch from a sequence's
# first chunk to its last, TRANSPOSED (D_v, D_k): the decay of a state is a
# channel of its keys, a row (1, D_k) over the lanes as the cumulative
# log-decay's last position is. q, k, v, f, o and their cotangents are (B, S,
# H D) as the mixer has them, a head one 128-lane tile; beta's logits come
# (B, S, H), every head's to every step (their cotangent goes (B, H / heads a
# step, S, heads a step), a head a column). A step makes in VMEM what
# `rule_arguments` makes and what `_chunk_parts` builds of it, and does one
# step of `_states` and `_read_out`; nothing of a chunk but o (and,
# differentiated, the state that entered it) reaches HBM.
#
# The pairwise decays inside a diagonal sub-block are FACTORED relative to the
# sub-block's own start R, (x_i e^{A_i - R}) . (k_j e^{R - A_j}), with both
# factors float32 and the product at "highest" precision: every term carries
# float32's relative error, as the exact pairwise form's does; A_i - R lies in
# [-80, 0] under the gate's bound (`_kernels_tile`) and the factors are centred
# on its middle (`_KERNEL_SHIFT`), e^{+-40} at most. One (2 C, D) x (D, C)
# product a head gives all four sub-blocks of both matrices (what it computes
# outside them is finite and masked). Before the sub-block the factors are
# `_pairwise_decays`' own, in the activations' dtype. The 16 x 16 inverses
# are 15 eliminations of a column on the vector unit (forward substitution,
# row for row what `_unit_lower_inverse` does), every sub-block of the chunk
# at once in a block-diagonal (C, C); the two merges are T - T (L between the
# halves) T, exact for a block-diagonal T.

_NN, _NT, _TN = ssd._NN, ssd._NT, ssd._TN        # a b, a b^T, a^T b


def _dots(a, b, dims=_NN, precision=None):
    """A product a head, back to back (Mosaic pipelines them), of operands
    (heads, ., .) or (., .) for one that every head shares -> (heads, ., .)."""
    heads = a.shape[0] if a.ndim == 3 else b.shape[0]
    of = lambda t, h: t[h] if t.ndim == 3 else t      # noqa: E731
    return jnp.stack([jax.lax.dot_general(of(a, h), of(b, h), dims, precision=precision,
                                          preferred_element_type=F32) for h in range(heads)])


def _stack(pieces):
    return jnp.concatenate(pieces, axis=1)


def _kernel_parts(q, k, v, a, beta):
    """A chunk's parts in VMEM, a step's heads side by side in every array so
    that one head's long chain of dependent steps is every head's: q, k, v
    (heads, C, D) in the activations' dtype, a (heads, C, D) and beta (heads,
    C, 1) float32 -> a dict of float32 arrays, `_chunk_parts`' and what the
    backward reads of how they were made."""
    dtype = q.dtype
    heads, c, d = a.shape
    sub, shift = SUBCHUNK, SUBCHUNK.bit_length() - 1
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = (row >> shift) == (col >> shift)
    qf, kf = q.astype(F32), k.astype(F32)
    tri = (row >= col).astype(F32)
    cum = _dots(tri, a, precision=_HIGHEST)
    starts = [jnp.zeros((heads, 1, d), F32)] + [cum[:, lo - 1:lo] for lo in range(sub, c, sub)]
    over_sub = lambda rows: _stack([jnp.broadcast_to(r, (heads, sub, d)) for r in rows])      # noqa: E731
    rel = cum - over_sub(starts)
    # rel is in [-80, 0] under the gate's bound: the factors of a diagonal sub-block are centred on its
    # middle, e^{rel + 40} and e^{-rel - 40}, so that neither times a small feature leaves float32's normals
    e_in, down = jnp.exp(rel), jnp.exp(-rel - _KERNEL_SHIFT)
    up = e_in * _KERNEL_UP
    kin, qin = kf * e_in, qf * e_in
    both, kdown = _stack([kf * up, qf * up]), kf * down
    diagonal = _dots(both, kdown, _NT, _HIGHEST)                      # (2 C, C)
    position = jax.lax.broadcasted_iota(jnp.int32, (c, d), 0)
    zero = jnp.zeros((heads, sub, c), F32)
    rows_k, rows_q, before = [zero], [zero], []
    for p in range(1, c // sub):
        lo = p * sub
        decay = jnp.exp(jnp.where(position < lo, starts[p] - cum, -jnp.inf))
        keyed = kf * decay
        here = _stack([kin[:, lo:lo + sub], qin[:, lo:lo + sub]]).astype(dtype)
        m = _dots(here, keyed.astype(dtype), _NT)                       # (2 sub, C), 0 from the sub-block on
        rows_k.append(m[:, :sub])
        rows_q.append(m[:, sub:])
        before.append((lo, decay, keyed, here))
    keys = jnp.where(same, jnp.where(row > col, diagonal[:, :c], 0.0), _stack(rows_k))
    queries = jnp.where(same, jnp.where(row >= col, diagonal[:, c:], 0.0), _stack(rows_q))
    lower = beta * keys
    # the diagonal sub-blocks' inverses, block-diagonal in (C, C)
    inverse = jnp.broadcast_to((row == col).astype(F32), (heads, c, c))
    for j in range(sub - 1):
        column = _stack([lower[:, lo:lo + sub, lo + j:lo + j + 1] for lo in range(0, c, sub)])
        pivot = _stack([jnp.broadcast_to(inverse[:, lo + j:lo + j + 1], (heads, sub, c)) for lo in range(0, c, sub)])
        inverse = inverse - column * pivot
    size = sub
    while size < c:
        level = size.bit_length() - 1
        between = jnp.where((((row >> level) & 1) == 1) & ((col >> level) == (row >> level) - 1), lower, 0.0)
        inverse = inverse - _dots(_dots(inverse, between, precision=_HIGHEST), inverse, precision=_HIGHEST)
        size *= 2
    e_all = e_in * over_sub([jnp.exp(start) for start in starts])
    last = cum[:, c - 1:]
    to_last = jnp.exp(last - cum)
    unweighted = jnp.concatenate([kf * e_all, v.astype(F32)], axis=2)  # (C, 2 D)
    solved = _dots(inverse, beta * unweighted, precision=_HIGHEST)
    return dict(kf=kf, e_in=e_in, up=up, down=down, kin=kin, qin=qin, kdown=kdown, both=both, before=before,
                keys=keys, queries=queries, inverse=inverse, e_all=e_all, to_last=to_last, last=last,
                leaving=jnp.exp(last),
                unweighted=unweighted, solved=solved, w=solved[:, :, :d], u_own=solved[:, :, d:],
                decayed_k=kf * to_last, decayed_q=qf * e_all, tri=tri, row=row, col=col, same=same,
                position=position)


def _by_head(ref, heads: int):
    """A block (1, C, heads x 128) -> (heads, C, 128); a channel's number, C = 1, as well."""
    return jnp.stack([ref[0, :, h * _LANES:(h + 1) * _LANES] for h in range(heads)])


def _logit_columns(ref, heads: int):
    """(beta, the norm's gate) of the step's heads, a column each (heads, C,
    1), from the block (1, C, 2 H) of every head's two logits as the small
    matmul wrote them, [beta | gate]: the sigmoid, then the block's heads
    picked out of the lanes by a mask (the block of heads is a grid index)."""
    every = jax.nn.sigmoid(ref[0])
    lane = jax.lax.broadcasted_iota(jnp.int32, every.shape, 1) - pl.program_id(1) * heads
    return tuple(jnp.stack([jnp.sum(jnp.where(lane == first + h, every, 0.0), axis=1, keepdims=True)
                            for h in range(heads)]) for first in (0, every.shape[1] // 2))


def _kernel_gated_norm(out, norm_eps: float):
    """o (heads, C, D) float32 as a step has it -> (o over its root mean
    square a head and position, the inverse root (heads, C, 1)): y is the
    first times the scale's row times the gate's column (`gated_head_norm`'s
    lines, on the float32 o where that reads its rounding)."""
    inverse = jax.lax.rsqrt(jnp.mean(out * out, axis=-1, keepdims=True) + norm_eps)
    return out * inverse, inverse


def _write(ref, value):
    """(heads, C, 128) into a block (1, C, heads x 128); C = 1 as well."""
    for h in range(value.shape[0]):
        ref[0, :, h * _LANES:(h + 1) * _LANES] = value[h].astype(ref.dtype)


def _kernel_arguments(q_ref, k_ref, f_ref, rate_ref, bias_ref, heads: int, lower_bound: float, eps: float):
    """What the recurrence takes, made in VMEM from what the mixer has (the
    XLA form's `rule_arguments`, rounded where it rounds): q and k (heads, C,
    D) over their L2 norm a head, q over sqrt(D), in the activations' dtype;
    the log-decay `lower_bound` x sigmoid(rate (f + bias)) in float32, never
    rounded. Beside them what their transposes read: the float32 unit vectors,
    the inverse norms (heads, C, 1), the sigmoid and f + bias."""
    dtype = q_ref.dtype
    units = {}
    for name, ref in (("q", q_ref), ("k", k_ref)):
        t = _by_head(ref, heads).astype(F32)
        inverse_norm = jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + eps)
        units[name] = (t * inverse_norm, inverse_norm)
    scale = _LANES ** -0.5
    rate = _by_head(rate_ref, heads)
    shifted = _by_head(f_ref, heads).astype(F32) + _by_head(bias_ref, heads)
    gate = jax.nn.sigmoid(rate * shifted)
    return dict(q=(units["q"][0] * scale).astype(dtype), k=units["k"][0].astype(dtype), a=lower_bound * gate,
                units=units, scale=scale, rate=rate, shifted=shifted, gate=gate)


def _fwd_kernel(q_ref, k_ref, v_ref, f_ref, logits_ref, rate_ref, bias_ref, scale_ref, y_ref, least_ref, *rest,
                lower_bound: float, eps: float, norm_eps: float):
    """A chunk of a block of heads from the states in `state_scr`, which it
    leaves updated: y, the float32 o over its root mean square a head times
    the norm's scale and the sigmoid of the head's gate logit, rounded once;
    the least cumulative log-decay a channel that any chunk of the
    sequence's reached at its last position (the block stays over a
    sequence's chunks); and where `rest` holds blocks for them the states
    that entered the chunk and o itself, rounded."""
    *kept_refs, state_scr = rest
    dtype = q_ref.dtype
    heads = state_scr.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_scr[...] = jnp.zeros_like(state_scr)
        least_ref[...] = jnp.zeros_like(least_ref)

    made = _kernel_arguments(q_ref, k_ref, f_ref, rate_ref, bias_ref, heads, lower_bound, eps)
    beta, gate = _logit_columns(logits_ref, heads)
    parts = _kernel_parts(made["q"], made["k"], _by_head(v_ref, heads), made["a"], beta)
    _write(least_ref, jnp.minimum(_by_head(least_ref, heads), parts["last"]))
    state = state_scr[...]
    if kept_refs:
        kept_refs[0][0, 0] = state
    narrow = state.astype(dtype)
    u = (parts["u_own"] - _dots(parts["w"].astype(dtype), narrow, _NT)).astype(dtype)
    out = _dots(parts["decayed_q"].astype(dtype), narrow, _NT) + _dots(parts["queries"].astype(dtype), u)
    _write(y_ref, _kernel_gated_norm(out, norm_eps)[0] * _by_head(scale_ref, heads) * gate)
    if kept_refs:
        _write(kept_refs[1], out)
    state_scr[...] = parts["leaving"] * state + _dots(u, parts["decayed_k"].astype(dtype), _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, f_ref, logits_ref, rate_ref, bias_ref, scale_ref, states_ref, o_ref, dy_ref,
                dq_ref, dk_ref, dv_ref, df_ref, dlogits_ref, dgate_ref, dgate_shifted_ref, dscale_ref, dstate_scr, *,
                lower_bound: float, eps: float, norm_eps: float):
    """The transpose of `_fwd_kernel`'s chunk, the chunks in reverse: the
    arguments and the parts again in VMEM, then the norm's statistics of the
    o that `kda_fwd` kept
    and y's cotangent through the scale, the gate and the norm into o's, in
    float32, rounded where the norm's transpose in XLA rounded it; the
    cotangent of the state that
    leaves the chunk in `dstate_scr`, replaced by that of the state that
    entered it; then the float32 cotangents of the recurrence's arguments
    through the norms', the gate's and beta's sigmoid's derivatives, written
    as those of the mixer's (one rounding each). The sums over the sequence
    that the gate's two parameters' gradients are made of stay float32: the
    cotangent d of rate (f + bias) and d (f + bias), a channel, added up over
    the chunks in blocks that stay, and so is the norm's scale's gradient a
    head. The two logits' cotangents leave as ONE block, the step's heads'
    beta's columns then their gates'. The sub-block starts R are constants of
    the factoring (the decays do not depend on them), so nothing reaches a
    through them."""
    dtype = q_ref.dtype
    heads = dstate_scr.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)
        dgate_ref[...] = jnp.zeros_like(dgate_ref)
        dgate_shifted_ref[...] = jnp.zeros_like(dgate_shifted_ref)
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    made = _kernel_arguments(q_ref, k_ref, f_ref, rate_ref, bias_ref, heads, lower_bound, eps)
    beta, gate = _logit_columns(logits_ref, heads)
    p = _kernel_parts(made["q"], made["k"], _by_head(v_ref, heads), made["a"], beta)
    _, c, d = p["kf"].shape
    row, col, same = p["row"], p["col"], p["same"]
    state, d_after = states_ref[0, 0], dstate_scr[...]                   # (heads, D_v, D_k)
    narrow, d_after_n = state.astype(dtype), d_after.astype(dtype)
    w_n, decayed_k_n = p["w"].astype(dtype), p["decayed_k"].astype(dtype)
    decayed_q_n, queries_n = p["decayed_q"].astype(dtype), p["queries"].astype(dtype)
    u = (p["u_own"] - _dots(w_n, narrow, _NT)).astype(dtype)
    # y = o / rms(o) * scale * gate: y's cotangent into the scale's, the gate's logit's and o's
    normed, inverse_rms = _kernel_gated_norm(_by_head(o_ref, heads).astype(F32), norm_eps)
    scale_row = _by_head(scale_ref, heads)
    d_y = _by_head(dy_ref, heads).astype(F32)
    along = d_y * normed
    _write(dscale_ref, _by_head(dscale_ref, heads) + jnp.sum(along * gate, axis=1, keepdims=True))
    d_gate = jnp.sum(along * scale_row, axis=-1, keepdims=True)          # (heads, C, 1): the gate's own cotangent
    d_gate_logit = d_gate * gate * (1.0 - gate)
    # o over its root mean square: the cotangent less its part along the normed o (a head's mean of d_y scale
    # gate normed, which is the gate's cotangent times the gate over D), over the root
    d_out = (inverse_rms * gate * (d_y * scale_row - normed * (d_gate * (1.0 / d)))).astype(dtype)
    # o = (Q e^A) S + queries u;  S' = e^{A_last} S + (K e^{A_last - A})^T u;  u = U' - W S
    d_u = _dots(queries_n, d_out, _TN) + _dots(decayed_k_n, d_after_n, _NT)
    d_u_n = d_u.astype(dtype)
    d_queries = jnp.where(row >= col, _dots(d_out, u, _NT), 0.0)
    d_decayed_q = _dots(d_out, narrow)
    d_w = -_dots(d_u_n, narrow)
    d_decayed_k = _dots(u, d_after_n)
    d_leaving = jnp.sum(state * d_after, axis=1, keepdims=True)         # (heads, 1, D_k)
    dstate_scr[...] = p["leaving"] * d_after + _dots(d_out, decayed_q_n, _TN) - _dots(d_u_n, w_n, _TN)
    # [W | U'] = T (beta [K e^A | V]),  T = (I + beta keys)^-1
    d_weighted = _dots(p["inverse"], jnp.concatenate([d_w, d_u], axis=2), _TN, _HIGHEST)
    d_lower = jnp.where(row > col, -_dots(d_weighted, p["solved"], _NT, _HIGHEST), 0.0)
    d_beta = (jnp.sum(d_weighted * p["unweighted"], axis=2, keepdims=True)
              + jnp.sum(d_lower * p["keys"], axis=2, keepdims=True))
    d_keyed_all, d_v = beta * d_weighted[:, :, :d], beta * d_weighted[:, :, d:]
    d_keys = beta * d_lower
    # the pairwise decays: the diagonal sub-blocks, then the rows before each sub-block
    d_diagonal = _stack([jnp.where(same, d_keys, 0.0), jnp.where(same, d_queries, 0.0)])   # (2 C, C)
    d_both = _dots(d_diagonal, p["kdown"], precision=_HIGHEST)          # (2 C, D): d of [K e^up; Q e^up]
    d_kdown = _dots(d_diagonal, p["both"], _TN, _HIGHEST)               # (C, D)
    zero = jnp.zeros((heads, SUBCHUNK, d), F32)
    rows_k, rows_q = [zero], [zero]
    d_k_before = d_cum_before = jnp.zeros((heads, c, d), F32)
    for lo, decay, keyed, here in p["before"]:
        d_m = _stack([d_keys[:, lo:lo + SUBCHUNK], d_queries[:, lo:lo + SUBCHUNK]]).astype(dtype)   # (2 sub, C)
        d_here = _dots(d_m, keyed.astype(dtype))                         # (2 sub, D)
        d_keyed = _dots(d_m, here, _TN)                                  # (C, D)
        rows_k.append(d_here[:, :SUBCHUNK])
        rows_q.append(d_here[:, SUBCHUNK:])
        d_k_before = d_k_before + d_keyed * decay
        d_cum_before = d_cum_before + d_keyed * keyed
    d_kin, d_qin = _stack(rows_k), _stack(rows_q)
    d_up = d_both * p["both"]
    dk = (d_both[:, :c] * p["up"] + d_kdown * p["down"] + d_kin * p["e_in"] + d_k_before
          + d_keyed_all * p["e_all"] + d_decayed_k * p["to_last"])
    dq = (d_both[:, c:] * p["up"] + d_qin * p["e_in"] + d_decayed_q * p["e_all"]) * made["scale"]
    to_last = d_decayed_k * p["decayed_k"]
    d_cum = (d_up[:, :c] + d_up[:, c:] - d_kdown * p["kdown"] + d_kin * p["kin"] + d_qin * p["qin"] - d_cum_before
             + d_keyed_all * p["unweighted"][:, :, :d] + d_decayed_q * p["decayed_q"] - to_last)
    d_last = jnp.sum(to_last, axis=1, keepdims=True) + d_leaving * p["leaving"]
    d_cum = d_cum + jnp.where(p["position"] == c - 1, d_last, 0.0)
    # a unit vector t / |t|: its cotangent less its part along the vector, over |t|
    for ref, name, d_unit in ((dq_ref, "q", dq), (dk_ref, "k", dk)):
        unit, inverse_norm = made["units"][name]
        _write(ref, inverse_norm * (d_unit - unit * jnp.sum(d_unit * unit, axis=-1, keepdims=True)))
    _write(dv_ref, d_v)
    d_a = _dots(p["tri"], d_cum, _TN, _HIGHEST)                           # the reverse cumulative sum
    d_pre = d_a * (lower_bound * made["gate"] * (1.0 - made["gate"]))
    _write(df_ref, d_pre * made["rate"])
    for ref, summed in ((dgate_ref, d_pre), (dgate_shifted_ref, d_pre * made["shifted"])):
        _write(ref, _by_head(ref, heads) + jnp.sum(summed, axis=1, keepdims=True))
    d_logit = d_beta * beta * (1.0 - beta)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, dlogits_ref.shape[2:], 1)
    d_logits = jnp.zeros(dlogits_ref.shape[2:], F32)
    for h in range(heads):
        d_logits = jnp.where(head_lane == h, d_logit[h], jnp.where(head_lane == heads + h, d_gate_logit[h], d_logits))
    dlogits_ref[0, 0] = d_logits


def _kda_call(q, k, v, f, logits, rate, bias, scale, *kept, heads: int, keep_states: bool, lower_bound: float,
              eps: float, norm_eps: float, interpret: bool):
    """`kda_fwd` (no `kept`) -> [y (B, S, H D), the least cumulative log-decay
    a channel at a chunk's end (B, 1, H D) float32] and, with `keep_states`,
    the float32 states that entered the chunks, transposed (B, chunks, H, D_v,
    D_k), and o (B, S, H D); or `kda_bwd` on `kept` = (those states, o, y's
    cotangent) -> [dq, dk,
    dv, df, the logits' cotangent (B, H / heads, S, 2 heads) float32 (a
    step's heads' beta's, then their gates'), the sums over a sequence of the
    cotangent of rate (f + bias) and of it times f + bias, a channel, and of
    the norm's scale's gradient, a head's features (B, 1, H D) float32 each].
    q, k, v, f (B, S, H D) as the mixer has them, the logits [beta | gate] (B,
    S, 2 H) float32, `rate`, `bias` and the norm's `scale` a head over (1, 1,
    H D) float32 a channel, `heads` the heads a grid step takes."""
    bsz, s, inner = q.shape
    chunks, width = s // CHUNK, heads * _LANES
    backward = bool(kept)
    of = (lambda n: chunks - 1 - n) if backward else (lambda n: n)
    wide = pl.BlockSpec((1, CHUNK, width), lambda b, g, n: (b, of(n), g))
    both = pl.BlockSpec((1, CHUNK, logits.shape[-1]), lambda b, g, n: (b, of(n), 0))
    channel = pl.BlockSpec((1, 1, width), lambda b, g, n: (0, 0, g))
    summed = pl.BlockSpec((1, 1, width), lambda b, g, n: (b, 0, g))         # stays over a sequence's chunks
    column = pl.BlockSpec((1, 1, CHUNK, 2 * heads), lambda b, g, n: (b, g, of(n), 0))
    states = pl.BlockSpec((1, 1, heads, _LANES, _LANES), lambda b, g, n: (b, of(n), g, 0, 0))
    states_shape = jax.ShapeDtypeStruct((bsz, chunks, inner // _LANES, _LANES, _LANES), F32)
    summed_shape = jax.ShapeDtypeStruct((bsz, 1, inner), F32)
    in_specs = [wide] * 4 + [both, channel, channel, channel]
    if backward:
        in_specs, out_specs = in_specs + [states, wide, wide], [wide] * 4 + [column] + [summed] * 3
        out_shape = [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (q, k, v, f)] + [
            jax.ShapeDtypeStruct((bsz, inner // width, s, 2 * heads), F32)] + [summed_shape] * 3
    else:
        out = jax.ShapeDtypeStruct(v.shape, v.dtype)
        out_specs = [wide, summed] + [states, wide] * keep_states
        out_shape = [out, summed_shape] + [states_shape, out] * keep_states
    return pl.pallas_call(
        functools.partial(_bwd_kernel if backward else _fwd_kernel, lower_bound=lower_bound, eps=eps,
                          norm_eps=norm_eps),
        grid=(bsz, inner // width, chunks), in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, _LANES, _LANES), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="kda_bwd" if backward else "kda_fwd",
    )(q, k, v, f, logits, rate, bias, scale, *kept)


def _kda_shapes(q, k, v, f, logits, rate, bias, scale, *kept, heads, keep_states, lower_bound, eps, norm_eps,
                interpret):
    del lower_bound, eps, norm_eps, interpret
    bsz, s, inner = q.shape
    summed = rate.update(shape=(bsz, 1, inner), weak_type=False)
    if kept:
        d_logits = rate.update(shape=(bsz, inner // (heads * _LANES), s, 2 * heads), weak_type=False)
        return [t.update(weak_type=False) for t in (q, k, v, f)] + [d_logits] + [summed] * 3
    states = rate.update(shape=(bsz, s // CHUNK, inner // _LANES, _LANES, _LANES), weak_type=False)
    return [v.update(weak_type=False), summed] + [states, v.update(weak_type=False)] * keep_states


# Every call site enters through ONE primitive whose lowering builds the kernel
# and is emitted out of line, as ops/ssd's `ssm_conv_p` is: a program lowers a
# kernel once a signature and calls that one function from every layer.
kda_p = Primitive("kda")
kda_p.multiple_results = True
kda_p.def_abstract_eval(_kda_shapes)
kda_p.def_impl(lambda *args, **params: jax.jit(functools.partial(kda_p.bind, **params))(*args))
mlir.register_lowering(kda_p, mlir.lower_fun(_kda_call, multiple_results=True), inline=False)


def _channel_rows(a_log, dt_bias, norm_scale):
    """(exp(A_log) a head over its channels, the gate's bias, the norm's scale a head over), float32 (1, 1, H D)
    each: the kernels' operands."""
    heads = a_log.shape[0]
    return (jnp.repeat(jnp.exp(a_log.astype(F32)), dt_bias.shape[0] // heads).reshape(1, 1, -1),
            dt_bias.astype(F32).reshape(1, 1, -1), jnp.tile(norm_scale.astype(F32), heads).reshape(1, 1, -1))


def _bind(q, k, v, f, logits, a_log, dt_bias, norm_scale, *kept, keep_states: bool, static):
    """The primitive on the mixer's arguments (and `kda_bwd`'s `kept`); `static`: its other parameters, as pairs."""
    return kda_p.bind(q, k, v, f, logits, *_channel_rows(a_log, dt_bias, norm_scale), *kept,
                      keep_states=keep_states, **dict(static))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _kernels(q, k, v, f, logits, a_log, dt_bias, norm_scale, static):
    """-> (y (B, S, H D), the least cumulative log-decay a channel at a chunk's end (B, 1, H D))."""
    return tuple(_bind(q, k, v, f, logits, a_log, dt_bias, norm_scale, keep_states=False, static=static))


def _kernels_fwd(*arguments_and_static):
    *arguments, static = arguments_and_static
    out, least, states, o = _bind(*arguments, keep_states=True, static=static)
    # what a checkpoint around the caller may keep: with the three, its
    # backward pass starts from here and does not run `kda_fwd` a second time
    # (y is what the caller's next matmul reads, o what `kda_bwd`'s norm does)
    out = checkpoint_name(out, "kda_chunk_out")
    states = checkpoint_name(states, "kda_chunk_states")
    return (out, least), (*arguments, states, checkpoint_name(o, "kda_chunk_o"))


def _kernels_bwd(static, kept, cotangents):
    *arguments, states, o = kept
    a_log, dt_bias, norm_scale = arguments[5:]
    b, s, _ = arguments[0].shape
    heads = a_log.shape[0]
    *d_arguments, d_logits, d_gate, d_gate_shifted, d_scale = _bind(*arguments, states, o, cotangents[0],
                                                                    keep_states=False, static=static)
    # rate (f + bias) with rate = exp(A_log) a head: the two parameters' gradients from the kernel's float32
    # sums a channel, over the sequences here; the norm's scale's over the sequences and the heads
    rate = jnp.exp(a_log.astype(F32))
    d_bias = jnp.sum(d_gate, axis=(0, 1)).reshape(heads, -1) * rate[:, None]
    d_a_log = jnp.sum(jnp.sum(d_gate_shifted, axis=(0, 1)).reshape(heads, -1), axis=1) * rate
    d_scale = jnp.sum(jnp.sum(d_scale, axis=(0, 1)).reshape(heads, -1), axis=0)
    # (B, blocks, S, [beta | gate] x heads a step) -> (B, S, [beta | gate], H)
    d_logits = jnp.transpose(d_logits.reshape(b, -1, s, 2, d_logits.shape[-1] // 2), (0, 2, 3, 1, 4))
    return (*d_arguments, d_logits.reshape(b, s, 2 * heads), d_a_log.astype(a_log.dtype),
            d_bias.reshape(dt_bias.shape).astype(dt_bias.dtype), d_scale.astype(norm_scale.dtype))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def rule_arguments(q: jax.Array, k: jax.Array, v: jax.Array, f: jax.Array, beta: jax.Array, a_log: jax.Array,
                   dt_bias: jax.Array, *, lower_bound: float, eps: float):
    """What the recurrence takes from what the mixer has, in `jnp` (the
    kernels make the same in VMEM, `_kernel_arguments`): q, k, v and the
    gate's input f flat (B, S, H D), beta's logits (B, S, H), `a_log` (H,),
    `dt_bias` (H D,) -> q and k a head over their L2 norm (`eps` inside the
    root), q over sqrt(D), in their dtype (B, S, H, D); v (B, S, H, D); the
    log-decay a = `lower_bound` x sigmoid(exp(a_log) (f + dt_bias)) float32
    (B, S, H, D); beta = sigmoid, float32."""
    b, s, inner = q.shape
    heads = a_log.shape[0]
    by_head = (b, s, heads, inner // heads)

    def unit(t):    # a head's features over their L2 norm, in float32
        t = t.reshape(by_head).astype(F32)
        return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + eps)

    rate = jnp.exp(a_log.astype(F32))[:, None]
    log_decay = lower_bound * jax.nn.sigmoid(rate * (f.astype(F32) + dt_bias.astype(F32)).reshape(by_head))
    return ((unit(q) * by_head[-1] ** -0.5).astype(q.dtype), unit(k).astype(k.dtype),
            v.reshape(b, s, heads, -1), log_decay, jax.nn.sigmoid(beta.astype(F32)))


def gated_head_norm(out: jax.Array, gate: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """The norm after the rule, in `jnp`: o (B, S, H, D) over its root mean
    square a head (float32, `eps` inside the root) times `scale` (D,) times
    the sigmoid of the head's ONE gate logit (B, S, H) float32, rounded once
    to o's dtype -> y flat (B, S, H D). The kernels do the same where o
    already is (`_kernel_gated_norm`)."""
    b, s, heads, d = out.shape
    y = rmsnorm(out.astype(F32), scale, eps=eps) * jax.nn.sigmoid(gate.astype(F32))[..., None]
    return y.astype(out.dtype).reshape(b, s, heads * d)


def kda_rule(q: jax.Array, k: jax.Array, v: jax.Array, f: jax.Array, beta_gate: jax.Array, a_log: jax.Array,
             dt_bias: jax.Array, norm_scale: jax.Array, *, eps: float, norm_eps: float, chunk: int = CHUNK,
             lower_bound: float = LOWER_BOUND, implementation: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """The delta rule from the mixer's arguments as the mixer has them
    (`rule_arguments` says which and what is made of them; `beta_gate` (B, S,
    2 H) holds beta's logits then the norm's gates', as one small matmul
    writes them), and the norm of its output a head under the head's gate
    (`gated_head_norm`; `norm_scale` a head's D features, `norm_eps` inside
    its root): -> (y (B, S, H D) in v's dtype, what the out-projection
    reads, named `kda_chunk_out` (o, which the norm's transpose reads,
    `kda_chunk_o`); the most negative cumulative log-decay any
    channel reaches inside a chunk, a scalar with no gradient).
    Differentiable in all eight. `lower_bound` is the gate's (the kernels'
    diagonal sub-blocks count on it); `implementation` is
    `resolve_kda_impl`'s, for tests: the kernels make the recurrence's
    arguments in VMEM and norm the float32 o there, the XLA form does both
    in `jnp` (`rule_arguments`, `kda_chunk`, then `gated_head_norm` on the
    rounded o under the scope `kda.gate_norm`)."""
    b, s, inner = q.shape
    heads = a_log.shape[0]
    d = inner // heads
    impl = resolve_kda_impl(implementation, chunk=chunk, d_k=d, d_v=v.shape[-1] // heads, lower_bound=lower_bound)
    if s % chunk:
        raise ValueError(f"kda_rule: a sequence of {s} is no multiple of the chunk {chunk}")
    if impl == "pallas":
        static = dict(heads=_heads_per_step(heads), lower_bound=float(lower_bound), eps=float(eps),
                      norm_eps=float(norm_eps), interpret=jax.default_backend() != "tpu")
        out, least = _kernels(q, k, v, f, beta_gate.astype(F32), a_log, dt_bias, norm_scale,
                              tuple(sorted(static.items())))
        return out, jax.lax.stop_gradient(jnp.min(least))
    q, k, v, log_decay, beta = rule_arguments(q, k, v, f, beta_gate[..., :heads], a_log, dt_bias,
                                              lower_bound=lower_bound, eps=eps)
    out = kda_chunk(q, k, v, log_decay, beta, chunk=chunk)
    with jax.named_scope("kda.gate_norm"):
        out = checkpoint_name(gated_head_norm(out, beta_gate[..., heads:], norm_scale, norm_eps), "kda_chunk_out")
    return out, jax.lax.stop_gradient(log_decay_chunk_min(log_decay, chunk))


def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, a: jax.Array, beta: jax.Array, *,
              chunk: int = CHUNK) -> jax.Array:
    """The XLA form on the recurrence's own arguments, what `kda_reference`
    is compared with: the delta rule with a decay a channel on q, k (B, S, H,
    D_k), v (B, S, H, D_v), the log-decay a (B, S, H, D_k) <= 0 and beta (B,
    S, H) -> o (B, S, H, D_v) in v's dtype, from a zero state, in chunks of
    `chunk` positions (the module's docstring). a and beta are taken in
    float32; differentiable in all five."""
    b, s, h, _ = q.shape
    if s % chunk:
        raise ValueError(f"kda_chunk: a sequence of {s} is no multiple of the chunk {chunk}")

    def cut(t):     # (B, S, H, ...) -> (N, B, H, C, ...)
        t = t.reshape(b, s // chunk, chunk, *t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 2, 3) if t.ndim == 5 else jnp.transpose(t, (1, 0, 3, 2))

    out = _chunked(cut(q), cut(k), cut(v), cut(a.astype(F32)), cut(beta.astype(F32)))
    return jnp.moveaxis(jnp.moveaxis(out, 3, 2), 0, 1).reshape(b, s, h, v.shape[-1])


def log_decay_chunk_min(a: jax.Array, chunk: int = CHUNK) -> jax.Array:
    """The most negative cumulative log-decay any channel reaches inside a
    chunk: the sum of a (B, S, H, D) over each chunk's positions."""
    b, s, h, d = a.shape
    return jnp.min(jnp.sum(a.astype(F32).reshape(b, s // chunk, chunk, h, d), axis=2))


def kda_reference(q, k, v, a, beta) -> Tuple[jax.Array, jax.Array]:
    """The recurrence one position at a time, in float32: what `kda_chunk` is
    compared with. -> (o (B, S, H, D_v), the last state (B, H, D_k, D_v))."""
    q, k, v, a, beta = (t.astype(F32) for t in (q, k, v, a, beta))

    def position(state, inputs):
        q_t, k_t, v_t, a_t, beta_t = inputs          # (B, H, D), ..., (B, H)
        state = jnp.exp(a_t)[..., None] * state
        u_t = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST))
        state = state + k_t[..., None] * u_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HIGHEST)

    zero = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), F32)
    last, out = jax.lax.scan(position, zero, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, beta)))
    return jnp.moveaxis(out, 0, 1), last
