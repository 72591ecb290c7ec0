"""Kimi Delta Attention's core (arXiv:2510.26692): the delta rule with a decay
a channel, in its chunked form (`kda_chunk`).

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

Differentiated, the op carries its own backward pass (`custom_vjp`): it keeps
q, k, v, a, beta, the state that entered each chunk and the output (named
`kda_chunk_states` and `kda_chunk_out` for a checkpoint policy around the
caller), builds the chunks' parts again, walks the chunks in reverse with the
state's cotangent (two small matmuls a step, as forward) and transposes the
parts of every chunk at once. ONE form today, XLA einsums everywhere
("xla_chunked"; `kda_plan` reports it): kernels follow ops/ssd's `_resolve`
rule when they are written.

A sequence that is no multiple of the chunk is refused by name.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

F32 = jnp.float32
_IMPLEMENTATIONS = ("xla_chunked",)
CHUNK = 64
# the sub-block inside which decays are formed pairwise and, across which,
# relative to the sub-block's start: `SUBCHUNK` x the gate's lower bound (5)
# stays inside float32's exponent (80 < 88)
SUBCHUNK = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def resolve_kda_impl(implementation: Optional[str] = None) -> str:
    """The implementation `kda_chunk` runs: "xla_chunked", the one there is."""
    if implementation not in (None, *_IMPLEMENTATIONS):
        raise ValueError(f"unknown kda implementation: {implementation!r}")
    return _IMPLEMENTATIONS[0]


def kda_plan(chunk: int = CHUNK, implementation: Optional[str] = None) -> dict:
    """What `kda_chunk` resolves to, for callers that report it."""
    return {"kda_impl": resolve_kda_impl(implementation), "kda_chunk": chunk, "kda_subchunk": _subchunk(chunk)}


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
    out = checkpoint_name(out.astype(v.dtype), "kda_chunk_out")
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


def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, a: jax.Array, beta: jax.Array, *,
              chunk: int = CHUNK, implementation: Optional[str] = None) -> jax.Array:
    """The delta rule with a decay a channel on q, k (B, S, H, D_k), v (B, S,
    H, D_v), the log-decay a (B, S, H, D_k) <= 0 and beta (B, S, H) -> o (B,
    S, H, D_v) in v's dtype, from a zero state, in chunks of `chunk`
    positions (the module's docstring). a and beta are taken in float32;
    differentiable in all five."""
    resolve_kda_impl(implementation)
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
