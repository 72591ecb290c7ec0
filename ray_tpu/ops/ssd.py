"""The state-space mixer's core (Mamba-2): a causal depthwise convolution
and the selective scan in its chunked, state-space-duality form.

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

The chunks are walked `BLOCK_CHUNKS` at a time by a `lax.scan` that carries
the state from block to block, with a backward pass of its own (`_walk`, a
`custom_vjp`): differentiated, the walk keeps its inputs and ONE state a
block (the state that entered it), and the backward pass computes a block
again from that state before it transposes it, whatever the caller
recomputes around it. What a block builds and drops is (B, chunks, H, chunk,
chunk) decays and scores and (B, chunks, H, P, N) states, 0.2 GB at 2 x
1,024 tokens of 64 heads where the whole 8,192-token sequence at once is 1.6
GB. The walk's output and the kept states carry the names `ssm_scan_out` and
`ssm_chunk_states` for a checkpoint policy around the caller.

A sequence that is no multiple of the chunk is refused by name: padding at
the end would be silent work, and the cell's sequences are multiples.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

F32 = jnp.float32

# Chunks a step of the scan over the sequence computes at once (and a
# backward step computes again). Set by a sweep on the chip at the
# `train-nemotron3nano-8k` cell's shapes (PERF.md section 6, PR 48).
BLOCK_CHUNKS = 8


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """silu(b[c] + sum_j w[c, j] x_{t - K + 1 + j}[c]) on x (B, S, C) with w
    (C, K), b (C,): a causal depthwise convolution with bias, zeros before
    the sequence. K shifted multiply-adds in float32, one fused pass."""
    k = w.shape[-1]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(F32)
    out = b.astype(F32) + sum(w[:, j] * padded[:, j:j + s].astype(F32) for j in range(k))
    return jax.nn.silu(out).astype(x.dtype)


def scan_plan(seq: int, chunk: int) -> dict:
    """What `ssd_scan` resolves to for a sequence of `seq`, for callers that
    report it: the implementation's name, the chunk and the chunks a step of
    the walk over the sequence computes at once."""
    return {"ssm_scan_impl": "xla_chunked", "ssm_chunk": chunk,
            "ssm_scan_block_chunks": _block_chunks(seq // chunk)}


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


def ssd_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array, c: jax.Array,
             d: jax.Array, *, chunk: int = 128) -> jax.Array:
    """The selective scan of the module's docstring in its chunked form. x
    (B, S, H, P); dt (B, S, H), the positive step (float32: after its bias
    and softplus); a_log, d (H,); b, c (B, S, G, N) with H a multiple of G
    -> y (B, S, H, P) in x's dtype. Differentiable in every argument; what
    the backward pass keeps is the module's docstring's."""
    bsz, s, h, p = x.shape
    g = b.shape[2]
    if s % chunk:
        raise ValueError(f"ssd_scan: a sequence of {s} is no multiple of the chunk {chunk}")
    if h % g:
        raise ValueError(f"ssd_scan: {h} heads are no multiple of the {g} groups")
    per_block = _block_chunks(s // chunk)
    dt = dt.astype(F32)
    da = dt * -jnp.exp(a_log.astype(F32))

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
