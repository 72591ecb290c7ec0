"""Plain reference for the `evabyte` family (EvaByte/EvaByte, 6.5B), by the
layer equations ISSUE 51 states: a byte-level dense decoder whose attention
(EVA: Zheng, Yuan, Wang, Kong, "Efficient Attention via Control Variates",
ICLR 2023, with the paper's sampled proposal replaced by two learned vectors
a head, as in the family's public modelling code) is EXACT inside a window and
reads everything before the window as one learned summary a chunk, and whose
one head matrix is several next-byte heads.

Straightforward `jax.numpy` in float32 at "highest" matmul precision: no
kernels, no log-sum-exp merge of two partial attentions, no chunked or fused
head, nothing imported from `ray_tpu`. With x the stream, s = D^-0.5, w the
window and c the chunk:

  norm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)          (`norm_add_unit_offset`)
  h = x + Wo EVA(norm1(x));   y = h + Wd (silu(Wg n) * (Wu n)),  n = norm2(h)
  q, k, v = H heads of D from Wq, Wk, Wv; rotary (split halves, the whole head,
      no scaling) on q and k at the position's index
  chunk j = positions [c j, c j + c); with the head's mu, phi (D,):
      a_t = softmax over the chunk's t of s (mu . k_t),   kbar_j = sum_t a_t k_t
      b_t = softmax over the chunk's t of s (phi . k_t),  vbar_j = sum_t b_t v_t
      (k after the rotation)
  query i of window W = i // w: ONE softmax over the explicit list of
      s (q_i . k_t) for the t of its own window with t <= i, and
      s (q_i . kbar_j) for every chunk j of an earlier window (j < W w / c);
      its output is the same weights times the v_t and the vbar_j.
      A query of the first window reads no summary.
  logits = norm_f(x) Wh, Wh (E, P V): head n = columns [n V, (n + 1) V) scores
      position t against byte t + 1 + n;
  loss = mean over n of the mean cross entropy over the positions that have
      that target (equal weights a head).

Departures from "straightforward", each forced by memory at 32,768 positions
and each leaving the arithmetic as it is: the attention runs a WINDOW of
queries at a time (the list of keys of a window's queries is its own window,
masked causally, and the summaries before it: no query ever sees another
list), each window, each window's MLP and each layer computed again in the
backward pass (`jax.checkpoint`), and the layers as one `lax.scan` over
their stacked leaves.

The weights are the system's own, so the reference reads the program's
layout: `blocks` leaves stacked on a leading layer axis, wq, wk, wv (L, E, H,
D), wo (L, H, D, E), w_up, w_gate (L, E, F), w_down (L, F, E), ln1_scale,
ln2_scale (L, E), eva_mu, eva_phi (L, H, D); wte (V, E), lnf_scale (E,),
lm_head (E, P V).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """x: (B, S, H, D). Rotary embedding over the whole head, split halves,
    at positions 0..S-1."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _summaries(k, v, mu, phi, chunk):
    """k, v (B, S, H, D) -> kbar, vbar (B, S / chunk, H, D)."""
    b, s, h, d = k.shape
    scale = d ** -0.5
    kc, vc = k.reshape(b, s // chunk, chunk, h, d), v.reshape(b, s // chunk, chunk, h, d)
    a = jax.nn.softmax(scale * jnp.einsum("bjthd,hd->bjth", kc, mu), axis=2)
    bw = jax.nn.softmax(scale * jnp.einsum("bjthd,hd->bjth", kc, phi), axis=2)
    return jnp.einsum("bjth,bjthd->bjhd", a, kc), jnp.einsum("bjth,bjthd->bjhd", bw, vc)


@jax.checkpoint
def _window_attention(q, k, v, kbar, vbar):
    """One window's queries q (B, w, H, D) against the explicit list of their
    keys: the window's own k, v (B, w, H, D), causally, and the summaries of
    every earlier window kbar, vbar (B, n, H, D; n may be 0), all visible."""
    w, d = q.shape[1], q.shape[-1]
    keys, values = jnp.concatenate([k, kbar], axis=1), jnp.concatenate([v, vbar], axis=1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, keys) * d ** -0.5
    column = jnp.arange(keys.shape[1])
    visible = (column[None, :] >= w) | (column[None, :] <= jnp.arange(w)[:, None])
    weights = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, values)


def _eva(q, k, v, mu, phi, window, chunk):
    kbar, vbar = _summaries(k, v, mu, phi, chunk)
    per_window = window // chunk
    outs = []
    for start in range(0, q.shape[1], window):
        rows = slice(start, start + window)
        before = (start // window) * per_window
        outs.append(_window_attention(q[:, rows], k[:, rows], v[:, rows],
                                      kbar[:, :before], vbar[:, :before]))
    return jnp.concatenate(outs, axis=1)


@jax.checkpoint
def _mlp(n, w_gate, w_up, w_down):
    return (jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down


def _layer(x, lp, *, theta, eps, window, chunk):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        n = _norm(x, lp["ln1_scale"], eps)
        q = _rope(jnp.einsum("bse,ehd->bshd", n, lp["wq"]), theta)
        k = _rope(jnp.einsum("bse,ehd->bshd", n, lp["wk"]), theta)
        v = jnp.einsum("bse,ehd->bshd", n, lp["wv"])
        attn = _eva(q, k, v, lp["eva_mu"], lp["eva_phi"], window, chunk)
        h = x + jnp.einsum("bshd,hde->bse", attn, lp["wo"])
        n = _norm(h, lp["ln2_scale"], eps)
        b, s, e = n.shape
        # a window's positions at a time: the MLP's activations of 32,768 are not held
        blocks = jnp.moveaxis(n.reshape(b, s // window, window, e), 1, 0)
        mlp = jax.lax.map(lambda block: _mlp(block, lp["w_gate"], lp["w_up"], lp["w_down"]), blocks)
        return h + jnp.moveaxis(mlp, 0, 1).reshape(b, s, e)


def _check(tokens, window, chunk):
    if window % chunk or tokens.shape[1] % window:
        raise ValueError(f"evabyte reference: a sequence of {tokens.shape[1]} is no multiple of the "
                         f"window {window}, or the window of the chunk {chunk}")


def _stack(params, tokens, *, rope_theta, norm_eps, window, chunk, recompute):
    _check(tokens, window, chunk)
    layer = functools.partial(_layer, theta=float(rope_theta), eps=float(norm_eps),
                              window=int(window), chunk=int(chunk))
    if recompute:
        layer = jax.checkpoint(layer)
    x = params["wte"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x, params["blocks"])
    with jax.default_matmul_precision("highest"):
        return _norm(x, params["lnf_scale"].astype(F32), float(norm_eps)) @ params["lm_head"].astype(F32)


def forward_logits(params: Dict[str, Any], tokens: jax.Array, **arch) -> jax.Array:
    """(B, S) int tokens -> (B, S, P V) float32 logits, head n's the n-th V columns."""
    arch.pop("pred_heads", None)
    return jax.jit(functools.partial(_stack, recompute=False, **arch))(params, tokens)


def head_losses(logits: jax.Array, rows: jax.Array, pred_heads: int):
    """[(summed cross entropy, positions that have the target)] a head, of
    logits (b, S, P V) against the (b, S + 1) `rows`: head n at position t is
    scored against rows[t + 1 + n], a loop over the heads."""
    s = rows.shape[1] - 1
    vocab = logits.shape[-1] // pred_heads
    out = []
    for n in range(pred_heads):
        have = s - n                                            # positions 0 .. S - 1 - n
        logp = jax.nn.log_softmax(logits[:, :have, n * vocab:(n + 1) * vocab], axis=-1)
        picked = jnp.take_along_axis(logp, rows[:, 1 + n:1 + n + have, None], axis=-1)
        out.append((-jnp.sum(picked), rows.shape[0] * have))
    return out


def objective_part(params: Dict[str, Any], rows: jax.Array, whole=None, *, total_tokens: int,
                   pred_heads: int, **arch):
    """What the (b, S + 1) `rows` add to the objective of a batch of
    `total_tokens` = B S positions, differentiable: (their share, that share x
    `total_tokens`, so that the shares' sum over B S is the batch's objective:
    what `train_ref.BatchGradient` reports as the loss). The objective is the
    mean over the heads of each head's mean cross entropy over the batch's
    positions that have its target, B (S - n) for head n."""
    del whole
    s = rows.shape[1] - 1
    batch = total_tokens // s
    logits = _stack(params, rows[:, :-1], recompute=True, **arch)
    share = sum(ce / (batch * (s - n)) for n, (ce, _) in
                enumerate(head_losses(logits, rows, pred_heads))) / pred_heads
    return share, share * total_tokens
