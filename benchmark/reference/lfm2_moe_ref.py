"""Plain reference for the `lfm2_moe` family (LiquidAI/LFM2-8B-A1B), after the
layer equations as ISSUE 61 states them.

Straightforward `jax.numpy` in float32 at "highest" matmul precision: no
kernels, no sort, no grouped matmul, nothing imported from `ray_tpu` (the
norm, the split-half rotary embedding, the SwiGLU, the blocked attention and
the head are the sibling reference's, afmoe_ref's). Published layer i:

  u  = RMSNorm_operator(x)
  conv layer (layer_types[i] == "conv"):
       [B | C | X] = u W_in  (thirds of hidden_size, in this order)
       z_t = B_t * X_t
       c_t = w[:, 0] z_{t-2} + w[:, 1] z_{t-1} + w[:, 2] z_t   (zeros before the
             sequence; conv_L_cache taps a channel, no bias, NO activation)
       x1 = x + (C * c) W_out
  attention layer (layer_types[i] == "full_attention"):
       q = u Wq (Hq x D), k = u Wk, v = u Wv (Hkv x D)
       q, k each through an RMSNorm over the D features of a HEAD, THEN
       split-half rotary positions over all D features, theta rope_theta
       o = softmax(q k^T / sqrt(D)) v over the keys j <= t, a key-value head
           serving its Hq / Hkv query heads;  x1 = x + o Wo   (no gate, no window)
  m  = RMSNorm_ffn(x1)
  x2 = x1 + mlp(m), mlp(m) = (silu(m Wgate) * (m Wup)) Wdown for i < num_dense_layers,
       else the routed sum:
  s   = sigmoid(m Wr) in float32, one score a published expert
  sel = the num_experts_per_tok largest of s + expert_bias (the bias enters
        the selection only, and carries no gradient)
  g   = s[sel] / (sum(s[sel]) + 1e-6) * routed_scaling_factor
  routed(m) = sum over e in sel of g_e SwiGLU_e(m)        (no shared expert)

The embedding is not scaled, a final RMSNorm (the family's `embedding_norm`)
precedes the logits h wte^T: the head is TIED, so the tree has no `lm_head`
and `wte` takes the gradient of both its uses. The loss is the cross entropy
alone (the configuration file's `departures`).

One chip's share: `held_experts` = (first, last) names the published experts
whose weights the tree holds; the router keeps all its outputs and its top-k
over all of them, the gates are normalised over all k chosen, and `routed` is
the sum over the chosen experts that are held: every held expert is applied
to every token and weighted by its gate, or by zero. `frozen_leaves` names
the leaves of a layer that are read as constants (the file's
`program.frozen_leaves`).

The departure from "straightforward" is afmoe_ref's: attention is taken a
key-value group and a block of queries at a time, each under
`jax.checkpoint`, because one 8,192-token row's float32 scores are 8.6 GB;
the head runs over `head_rows` positions at a time.

The weights are the system's own, so the reference reads the program's
parameter layout: params["runs"][r][p] is one dict of leaves stacked on a
leading axis of the run's repeats, the stack's layers in order being run by
run, repeat by repeat, position by position: ln1_scale, ln2_scale (M,); a
conv layer's sconv_in (M, 3 M), sconv_w (M, K), sconv_out (M, M); an attention
layer's wq (M, Hq, D), wk/wv (M, Hkv, D), wo (Hq, D, M), q_norm_scale /
k_norm_scale (D,); a dense layer's w_gate/w_up (M, F), w_down (F, M); an
expert layer's router (M, E), expert_bias (E,), we_gate/we_up (held, M, F),
we_down (held, F, M). Which layer is of which kind is the caller's list,
`layer_types`: the published entries of the layers that are run, in order.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .afmoe_ref import F32, _attention, _head, _rmsnorm, _rope, _swiglu, layers_of

ROUTE_NORM_EPS = 1e-6


def _short_conv(u, w_in, taps, w_out):
    """The gated short convolution on the normed stream u (B, S, M): three
    shifted products (as many as `taps` has columns)."""
    s, k = u.shape[1], taps.shape[-1]
    before, after, x = jnp.split(u @ w_in, 3, axis=-1)
    z = jnp.pad(before * x, ((0, 0), (k - 1, 0), (0, 0)))
    return (after * sum(taps[:, j] * z[:, j:j + s] for j in range(k))) @ w_out


def _gqa(u, lp, *, theta: float, eps: float, query_block: int):
    q, k, v = (jnp.einsum("bse,ehd->bshd", u, lp[w]) for w in ("wq", "wk", "wv"))
    q = _rope(_rmsnorm(q, lp["q_norm_scale"], eps), theta)
    k = _rope(_rmsnorm(k, lp["k_norm_scale"], eps), theta)
    return jnp.einsum("bshd,hde->bse", _attention(q, k, v, None, query_block), lp["wo"])


def _gates(scores, expert_bias, top_k: int, route_scale: float):
    """scores (B, S, E) -> (the gate of every published expert, zero where it
    was not chosen; the chosen experts (B, S, k))."""
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(expert_bias), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS) * route_scale
    return jnp.einsum("bsk,bske->bse", weights, jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32)), chosen


def routed(m, lp, *, top_k: int, route_scale: float, held_experts: Optional[Tuple[int, int]]):
    """The expert layer's output on the normed stream m (B, S, M): the sum over
    the chosen experts that `lp` holds (`held_experts`; None: all of them).
    -> (the sum, the chosen experts (B, S, k))."""
    scores = jax.nn.sigmoid(m @ lp["router"])                                   # (B, S, E)
    gates, chosen = _gates(scores, lp["expert_bias"], top_k, route_scale)
    first, last = held_experts or (0, scores.shape[-1])

    @jax.checkpoint      # the backward pass computes an expert again
    def gated(w_gate, w_up, w_down, gate):
        return gate[..., None] * _swiglu(m, w_gate, w_up, w_down)

    out, _ = jax.lax.scan(
        lambda total, expert: (total + gated(*expert), None), jnp.zeros_like(m),
        (lp["we_gate"], lp["we_up"], lp["we_down"], jnp.moveaxis(gates[..., first:last], -1, 0)))
    return out, chosen


@functools.partial(jax.jit, static_argnames=(
    "attention", "dense", "theta", "eps", "top_k", "route_scale", "held_experts", "frozen_leaves",
    "query_block"))
def _layer(x, lp, *, attention: bool, dense: bool, theta: float, eps: float, top_k: int,
           route_scale: float, held_experts: Optional[Tuple[int, int]],
           frozen_leaves: Tuple[str, ...], query_block: int):
    """-> (x after the layer, the chosen experts (B, S, k); None for a dense layer)."""
    with jax.default_matmul_precision("highest"):
        lp = {name: jax.lax.stop_gradient(w) if name in frozen_leaves else w for name, w in lp.items()}
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        u = _rmsnorm(x, lp["ln1_scale"], eps)
        if attention:
            x = x + _gqa(u, lp, theta=theta, eps=eps, query_block=query_block)
        else:
            x = x + _short_conv(u, lp["sconv_in"], lp["sconv_w"], lp["sconv_out"])
        m = _rmsnorm(x, lp["ln2_scale"], eps)
        if dense:
            return x + _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        out, chosen = routed(m, lp, top_k=top_k, route_scale=route_scale, held_experts=held_experts)
        return x + out, chosen


def _layer_fns(*, layer_types: Sequence[str], first_layer: int, num_dense_layers: int, rope_theta: float,
               norm_eps: float, top_k: int, route_scale: float, held_experts: Optional[Tuple[int, int]],
               frozen_leaves: Tuple[str, ...] = (), query_block: int):
    """A function of (x, its leaves) for each layer that is run: the i-th is
    published layer `first_layer` + i, of the mixer `layer_types[i]` names."""
    unknown = set(layer_types) - {"conv", "full_attention"}
    if unknown:
        raise ValueError(f"layer_types: {sorted(unknown)} is neither conv nor full_attention")
    return [functools.partial(
        _layer, attention=kind == "full_attention", dense=int(first_layer) + i < int(num_dense_layers),
        theta=float(rope_theta), eps=float(norm_eps), top_k=int(top_k), route_scale=float(route_scale),
        held_experts=None if held_experts is None else tuple(held_experts),
        frozen_leaves=tuple(frozen_leaves), query_block=int(query_block))
        for i, kind in enumerate(layer_types)]


def forward(params: Dict[str, Any], tokens: jax.Array, *, query_block: int = 1024,
            **arch) -> Tuple[jax.Array, List[Any]]:
    """(B, S) int tokens -> ((B, S, V) float32 logits, the chosen experts of
    every expert layer)."""
    x, chosen = params["wte"][tokens].astype(F32), []
    layers = list(layers_of(params))
    fns = _layer_fns(query_block=query_block, **arch)
    if len(fns) != len(layers):
        raise ValueError(f"layer_types names {len(fns)} layers, the tree holds {len(layers)}")
    for fn, lp in zip(fns, layers):
        x, layer_chosen = fn(x, lp)
        if layer_chosen is not None:
            chosen.append(layer_chosen)
    return _head(x, params["lnf_scale"], params["wte"].T, eps=float(arch["norm_eps"])), chosen


def forward_logits(params: Dict[str, Any], tokens: jax.Array, **arch) -> jax.Array:
    return forward(params, tokens, **arch)[0]


def objective(params: Dict[str, Any], tokens: jax.Array, **arch) -> jax.Array:
    """Mean next-token cross entropy of (B, S + 1) tokens, the whole batch at
    once and differentiable: all this family trains on here."""
    logits = forward_logits(params, tokens[:, :-1], **arch)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def objective_part(params: Dict[str, Any], rows: jax.Array, stats: None = None, *,
                   total_tokens: int, head_rows: int = 1024, query_block: int = 1024, **arch):
    """What the (b, S + 1) `rows` add to the mean cross entropy of a batch of
    `total_tokens` targets, differentiable: (their share, their summed cross
    entropy). Each layer is computed again in the backward pass, and the head
    runs over `head_rows` positions at a time."""
    del stats
    tokens, targets = rows[:, :-1], rows[:, 1:]
    x = params["wte"][tokens].astype(F32)
    for fn, lp in zip(_layer_fns(query_block=query_block, **arch), layers_of(params)):
        x, _ = jax.checkpoint(fn)(x, lp)

    @jax.checkpoint
    def chunk_ce(args):
        xc, tc = args
        logp = jax.nn.log_softmax(
            _head(xc, params["lnf_scale"], params["wte"].T, eps=float(arch["norm_eps"])), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tc[..., None], axis=-1))

    b, s, e = x.shape
    n = max(s // head_rows, 1) if s % head_rows == 0 else 1
    ce_sum = jnp.sum(jax.lax.map(chunk_ce, (
        jnp.moveaxis(x.reshape(b, n, s // n, e), 1, 0),
        jnp.moveaxis(targets.reshape(b, n, s // n), 1, 0))))
    return ce_sum / total_tokens, ce_sum
