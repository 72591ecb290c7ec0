"""Plain reference for the `afmoe` family (arcee-ai/Trinity-Mini), after the
family's `modeling_afmoe.py` in the `transformers` library as ISSUE 33
states it.

Straightforward `jax.numpy` in float32 at "highest" matmul precision: no
kernels, no sort, no grouped matmul, nothing imported from `ray_tpu`. Layer
i of the published stack is

  a  = RMSNorm_in(x)
  q = a Wq (Hq x D), k = a Wk, v = a Wv (Hkv x D), g = a Wg (Hq x D)
  q, k each through an RMSNorm over the D features of a HEAD
  sliding layer ((i + 1) % global_attn_every_n_layers != 0): split-half rotary
       positions on q and k, theta 10,000; key j is visible to query t iff
       t - sliding_window < j <= t
  full layer: NO positional encoding; key j visible iff j <= t
  o  = softmax(q k^T / sqrt(D)) v, gated: o * sigmoid(g);  y = o Wo
  x1 = x + RMSNorm_post_attn(y)
  b  = RMSNorm_pre_mlp(x1);  x2 = x1 + RMSNorm_post_mlp(mlp(b))

with mlp(b) = (silu(b Wgate) * (b Wup)) Wdown for the first num_dense_layers
layers and, for the others, shared(b) + routed(b):

  s   = sigmoid(b Wr) in float32, one score a published expert
  sel = the num_experts_per_tok largest of s + expert_bias (the bias enters
        the selection only, and carries no gradient)
  w   = s[sel] / (sum(s[sel]) + 1e-20) * route_scale
  routed(b) = sum over e in sel of w_e SwiGLU_e(b)

The embedding is scaled by sqrt(hidden_size), the head is untied, the loss is
the cross entropy alone (`load_balance_coeff` and whatever updates
`expert_bias` between steps are not part of the modeling code's forward or
loss: the configuration file's `departures`).

One chip's share: `held_experts` = (first, last) names the published experts
whose weights the parameter tree holds; the router keeps all its outputs and
its top-k over all of them, the gates are normalised over all k chosen, and
`routed` is the sum over the chosen experts that are held. Every held expert
is applied to every token and weighted by its gate, or by zero.
`frozen_leaves` names the leaves of a layer that are read as constants and
so get a zero gradient (the configuration's `program.frozen_leaves`: a share
does not train its router, whose whole gradient needs the outputs of all the
chosen experts).

ONE departure from "straightforward", because one 8,192-token row's float32
scores are 8.6 GB a layer: attention is taken a key-value group and a block
of queries at a time, each under `jax.checkpoint` (the backward pass
computes a block's scores again). Nothing else is blocked but the head,
which runs over `head_rows` positions at a time as olmoe_ref's does.

The weights are the system's own (the comparison is of arithmetic, not of
initialisation), so the reference reads the program's parameter layout:
params["runs"][r][p] is one dict of leaves, each stacked on a leading axis of
the run's repeats, the stack's layers in order being run by run, repeat by
repeat, position by position: wq/wg (M, Hq, D), wk/wv (M, Hkv, D), wo (Hq, D,
M), q_norm_scale/k_norm_scale (D,), ln1_scale, ln1_post_scale, ln2_scale,
ln2_post_scale (M,); a dense layer's w_gate/w_up (M, F), w_down (F, M); an
expert layer's router (M, E), expert_bias (E,), we_gate/we_up (held, M, F),
we_down (held, F, M), ws_gate/ws_up (M, Fs), ws_down (Fs, M). Which layer is
of which kind is worked out here, from the configuration's rule.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROUTE_NORM_EPS = 1e-20


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, D). Split-half rotary embedding at positions 0..S-1."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(b, w_gate, w_up, w_down):
    return (jax.nn.silu(b @ w_gate) * (b @ w_up)) @ w_down


def _attention(q, k, v, window: Optional[int], query_block: int):
    """q (B, S, Hq, D), k, v (B, S, Hkv, D) -> (B, S, Hq, D). Causal softmax
    attention, under `window` also only the last `window` keys; a key-value
    group and `query_block` queries at a time (the module's one departure)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    block = query_block if s % query_block == 0 else s
    q = q.reshape(b, s // block, block, hkv, hq // hkv, d)
    key_at = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qb, kg, vg, first = args            # (B, block, G, D), (B, S, D), (B, S, D), ()
        query_at = first + jnp.arange(block)
        visible = key_at[None, :] <= query_at[:, None]
        if window is not None:
            visible &= key_at[None, :] > query_at[:, None] - window
        scores = jnp.einsum("bqgd,bkd->bgqk", qb, kg) / jnp.sqrt(F32(d))
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(scores, axis=-1), vg)

    def group(args):
        qg, kg, vg = args                   # (B, blocks, block, G, D), (B, S, D), (B, S, D)
        firsts = jnp.arange(s // block) * block
        return jax.lax.map(lambda a: one((a[0], kg, vg, a[1])), (jnp.moveaxis(qg, 1, 0), firsts))

    out = jax.lax.map(group, (jnp.moveaxis(q, 3, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    # (Hkv, blocks, B, block, G, D) -> (B, S, Hq, D)
    return jnp.transpose(out, (2, 1, 3, 0, 4, 5)).reshape(b, s, hq, d)


def _gates(scores, expert_bias, top_k: int, route_scale: float):
    """scores (B, S, E) -> (the gate of every published expert, zero where it
    was not chosen; the chosen experts (B, S, k))."""
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(expert_bias), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS) * route_scale
    onehot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32)          # (B, S, k, E)
    return jnp.einsum("bsk,bske->bse", weights, onehot), chosen


@functools.partial(jax.jit, static_argnames=(
    "full", "dense", "window", "theta", "eps", "top_k", "route_scale", "held_experts",
    "frozen_leaves", "query_block"))
def _layer(x, lp, *, full: bool, dense: bool, window: int, theta: float, eps: float, top_k: int,
           route_scale: float, held_experts: Optional[Tuple[int, int]],
           frozen_leaves: Tuple[str, ...], query_block: int):
    """-> (x after the layer, the chosen experts (B, S, k); None for a dense layer)."""
    with jax.default_matmul_precision("highest"):
        lp = {name: jax.lax.stop_gradient(w) if name in frozen_leaves else w
              for name, w in lp.items()}
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        a = _rmsnorm(x, lp["ln1_scale"], eps)
        q = jnp.einsum("bse,ehd->bshd", a, lp["wq"])
        k = jnp.einsum("bse,ehd->bshd", a, lp["wk"])
        v = jnp.einsum("bse,ehd->bshd", a, lp["wv"])
        g = jnp.einsum("bse,ehd->bshd", a, lp["wg"])
        q, k = _rmsnorm(q, lp["q_norm_scale"], eps), _rmsnorm(k, lp["k_norm_scale"], eps)
        if not full:
            q, k = _rope(q, theta), _rope(k, theta)
        o = _attention(q, k, v, None if full else window, query_block) * jax.nn.sigmoid(g)
        x = x + _rmsnorm(jnp.einsum("bshd,hde->bse", o, lp["wo"]), lp["ln1_post_scale"], eps)

        b = _rmsnorm(x, lp["ln2_scale"], eps)
        if dense:
            out, chosen = _swiglu(b, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        else:
            scores = jax.nn.sigmoid(b @ lp["router"])                     # (B, S, E)
            gates, chosen = _gates(scores, lp["expert_bias"], top_k, route_scale)
            first, last = held_experts or (0, scores.shape[-1])

            @jax.checkpoint      # the backward pass computes an expert again
            def gated(w_gate, w_up, w_down, gate):
                return gate[..., None] * _swiglu(b, w_gate, w_up, w_down)

            out, _ = jax.lax.scan(
                lambda total, expert: (total + gated(*expert), None),
                _swiglu(b, lp["ws_gate"], lp["ws_up"], lp["ws_down"]),
                (lp["we_gate"], lp["we_up"], lp["we_down"],
                 jnp.moveaxis(gates[..., first:last], -1, 0)))
        return x + _rmsnorm(out, lp["ln2_post_scale"], eps), chosen


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, head, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, scale.astype(F32), eps) @ head.astype(F32)


def layers_of(params: Dict[str, Any]) -> Iterator[Dict[str, Any]]:
    """The stack's layers in order, out of the run-stacked tree."""
    for period in params["runs"]:
        for repeat in range(next(iter(period[0].values())).shape[0]):
            for position in period:
                yield {name: w[repeat] for name, w in position.items()}


def _layer_fn(index: int, *, global_attn_every: int, num_dense_layers: int, sliding_window: int,
              rope_theta: float, norm_eps: float, top_k: int, route_scale: float,
              held_experts: Optional[Tuple[int, int]], frozen_leaves: Tuple[str, ...] = (),
              query_block: int):
    return functools.partial(
        _layer, full=(index + 1) % global_attn_every == 0, dense=index < num_dense_layers,
        window=int(sliding_window), theta=float(rope_theta), eps=float(norm_eps),
        top_k=int(top_k), route_scale=float(route_scale),
        held_experts=None if held_experts is None else tuple(held_experts),
        frozen_leaves=tuple(frozen_leaves), query_block=int(query_block))


def forward(params: Dict[str, Any], tokens: jax.Array, *, query_block: int = 1024,
            **arch) -> Tuple[jax.Array, List[Any]]:
    """(B, S) int tokens -> ((B, S, V) float32 logits, the chosen experts of
    every expert layer)."""
    x = params["wte"][tokens].astype(F32) * jnp.sqrt(F32(params["wte"].shape[1]))
    chosen = []
    for index, lp in enumerate(layers_of(params)):
        x, layer_chosen = _layer_fn(index, query_block=query_block, **arch)(x, lp)
        if layer_chosen is not None:
            chosen.append(layer_chosen)
    return _head(x, params["lnf_scale"], params["lm_head"], eps=float(arch["norm_eps"])), chosen


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
    x = params["wte"][tokens].astype(F32) * jnp.sqrt(F32(params["wte"].shape[1]))
    for index, lp in enumerate(layers_of(params)):
        x, _ = jax.checkpoint(_layer_fn(index, query_block=query_block, **arch))(x, lp)

    @jax.checkpoint
    def chunk_ce(args):
        xc, tc = args
        logp = jax.nn.log_softmax(
            _head(xc, params["lnf_scale"], params["lm_head"], eps=float(arch["norm_eps"])), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tc[..., None], axis=-1))

    b, s, e = x.shape
    n = max(s // head_rows, 1) if s % head_rows == 0 else 1
    ce_sum = jnp.sum(jax.lax.map(chunk_ce, (
        jnp.moveaxis(x.reshape(b, n, s // n, e), 1, 0),
        jnp.moveaxis(targets.reshape(b, n, s // n), 1, 0))))
    return ce_sum / total_tokens, ce_sum
