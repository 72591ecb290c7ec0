"""Plain reference for the `smallthinker` family
(PowerInfer/SmallThinker-21BA3B-Instruct), from the keys of its config.json
and, for what no key says, the configuration file's `assumed` group.

Straightforward `jax.numpy` in float32 at "highest" matmul precision: no
kernels, no sort, no grouped matmul, nothing imported from `ray_tpu`. Every
layer is an expert layer; layer i of the published 52 is, with
`full = sliding_window_layout[i] == 0` and `rope = rope_layout[i] == 1`
(both lists follow one rule: the FIRST layer of every period of
`global_attn_every` = 4 is full and encodes no positions, the other three
slide and rotate):

  r  = x Wr                             (T, 64) router logits in float32, from
                                        the tensor the layer's ATTENTION
                                        sublayer takes, BEFORE its norm: the
                                        routing is decided before attention
  a  = RMSNorm(x, g1, 1e-6)
  q = a Wq (28 x 128), k = a Wk (4 x 128), v = a Wv (4 x 128)
  rope: split-half rotary positions on q and k, whole head, theta 1.5e6
  sliding layer: key j is visible to query t iff t - 4096 < j <= t
  full layer:    key j is visible iff j <= t
  x  = x + softmax(q k^T / sqrt(128)) v  Wo
  m  = RMSNorm(x, g2, 1e-6)
  e  = the 6 largest of r;  w = softmax(r[e])   (= the softmax over all 64,
                                        the chosen ones, renormalised)
  x  = x + sum over j in e of w_j (relu(m Wgate_j) * (m Wup_j)) Wdown_j

No shared expert, no selection bias, no dense layer, no biases, no QK-norm,
no output gate, no norm on a sublayer's output, no embedding scale; untied
embedding and head, a final RMSNorm. ISSUE 36 read the router's input as the
NORMED tensor `a`; the family's graph applies the router to the layer's
input and takes the attention norm afterwards (the configuration file's
`assumed.router_input` gives the origin), and the program and this file
follow that.

The routing is a discrete choice, and it is made on the stream as the
configuration's activation precision holds it (`router_reads`, the file's
`dtype.compute`: the published `torch_dtype` and the program's stream are
bfloat16): the router's input is rounded to that precision first, its
gradient passed straight through, and the matmul is float32 as every other.
The first layer's router reads a token's embedding alone, so every occurrence
of a token id is routed alike; of Zipf ids one id is 15% of a 16,384-token
row, and where its sixth and seventh logits lie within the rounding of the
embedding (0.0026 apart on seed 3600531676, my chip run and CPU count, PR 36)
a float32 router sends 2,439 rows to another expert than the model does:
that is another model's routing, not an error of arithmetic.

Departures from the published model (the configuration file's `departures`):
the loss is the cross entropy alone (no key defines a balancing loss);
`frozen_leaves` names the leaves of a layer that are read as constants and
so get a zero gradient (a chip's share does not train its router, whose
whole gradient needs the outputs of all the chosen experts).

One chip's share: `held_experts` = (first, last) names the published experts
whose weights the parameter tree holds; the router keeps all its outputs and
its top-k over all of them, the gates are the softmax over all k chosen, and
the layer adds the sum over the chosen experts that are held. Every held
expert is applied to every token and weighted by its gate, or by zero.

ONE departure from "straightforward", because one 16,384-token row's float32
scores are 30 GB a layer and its float32 queries, gate and up projections
several more: a layer is taken a block of positions at a time. The keys and
values are made for the whole sequence first (4 heads: 34 MB each); then,
for each block of `query_block` positions and under `jax.checkpoint` (the
backward pass computes the block again): the router, the queries, attention a
key-value group at a time (the group's scores against every key: 0.47 GB),
the output projection and the experts. Every step of it is position-wise but
the scores, so the numbers are those of the layer taken whole. The head runs
over `head_rows` positions at a time as olmoe_ref's does.

The weights are the system's own (the comparison is of arithmetic, not of
initialisation), so the reference reads the program's parameter layout:
params["runs"][r][p] is one dict of leaves, each stacked on a leading axis of
the run's repeats, the stack's layers in order being run by run, repeat by
repeat, position by position: wq (M, Hq, D), wk/wv (M, Hkv, D), wo (Hq, D,
M), ln1_scale, ln2_scale (M,), router (M, E), we_gate/we_up (held, M, F),
we_down (held, F, M). Which layer is of which kind is worked out here, from
the configuration's rule.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta, positions):
    """x: (B, S, H, D) at `positions` (S,). Split-half rotary embedding."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _reglu(m, w_gate, w_up, w_down):
    return (jax.nn.relu(m @ w_gate) * (m @ w_up)) @ w_down


def _attention(q, k, v, query_at, window: Optional[int]):
    """q (B, Q, Hq, D) at positions `query_at` (Q,), k, v (B, S, Hkv, D) ->
    (B, Q, Hq, D). Causal softmax attention, under `window` also only the
    last `window` keys; a key-value group at a time."""
    b, n, hq, d = q.shape
    hkv = k.shape[2]
    key_at = jnp.arange(k.shape[1])
    visible = key_at[None, :] <= query_at[:, None]
    if window is not None:
        visible &= key_at[None, :] > query_at[:, None] - window

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args                   # (B, Q, G, D), (B, S, D), (B, S, D)
        scores = jnp.einsum("bqgd,bkd->bgqk", qg, kg) / jnp.sqrt(F32(d))
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(scores, axis=-1), vg)

    out = jax.lax.map(group, (jnp.moveaxis(q.reshape(b, n, hkv, hq // hkv, d), 2, 0),
                              jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, n, hq, d)        # (Hkv, B, Q, G, D) -> (B, Q, Hq, D)


def _as_held(x, dtype: Optional[str]):
    """x with the values a stream of `dtype` holds; the gradient is x's own.
    Behind a barrier: inside a jitted program the TPU's compiler removes a
    narrowing and widening pair of converts (the first chip readings with this
    rounding, PR 36, equalled those without it to the last digit)."""
    if dtype is None or jnp.dtype(dtype) == x.dtype:
        return x
    held = jax.lax.optimization_barrier(x.astype(jnp.dtype(dtype))).astype(F32)
    return x + jax.lax.stop_gradient(held - x)


def _gates(logits, top_k: int):
    """logits (B, S, E) -> (the gate of every published expert, zero where it
    was not chosen; the chosen experts (B, S, k)): the softmax over the k
    largest logits."""
    chosen_logits, chosen = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(chosen_logits, axis=-1)
    onehot = jax.nn.one_hot(chosen, logits.shape[-1], dtype=F32)          # (B, S, k, E)
    return jnp.einsum("bsk,bske->bse", weights, onehot), chosen


@functools.partial(jax.jit, static_argnames=(
    "full", "window", "theta", "eps", "top_k", "held_experts", "frozen_leaves", "router_reads",
    "query_block"))
def _layer(x, lp, *, full: bool, window: int, theta: float, eps: float, top_k: int,
           held_experts: Optional[Tuple[int, int]], frozen_leaves: Tuple[str, ...],
           router_reads: Optional[str], query_block: int):
    """-> (x after the layer, the chosen experts (B, S, k))."""
    with jax.default_matmul_precision("highest"):
        lp = {name: jax.lax.stop_gradient(w) if name in frozen_leaves else w
              for name, w in lp.items()}
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        b, s, e = x.shape
        block = query_block if s % query_block == 0 else s
        a = _rmsnorm(x, lp["ln1_scale"], eps)
        k = jnp.einsum("bse,ehd->bshd", a, lp["wk"])
        v = jnp.einsum("bse,ehd->bshd", a, lp["wv"])
        if not full:
            k = _rope(k, theta, jnp.arange(s))
        first, last = held_experts or (0, lp["router"].shape[-1])

        @jax.checkpoint
        def positions(args):
            xb, start = args                # (B, block, E), ()
            at = start + jnp.arange(block)
            # before the attention's norm
            gates, chosen = _gates(_as_held(xb, router_reads) @ lp["router"], top_k)
            q = jnp.einsum("bse,ehd->bshd", _rmsnorm(xb, lp["ln1_scale"], eps), lp["wq"])
            if not full:
                q = _rope(q, theta, at)
            o = _attention(q, k, v, at, None if full else window)
            xb = xb + jnp.einsum("bshd,hde->bse", o, lp["wo"])
            m = _rmsnorm(xb, lp["ln2_scale"], eps)
            out, _ = jax.lax.scan(
                lambda total, expert: (
                    total + expert[3][..., None] * _reglu(m, expert[0], expert[1], expert[2]), None),
                jnp.zeros_like(xb),
                (lp["we_gate"], lp["we_up"], lp["we_down"], jnp.moveaxis(gates[..., first:last], -1, 0)))
            return xb + out, chosen

        out, chosen = jax.lax.map(positions, (
            jnp.moveaxis(x.reshape(b, s // block, block, e), 1, 0), jnp.arange(s // block) * block))
        # (blocks, B, block, ...) -> (B, S, ...)
        return (jnp.moveaxis(out, 0, 1).reshape(b, s, e),
                jnp.moveaxis(chosen, 0, 1).reshape(b, s, top_k))


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


def _layer_fn(index: int, *, global_attn_every: int, sliding_window: int, rope_theta: float,
              norm_eps: float, top_k: int, held_experts: Optional[Tuple[int, int]],
              frozen_leaves: Tuple[str, ...] = (), router_reads: Optional[str] = None,
              query_block: int):
    return functools.partial(
        _layer, full=index % global_attn_every == 0, window=int(sliding_window),
        theta=float(rope_theta), eps=float(norm_eps), top_k=int(top_k),
        held_experts=None if held_experts is None else tuple(held_experts),
        frozen_leaves=tuple(frozen_leaves), router_reads=router_reads,
        query_block=int(query_block))


def forward(params: Dict[str, Any], tokens: jax.Array, *, query_block: int = 1024,
            **arch) -> Tuple[jax.Array, List[Any]]:
    """(B, S) int tokens -> ((B, S, V) float32 logits, the chosen experts of
    every layer)."""
    x = params["wte"][tokens].astype(F32)
    chosen = []
    for index, lp in enumerate(layers_of(params)):
        x, layer_chosen = _layer_fn(index, query_block=query_block, **arch)(x, lp)
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
    x = params["wte"][tokens].astype(F32)
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
