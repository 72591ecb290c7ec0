"""Plain reference for OLMoE (allenai/OLMoE-1B-7B), after Hugging Face's
`modeling_olmoe.py`.

Straightforward `jax.numpy` in float32 at "highest" matmul precision (on a
TPU a float32 matmul otherwise runs in bfloat16 passes): no kernels, no
sort, no grouped matmul, no capacity, nothing imported from `ray_tpu`.
Every expert is applied to every token and weighted by its gate, or by
zero where the router did not choose it. One layer is

  h  = RMSNorm(x);  q = RMSNorm_q(h Wq), k = RMSNorm_k(h Wk), v = h Wv
       (QK-norm over the whole projection, before the split into heads;
       `clip_qkv` is null); split-half rope on q, k; causal softmax
       attention; x = x + attn Wo
  h2 = RMSNorm(x);  p = softmax(h2 Wr) in float32 over all experts; the
       top_k largest p_e are the gates as they are (`norm_topk_prob`
       false) or divided by their sum (true);
       x = x + sum_e gate_e * W_down,e (silu(W_gate,e h2) * (W_up,e h2))

and the load-balancing loss of a layer is E * sum_e f_e P_e, f_e the share
of (token, choice) pairs routed to e and P_e the mean of p_e over the
tokens, summed over the layers.

Departures from the published code, each also in the configuration file:
the Hugging Face function sums f over the k choice slots without dividing
(its value is top_k times this one; the coefficient is the published
0.01 either way); the paper's router z-loss and dropout are not applied.

The weights are the system's own (the comparison is of arithmetic, not of
initialisation), so the reference reads the program's parameter layout:
`blocks` leaves stacked on a leading layer axis, wq/wk/wv (L, M, H, D), wo
(L, H, D, M), q_norm_scale/k_norm_scale (L, H x D), router (L, M, E),
we_gate/we_up (L, E, M, F), we_down (L, E, F, M). It runs layer by layer
and upcasts one layer at a time.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


def _attention(q, k, v):
    """q, k, v: (B, S, H, D). Causal softmax attention, one head at a time:
    a 4,096-token row's float32 scores are 64 MiB a head, and the backward
    pass computes a head's again instead of keeping all sixteen."""
    s, d = q.shape[1], q.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                                            # (B, S, D)
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh) / jnp.sqrt(F32(d))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), vh)

    heads = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(heads, 0, 2)


def _gates(p, top_k: int, norm_topk_prob: bool):
    """p (B, S, E) -> (gate of every expert, zero where it was not chosen;
    the chosen experts (B, S, k))."""
    chosen_p, chosen = jax.lax.top_k(p, top_k)
    if norm_topk_prob:
        chosen_p = chosen_p / jnp.sum(chosen_p, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, p.shape[-1], dtype=F32)          # (B, S, k, E)
    return jnp.einsum("bsk,bske->bse", chosen_p, onehot), chosen


@functools.partial(jax.jit, static_argnames=("theta", "eps", "top_k", "norm_topk_prob"))
def _layer(x, lp, *, theta: float, eps: float, top_k: int, norm_topk_prob: bool):
    """-> (x after the layer, its load-balancing loss, the chosen experts,
    the router's probabilities summed over the tokens (E,))."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        h = _rmsnorm(x, lp["ln1_scale"], eps)
        q = jnp.einsum("bse,ehd->bshd", h, lp["wq"])
        k = jnp.einsum("bse,ehd->bshd", h, lp["wk"])
        v = jnp.einsum("bse,ehd->bshd", h, lp["wv"])
        b, s, heads, d = q.shape
        q = _rmsnorm(q.reshape(b, s, heads * d), lp["q_norm_scale"], eps).reshape(q.shape)
        k = _rmsnorm(k.reshape(b, s, heads * d), lp["k_norm_scale"], eps).reshape(k.shape)
        q, k = _rope(q, theta), _rope(k, theta)
        x = x + jnp.einsum("bshd,hde->bse", _attention(q, k, v), lp["wo"])

        h2 = _rmsnorm(x, lp["ln2_scale"], eps)
        p = jax.nn.softmax(h2 @ lp["router"], axis=-1)                # (B, S, E)
        gates, chosen = _gates(p, top_k, norm_topk_prob)

        @jax.checkpoint      # the backward pass computes an expert again: nothing is kept of the 64
        def gated(w_gate, w_up, w_down, gate):
            return gate[..., None] * ((jax.nn.silu(h2 @ w_gate) * (h2 @ w_up)) @ w_down)

        def one_expert(total, expert):
            return total + gated(*expert), None

        out, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(x),
            (lp["we_gate"], lp["we_up"], lp["we_down"], jnp.moveaxis(gates, -1, 0)))

        n_experts = p.shape[-1]
        share = jnp.mean(jax.nn.one_hot(chosen, n_experts, dtype=F32), axis=(0, 1, 2))
        aux = n_experts * jnp.sum(jax.lax.stop_gradient(share) * jnp.mean(p, axis=(0, 1)))
        return x + out, aux, chosen, jnp.sum(p, axis=(0, 1))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, head, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, scale.astype(F32), eps) @ head.astype(F32)


def forward(params: Dict[str, Any], tokens: jax.Array, *, top_k: int,
            norm_topk_prob: bool = False, rope_theta: float = 10000.0,
            norm_eps: float = 1e-5, n_layers: Optional[int] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(B, S) int tokens -> ((B, S, V) float32 logits, the load-balancing
    loss summed over the layers, the chosen experts (L, B, S, k))."""
    blocks = params["blocks"]
    depth = n_layers if n_layers is not None else blocks["wq"].shape[0]
    x = params["wte"][tokens].astype(F32)
    aux, chosen = jnp.zeros((), F32), []
    for layer in range(depth):
        lp = {name: w[layer] for name, w in blocks.items()}
        x, layer_aux, layer_chosen, _ = _layer(
            x, lp, theta=float(rope_theta), eps=float(norm_eps), top_k=int(top_k),
            norm_topk_prob=bool(norm_topk_prob))
        aux = aux + layer_aux
        chosen.append(layer_chosen)
    logits = _head(x, params["lnf_scale"], params["lm_head"], eps=float(norm_eps))
    return logits, aux, jnp.stack(chosen)


def forward_logits(params: Dict[str, Any], tokens: jax.Array, **arch) -> jax.Array:
    return forward(params, tokens, **arch)[0]


def router_aux(params: Dict[str, Any], tokens: jax.Array, **arch) -> jax.Array:
    """The load-balancing loss of (B, S) tokens, summed over the layers."""
    return forward(params, tokens, **arch)[1]


def objective(params: Dict[str, Any], tokens: jax.Array, *, router_aux_loss_coef: float,
              **arch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """What training minimises on (B, S + 1) tokens, the whole batch at
    once and differentiable: mean next-token cross entropy + coefficient x
    load-balancing loss. -> (objective, its two parts)."""
    logits, aux, _ = forward(params, tokens[:, :-1], **arch)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    return ce + router_aux_loss_coef * aux, {"cross_entropy": ce, "router_aux": aux}


def expert_shares(params: Dict[str, Any], tokens: jax.Array, *, rows_at_a_time: int = 1,
                  **arch) -> jax.Array:
    """f of the load-balancing loss for the whole (B, S) batch: (L, E), the
    share of its (token, choice) pairs routed to each expert of each layer,
    a few rows at a time."""
    n_experts = params["blocks"]["router"].shape[-1]
    counts = 0
    for i in range(0, tokens.shape[0], rows_at_a_time):
        chosen = forward(params, tokens[i: i + rows_at_a_time], **arch)[2]      # (L, b, S, k)
        counts = counts + jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=F32), axis=(1, 2, 3))
    return counts / jnp.sum(counts, axis=-1, keepdims=True)


def objective_part(params: Dict[str, Any], rows: jax.Array, shares: jax.Array, *,
                   total_tokens: int, router_aux_loss_coef: float, head_rows: int = 1024,
                   top_k: int, norm_topk_prob: bool = False, rope_theta: float = 10000.0,
                   norm_eps: float = 1e-5):
    """What the (b, S + 1) `rows` add to the objective of a batch of
    `total_tokens` targets, of which `shares` (L, E) are the whole batch's
    `expert_shares` (constants of the load-balancing loss: no gradient flows
    through f), differentiable: (their share, their summed cross entropy).
    Each layer is computed again in the backward pass, and the head runs
    over `head_rows` positions at a time."""
    tokens, targets = rows[:, :-1], rows[:, 1:]
    blocks = params["blocks"]
    layer = jax.checkpoint(functools.partial(
        _layer, theta=float(rope_theta), eps=float(norm_eps), top_k=int(top_k),
        norm_topk_prob=bool(norm_topk_prob)))
    x = params["wte"][tokens].astype(F32)
    aux = jnp.zeros((), F32)
    for index in range(blocks["wq"].shape[0]):
        x, _, _, p_sum = layer(x, {name: w[index] for name, w in blocks.items()})
        aux = aux + shares.shape[-1] * jnp.sum(shares[index] * p_sum) / total_tokens

    @jax.checkpoint
    def chunk_ce(args):
        xc, tc = args
        logp = jax.nn.log_softmax(
            _head(xc, params["lnf_scale"], params["lm_head"], eps=float(norm_eps)), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tc[..., None], axis=-1))

    b, s, e = x.shape
    n = max(s // head_rows, 1) if s % head_rows == 0 else 1
    ce_sum = jnp.sum(jax.lax.map(chunk_ce, (
        jnp.moveaxis(x.reshape(b, n, s // n, e), 1, 0),
        jnp.moveaxis(targets.reshape(b, n, s // n), 1, 0))))
    return ce_sum / total_tokens + router_aux_loss_coef * aux, ce_sum


def loss(params: Dict[str, Any], tokens: jax.Array, *, rows_at_a_time: int = 1,
         **arch) -> float:
    """Mean next-token cross entropy of (B, S + 1) tokens, a few rows at a
    time so that the float32 logits of the whole batch are never held."""
    total, count = 0.0, 0
    for i in range(0, tokens.shape[0], rows_at_a_time):
        rows = tokens[i: i + rows_at_a_time]
        logits = forward_logits(params, rows[:, :-1], **arch)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, rows[:, 1:, None], axis=-1)
        total += float(-jnp.sum(picked))
        count += int(rows.shape[0] * (rows.shape[1] - 1))
    return total / count
