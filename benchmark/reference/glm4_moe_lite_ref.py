"""Plain reference for the `glm4_moe_lite` family (zai-org/GLM-4.7-Flash), by
the layer equations ISSUE 44 states: latent attention, one leading dense
layer, sigmoid-routed experts beside a shared one, and one multi-token
prediction module in the objective.

Straightforward `jax.numpy` in float32 at "highest" matmul precision: no
kernels, no sort, no grouped matmul, nothing imported from `ray_tpu`. What
the family shares with `afmoe` to the letter is taken from that family's
reference and not written twice: the RMSNorm, the split-half rotary
embedding, the SwiGLU, the sigmoid router's gates (`_gates`: the top-k of
score + bias, the bias without gradient and in the selection only, the
chosen scores over their sum + 1e-20, times the scale), the blocked causal
attention (that module's one departure from "straightforward": a block of
queries at a time under `jax.checkpoint`, since one 8,192-token row's
float32 scores are 5.4 GB a layer here) and the final norm and head.

Layer i, pre-norm, x + sublayer(norm(x)), no norm on a sublayer's output:

  h    = RMSNorm(x)
  c_q  = RMSNorm_q(h Wqa)                       q_lora_rank
  q    = c_q Wqb  -> H heads of D = [q_nope | q_rope], q_rope the LAST R
  [c_kv | k_rope] = h Wkva                      kv_lora_rank | R
  c_kv = RMSNorm_kv(c_kv)
  [k_nope | v] a head = c_kv Wkvb               (D - R) | D
  q = [q_nope | rot(q_rope)], k = [k_nope | rot(k_rope)]: ONE rotary key head
       of R, theta rope_theta, given to all H heads
  x1   = x + softmax(q k^T / sqrt(D), causal) v Wo
  m    = RMSNorm(x1)
  x2   = x1 + SwiGLU(m)                          layers before first_k_dense_replace
  x2   = x1 + shared(m) + sum over the chosen experts HELD of g_e SwiGLU_e(m)

and after the stack's final RMSNorm, H_i the result at position i, E the
embedding (no scale), the multi-token prediction module:

  z_i  = [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(H_i)] Weh
  one more layer of the expert kind on z (positions 0..S-1), RMSNorm, the
  SHARED head: logits for t_{i+2}

  objective = CE(main logits, t_{i+1}) + mtp_loss_weight x CE(module logits,
  t_{i+2}), each the mean over the positions that have such a target.

One chip's share is afmoe_ref's: `held_experts` = (first, last) of the
published experts whose weights the tree holds, the router keeps all its
outputs and its top-k, the gates are normalised over all k chosen, `routed`
sums the chosen experts that are held; `frozen_leaves` are read as
constants.

The weights are the system's own, so the reference reads the program's
layout: params["runs"][r][p] a dict of leaves stacked on a leading axis of
the run's repeats (wq_a (M, Rq), q_a_norm_scale (Rq,), wq_b (Rq, H, D), wkv_a
(M, Rkv + R), kv_a_norm_scale (Rkv,), wkv_b (Rkv, H, 2 D - R), wo (H, D, M),
ln1_scale, ln2_scale (M,); a dense layer's w_gate / w_up (M, F), w_down; an
expert layer's router (M, E), expert_bias (E,), we_* (held, ...), ws_*),
params["mtp"] = {enorm_scale, hnorm_scale (M,), eh_proj (2 M, M), norm_scale
(M,), block: one expert layer's leaves stacked (1, ...)}, and wte, lnf_scale,
lm_head.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .afmoe_ref import F32, _attention, _gates, _head, _rmsnorm, _rope, _swiglu


@functools.partial(jax.jit, static_argnames=(
    "dense", "rope", "theta", "eps", "top_k", "route_scale", "held_experts", "frozen_leaves",
    "query_block"))
def _layer(x, lp, *, dense: bool, rope: int, theta: float, eps: float, top_k: int,
           route_scale: float, held_experts: Optional[Tuple[int, int]],
           frozen_leaves: Tuple[str, ...], query_block: int):
    """-> (x after the layer, the chosen experts (B, S, k); None for a dense layer)."""
    with jax.default_matmul_precision("highest"):
        lp = {name: jax.lax.stop_gradient(w) if name in frozen_leaves else w
              for name, w in lp.items()}
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        h = _rmsnorm(x, lp["ln1_scale"], eps)
        q = jnp.einsum("bsr,rhd->bshd", _rmsnorm(h @ lp["wq_a"], lp["q_a_norm_scale"], eps),
                       lp["wq_b"])
        kv_a = h @ lp["wkv_a"]
        kv = jnp.einsum("bsr,rhd->bshd", _rmsnorm(kv_a[..., :-rope], lp["kv_a_norm_scale"], eps),
                        lp["wkv_b"])
        nope = q.shape[-1] - rope
        k_rope = _rope(kv_a[..., None, -rope:], theta)                     # (B, S, 1, R)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (*q.shape[:-1], rope))], axis=-1)
        o = _attention(q, k, kv[..., nope:], None, query_block)
        x = x + jnp.einsum("bshd,hde->bse", o, lp["wo"])

        m = _rmsnorm(x, lp["ln2_scale"], eps)
        if dense:
            return x + _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        scores = jax.nn.sigmoid(m @ lp["router"])                          # (B, S, E)
        gates, chosen = _gates(scores, lp["expert_bias"], top_k, route_scale)
        first, last = held_experts or (0, scores.shape[-1])

        @jax.checkpoint      # the backward pass computes an expert again
        def gated(w_gate, w_up, w_down, gate):
            return gate[..., None] * _swiglu(m, w_gate, w_up, w_down)

        out, _ = jax.lax.scan(
            lambda total, expert: (total + gated(*expert), None),
            _swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"]),
            (lp["we_gate"], lp["we_up"], lp["we_down"], jnp.moveaxis(gates[..., first:last], -1, 0)))
        return x + out, chosen


def _layer_fn(dense: bool, *, qk_rope_dim: int, rope_theta: float, norm_eps: float, top_k: int,
              route_scale: float, held_experts: Optional[Tuple[int, int]],
              frozen_leaves: Tuple[str, ...] = (), query_block: int, **_):
    """One layer as a function of (x, its leaves), computed again in the
    backward pass: what a row holds through the stack is a layer's input each."""
    return jax.checkpoint(functools.partial(
        _layer, dense=dense, rope=int(qk_rope_dim), theta=float(rope_theta), eps=float(norm_eps),
        top_k=int(top_k), route_scale=float(route_scale),
        held_experts=None if held_experts is None else tuple(held_experts),
        frozen_leaves=tuple(frozen_leaves), query_block=int(query_block)))


def _stack(params, tokens, *, num_dense_layers: int, query_block: int, **arch):
    """(B, S) tokens -> (the stack's output before its final norm, the chosen
    experts of every expert layer). The layers in order are the tree's runs,
    repeat by repeat, position by position; a run of several repeats is one
    `lax.scan` over its stacked leaves, so that the gradient of a stacked leaf
    is written a layer at a time into ONE buffer (taken a slice at a time, the
    four expert layers' gradients are each padded to the stack's size first:
    6.8 GB beside the parameters at the published widths)."""
    x, chosen, index = params["wte"][tokens].astype(F32), [], 0
    for period in params["runs"]:
        repeats = next(iter(period[0].values())).shape[0]
        dense = [index + p < num_dense_layers for p in range(len(period))]
        assert all((index + r * len(period) + p < num_dense_layers) == dense[p]
                   for r in range(repeats) for p in range(len(period))), "a run mixes dense and expert layers"
        layers = [_layer_fn(kind, query_block=query_block, **arch) for kind in dense]

        def repeat(x, lps, layers=layers):
            picked = []
            for layer, lp in zip(layers, lps):
                x, layer_chosen = layer(x, lp)
                picked += [] if layer_chosen is None else [layer_chosen]
            return x, picked

        if repeats == 1:
            x, picked = repeat(x, [{name: w[0] for name, w in lp.items()} for lp in period])
            chosen += picked
        else:
            x, picked = jax.lax.scan(repeat, x, period)
            chosen += [layer_chosen[r] for r in range(repeats) for layer_chosen in picked]
        index += repeats * len(period)
    return x, chosen


@functools.partial(jax.jit, static_argnames=("eps",))
def _joined(embedded, hidden, mp, *, eps: float):
    """[RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(H_i)] Weh."""
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [_rmsnorm(embedded, mp["enorm_scale"].astype(F32), eps),
             _rmsnorm(hidden, mp["hnorm_scale"].astype(F32), eps)], axis=-1) @ mp["eh_proj"].astype(F32)


def _module(params, x, next_tokens, *, query_block: int, **arch):
    """The stack's output before its final norm and the tokens after its
    positions -> the module's stream before ITS final norm (`mtp.norm_scale`)."""
    eps, mp = float(arch["norm_eps"]), params["mtp"]
    hidden = _rmsnorm(x, params["lnf_scale"].astype(F32), eps)
    z = _joined(params["wte"][next_tokens].astype(F32), hidden,
                {name: w for name, w in mp.items() if name != "block"}, eps=eps)
    return _layer_fn(False, query_block=query_block, **arch)(
        z, {name: w[0] for name, w in mp["block"].items()})[0]


def forward(params: Dict[str, Any], tokens: jax.Array, *, query_block: int = 1024,
            **arch) -> Tuple[jax.Array, List[Any]]:
    """(B, S) int tokens -> ((B, S, V) float32 logits of the main stack, the
    chosen experts of every expert layer)."""
    x, chosen = _stack(params, tokens, query_block=query_block, **arch)
    return _head(x, params["lnf_scale"], params["lm_head"], eps=float(arch["norm_eps"])), chosen


def forward_logits(params: Dict[str, Any], tokens: jax.Array, **arch) -> jax.Array:
    return forward(params, tokens, **arch)[0]


def module_logits(params: Dict[str, Any], tokens: jax.Array, *, query_block: int = 1024,
                  **arch) -> jax.Array:
    """(B, S + 1) tokens -> (B, S, V): row i the module's logits for token
    i + 2 (the last row has no target in the batch)."""
    x, _ = _stack(params, tokens[:, :-1], query_block=query_block, **arch)
    z = _module(params, x, tokens[:, 1:], query_block=query_block, **arch)
    return _head(z, params["mtp"]["norm_scale"], params["lm_head"], eps=float(arch["norm_eps"]))


def _cross_entropy_sum(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def objective(params: Dict[str, Any], tokens: jax.Array, *, mtp_loss_weight: float,
              **arch) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """(B, S + 1) tokens, the whole batch at once and differentiable: (main +
    mtp_loss_weight x module, (the main mean cross entropy, the module's))."""
    b, s = tokens.shape[0], tokens.shape[1] - 1
    main = _cross_entropy_sum(forward_logits(params, tokens[:, :-1], **arch), tokens[:, 1:]) / (b * s)
    module = _cross_entropy_sum(module_logits(params, tokens, **arch)[:, :-1],
                                tokens[:, 2:]) / (b * (s - 1))
    return main + mtp_loss_weight * module, (main, module)


def objective_part(params: Dict[str, Any], rows: jax.Array, stats: None = None, *,
                   total_tokens: int, mtp_loss_weight: float, head_rows: int = 1024,
                   query_block: int = 1024, **arch):
    """What the (b, S + 1) `rows` add to the objective of a batch of
    `total_tokens` next-token targets (and total_tokens x (S - 1) / S targets
    of the module), differentiable: (their share, their summed MAIN cross
    entropy). Each layer is computed again in the backward pass (`_layer_fn`),
    and the head runs over `head_rows` positions at a time, for the stack and
    for the module alike."""
    del stats
    eps = float(arch["norm_eps"])
    tokens, targets = rows[:, :-1], rows[:, 1:]
    x, _ = _stack(params, tokens, query_block=query_block, **arch)
    z = _module(params, x, targets, query_block=query_block, **arch)
    b, s, e = x.shape
    n = max(s // head_rows, 1) if s % head_rows == 0 else 1

    def summed(stream, scale, targets, counted):
        @jax.checkpoint
        def chunk_ce(args):
            xc, tc, mc = args
            logp = jax.nn.log_softmax(_head(xc, scale, params["lm_head"], eps=eps), axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0] * mc)

        chunks = lambda a: jnp.moveaxis(a.reshape(b, n, s // n, *a.shape[2:]), 1, 0)  # noqa: E731
        return jnp.sum(jax.lax.map(chunk_ce, (chunks(stream), chunks(targets), chunks(counted))))

    ce_sum = summed(x, params["lnf_scale"], targets, jnp.ones((b, s), F32))
    # position i of the module predicts token i + 2: the last has no target
    after_next = jnp.concatenate([targets[:, 1:], targets[:, :1]], axis=1)
    has_target = jnp.broadcast_to((jnp.arange(s) < s - 1).astype(F32), (b, s))
    module_sum = summed(z, params["mtp"]["norm_scale"], after_next, has_target)
    share = ce_sum / total_tokens + mtp_loss_weight * module_sum / (total_tokens * (s - 1) / s)
    return share, ce_sum
