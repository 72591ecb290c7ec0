"""Plain reference for the `bailing_hybrid` family (inclusionAI/Ling-3.0-flash),
by the layer equations ISSUE 55 states: pre-norm blocks x <- x + mixer(RMSNorm(x)),
x <- x + mlp(RMSNorm(x)), no biases, a final RMSNorm and an untied head. Published
layer i has a LATENT-attention mixer iff (i + 1) % layer_group_size == 0 and a
Kimi-Delta-Attention (KDA) mixer otherwise; its MLP is a dense SwiGLU for
i < first_k_dense_replace and a group-limited sigmoid-routed expert layer beside a
shared expert after that. The layers run are `first_layer`, `first_layer` + 1, ...

Straightforward `jax.numpy` in float32 at "highest" matmul precision: no kernels,
no sort, no grouped matmul, NO CHUNKS, nothing imported from `ray_tpu`. The RMSNorm,
the split-half rotary embedding, the SwiGLU and the final norm and head are
`afmoe_ref`'s, to the letter, and not written twice.

  KDA  u = RMSNorm(x);  [q^ | k^ | v^ | f] = u W_in  (4 x H D);  [b | g] = u W_bg  (2 x H)
       q^, k^, v = silu(conv4(.)): causal, depthwise, no bias, zeros before t = 0
       q = q^ / sqrt(|q^|^2 + 1e-6) / sqrt(D),  k = k^ / sqrt(|k^|^2 + 1e-6)   a head
       a_t = lower_bound x sigmoid(exp(A_log[h]) (f_t + dt_bias))  a CHANNEL, in (-5, 0)
       beta_t = sigmoid(b_t)  a head
       S_t = (I - beta_t k_t k_t^T) Diag(e^{a_t}) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0  (D x D a head)
       o_t = S_t^T q_t;  y_t = RMSNorm_D(o_t) sigmoid(g_t[h]);  x + y W_out
  LAT  h = RMSNorm(x);  q = h W_q  (H heads of [q_nope N | q_rope R]: no q latent)
       [c | k_rope] = h W_kva (C | R);  c = RMSNorm_c(c);  [k_nope N | v V] a head = c W_kvb
       rotary (split-half, theta) on q_rope and on the ONE k_rope head given to all H
       o = softmax(q k^T / sqrt(N + R), causal) v;  y = o sigmoid((h W_g)[h]);  x + y W_o
  EXP  m = RMSNorm(x);  s = sigmoid(m W_r) over all E;  on s + expert_bias: a group's
       score the sum of its two largest, the `topk_group` best of `n_group` groups of
       neighbours kept, the k largest inside them chosen;  gates s[chosen] / (sum + 1e-20)
       x scale;  x + shared(m) + sum over the chosen experts HELD of g_e SwiGLU_e(m)

THE RECURRENCE RUNS ONE POSITION AT A TIME (`lax.scan` over t carrying S): the
program's chunked form and this file share no algorithm. Departures from
"straightforward", each forced by memory (nemotron_h_ref's reasons): the time axis is
cut into segments of `time_block` positions and a segment is computed again in the
backward pass, attention a block of queries at a time, an expert at a time under
`jax.checkpoint`, each layer computed again in the backward pass, the head over
`head_rows` positions at a time.

One chip's share is afmoe_ref's: `held_experts` = (first, last) of the published
experts whose weights the tree holds, the router keeps all its outputs, its groups and
its top-k, the gates are normalised over all k chosen, `routed` sums the chosen experts
that are held; `frozen_leaves` are read as constants.

The weights are the system's own, so the reference reads the program's layout:
params["runs"][r][p] a dict of leaves stacked on a leading axis of the run's repeats (a
KDA layer's ln1_scale (M,), kda_in (M, 4 H D), kda_bg (M, 2 H), kda_conv_w (3 H D, K),
kda_a_log (H,), kda_dt_bias (H D,), kda_norm_scale (D,), kda_out (H, D, M); a latent
layer's ln1_scale, wq (M, H, N + R), wkv_a (M, C + R), kv_a_norm_scale (C,), wkv_b (C, H,
N + V), wg (M, H), wo (H, V, M); a dense MLP's ln2_scale, w_gate / w_up (M, F), w_down;
an expert layer's ln2_scale, router (M, E), expert_bias (E,), we_* (held, ...), ws_*), and
wte, lnf_scale, lm_head.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .afmoe_ref import F32, ROUTE_NORM_EPS, _head, _rmsnorm, _rope, _swiglu

L2_EPS = 1e-6


def layer_kind(index: int, *, layer_group_size: int, first_k_dense_replace: int) -> Tuple[str, str]:
    """(mixer, mlp) of PUBLISHED layer `index`."""
    return ("latent" if (index + 1) % layer_group_size == 0 else "kda",
            "dense" if index < first_k_dense_replace else "experts")


def _recurrence(q, k, v, a, beta, time_block: int):
    """q, k, a (B, S, H, Dk), v (B, S, H, Dv), beta (B, S, H) -> o (B, S, H, Dv),
    position by position from S_0 = 0; a segment of `time_block` positions is
    computed again in the backward pass."""
    bsz, s, h, dk = q.shape
    block = time_block if s % time_block == 0 else s

    def position(state, inputs):
        q_t, k_t, v_t, a_t, beta_t = inputs               # (B, H, D) x 4, (B, H)
        state = jnp.exp(a_t)[..., None] * state           # Diag(alpha_t) S_{t-1}
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)    # what the decayed state returns for k_t
        state = state + (beta_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def segment(state, inputs):
        return jax.lax.scan(position, state, inputs)

    def cut(t):     # (B, S, ...) -> (S / block, block, B, ...)
        return jnp.moveaxis(t, 1, 0).reshape(s // block, block, *t.shape[:1], *t.shape[2:])

    _, out = jax.lax.scan(segment, jnp.zeros((bsz, h, dk, v.shape[-1]), F32),
                          (cut(q), cut(k), cut(v), cut(a), cut(beta)))
    return jnp.moveaxis(out.reshape(s, bsz, h, v.shape[-1]), 0, 1)


def _kda(x, lp, *, eps: float, lower_bound: float, time_block: int):
    heads, d = lp["kda_a_log"].shape[0], lp["kda_norm_scale"].shape[0]
    inner, (bsz, s, _) = heads * d, x.shape
    u = _rmsnorm(x, lp["ln1_scale"], eps)
    projected, beta_gate = u @ lp["kda_in"], u @ lp["kda_bg"]
    taps = lp["kda_conv_w"].shape[-1]
    padded = jnp.pad(projected[..., :3 * inner], ((0, 0), (taps - 1, 0), (0, 0)))
    convolved = jax.nn.silu(sum(lp["kda_conv_w"][:, j] * padded[:, j:j + s] for j in range(taps)))
    q, k, v = (t.reshape(bsz, s, heads, d) for t in jnp.split(convolved, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / jnp.sqrt(F32(d))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    gate_in = (projected[..., 3 * inner:] + lp["kda_dt_bias"]).reshape(bsz, s, heads, d)
    a = lower_bound * jax.nn.sigmoid(jnp.exp(lp["kda_a_log"])[:, None] * gate_in)
    o = _recurrence(q, k, v, a, jax.nn.sigmoid(beta_gate[..., :heads]), time_block)
    y = _rmsnorm(o, lp["kda_norm_scale"], eps) * jax.nn.sigmoid(beta_gate[..., heads:])[..., None]
    return x + jnp.einsum("bshd,hde->bse", y, lp["kda_out"])


def _attention(q, k, v, query_block: int):
    """q, k (B, S, H, D), v (B, S, H, V) -> (B, S, H, V): causal softmax attention,
    scores over sqrt(D), `query_block` queries at a time (the one departure)."""
    b, s, h, d = q.shape
    block = query_block if s % query_block == 0 else s
    key_at = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qb, first = args                                    # (B, block, H, D), ()
        visible = key_at[None, :] <= (first + jnp.arange(block))[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(F32(d))
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one, (jnp.moveaxis(q.reshape(b, s // block, block, h, d), 1, 0),
                            jnp.arange(s // block) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


def _latent(x, lp, *, rope: int, theta: float, eps: float, query_block: int):
    h = _rmsnorm(x, lp["ln1_scale"], eps)
    q = jnp.einsum("bse,ehd->bshd", h, lp["wq"])
    kv_a = h @ lp["wkv_a"]
    kv = jnp.einsum("bsr,rhd->bshd", _rmsnorm(kv_a[..., :-rope], lp["kv_a_norm_scale"], eps), lp["wkv_b"])
    nope = q.shape[-1] - rope
    k_rope = _rope(kv_a[..., None, -rope:], theta)                              # (B, S, 1, R)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (*q.shape[:-1], rope))], axis=-1)
    o = _attention(q, k, kv[..., nope:], query_block) * jax.nn.sigmoid(h @ lp["wg"])[..., None]
    return x + jnp.einsum("bshd,hde->bse", o, lp["wo"])


def _gates(scores, expert_bias, *, top_k: int, route_scale: float, n_group: int, topk_group: int):
    """scores (B, S, E) -> (the gate of every published expert, zero where it was not
    chosen; the chosen experts (B, S, k)): group-limited selection on score + bias."""
    select = scores + jax.lax.stop_gradient(expert_bias)
    experts = select.shape[-1]
    grouped = select.reshape(*select.shape[:-1], n_group, experts // n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)                # (B, S, G)
    _, best = jax.lax.top_k(group_score, topk_group)
    kept = jnp.sum(jax.nn.one_hot(best, n_group, dtype=F32), axis=-2) > 0       # (B, S, G)
    limited = jnp.where(jnp.repeat(kept, experts // n_group, axis=-1), select, -jnp.inf)
    _, chosen = jax.lax.top_k(limited, top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS) * route_scale
    return jnp.einsum("bsk,bske->bse", weights, jax.nn.one_hot(chosen, experts, dtype=F32)), chosen


def _experts(x, lp, *, eps: float, held_experts: Optional[Tuple[int, int]], **routing):
    m = _rmsnorm(x, lp["ln2_scale"], eps)
    scores = jax.nn.sigmoid(m @ lp["router"])                                   # (B, S, E)
    gates, chosen = _gates(scores, lp["expert_bias"], **routing)
    first, last = held_experts or (0, scores.shape[-1])

    @jax.checkpoint      # the backward pass computes an expert again
    def gated(w_gate, w_up, w_down, gate):
        return gate[..., None] * _swiglu(m, w_gate, w_up, w_down)

    out, _ = jax.lax.scan(
        lambda total, expert: (total + gated(*expert), None),
        _swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"]),
        (lp["we_gate"], lp["we_up"], lp["we_down"], jnp.moveaxis(gates[..., first:last], -1, 0)))
    return x + out, chosen


@functools.partial(jax.jit, static_argnames=(
    "mixer", "mlp", "rope", "theta", "eps", "lower_bound", "top_k", "route_scale", "n_group", "topk_group",
    "held_experts", "frozen_leaves", "query_block", "time_block"))
def _layer(x, lp, *, mixer: str, mlp: str, rope: int, theta: float, eps: float, lower_bound: float,
           top_k: int, route_scale: float, n_group: int, topk_group: int,
           held_experts: Optional[Tuple[int, int]], frozen_leaves: Tuple[str, ...], query_block: int,
           time_block: int):
    """-> (x after the layer, the chosen experts (B, S, k); None for a dense MLP)."""
    with jax.default_matmul_precision("highest"):
        lp = {name: jax.lax.stop_gradient(w) if name in frozen_leaves else w for name, w in lp.items()}
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        if mixer == "kda":
            x = _kda(x, lp, eps=eps, lower_bound=lower_bound, time_block=time_block)
        else:
            x = _latent(x, lp, rope=rope, theta=theta, eps=eps, query_block=query_block)
        if mlp == "dense":
            m = _rmsnorm(x, lp["ln2_scale"], eps)
            return x + _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        return _experts(x, lp, eps=eps, held_experts=held_experts, top_k=top_k, route_scale=route_scale,
                        n_group=n_group, topk_group=topk_group)


def _layer_fn(kind: Tuple[str, str], *, qk_rope_dim: int, rope_theta: float, norm_eps: float,
              kda_lower_bound: float, top_k: int, route_scale: float, n_group: int, topk_group: int,
              held_experts: Optional[Tuple[int, int]], frozen_leaves: Tuple[str, ...] = (),
              query_block: int, time_block: int, **_):
    """One layer as a function of (x, its leaves), computed again in the backward
    pass: what a row holds through the stack is a layer's input each."""
    return jax.checkpoint(functools.partial(
        _layer, mixer=kind[0], mlp=kind[1], rope=int(qk_rope_dim), theta=float(rope_theta),
        eps=float(norm_eps), lower_bound=float(kda_lower_bound), top_k=int(top_k),
        route_scale=float(route_scale), n_group=int(n_group), topk_group=int(topk_group),
        held_experts=None if held_experts is None else tuple(held_experts),
        frozen_leaves=tuple(frozen_leaves), query_block=int(query_block), time_block=int(time_block)))


def _stack(params, tokens, *, first_layer: int, layer_group_size: int, first_k_dense_replace: int,
           query_block: int, time_block: int, **arch):
    """(B, S) tokens -> (the stack's output before its final norm, the chosen experts
    of every expert layer). The layers in order are the tree's runs, repeat by repeat,
    position by position; the i-th is published layer `first_layer` + i."""
    x, chosen, index = params["wte"][tokens].astype(F32), [], int(first_layer)
    kind_of = functools.partial(layer_kind, layer_group_size=int(layer_group_size),
                                first_k_dense_replace=int(first_k_dense_replace))
    for period in params["runs"]:
        repeats, n = next(iter(period[0].values())).shape[0], len(period)
        kinds = [kind_of(index + p) for p in range(n)]
        assert all(kind_of(index + r * n + p) == kinds[p] for r in range(repeats) for p in range(n)), \
            "a run is no repeated period"
        layers = [_layer_fn(kind, query_block=query_block, time_block=time_block, **arch) for kind in kinds]

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
        index += repeats * n
    return x, chosen


def forward(params: Dict[str, Any], tokens: jax.Array, *, query_block: int = 512,
            time_block: int = 64, **arch) -> Tuple[jax.Array, List[Any]]:
    """(B, S) int tokens -> ((B, S, V) float32 logits, the chosen experts of every
    expert layer)."""
    x, chosen = _stack(params, tokens, query_block=query_block, time_block=time_block, **arch)
    return _head(x, params["lnf_scale"], params["lm_head"], eps=float(arch["norm_eps"])), chosen


def forward_logits(params: Dict[str, Any], tokens: jax.Array, **arch) -> jax.Array:
    return forward(params, tokens, **arch)[0]


def objective(params: Dict[str, Any], tokens: jax.Array, **arch) -> jax.Array:
    """Mean next-token cross entropy of (B, S + 1) tokens, the whole batch at once and
    differentiable: all this family trains on here (the multi-token prediction
    module's loss has the published weight 0)."""
    logits = forward_logits(params, tokens[:, :-1], **arch)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def objective_part(params: Dict[str, Any], rows: jax.Array, stats: None = None, *,
                   total_tokens: int, head_rows: int = 1024, query_block: int = 512,
                   time_block: int = 64, **arch):
    """What the (b, S + 1) `rows` add to the mean cross entropy of a batch of
    `total_tokens` targets, differentiable: (their share, their summed cross entropy).
    Each layer is computed again in the backward pass, and the head runs over
    `head_rows` positions at a time."""
    del stats
    tokens, targets = rows[:, :-1], rows[:, 1:]
    x, _ = _stack(params, tokens, query_block=query_block, time_block=time_block, **arch)

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
