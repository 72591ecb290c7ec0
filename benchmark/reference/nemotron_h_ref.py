"""Plain reference for the `nemotron_h` family (nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16), by the layer equations ISSUE 48 states: a stack whose every
layer is ONE mixer behind one RMSNorm, x <- x + mixer(RMSNorm(x)), the mixer
a Mamba-2 state-space layer ("M"), causal attention without positions ("*")
or sigmoid-routed squared-ReLU experts beside a shared one ("E"), by the
characters of `hybrid_override_pattern`; a final RMSNorm and an untied head.

Straightforward `jax.numpy` in float32 at "highest" matmul precision: no
kernels, no sort, no grouped matmul, no chunked scan, nothing imported from
`ray_tpu`. What the family shares with `afmoe` to the letter is taken from
that family's reference and not written twice: the RMSNorm, the sigmoid
router's gates (`_gates`: the top-k of score + bias, the bias without
gradient and in the selection only, the chosen scores over their sum + 1e-20,
times the scale), the blocked causal attention (a block of queries at a time
under `jax.checkpoint`) and the final norm and head.

  M   u = RMSNorm(x);  [z | xBC | dt] = u W_in      (H P | H P + 2 G N | H)
      xBC_t[c] = silu(b[c] + sum_{j<K} w[c, j] xBC_{t-K+1+j}[c]), zeros before t = 0
      x (H heads of P), B, C (G groups of N) = split(xBC); head h reads group h // (H / G)
      D_t[h] = softplus(dt_t[h] + dt_bias[h]);  a_t[h] = exp(-D_t[h] exp(A_log[h]))
      S_t[h] = a_t[h] S_{t-1}[h] + D_t[h] x_t[h] (x) B_t[g],   S_{-1} = 0    (P x N a head)
      y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
      y = GroupRMSNorm(y * silu(z)) * w_norm   (the gate FIRST, then an RMS norm
          over each of the G groups of H P / G features)
      x + y W_out
  *   q, k, v = RMSNorm(x) Wq, Wk, Wv (Hq / Hkv heads of D), NO positions;
      x + softmax(q k^T / sqrt(D), causal) v Wo
  E   m = RMSNorm(x);  s = sigmoid(m Wr);  gates as above (scale 2.5)
      x + W2s relu(W1s m)^2 + sum over the chosen experts HELD of g_e W2e relu(W1e m)^2

THE RECURRENCE RUNS ONE POSITION AT A TIME (`lax.scan` over t carrying S):
the program's chunked form and this file share no algorithm. Departures
from "straightforward", each forced by memory: the time axis is cut into
segments of `time_block` positions and a segment is computed again in the
backward pass (a state is H P N floats a row, 2.1 MB at the published sizes,
and 8,192 of them a layer do not fit), attention a block of queries at a
time, an expert at a time under `jax.checkpoint`, each layer computed again
in the backward pass, the head over `head_rows` positions at a time, and a
run of several repeats as one `lax.scan` over its stacked leaves
(glm4_moe_lite_ref's reasons).

One chip's share is afmoe_ref's: `held_experts` = (first, last) of the
published experts whose weights the tree holds, the router keeps all its
outputs and its top-k, the gates are normalised over all k chosen, `routed`
sums the chosen experts that are held; `frozen_leaves` are read as
constants. No positional encoding anywhere: the family's public code applies
no rotary embedding in its attention (`rope_theta` is a key it never reads).
No clamp on the step D_t (the public configuration names none).

The weights are the system's own, so the reference reads the program's
layout: params["runs"][r][p] a dict of leaves stacked on a leading axis of
the run's repeats (an M layer's ln1_scale (M,), ssm_in (M, 2 H P + 2 G N + H),
ssm_conv_w (H P + 2 G N, K), ssm_conv_b, ssm_dt_bias (H,), ssm_a_log (H,),
ssm_d (H,), ssm_norm_scale (H, P), ssm_out (H, P, M); a * layer's ln1_scale,
wq (M, Hq, D), wk, wv (M, Hkv, D), wo (Hq, D, M); an E layer's ln2_scale,
router (M, E), expert_bias (E,), we_up (held, M, F), we_down (held, F, M),
ws_up (M, Fs), ws_down (Fs, M)), and wte, lnf_scale, lm_head.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .afmoe_ref import F32, _attention, _gates, _head, _rmsnorm


def _relu2(m, w_up, w_down):
    return jnp.square(jax.nn.relu(m @ w_up)) @ w_down


def _recurrence(x, step, a, b, c, time_block: int):
    """x (B, S, H, P), step (B, S, H), a (H,) < 0, b, c (B, S, H, N) -> y
    (B, S, H, P) = S_t C_t, position by position from S_{-1} = 0; a segment
    of `time_block` positions is computed again in the backward pass."""
    bsz, s, h, p = x.shape
    block = time_block if s % time_block == 0 else s

    def position(state, inputs):
        x_t, d_t, b_t, c_t = inputs                    # (B, H, P), (B, H), (B, H, N), (B, H, N)
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    @jax.checkpoint
    def segment(state, inputs):
        return jax.lax.scan(position, state, inputs)

    def cut(t):     # (B, S, ...) -> (S / block, block, B, ...)
        return jnp.moveaxis(t, 1, 0).reshape(s // block, block, *t.shape[:1], *t.shape[2:])

    _, y = jax.lax.scan(segment, jnp.zeros((bsz, h, p, b.shape[-1]), F32),
                        (cut(x), cut(step), cut(b), cut(c)))
    return jnp.moveaxis(y.reshape(s, bsz, h, p), 0, 1)


def _mamba(x, lp, *, groups: int, state: int, eps: float, time_block: int):
    heads, p = lp["ssm_norm_scale"].shape
    inner, bsz, s = heads * p, x.shape[0], x.shape[1]
    projected = _rmsnorm(x, lp["ln1_scale"], eps) @ lp["ssm_in"]
    z, xbc, dt = jnp.split(projected, [inner, projected.shape[-1] - heads], axis=-1)
    taps = lp["ssm_conv_w"].shape[-1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(lp["ssm_conv_b"] + sum(lp["ssm_conv_w"][:, j] * padded[:, j:j + s]
                                             for j in range(taps)))
    xs = xbc[..., :inner].reshape(bsz, s, heads, p)
    b, c = (jnp.repeat(t.reshape(bsz, s, groups, state), heads // groups, axis=2)
            for t in jnp.split(xbc[..., inner:], 2, axis=-1))
    step = jax.nn.softplus(dt + lp["ssm_dt_bias"])
    y = _recurrence(xs, step, -jnp.exp(lp["ssm_a_log"]), b, c, time_block)
    y = (y + lp["ssm_d"][:, None] * xs).reshape(bsz, s, inner) * jax.nn.silu(z)
    y = _rmsnorm(y.reshape(bsz, s, groups, inner // groups),
                 lp["ssm_norm_scale"].reshape(groups, inner // groups), eps)
    return x + jnp.einsum("bshp,hpe->bse", y.reshape(bsz, s, heads, p), lp["ssm_out"]), None


def _attend(x, lp, *, eps: float, query_block: int):
    a = _rmsnorm(x, lp["ln1_scale"], eps)
    q, k, v = (jnp.einsum("bse,ehd->bshd", a, lp[name]) for name in ("wq", "wk", "wv"))
    return x + jnp.einsum("bshd,hde->bse", _attention(q, k, v, None, query_block), lp["wo"]), None


def _experts(x, lp, *, eps: float, top_k: int, route_scale: float,
             held_experts: Optional[Tuple[int, int]]):
    m = _rmsnorm(x, lp["ln2_scale"], eps)
    scores = jax.nn.sigmoid(m @ lp["router"])                          # (B, S, E)
    gates, chosen = _gates(scores, lp["expert_bias"], top_k, route_scale)
    first, last = held_experts or (0, scores.shape[-1])

    @jax.checkpoint      # the backward pass computes an expert again
    def gated(w_up, w_down, gate):
        return gate[..., None] * _relu2(m, w_up, w_down)

    out, _ = jax.lax.scan(
        lambda total, expert: (total + gated(*expert), None),
        _relu2(m, lp["ws_up"], lp["ws_down"]),
        (lp["we_up"], lp["we_down"], jnp.moveaxis(gates[..., first:last], -1, 0)))
    return x + out, chosen


@functools.partial(jax.jit, static_argnames=(
    "kind", "groups", "state", "eps", "top_k", "route_scale", "held_experts", "frozen_leaves",
    "query_block", "time_block"))
def _layer(x, lp, *, kind: str, groups: int, state: int, eps: float, top_k: int,
           route_scale: float, held_experts: Optional[Tuple[int, int]],
           frozen_leaves: Tuple[str, ...], query_block: int, time_block: int):
    """-> (x after the layer, the chosen experts (B, S, k); None but for an E layer)."""
    with jax.default_matmul_precision("highest"):
        lp = {name: jax.lax.stop_gradient(w) if name in frozen_leaves else w
              for name, w in lp.items()}
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        if kind == "M":
            return _mamba(x, lp, groups=groups, state=state, eps=eps, time_block=time_block)
        if kind == "*":
            return _attend(x, lp, eps=eps, query_block=query_block)
        if kind == "E":
            return _experts(x, lp, eps=eps, top_k=top_k, route_scale=route_scale,
                            held_experts=held_experts)
        raise ValueError(f"nemotron_h_ref: no layer of the kind {kind!r}")


def _layer_fn(kind: str, *, ssm_groups: int, ssm_state: int, norm_eps: float, top_k: int,
              route_scale: float, held_experts: Optional[Tuple[int, int]],
              frozen_leaves: Tuple[str, ...] = (), query_block: int, time_block: int, **_):
    """One layer as a function of (x, its leaves), computed again in the
    backward pass: what a row holds through the stack is a layer's input each."""
    return jax.checkpoint(functools.partial(
        _layer, kind=kind, groups=int(ssm_groups), state=int(ssm_state), eps=float(norm_eps),
        top_k=int(top_k), route_scale=float(route_scale),
        held_experts=None if held_experts is None else tuple(held_experts),
        frozen_leaves=tuple(frozen_leaves), query_block=int(query_block),
        time_block=int(time_block)))


def _stack(params, tokens, *, pattern: str, query_block: int, time_block: int, **arch):
    """(B, S) tokens -> (the stack's output before its final norm, the chosen
    experts of every E layer). The layers in order are the tree's runs, repeat
    by repeat, position by position, layer i of the kind `pattern[i]`; a run
    of several repeats is one `lax.scan` over its stacked leaves."""
    x, chosen, index = params["wte"][tokens].astype(F32), [], 0
    for period in params["runs"]:
        repeats, n = next(iter(period[0].values())).shape[0], len(period)
        kinds = pattern[index: index + n]
        assert pattern[index: index + repeats * n] == kinds * repeats, "a run is no repeated period"
        layers = [_layer_fn(kind, query_block=query_block, time_block=time_block, **arch)
                  for kind in kinds]

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


def forward(params: Dict[str, Any], tokens: jax.Array, *, query_block: int = 1024,
            time_block: int = 128, **arch) -> Tuple[jax.Array, List[Any]]:
    """(B, S) int tokens -> ((B, S, V) float32 logits, the chosen experts of
    every E layer)."""
    x, chosen = _stack(params, tokens, query_block=query_block, time_block=time_block, **arch)
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
                   total_tokens: int, head_rows: int = 1024, query_block: int = 1024,
                   time_block: int = 128, **arch):
    """What the (b, S + 1) `rows` add to the mean cross entropy of a batch of
    `total_tokens` targets, differentiable: (their share, their summed cross
    entropy). Each layer is computed again in the backward pass, and the head
    runs over `head_rows` positions at a time."""
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
