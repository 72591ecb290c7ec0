"""Plain reference for the decoder-only transformers the cells run.

Straightforward `jax.numpy` in float32 at "highest" matmul precision (on a
TPU a float32 matmul otherwise runs in bfloat16 passes): no kernels, no
cache, no batching tricks, nothing imported from `ray_tpu`. It follows the
published descriptions:

  mistral  pre-norm blocks, RMSNorm, rotary embedding in the split-half
           (Hugging Face) convention, grouped-query attention, SwiGLU MLP,
           untied output head, no biases.
  gpt2     pre-norm blocks, LayerNorm with bias, learned positions, MHA
           with biases, tanh-approximated GELU ("gelu_new"), tied head.

The weights are the system's own (the comparison is of arithmetic, not of
initialisation), so the reference reads the program's parameter layout:
`blocks` leaves stacked on a leading layer axis, wq (L, E, Hq, D), wk/wv
(L, E, Hkv, D), wo (L, Hq, D, E), w_up/w_gate (L, E, F), w_down (L, F, E).
It runs layer by layer and upcasts one layer at a time, so that only one
layer is ever held in float32.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layernorm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, theta):
    """x: (B, S, H, D). Split-half rotary embedding at positions 0..S-1."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """q: (B, S, Hq, D); k, v: (B, S, Hkv, D). Causal softmax attention."""
    b, s, hq, d = q.shape
    group = hq // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


@functools.partial(jax.jit, static_argnames=("family", "theta", "eps"))
def _layer(x, lp, *, family: str, theta: float, eps: float):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        if family == "mistral":
            h = _rmsnorm(x, lp["ln1_scale"], eps)
        else:
            h = _layernorm(x, lp["ln1_scale"], lp["ln1_bias"], eps)
        q = jnp.einsum("bse,ehd->bshd", h, lp["wq"])
        k = jnp.einsum("bse,ehd->bshd", h, lp["wk"])
        v = jnp.einsum("bse,ehd->bshd", h, lp["wv"])
        if family == "mistral":
            q, k = _rope(q, theta), _rope(k, theta)
        else:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        attn = jnp.einsum("bshd,hde->bse", _attention(q, k, v), lp["wo"])
        if family == "gpt2":
            attn = attn + lp["bo"]
        x = x + attn
        if family == "mistral":
            h = _rmsnorm(x, lp["ln2_scale"], eps)
            act = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
            return x + act @ lp["w_down"]
        h = _layernorm(x, lp["ln2_scale"], lp["ln2_bias"], eps)
        act = jax.nn.gelu(h @ lp["w_up"] + lp["b_up"], approximate=True)
        return x + act @ lp["w_down"] + lp["b_down"]


@functools.partial(jax.jit, static_argnames=("family", "eps"))
def _head(x, final, head, *, family: str, eps: float):
    with jax.default_matmul_precision("highest"):
        final = jax.tree.map(lambda w: w.astype(F32), final)
        if family == "mistral":
            x = _rmsnorm(x, final["lnf_scale"], eps)
        else:
            x = _layernorm(x, final["lnf_scale"], final["lnf_bias"], eps)
        return x @ head.astype(F32)


def forward_logits(params: Dict[str, Any], tokens: jax.Array, *, family: str,
                   rope_theta: float = 10000.0, norm_eps: float = 1e-5,
                   n_layers: Optional[int] = None) -> jax.Array:
    """(B, S) int tokens -> (B, S, V) float32 logits."""
    if family not in ("mistral", "gpt2"):
        raise ValueError(f"no reference for model family {family!r}")
    blocks = params["blocks"]
    depth = n_layers if n_layers is not None else blocks["wq"].shape[0]
    x = params["wte"][tokens].astype(F32)
    if family == "gpt2":
        x = x + params["wpe"][: tokens.shape[1]].astype(F32)[None]
    for layer in range(depth):
        lp = {name: w[layer] for name, w in blocks.items()}
        x = _layer(x, lp, family=family, theta=float(rope_theta), eps=float(norm_eps))
    final = {k: params[k] for k in ("lnf_scale", "lnf_bias") if k in params}
    head = params["lm_head"] if "lm_head" in params else params["wte"].T
    return _head(x, final, head, family=family, eps=float(norm_eps))


def objective_part(params: Dict[str, Any], rows: jax.Array, whole=None, *, total_tokens: int,
                   family: str,
                   rope_theta: float = 10000.0, norm_eps: float = 1e-5):
    """What the (b, S + 1) `rows` add to the mean next-token cross entropy
    of a batch of `total_tokens` targets, differentiable: (their share,
    their summed cross entropy); nothing `whole` of the batch is needed
    beforehand. The layers are scanned and each is
    computed again in the backward pass, so one layer's float32
    activations are all that is held."""
    tokens, targets = rows[:, :-1], rows[:, 1:]
    x = params["wte"][tokens].astype(F32)
    if family == "gpt2":
        x = x + params["wpe"][: tokens.shape[1]].astype(F32)[None]
    layer = jax.checkpoint(functools.partial(
        _layer, family=family, theta=float(rope_theta), eps=float(norm_eps)))
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x, params["blocks"])
    final = {k: params[k] for k in ("lnf_scale", "lnf_bias") if k in params}
    head = params["lm_head"] if "lm_head" in params else params["wte"].T
    logp = jax.nn.log_softmax(_head(x, final, head, family=family, eps=float(norm_eps)), axis=-1)
    ce_sum = -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return ce_sum / total_tokens, ce_sum


def loss(params: Dict[str, Any], tokens: jax.Array, *, rows_at_a_time: int = 2,
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
