"""Transformer layer primitives: norms, rotary embeddings, gated MLP acts.

These are deliberately plain jnp: XLA fuses elementwise chains into the
surrounding matmuls on TPU, so hand-written Pallas buys nothing here (the
Pallas budget goes to attention and serving kernels instead). The one
exception is a norm over GROUPS of the lane axis: `rmsnorm` on a view (...,
groups, width) puts the groups on the sublanes, which on the chip is a
shuffle of the whole float32 array, so the Mamba-2 mixer's gated group norm
is two kernels that reduce each group's lanes in place
(ops/ssd.gated_group_norm; off a TPU it is `rmsnorm` on that view).
Computation is done in float32 and cast back, the standard mixed-precision
discipline for bf16 training.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6, axis=-1) -> jax.Array:
    """RMSNorm (Llama-family) over `axis` (one or several); scale has
    shape (d,), or any shape that broadcasts against x."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=axis, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * scale.astype(jnp.float32)).astype(dtype)


def layernorm(
    x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5
) -> jax.Array:
    """LayerNorm (GPT-2-family)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    normed = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (normed * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def gelu(x: jax.Array) -> jax.Array:
    """tanh-approximated GELU (GPT-2 uses the approximate form)."""
    return jax.nn.gelu(x, approximate=True)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    """SwiGLU gate: silu(gate) * up (Llama/Mixtral MLP)."""
    return jax.nn.silu(gate) * up


def rope_frequencies(
    head_dim: int, max_seq: int, theta: float = 10000.0, dtype=jnp.float32
) -> Tuple[jax.Array, jax.Array]:
    """Precompute (cos, sin) tables of shape (max_seq, head_dim // 2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(
    x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array | None = None,
    *, rotary_dims: int | None = None,
) -> jax.Array:
    """Rotary position embedding over the last dim of x (B, H, S, D).

    `positions` (B, S) selects rows of the (max_seq, D/2) tables; defaults to
    arange(S). Uses the split-half convention (matches HF Llama).
    `rotary_dims` (None: the whole head) rotates the LAST `rotary_dims`
    features of every head alone, with tables of `rotary_dims` / 2 columns,
    and passes the features before them through: a latent-attention head's
    [no positions | rotary] layout. A rotary key part that all heads share is
    a call with H = 1.
    """
    if rotary_dims is not None and rotary_dims != x.shape[-1]:
        through, rotary = jnp.split(x, [x.shape[-1] - rotary_dims], axis=-1)
        return jnp.concatenate([through, apply_rope(rotary, cos, sin, positions)], axis=-1)
    b, _, s, d = x.shape
    if positions is None:
        cos_sel = cos[:s][None, None]  # (1, 1, S, D/2)
        sin_sel = sin[:s][None, None]
    else:
        cos_sel = cos[positions][:, None]  # (B, 1, S, D/2)
        sin_sel = sin[positions][:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos_sel = cos_sel.astype(jnp.float32)
    sin_sel = sin_sel.astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos_sel - x2 * sin_sel, x2 * cos_sel + x1 * sin_sel], axis=-1
    )
    return out.astype(x.dtype)
