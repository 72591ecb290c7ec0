"""The gated short convolution (the LFM2 family's mixer core): on the
in-projection's output [B | C | X], thirds of C channels each,

  z_t = B_t * X_t
  c_t = sum_{j < K} w[:, j] * z_{t - K + 1 + j}        (zeros before the sequence)
  y_t = C_t * c_t

a causal depthwise convolution of K taps a channel with no bias and no
activation, gated before and after by two thirds of the same projection. It
keeps no state beyond K - 1 rows and is bound by memory: 7 operations a
channel and token at K = 3 (two gates, three multiplications and two
additions of the taps) for B, C, X read and y written.

`ops/ssd.causal_conv1d` is another function (silu(bias + conv(x)) with
nothing around it; its two kernels have the bias and the silu in their
bodies), so this one has its own forms, chosen by that module's one rule
(`ssd._resolve`: backend, static shape and context mesh; no flag, no
environment variable, and a form is never tried and swapped for the other):

- "xla", the form that runs today everywhere: K shifted multiply-adds in
  float32 on the thirds read out of the projection as it is, which JAX
  differentiates.
- "pallas": no kernels tile any size yet (`resolve_sconv_impl` tells the
  rule so), so the rule never picks it and one asked for by name is refused
  by name. The pair a later change brings (`sconv_fwd` / `sconv_bwd` under
  one `custom_vjp`, a grid step a tile of rows of one sequence with every
  channel, as `ssm_conv_fwd` / `ssm_conv_bwd`) says there which sizes it
  tiles, and `sconv_plan` then reports it with no caller changed.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .ssd import _resolve

F32 = jnp.float32
_IMPLEMENTATIONS = ("xla", "pallas")


def resolve_sconv_impl(implementation: Optional[str] = None, *, seq: int, channels: int, taps: int) -> str:
    """The implementation `gated_short_conv` runs, by the scan's rule
    (`ssd._resolve`), which is told that kernels tile no size: none exist yet."""
    return _resolve(implementation, _IMPLEMENTATIONS, False, "gated short convolution",
                    f"gated_short_conv: no kernels tile sequences of {seq} with {channels} channels "
                    f"under {taps} taps")


def sconv_plan(seq: int, channels: int, taps: int) -> dict:
    """What `gated_short_conv` resolves to, for callers that report it: the
    implementation's name and the rows of a sequence a grid step of the
    kernels takes (0 for the XLA form)."""
    return {"sconv_impl": resolve_sconv_impl(seq=seq, channels=channels, taps=taps), "sconv_rows": 0}


def gated_short_conv(bcx: jax.Array, w: jax.Array, *, implementation: Optional[str] = None) -> jax.Array:
    """C * conv(B * X) on the in-projection's output `bcx` (B, S, 3 C), the
    thirds [B | C | X] read in place, with the taps `w` (C, K) -> (B, S, C)
    in bcx's dtype. The products and the taps' sum are float32;
    differentiable in bcx and w. `implementation` is `resolve_sconv_impl`'s,
    for tests."""
    channels, taps = w.shape
    if bcx.shape[-1] != 3 * channels:
        raise ValueError(f"gated_short_conv: {bcx.shape[-1]} features are not [B | C | X] of {channels} channels")
    seq = bcx.shape[1]
    resolve_sconv_impl(implementation, seq=seq, channels=channels, taps=taps)    # one form runs; a name is checked
    before, after, x = (bcx[..., i * channels:(i + 1) * channels].astype(F32) for i in range(3))
    z = jnp.pad(before * x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(F32)
    conv = sum(w[:, j] * z[:, j:j + seq] for j in range(taps))
    return (after * conv).astype(bcx.dtype)
