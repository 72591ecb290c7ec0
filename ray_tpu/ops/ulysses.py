"""Ulysses context parallelism: all-to-all head-scattered attention.

NEW capability relative to the reference — czxxing/ray has no sequence/
context parallelism (SURVEY.md §2.4). This is the DeepSpeed-Ulysses
recipe mapped to TPU: inputs arrive SEQUENCE-sharded on the `sp` mesh
axis; one `all_to_all` over ICI re-shards them HEAD-wise so every device
holds the full sequence for H/n heads, runs ordinary (flash) attention
locally — the Pallas kernel, fully fused, no ring bookkeeping — and a
second all_to_all restores sequence sharding.

Compared to ring attention: 2 collectives total instead of n ppermute
hops, and the local compute is the plain fused kernel; the tradeoff is
that heads must divide the axis size (rings have no such constraint)
and each device momentarily holds S × H/n activations. Use Ulysses when
H ≥ n; fall back to the ring for very long sequences on large axes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .attention import flash_attention

P = PartitionSpec


def _ulysses_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool,
    sm_scale: float,
    implementation: Optional[str],
):
    """Per-shard body (under shard_map). q/k/v: (B, H, S_local, D)."""
    # scatter heads, gather sequence: (B, H, S/n, D) -> (B, H/n, S, D)
    q = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    out = flash_attention(
        q, k, v, causal=causal, sm_scale=sm_scale,
        implementation=implementation,
    )
    # scatter sequence, gather heads: back to (B, H, S/n, D)
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1, tiled=True)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    sm_scale: Optional[float] = None,
    implementation: Optional[str] = None,
) -> jax.Array:
    """Sequence-parallel exact attention via head scattering.

    q (B,Hq,S,D), k/v (B,Hkv,S,D); S and Hq must divide by
    mesh.shape[axis]. Returns (B,Hq,S,D) sharded like q. Differentiable
    (all_to_all transposes to itself; the local kernel has its own vjp).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        groups = hq // hkv
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(f"seq {q.shape[2]} not divisible by {axis}={n}")
    if hq % n:
        raise ValueError(
            f"Ulysses needs heads ({hq}) divisible by the {axis} axis ({n}); "
            "use ring_attention for head counts below the axis size"
        )
    spec = P(None, None, axis, None)
    body = functools.partial(
        _ulysses_local, axis_name=axis, causal=causal, sm_scale=sm_scale,
        implementation=implementation,
    )
    fn = shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "sp",
    causal: bool = False,
) -> jax.Array:
    """Convenience: device_put inputs seq-sharded, run, leave output sharded."""
    spec = NamedSharding(mesh, P(None, None, axis, None))
    q = jax.device_put(q, spec)
    k = jax.device_put(k, spec)
    v = jax.device_put(v, spec)
    return ulysses_attention(q, k, v, mesh=mesh, axis=axis, causal=causal)
