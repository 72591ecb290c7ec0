"""Ring attention: exact attention over sequence shards on a mesh axis.

NEW capability relative to the reference — czxxing/ray has no sequence/
context parallelism at all (SURVEY.md §2.4: grep for ring_attention/
ulysses/context_parallel is empty). This is the TPU-native design: shard
the sequence over the `sp` mesh axis, keep Q local, and rotate K/V shards
around the ring with `ppermute` (ICI neighbor hops) while accumulating
blockwise online softmax (Liu et al., Ring Attention; the flash-attention
recurrence across devices instead of across VMEM tiles).

Per ring step each device computes one (Q_local × KV_visiting) block —
compute overlaps the next KV transfer in XLA's schedule. Memory per device
is O(S/n · S/n) per block, never O(S²); sequence length scales linearly
with the ring size.

Differentiable: the step loop is a `lax.scan` and `ppermute` transposes to
the reverse rotation, so jax.grad gives the ring-parallel backward
automatically (each device re-sees every KV shard in reverse order).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

_NEG_INF = -1e30


def _ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool,
    sm_scale: float,
):
    """Per-shard body (call under shard_map). q/k/v: (B, H, S_local, D)."""
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape

    q32 = q.astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]  # send kv to the next host

    def _block(m_prev, l_prev, acc, k_cur, v_cur, kv_idx, masked: bool):
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q32, k_cur.astype(jnp.float32)
        ) * sm_scale
        if masked:
            q_pos = my_idx * s_local + lax.broadcasted_iota(
                jnp.int32, (1, 1, s_local, s_local), 2
            )
            kv_pos = kv_idx * s_local + lax.broadcasted_iota(
                jnp.int32, (1, 1, s_local, s_local), 3
            )
            s = jnp.where(kv_pos <= q_pos, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32)
        )
        return m_new, l_new, acc

    def step(carry, step_idx):
        m_prev, l_prev, acc, k_cur, v_cur = carry
        # whose kv shard do we hold after `step_idx` rotations?
        kv_idx = (my_idx - step_idx) % n

        if causal:
            # Causal block skipping (Liu et al.): a KV shard entirely in
            # this device's future contributes nothing — branch to a
            # no-op instead of computing a fully-masked block, so the
            # ring does ~n/2 block matmuls instead of n. The diagonal
            # block is the only one that needs the intra-block mask.
            branch = jnp.where(
                kv_idx > my_idx, 0, jnp.where(kv_idx == my_idx, 1, 2)
            )
            m_new, l_new, acc = lax.switch(
                branch,
                [
                    lambda *a: (m_prev, l_prev, acc),  # future: skip
                    lambda *a: _block(*a, masked=True),  # diagonal
                    lambda *a: _block(*a, masked=False),  # past: full
                ],
                m_prev, l_prev, acc, k_cur, v_cur, kv_idx,
            )
        else:
            m_new, l_new, acc = _block(
                m_prev, l_prev, acc, k_cur, v_cur, kv_idx, masked=False
            )
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc, k_next, v_next), None

    m0 = jnp.full((b, h, s_local, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    (m, l, acc, _, _), _ = lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n)
    )
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l).astype(q.dtype)


def _ring_fused_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool,
    sm_scale: float,
    block_impl: Optional[str] = None,
):
    """Fused per-shard body: each ring block runs through the Pallas flash
    kernel (ops/attention.py — online softmax INSIDE the block stays in
    VMEM, no (S_local × S_local) f32 logits in HBM) and blocks merge
    across ring steps by logsumexp reweighting, which is algebraically
    the same online-softmax recurrence the einsum body carries as
    (m, l, acc). The diagonal block is the causal kernel; past blocks the
    full kernel; future blocks skip (Liu et al. causal skipping).

    The kernel choice is flash_attention_with_lse's, i.e. the static
    rule ops.attention.resolve_attention_impl: no separate code path."""
    from .attention import flash_attention_with_lse

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def merge(o_acc, lse_acc, o_new, lse_new):
        lse = jnp.logaddexp(lse_acc, lse_new)
        w_acc = jnp.exp(lse_acc - lse)
        w_new = jnp.exp(lse_new - lse)
        return o_acc * w_acc + o_new.astype(jnp.float32) * w_new, lse

    def diag(o_acc, lse_acc, k_cur, v_cur):
        o, lse = flash_attention_with_lse(
            q, k_cur, v_cur, causal=True, sm_scale=sm_scale,
            implementation=block_impl,
        )
        return merge(o_acc, lse_acc, o, lse)

    def full(o_acc, lse_acc, k_cur, v_cur):
        o, lse = flash_attention_with_lse(
            q, k_cur, v_cur, causal=False, sm_scale=sm_scale,
            implementation=block_impl,
        )
        return merge(o_acc, lse_acc, o, lse)

    def step(carry, step_idx):
        o_acc, lse_acc, k_cur, v_cur = carry
        kv_idx = (my_idx - step_idx) % n
        if causal:
            branch = jnp.where(
                kv_idx > my_idx, 0, jnp.where(kv_idx == my_idx, 1, 2)
            )
            o_acc, lse_acc = lax.switch(
                branch,
                [lambda o, l, *_: (o, l), diag, full],
                o_acc, lse_acc, k_cur, v_cur,
            )
        else:
            o_acc, lse_acc = full(o_acc, lse_acc, k_cur, v_cur)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (o_acc, lse_acc, k_next, v_next), None

    o0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    lse0 = jnp.full((b, h, s_local, 1), _NEG_INF, jnp.float32)
    (o_acc, lse_acc, _, _), _ = lax.scan(
        step, (o0, lse0, k, v), jnp.arange(n)
    )
    return o_acc.astype(q.dtype)


def _make_fused_body(axis_name: str, causal: bool, sm_scale: float,
                     block_impl: Optional[str] = None):
    """Fused forward + einsum-reference backward. The flash kernel's VJP
    does not thread through the cross-step lse merge, so the backward
    recomputes the whole ring via the differentiable einsum body — same
    collective pattern, transposed ppermutes, mathematically identical."""

    @jax.custom_vjp
    def body(q, k, v):
        return _ring_fused_local(
            q, k, v, axis_name=axis_name, causal=causal, sm_scale=sm_scale,
            block_impl=block_impl,
        )

    def fwd(q, k, v):
        return body(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, pullback = jax.vjp(
            lambda q_, k_, v_: _ring_attention_local(
                q_, k_, v_, axis_name=axis_name, causal=causal,
                sm_scale=sm_scale,
            ),
            q, k, v,
        )
        return pullback(g)

    body.defvjp(fwd, bwd)
    return body


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    sm_scale: Optional[float] = None,
    impl: str = "fused",
    block_impl: Optional[str] = None,
) -> jax.Array:
    """Sequence-parallel exact attention. q (B,Hq,S,D), k/v (B,Hkv,S,D);
    S must divide by mesh.shape[axis]. Returns (B,Hq,S,D) sharded like q.

    impl: "fused" (default — per-block Pallas flash kernel on TPU, fused
    XLA reference elsewhere) or "einsum" (the original blockwise einsum
    body; also the backward path of "fused"). block_impl picks the flash
    kernel inside each fused ring block (None = the static rule
    ops.attention.resolve_attention_impl)."""
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

    spec = P(None, None, axis, None)
    if impl == "fused":
        body = _make_fused_body(axis, causal, sm_scale, block_impl)
    elif impl == "einsum":
        body = functools.partial(
            _ring_attention_local, axis_name=axis, causal=causal,
            sm_scale=sm_scale,
        )
    else:
        raise ValueError(f"unknown ring impl {impl!r}")
    fn = shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ring_attention_sharded(
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
    return ring_attention(q, k, v, mesh=mesh, axis=axis, causal=causal)
