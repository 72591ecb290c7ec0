"""Ulysses (all-to-all head-scattered) context parallelism vs dense
reference — the second SP strategy next to ring attention (SURVEY §2.4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import mha_reference, ulysses_attention, ulysses_attention_sharded
from ray_tpu.parallel import MeshSpec, build_mesh


@pytest.fixture
def sp_mesh():
    return build_mesh(MeshSpec(sp=8))


@pytest.fixture
def sp4_mesh():
    return build_mesh(MeshSpec(dp=2, sp=4))


def _qkv(key, b, h, s, d, hkv=None):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, h, s, d)),
        jax.random.normal(kk, (b, hkv or h, s, d)),
        jax.random.normal(kv, (b, hkv or h, s, d)),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(sp_mesh, causal):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 8, 128, 32)
    expected = mha_reference(q, k, v, causal=causal)
    out = ulysses_attention_sharded(q, k, v, sp_mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)


def test_ulysses_gqa(sp4_mesh):
    """GQA: kv heads repeat up to q heads before the head scatter."""
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 4, 64, 32, hkv=2)
    expected = mha_reference(q, k, v, causal=True)
    out = ulysses_attention_sharded(q, k, v, sp4_mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)


def test_ulysses_backward_matches_reference(sp4_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 4, 64, 16)

    def loss_u(q, k, v):
        out = ulysses_attention_sharded(q, k, v, sp4_mesh, causal=True)
        return jnp.sum(out * out)

    def loss_ref(q, k, v):
        out = mha_reference(q, k, v, causal=True)
        return jnp.sum(out * out)

    gu = jax.jit(jax.grad(loss_u, argnums=(0, 1, 2)))(q, k, v)
    ge = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gu, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_ulysses_matches_ring(sp_mesh):
    """The two SP strategies are interchangeable on the same shards."""
    from ray_tpu.ops import ring_attention_sharded

    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 8, 128, 16)
    u = ulysses_attention_sharded(q, k, v, sp_mesh, causal=True)
    r = ring_attention_sharded(q, k, v, sp_mesh, causal=True)
    np.testing.assert_allclose(np.asarray(u), np.asarray(r), atol=2e-5, rtol=2e-5)


def test_ulysses_rejects_indivisible_heads(sp_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 4, 128, 16)  # 4 heads < sp=8
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q, k, v, mesh=sp_mesh, causal=False)


def test_ulysses_under_jit_keeps_sharding(sp_mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 8, 64, 16)
    spec = NamedSharding(sp_mesh, P(None, None, "sp", None))
    q, k, v = (jax.device_put(x, spec) for x in (q, k, v))
    fn = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, mesh=sp_mesh, causal=True))
    out = fn(q, k, v)
    assert out.sharding.spec == P(None, None, "sp", None)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mha_reference(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5,
    )
