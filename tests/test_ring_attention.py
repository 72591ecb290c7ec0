"""Ring attention vs dense reference on an sp-sharded mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import mha_reference
from ray_tpu.ops.ring_attention import ring_attention, ring_attention_sharded
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
def test_ring_matches_reference(sp_mesh, causal):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 4, 128, 32)
    expected = mha_reference(q, k, v, causal=causal)
    out = ring_attention_sharded(q, k, v, sp_mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)


def test_ring_gqa(sp4_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 4, 64, 32, hkv=2)
    expected = mha_reference(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, sp4_mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)


def test_ring_under_jit_keeps_sharding(sp_mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 2, 64, 16)
    spec = NamedSharding(sp_mesh, P(None, None, "sp", None))
    q, k, v = (jax.device_put(x, spec) for x in (q, k, v))
    fn = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=sp_mesh, causal=True))
    out = fn(q, k, v)
    assert out.sharding.spec == P(None, None, "sp", None)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mha_reference(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5,
    )


def test_ring_backward_matches_reference(sp_mesh):
    """Autodiff through the ring (scan + ppermute transpose)."""
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 2, 64, 16)

    def loss_ring(q, k, v):
        out = ring_attention_sharded(q, k, v, sp_mesh, causal=True)
        return jnp.sum(out * out)

    def loss_ref(q, k, v):
        out = mha_reference(q, k, v, causal=True)
        return jnp.sum(out * out)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    ge = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gr, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_ring_rejects_indivisible_seq(sp_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 2, 100, 16)
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q, k, v, mesh=sp_mesh, causal=False)


def test_fused_matches_einsum_body(sp_mesh):
    """The fused (flash-kernel) ring body and the einsum reference body
    are the same online-softmax recurrence — outputs must agree."""
    q, k, v = _qkv(jax.random.PRNGKey(7), 2, 4, 64, 16)
    fused = ring_attention(q, k, v, mesh=sp_mesh, causal=True, impl="fused")
    ein = ring_attention(q, k, v, mesh=sp_mesh, causal=True, impl="einsum")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ein), atol=2e-5)


def test_fused_gradients_match_dense(sp_mesh):
    """Gradients through the fused body (custom_vjp → einsum ring
    backward) must match the dense reference gradients."""
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, 2, 32, 8)

    def ring_loss(q, k, v):
        return jnp.sum(
            ring_attention(q, k, v, mesh=sp_mesh, causal=True, impl="fused") ** 2
        )

    def dense_loss(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd), atol=1e-4)
