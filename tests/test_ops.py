"""Kernel correctness: Pallas flash attention (interpret mode) vs XLA reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (
    apply_rope,
    cross_entropy_loss,
    flash_attention,
    layernorm,
    mha_reference,
    rmsnorm,
    rope_frequencies,
)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("gqa", [False, True])
def test_flash_forward_matches_reference(causal, gqa):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    b, hq, s, d = 2, 4, 256, 64
    hkv = 2 if gqa else hq
    q = _rand(kq, (b, hq, s, d))
    k = _rand(kk, (b, hkv, s, d))
    v = _rand(kv, (b, hkv, s, d))
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, implementation="pallas",
                          block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_forward_unpadded_vs_padded():
    # seq not a multiple of the block: wrapper pads + masks
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, s, d = 1, 2, 192, 64
    q = _rand(kq, (b, h, s, d))
    k = _rand(kk, (b, h, s, d))
    v = _rand(kv, (b, h, s, d))
    ref = mha_reference(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, implementation="pallas",
                          block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, s, d = 1, 2, 256, 64
    q = _rand(kq, (b, h, s, d))
    k = _rand(kk, (b, h, s, d))
    v = _rand(kv, (b, h, s, d))

    def loss_pallas(q, k, v):
        o = flash_attention(q, k, v, causal=causal, implementation="pallas")
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = mha_reference(q, k, v, causal=causal)
        return jnp.sum(o * o)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-3, rtol=1e-3)


def test_flash_backward_gqa():
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    b, hq, hkv, s, d = 1, 4, 2, 128, 32
    q = _rand(kq, (b, hq, s, d))
    k = _rand(kk, (b, hkv, s, d))
    v = _rand(kv, (b, hkv, s, d))

    def loss_pallas(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, implementation="pallas") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-3, rtol=1e-3)


def test_rmsnorm_and_layernorm():
    x = _rand(jax.random.PRNGKey(4), (2, 8, 64))
    scale = jnp.ones((64,))
    bias = jnp.zeros((64,))
    out = rmsnorm(x, scale)
    expected = x / np.sqrt(np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)
    ln = layernorm(x, scale, bias)
    np.testing.assert_allclose(np.asarray(ln).mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ln).std(-1), 1.0, atol=1e-3)


def test_rope_rotation_preserves_norm():
    cos, sin = rope_frequencies(64, 128)
    x = _rand(jax.random.PRNGKey(5), (1, 2, 16, 64))
    out = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(out[:, :, 0]), np.asarray(x[:, :, 0]), atol=1e-6)


def test_rope_with_positions():
    cos, sin = rope_frequencies(32, 64)
    x = _rand(jax.random.PRNGKey(6), (2, 1, 4, 32))
    pos = jnp.array([[3, 4, 5, 6], [0, 1, 2, 3]])
    out = apply_rope(x, cos, sin, positions=pos)
    # batch 1 with offset positions == default arange
    default = apply_rope(x[1:2], cos, sin)
    np.testing.assert_allclose(np.asarray(out[1:2]), np.asarray(default), atol=1e-6)


def test_cross_entropy_against_manual():
    logits = _rand(jax.random.PRNGKey(7), (4, 16))
    targets = jnp.array([1, 5, 2, 9])
    loss, n = cross_entropy_loss(logits, targets)
    logp = jax.nn.log_softmax(np.asarray(logits, dtype=np.float32), axis=-1)
    expected = -np.mean([logp[i, t] for i, t in enumerate(np.asarray(targets))])
    np.testing.assert_allclose(float(loss), expected, rtol=1e-6)
    assert float(n) == 4.0


def test_cross_entropy_masked():
    logits = _rand(jax.random.PRNGKey(8), (2, 4, 16))
    targets = jnp.zeros((2, 4), dtype=jnp.int32)
    mask = jnp.array([[1, 1, 0, 0], [1, 0, 0, 0]])
    loss, n = cross_entropy_loss(logits, targets, mask=mask)
    assert float(n) == 3.0
    assert np.isfinite(float(loss))


def test_cross_entropy_z_loss_increases_loss():
    logits = 5.0 * _rand(jax.random.PRNGKey(9), (4, 16))
    targets = jnp.array([0, 1, 2, 3])
    base, _ = cross_entropy_loss(logits, targets)
    with_z, _ = cross_entropy_loss(logits, targets, z_loss_coeff=1e-2)
    assert float(with_z) > float(base)


# ----------------------------------------------- pipelined kernel numerics
#
# The emit_pipeline kernel's interpret driver executes the same stage
# functions and slot arithmetic as the TPU driver, so these tests pin the
# pipelined dataflow (skewed stages, double-buffered score slots, causal
# trip counts) against the classic kernel BIT-FOR-BIT at f32 — the
# acceptance bar for swapping the default kernel.


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("gqa", [False, True])
def test_pipelined_forward_bitwise_vs_classic(causal, gqa):
    key = jax.random.PRNGKey(20)
    kq, kk, kv = jax.random.split(key, 3)
    b, hq, s, d = 2, 4, 256, 64
    hkv = 2 if gqa else hq
    q = _rand(kq, (b, hq, s, d))
    k = _rand(kk, (b, hkv, s, d))
    v = _rand(kv, (b, hkv, s, d))
    classic = flash_attention(q, k, v, causal=causal, implementation="pallas",
                              block_q=128, block_kv=64)
    pipe = flash_attention(q, k, v, causal=causal,
                           implementation="pallas_pipelined",
                           block_q=128, block_kv=64)
    np.testing.assert_array_equal(np.asarray(classic), np.asarray(pipe))
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(pipe), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pipelined_backward_bitwise_vs_classic(causal):
    key = jax.random.PRNGKey(21)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, s, d = 1, 2, 256, 64
    q = _rand(kq, (b, h, s, d))
    k = _rand(kk, (b, h, s, d))
    v = _rand(kv, (b, h, s, d))

    def loss(impl):
        def f(q, k, v):
            o = flash_attention(q, k, v, causal=causal, implementation=impl,
                                block_q=64, block_kv=64)
            return jnp.sum(o * o)
        return f

    gp = jax.grad(loss("pallas_pipelined"), argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gc):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


def test_pipelined_backward_gqa_matches_reference():
    key = jax.random.PRNGKey(22)
    kq, kk, kv = jax.random.split(key, 3)
    b, hq, hkv, s, d = 1, 4, 2, 128, 32
    q = _rand(kq, (b, hq, s, d))
    k = _rand(kk, (b, hkv, s, d))
    v = _rand(kv, (b, hkv, s, d))

    def loss_pipe(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, implementation="pallas_pipelined",
            block_q=64, block_kv=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gp = jax.grad(loss_pipe, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


def test_pipelined_odd_sequence_tail():
    """Seq not a multiple of either block: wrapper pads, kernel masks; same
    tiles -> bitwise equal to the classic kernel, close to XLA."""
    key = jax.random.PRNGKey(23)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, s, d = 1, 2, 192, 64
    q = _rand(kq, (b, h, s, d))
    k = _rand(kk, (b, h, s, d))
    v = _rand(kv, (b, h, s, d))
    classic = flash_attention(q, k, v, implementation="pallas",
                              block_q=128, block_kv=64)
    pipe = flash_attention(q, k, v, implementation="pallas_pipelined",
                           block_q=128, block_kv=64)
    np.testing.assert_array_equal(np.asarray(classic), np.asarray(pipe))
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(pipe), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pipelined_lse_matches_classic_and_boundary():
    """flash_attention_with_lse parity incl. the fully-masked boundary
    (kv_len=0): both kernels share the finalize contract bit-for-bit."""
    from ray_tpu.ops.attention import (
        _fwd_pallas, _fwd_pipe, flash_attention_with_lse,
    )

    key = jax.random.PRNGKey(24)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (1, 2, 256, 64))
    k = _rand(kk, (1, 2, 256, 64))
    v = _rand(kv, (1, 2, 256, 64))
    o1, l1 = flash_attention_with_lse(q, k, v, causal=True,
                                      implementation="pallas",
                                      block_q=128, block_kv=64)
    o2, l2 = flash_attention_with_lse(q, k, v, causal=True,
                                      implementation="pallas_pipelined",
                                      block_q=128, block_kv=64)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    # lse agrees with the dense logsumexp of the scaled causal scores
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float32),
                  np.asarray(k, np.float32)) / np.sqrt(64.0)
    mask = np.tril(np.ones((256, 256), bool))
    s = np.where(mask[None, None], s, -np.inf)
    dense_lse = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
    np.testing.assert_allclose(np.asarray(l2)[..., 0], dense_lse,
                               atol=1e-4, rtol=1e-4)
    # boundary: kv_len=0 masks everything; pipelined == classic on the
    # degenerate rows too (shared finalize semantics)
    ob1, lb1 = _fwd_pallas(q, k, v, False, 0.125, 64, 64, 0, True)
    ob2, lb2 = _fwd_pipe(q, k, v, False, 0.125, 64, 64, 0, True)
    np.testing.assert_array_equal(np.asarray(ob1), np.asarray(ob2))
    np.testing.assert_array_equal(np.asarray(lb1), np.asarray(lb2))


def test_pipelined_auto_fallback_single_tile():
    """Shapes with <2 kv tiles fall back to the classic kernel instead of
    degenerate pipelining."""
    key = jax.random.PRNGKey(25)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (1, 2, 64, 32))
    k = _rand(kk, (1, 2, 64, 32))
    v = _rand(kv, (1, 2, 64, 32))
    out = flash_attention(q, k, v, implementation="pallas_pipelined")
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_kernel_runs_per_shard_under_a_context_mesh():
    """Traced under a context mesh (as make_train_step does) the Pallas
    kernel is shard_mapped — batch over dp x fsdp, heads over tp — because
    GSPMD cannot partition a Mosaic call on a real chip. Same values and
    gradients as the unsharded reference, GQA included."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(26), 3)
    q = _rand(kq, (4, 4, 64, 32))
    k = _rand(kk, (4, 2, 64, 32))
    v = _rand(kv, (4, 2, 64, 32))
    spec = NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), "tp", None, None))

    def loss_sharded(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            out = flash_attention(q, k, v, causal=True, implementation="pallas",
                                  block_q=32, block_kv=32)
        return jnp.sum(out * out)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    step = jax.jit(jax.value_and_grad(loss_sharded, argnums=(0, 1, 2)),
                   in_shardings=(spec, spec, spec))
    assert "manual_computation" in step.lower(q, k, v).as_text()
    val, grads = step(q, k, v)
    ref_val, ref_grads = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(val), float(ref_val), rtol=1e-4)
    for a, b_ in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


def test_auto_loss_chunk_crossover():
    """Pins the dense->fused crossover at the measured v5e numbers: batch
    24 stays dense on a 16G chip, batch 32 (the measured regression) flips
    to the fused chunked path; unknown HBM (CPU) always dense."""
    from ray_tpu.ops.losses import auto_loss_chunk

    v5e = 16 * 1024**3
    assert auto_loss_chunk(24, 1024, 50257, v5e) == 0
    assert auto_loss_chunk(32, 1024, 50257, v5e) == 512
    # seq indivisible by the preferred chunks falls back down the ladder
    assert auto_loss_chunk(32, 1280, 50257, v5e) in (256, 128, 0)
    assert auto_loss_chunk(1024, 1024, 50257, None) == 0  # no HBM info
    assert auto_loss_chunk(24, 1024, 50257, 0) == 0


def test_check_kernel_fallbacks_wired():
    """scripts/check_kernel_fallbacks.py is now a shim over the raylint
    kernel-fallbacks rule; the repo-wide gate runs ONCE in
    tests/test_raylint.py. Here: the round-6 knobs stay registered and
    the shim's compat API resolves cfg reads."""
    import ast
    import importlib.util
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    script = repo / "scripts" / "check_kernel_fallbacks.py"
    spec = importlib.util.spec_from_file_location("ckf", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    config_tree = ast.parse(
        (repo / "ray_tpu" / "core" / "config.py").read_text()
    )
    flags = mod.defined_flags(config_tree)
    assert set(mod.REQUIRED_FLAGS) <= flags
    reads = mod.cfg_reads(ast.parse(
        "from .config import cfg\nx = cfg.attn_pipeline\n"
    ))
    assert reads == [(2, "attn_pipeline")]


def test_fused_linear_cross_entropy_matches_dense():
    """The chunked fused head+CE (PERF_NOTES.md) must agree with the
    dense path — values AND gradients — including mask and z-loss."""
    from ray_tpu.ops.losses import fused_linear_cross_entropy

    key = jax.random.PRNGKey(11)
    b, s, e, v, chunk = 2, 8, 16, 32, 4
    x = _rand(key, (b, s, e))
    head = _rand(jax.random.PRNGKey(12), (e, v))
    targets = jax.random.randint(jax.random.PRNGKey(13), (b, s), 0, v)
    mask = jnp.array([[1] * 8, [1, 1, 1, 1, 0, 0, 0, 0]])

    def dense(x, head):
        logits = jnp.einsum("bse,ev->bsv", x, head)
        return cross_entropy_loss(
            logits, targets, mask=mask, z_loss_coeff=1e-3
        )[0]

    def fused(x, head):
        return fused_linear_cross_entropy(
            x, head, targets, chunk=chunk, mask=mask, z_loss_coeff=1e-3
        )[0]

    np.testing.assert_allclose(
        float(dense(x, head)), float(fused(x, head)), rtol=1e-5
    )
    gd = jax.grad(dense, argnums=(0, 1))(x, head)
    gf = jax.grad(fused, argnums=(0, 1))(x, head)
    for a, b_ in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5)

    with pytest.raises(ValueError):
        fused_linear_cross_entropy(x, head, targets, chunk=5)
