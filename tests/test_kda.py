"""ops/kda.py: the chunked delta rule with a decay a channel against the
recurrence one position at a time, values and gradients, float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda


def _inputs(seed, b=2, s=128, h=2, d_k=16, d_v=8, gate=None, dtype=jnp.float32, same_keys=False):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (b, s, h, d_k))
    k = jax.random.normal(keys[1], (b, 1 if same_keys else s, h, d_k))
    k = jnp.broadcast_to(k, (b, s, h, d_k))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d_k)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, s, h, d_v))
    if gate is None:        # log-decays over the whole range of the bounded gate
        a = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(keys[3], (b, s, h, d_k)))
    else:
        a = jnp.full((b, s, h, d_k), gate)
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], (b, s, h)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), a, beta


def _raw(seed, b=1, s=128, h=2, d_k=128, d_v=128, f=None, dtype=jnp.float32, same_keys=False, zero_query_head=False):
    """The mixer's own arguments for `kda.kda_rule`: q, k, v and the gate's input f flat (B, S, H D), none of
    them normalised, the two logits a head [beta | the norm's gate] (B, S, 2 H), `A_log` (H,), the gate's bias
    (H D_k,) and the norm's scale (D_v,). With `f` a number the gate is that input everywhere under a rate of 1
    and no bias: 5 sigmoid(f) is the decay a position."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    q = 3.0 * jax.random.normal(keys[0], (b, s, h, d_k))
    if zero_query_head:     # the head's L2 norm is the epsilon's alone
        q = q.at[:, :, 0].set(0.0)
    k = jnp.broadcast_to(2.0 * jax.random.normal(keys[1], (b, 1 if same_keys else s, h, d_k)), (b, s, h, d_k))
    v = jax.random.normal(keys[2], (b, s, h * d_v))
    if f is None:           # log-decays over the whole range of the bounded gate, a rate a head, a bias a channel
        gate = 3.0 * jax.random.normal(keys[3], (b, s, h * d_k))
        a_log = jnp.log(jax.random.uniform(keys[5], (h,), minval=1.0, maxval=4.0))
        bias = 0.5 * jax.random.normal(keys[6], (h * d_k,))
    else:
        gate, a_log, bias = jnp.full((b, s, h * d_k), f), jnp.zeros((h,)), jnp.zeros((h * d_k,))
    beta_gate = 2.0 * jnp.concatenate([jax.random.normal(keys[4], (b, s, h)), jax.random.normal(keys[7], (b, s, h))],
                                      axis=-1)
    scale = 1.0 + 0.3 * jax.random.normal(keys[8], (d_v,))
    flat = (b, s, h * d_k)
    return (q.reshape(flat).astype(dtype), k.reshape(flat).astype(dtype), v.astype(dtype), gate.astype(dtype),
            beta_gate, a_log, bias, scale)


def _weighted(fn, weights):
    return lambda *inputs: jnp.sum(fn(*inputs) * weights)


@pytest.mark.parametrize("case", ["mixed_gates", "at_the_bound", "no_decay", "same_keys"])
def test_chunked_matches_the_recurrence_in_values_and_gradients(case):
    how = {"mixed_gates": {}, "at_the_bound": {"gate": -5.0}, "no_decay": {"gate": 0.0},
           "same_keys": {"same_keys": True, "gate": -0.01}}[case]
    inputs = _inputs(3, **how)
    weights = jax.random.normal(jax.random.PRNGKey(9), (*inputs[0].shape[:3], inputs[2].shape[-1]))
    chunked = jax.jit(lambda *t: kda.kda_chunk(*t, chunk=64))
    plain = jax.jit(lambda *t: kda.kda_reference(*t)[0])
    got, want = chunked(*inputs), plain(*inputs)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    grads = jax.jit(jax.grad(_weighted(lambda *t: kda.kda_chunk(*t, chunk=64), weights), argnums=range(5)))(*inputs)
    wanted = jax.jit(jax.grad(_weighted(lambda *t: kda.kda_reference(*t)[0], weights), argnums=range(5)))(*inputs)
    for name, g, w in zip("q k v a beta".split(), grads, wanted):
        assert bool(jnp.all(jnp.isfinite(g))), name
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        np.testing.assert_allclose(g / scale, w / scale, atol=3e-4, err_msg=name)


def test_a_whole_chunk_at_the_bound_underflows_and_stays_finite():
    # -5 a position is -320 over a chunk: e^{-A} would be e^{320}
    q, k, v, a, beta = _inputs(5, s=192, gate=-5.0)
    assert float(kda.log_decay_chunk_min(a, 64)) == pytest.approx(-320.0)
    out, grads = jax.jit(jax.value_and_grad(
        lambda *t: jnp.sum(jnp.square(kda.kda_chunk(*t, chunk=64))), argnums=range(5)))(q, k, v, a, beta)
    assert np.isfinite(float(out)) and all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_a_chunk_that_is_one_sub_block_and_half_precision_inputs():
    inputs = _inputs(7, s=64, d_k=8, d_v=8)
    np.testing.assert_allclose(kda.kda_chunk(*inputs, chunk=8), kda.kda_reference(*inputs)[0],
                               rtol=2e-4, atol=2e-5)
    half = _inputs(7, s=64, dtype=jnp.bfloat16)
    out = kda.kda_chunk(*half, chunk=32)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32), kda.kda_reference(*half)[0], atol=0.05)


def test_the_unit_lower_inverse_is_exact_where_a_series_would_cancel():
    lower = jnp.tril(jnp.ones((3, 64, 64)), -1)       # every key the same, beta 1, no decay
    inverse = kda._unit_lower_inverse(lower, 16)
    np.testing.assert_allclose(inverse @ (jnp.eye(64) + lower), jnp.broadcast_to(jnp.eye(64), (3, 64, 64)),
                               atol=1e-5)


def test_the_rule_from_the_mixers_arguments_is_the_recurrence_then_the_plain_norm():
    """`kda_rule`'s XLA form on the fused contract (PR 63), float32 at sizes the kernels do not tile: y flat,
    the rule's output over its root mean square a head times the scale times the sigmoid of the head's gate
    logit, and all eight gradients, against the recurrence one position at a time on what `rule_arguments`
    makes, followed by the plain norm's three lines."""
    from ray_tpu.ops.layers import rmsnorm

    raw = _raw(6, b=2, s=64, h=3, d_k=16, d_v=8)
    weights = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 3 * 8))

    def plain(q, k, v, f, beta_gate, a_log, bias, scale):
        out = kda.kda_reference(*kda.rule_arguments(q, k, v, f, beta_gate[..., :3], a_log, bias, lower_bound=-5.0,
                                                    eps=1e-6))[0]
        return (rmsnorm(out, scale, eps=1e-5) * jax.nn.sigmoid(beta_gate[..., 3:])[..., None]).reshape(2, 64, 24)

    rule = lambda *t: kda.kda_rule(*t, eps=1e-6, norm_eps=1e-5, chunk=32)[0]       # noqa: E731
    got, want = jax.jit(rule)(*raw), jax.jit(plain)(*raw)
    assert got.shape == (2, 64, 24) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    grads = jax.jit(jax.grad(_weighted(rule, weights), argnums=range(8)))(*raw)
    wanted = jax.jit(jax.grad(_weighted(plain, weights), argnums=range(8)))(*raw)
    for name, g, w in zip("q k v f beta_gate a_log dt_bias norm_scale".split(), grads, wanted):
        assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g))), name
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        np.testing.assert_allclose(g / scale, w / scale, atol=3e-4, err_msg=name)
    # the gate's logits reach y and the scale has a gradient a feature: neither is a constant of the op
    assert float(jnp.max(jnp.abs(grads[4][..., 3:]))) > 0 and float(jnp.min(jnp.abs(grads[7]))) > 0


def test_refusals_and_the_plan():
    with pytest.raises(ValueError, match="kda_chunk: a sequence of 96 is no multiple of the chunk"):
        kda.kda_chunk(*_inputs(1, s=96), chunk=64)
    raw = _raw(1, s=96, d_k=16, d_v=8)
    with pytest.raises(ValueError, match="kda_rule: a sequence of 96 is no multiple of the chunk"):
        kda.kda_rule(*raw, eps=1e-6, norm_eps=1e-6, chunk=64)
    with pytest.raises(ValueError, match="unknown kda implementation"):
        kda.kda_rule(*raw, eps=1e-6, norm_eps=1e-6, chunk=32, implementation="mosaic")
    with pytest.raises(ValueError, match="the kernels do not tile a chunk of 32, key heads of 16"):
        kda.kda_rule(*raw, eps=1e-6, norm_eps=1e-6, chunk=32, implementation="pallas")
    assert kda.kda_plan() == {"kda_impl": "xla_chunked", "kda_chunk": 64, "kda_subchunk": 16, "kda_kernels": 0,
                              "kda_heads_per_step": 0, "kda_state_bytes": 0, "kda_prologue": "xla",
                              "kda_epilogue": "xla"}
    assert kda.kda_plan(24)["kda_subchunk"] == 24
