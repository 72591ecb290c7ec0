"""The gated short convolution (ops/short_conv.py) against its plain form,
three shifted products written out here: values and gradients in all of the
projection and the taps, at a sequence shorter than the taps, at the first
rows, in both dtypes; what the rule resolves to and what it refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import short_conv


def plain(bcx, w):
    """y_t = C_t * sum_j w[:, j] (B X)_{t - K + 1 + j}, a position at a time in float64-free numpy."""
    bcx, w = np.asarray(bcx, np.float32), np.asarray(w, np.float32)
    channels, taps = w.shape
    before, after, x = (bcx[..., i * channels:(i + 1) * channels] for i in range(3))
    z = before * x
    out = np.zeros_like(z)
    for t in range(z.shape[1]):
        for j in range(taps):
            at = t - taps + 1 + j
            if at >= 0:
                out[:, t] += w[:, j] * z[:, at]
    return after * out


def inputs(batch, seq, channels, taps, dtype=jnp.float32, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (batch, seq, 3 * channels), jnp.float32).astype(dtype),
            jax.random.uniform(k2, (channels, taps), jnp.float32, -0.6, 0.6))


@pytest.mark.parametrize("batch,seq,channels,taps", [
    (2, 16, 8, 3), (1, 2, 8, 3), (1, 1, 4, 3), (2, 9, 128, 3), (1, 12, 8, 2), (1, 12, 8, 4)],
    ids=["plain", "shorter-than-the-taps", "one-position", "a-lane-tile", "two-taps", "four-taps"])
def test_values_are_the_shifted_products(batch, seq, channels, taps):
    bcx, w = inputs(batch, seq, channels, taps)
    got = jax.jit(short_conv.gated_short_conv)(bcx, w)
    assert got.shape == (batch, seq, channels) and got.dtype == bcx.dtype
    np.testing.assert_allclose(np.asarray(got), plain(bcx, w), atol=1e-5)


def test_the_first_rows_see_zeros_before_the_sequence():
    """Row 0 is C_0 w[:, 2] B_0 X_0 and row 1 adds w[:, 1] B_0 X_0: nothing wraps round from the end."""
    bcx, w = inputs(1, 6, 4, 3, seed=3)
    got = np.asarray(short_conv.gated_short_conv(bcx, w))
    before, after, x = (np.asarray(bcx)[0, :, i * 4:(i + 1) * 4] for i in range(3))
    z, w = before * x, np.asarray(w)
    np.testing.assert_allclose(got[0, 0], after[0] * w[:, 2] * z[0], atol=1e-6)
    np.testing.assert_allclose(got[0, 1], after[1] * (w[:, 2] * z[1] + w[:, 1] * z[0]), atol=1e-6)
    # a change at the last position moves no earlier output (causal)
    moved = np.asarray(short_conv.gated_short_conv(bcx.at[0, 5].add(1.0), jnp.asarray(w)))
    np.testing.assert_array_equal(moved[0, :5], got[0, :5])


@pytest.mark.parametrize("seq", [2, 7, 16], ids=["shorter-than-the-taps", "odd", "plain"])
def test_gradients_in_the_projection_and_the_taps_are_the_plain_forms(seq):
    bcx, w = inputs(2, seq, 8, 3, seed=seq)
    cotangent = jax.random.normal(jax.random.PRNGKey(9), (2, seq, 8))

    def by_shifts(bcx, w):
        before, after, x = jnp.split(bcx, 3, axis=-1)
        z = before * x
        shifted = [jnp.concatenate([jnp.zeros_like(z[:, :d]), z[:, :seq - d]], axis=1) for d in (2, 1, 0)]
        return jnp.sum(after * sum(w[:, j] * shifted[j] for j in range(3)) * cotangent)

    got = jax.jit(jax.grad(lambda b, w: jnp.sum(short_conv.gated_short_conv(b, w) * cotangent),
                           argnums=(0, 1)))(bcx, w)
    want = jax.grad(by_shifts, argnums=(0, 1))(bcx, w)
    for g, wanted in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wanted), atol=2e-5)
    # every third of the projection and every tap that reaches a position has a gradient
    assert all(float(jnp.max(jnp.abs(third))) > 0 for third in jnp.split(got[0], 3, axis=-1))
    reached = min(seq, 3)
    assert np.all(np.abs(np.asarray(got[1]))[:, 3 - reached:].max(axis=0) > 0)
    if seq < 3:
        assert float(jnp.max(jnp.abs(got[1][:, 0]))) == 0.0      # the first tap reaches no position


def test_bfloat16_in_and_out_with_the_sum_in_float32():
    bcx, w = inputs(2, 32, 16, 3, jnp.bfloat16, seed=5)
    got = jax.jit(short_conv.gated_short_conv)(bcx, w)
    assert got.dtype == jnp.bfloat16
    # one rounding of the output: the products and the sum are not rounded on the way
    want = plain(bcx.astype(jnp.float32), w)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=2 ** -8 * np.abs(want).max())
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(jnp.asarray(want).astype(jnp.bfloat16),
                                                                          np.float32))


def test_the_rule_runs_the_xla_form_and_refuses_what_it_cannot_run():
    assert short_conv.resolve_sconv_impl(seq=8192, channels=2048, taps=3) == "xla"
    assert short_conv.resolve_sconv_impl("xla", seq=8192, channels=2048, taps=3) == "xla"
    assert short_conv.sconv_plan(8192, 2048, 3) == {"sconv_impl": "xla", "sconv_rows": 0}
    bcx, w = inputs(1, 8, 4, 3)
    with pytest.raises(ValueError, match="no kernels tile sequences of 8 with 4 channels under 3 taps"):
        short_conv.gated_short_conv(bcx, w, implementation="pallas")
    with pytest.raises(ValueError, match="unknown gated short convolution implementation"):
        short_conv.gated_short_conv(bcx, w, implementation="triton")
    with pytest.raises(ValueError, match=r"12 features are not \[B \| C \| X\] of 5 channels"):
        short_conv.gated_short_conv(bcx, jnp.zeros((5, 3)))
    np.testing.assert_array_equal(np.asarray(short_conv.gated_short_conv(bcx, w, implementation="xla")),
                                  np.asarray(short_conv.gated_short_conv(bcx, w)))
