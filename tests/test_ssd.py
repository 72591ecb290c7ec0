"""ops/ssd.py: the chunked selective scan against the recurrence taken one
position at a time (`ssd_reference`: float32, a state a position), outputs
and every argument's gradient; the causal depthwise convolution; what is
refused by name; what the walk keeps for its backward pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd


def _inputs(seq, *, heads=4, groups=2, features=8, state=16, batch=2, dtype=jnp.float32, seed=0):
    """Steps of 0.03-0.5 under decays of up to 16 a unit step: a chunk of 16
    forgets what entered it (exp(-30)), so a state that crosses a border
    matters where the head is slow and not where it is fast."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (batch, seq, heads, features), dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)) - 2.0)
    a_log = jnp.log(jax.random.uniform(keys[2], (heads,), minval=0.05, maxval=16.0))
    b = jax.random.normal(keys[3], (batch, seq, groups, state), dtype)
    c = jax.random.normal(keys[4], (batch, seq, groups, state), dtype)
    d = 1.0 + 0.1 * jax.random.normal(keys[5], (heads,))
    return x, dt, a_log, b, c, d


def _scaled_gap(got, want):
    """The largest difference, over the largest value of what is wanted."""
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))


# float32: sums of a chunk's 16 to 128 terms in another order than the recurrence's.
# bfloat16 operands (8 bits of mantissa) under float32 decays, states and accumulation:
# a product rounds at 2^-9, and the sums average it down
TOLERANCE = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("seq, chunk, groups, dtype", [
    (16, 16, 2, jnp.float32), (64, 16, 2, jnp.float32), (384, 16, 2, jnp.float32),
    (256, 128, 1, jnp.float32), (48, 16, 4, jnp.float32),
    (384, 16, 2, jnp.bfloat16), (256, 128, 1, jnp.bfloat16)],
    ids=["one-chunk", "four-chunks-one-block", "three-blocks-of-8-chunks", "published-chunk-one-group",
         "a-group-a-head", "bfloat16-three-blocks", "bfloat16-published-chunk"])
def test_chunked_scan_equals_the_recurrence_outputs_and_gradients(seq, chunk, groups, dtype):
    args = _inputs(seq, groups=groups, dtype=dtype)
    exact = tuple(t.astype(jnp.float32) for t in args)      # the same rounded inputs, in float32
    y = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=chunk))(*args)
    want, _ = jax.jit(ssd.ssd_reference)(*exact)
    assert y.dtype == dtype and y.shape == args[0].shape
    assert _scaled_gap(y, want) < TOLERANCE[dtype]
    weight = jax.random.normal(jax.random.PRNGKey(9), want.shape)

    def objective(scan):
        return lambda *a: jnp.sum(weight * scan(*a).astype(jnp.float32))

    ours = jax.jit(jax.grad(objective(lambda *a: ssd.ssd_scan(*a, chunk=chunk)), argnums=range(6)))(*args)
    theirs = jax.jit(jax.grad(objective(lambda *a: ssd.ssd_reference(*a)[0]), argnums=range(6)))(*exact)
    for name, got, ref in zip(("x", "dt", "a_log", "b", "c", "d"), ours, theirs):
        assert got.shape == ref.shape, name
        assert _scaled_gap(got, ref) < 5 * TOLERANCE[dtype], name


def test_a_state_crosses_chunk_and_block_borders():
    """A slow head (decay 0.05 a unit step) with input at position 0 ALONE:
    every later output is the carried state's read-out, across 24 chunks in
    three blocks; cut off at a border it would be zero."""
    x, dt, a_log, b, c, d = _inputs(384, heads=2, groups=1)
    x = x.at[:, 1:].set(0.0)
    a_log = jnp.log(jnp.full((2,), 0.05))
    y = ssd.ssd_scan(x, dt, a_log, b, c, jnp.zeros((2,)), chunk=16)
    want, last = ssd.ssd_reference(x, dt, a_log, b, c, jnp.zeros((2,)))
    assert _scaled_gap(y, want) < 2e-5
    for position in (15, 16, 127, 128, 383):      # inside, over a chunk's border, over a block's, the end
        assert float(jnp.max(jnp.abs(y[:, position]))) > 1e-3 * float(jnp.max(jnp.abs(y[:, 0])))
    assert float(jnp.max(jnp.abs(last))) > 0


def test_convolution_sees_zeros_before_the_sequence():
    """Positions 0, 1, 2 of a 4-tap convolution read 1, 2, 3 inputs: taps
    w[:, 3], w[:, 2:], w[:, 1:] of x_0..x_t, the bias, silu; position 3 on
    reads all four."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 6, 5))
    w, b = jax.random.normal(jax.random.fold_in(key, 1), (5, 4)), jax.random.normal(jax.random.fold_in(key, 2), (5,))
    out = ssd.causal_conv1d(x, w, b)
    for t in range(6):
        seen = x[:, max(t - 3, 0): t + 1]                                 # (B, taps seen, C)
        want = jax.nn.silu(b + jnp.einsum("bjc,cj->bc", seen, w[:, 4 - seen.shape[1]:]))
        np.testing.assert_allclose(np.asarray(out[:, t]), np.asarray(want), atol=1e-6)
    assert ssd.causal_conv1d(x.astype(jnp.bfloat16), w, b).dtype == jnp.bfloat16
    # causal: a later input moves no earlier output
    moved = ssd.causal_conv1d(x.at[:, 4].add(1.0), w, b)
    np.testing.assert_array_equal(np.asarray(moved[:, :4]), np.asarray(out[:, :4]))


def test_a_ragged_sequence_and_ragged_groups_are_refused_by_name():
    x, dt, a_log, b, c, d = _inputs(40)
    with pytest.raises(ValueError, match="sequence of 40 is no multiple of the chunk 16"):
        ssd.ssd_scan(x, dt, a_log, b, c, d, chunk=16)
    with pytest.raises(ValueError, match="4 heads are no multiple of the 3 groups"):
        ssd.ssd_scan(x[:, :32], dt[:, :32], a_log, jnp.zeros((2, 32, 3, 16)), jnp.zeros((2, 32, 3, 16)), d,
                     chunk=16)


def test_plan_and_the_most_negative_log_decay_of_a_chunk():
    sizes = dict(heads=4, groups=2, head_dim=8, state=16)
    assert ssd.scan_plan(8192, 128, **sizes) == {       # the CPU's answer: the XLA form, no kernel
        "ssm_scan_impl": "xla_chunked", "ssm_chunk": 128, "ssm_scan_block_chunks": ssd.BLOCK_CHUNKS,
        "ssm_scan_kernels": 0, "ssm_scan_state_bytes": 0}
    assert ssd.scan_plan(48, 16, **sizes)["ssm_scan_block_chunks"] == 3      # a divisor of the chunks
    dt = jnp.full((1, 32, 2), 0.5)
    a_log = jnp.log(jnp.asarray([1.0, 4.0]))
    # 16 positions x 0.5 x -4
    assert float(ssd.log_decay_chunk_min(dt, a_log, 16)) == pytest.approx(-32.0)


def test_the_walk_keeps_its_inputs_and_one_state_a_block_under_their_names():
    """What a checkpoint around the caller may keep: `ssm_scan_out` and
    `ssm_chunk_states`, (blocks, B, H, P, N) float32; with both kept, the
    backward pass of a checkpointed caller holds no second forward walk."""
    args = _inputs(384)
    policy = jax.checkpoint_policies.save_only_these_names("ssm_scan_out", "ssm_chunk_states")

    def loss(*a):
        return jnp.sum(ssd.ssd_scan(*a, chunk=16) ** 2)

    def walks(fn):      # forward walks over the blocks in fn's gradient, the backward's reversed one aside
        def scans(jaxpr):
            found = 0
            for eqn in jaxpr.eqns:
                found += eqn.primitive.name == "scan" and not eqn.params["reverse"]
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    found += scans(sub)
            return found

        return scans(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2, 3, 4, 5)))(*args).jaxpr)

    assert walks(jax.checkpoint(loss, policy=policy)) == walks(loss) == 1
    assert walks(jax.checkpoint(loss)) == 2
    kept = [str(shape) for shape, _ in jax._src.ad_checkpoint.saved_residuals(
        jax.checkpoint(loss, policy=policy), *args)]
    assert "float32[3,2,4,8,16]" in kept and "float32[3,2,8,16,4,8]" in kept, kept
