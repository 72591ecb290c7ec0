"""ops/ssd.py's kernels (`ssd_fwd`, `ssd_bwd`), interpreted: against the
recurrence taken one position at a time (`ssd_reference`) AND against the XLA
chunked form, outputs and every argument's gradient, at the published head
shapes (P 64, N 128, chunk 128); the rule that chooses the form; what a
checkpoint around a differentiated scan keeps. The whole file takes under a
minute alone (the rule at the top of conftest.py): two or three chunks a
case."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd
from tests.test_ssd import TOLERANCE, _inputs, _scaled_gap

NAMES = ("x", "dt", "a_log", "b", "c", "d")
PUBLISHED = dict(features=64, state=128, batch=1)


def _scan(implementation):
    return lambda *a: ssd.ssd_scan(*a, chunk=128, implementation=implementation)


def _output_and_gradients(scan, weight, args):
    """(y, the six arguments' gradients of sum(weight y)), one compilation."""
    def objective(*a):
        y = scan(*a)
        return jnp.sum(weight * y.astype(jnp.float32)), y

    grads, y = jax.jit(jax.grad(objective, argnums=range(6), has_aux=True))(*args)
    return y, grads


@pytest.mark.parametrize("groups, dtype", [(1, jnp.float32), (2, jnp.float32), (1, jnp.bfloat16), (2, jnp.bfloat16)],
                         ids=["one-group", "two-groups", "bfloat16-one-group", "bfloat16-two-groups"])
def test_kernels_equal_the_recurrence_and_the_xla_form_outputs_and_gradients(groups, dtype):
    """8 heads of 64 with a state of 128 in 1 and in 2 groups, three chunks
    of 128: the state crosses two borders in VMEM scratch."""
    args = _inputs(384, heads=8, groups=groups, dtype=dtype, **PUBLISHED)
    exact = tuple(t.astype(jnp.float32) for t in args)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    y, ours = _output_and_gradients(_scan("pallas"), weight, args)
    plain, plains = _output_and_gradients(_scan("xla_chunked"), weight, args)
    want, theirs = _output_and_gradients(lambda *a: ssd.ssd_reference(*a)[0], weight, exact)
    assert y.dtype == dtype and y.shape == args[0].shape
    assert _scaled_gap(y, want) < TOLERANCE[dtype]
    assert _scaled_gap(y, plain.astype(jnp.float32)) < TOLERANCE[dtype]
    for name, got, plain, ref in zip(NAMES, ours, plains, theirs):
        assert got.shape == ref.shape and got.dtype == plain.dtype, name
        assert _scaled_gap(got, ref) < 5 * TOLERANCE[dtype], name
        assert _scaled_gap(got, plain.astype(jnp.float32)) < 5 * TOLERANCE[dtype], name


def test_a_state_crosses_every_chunk_border_on_the_chip():
    """A slow head (decay 0.05 a unit step) with input at position 0 ALONE:
    every later output is the read-out of the state the scratch carries, and
    every gradient reaches position 0 through it."""
    x, dt, a_log, b, c, d = _inputs(384, heads=2, groups=1, **PUBLISHED)
    x = x.at[:, 1:].set(0.0)
    a_log, d = jnp.log(jnp.full((2,), 0.05)), jnp.zeros((2,))
    weight = jnp.zeros(x.shape).at[:, 300:].set(1.0)        # the objective reads the last chunk only
    y, ours = _output_and_gradients(_scan("pallas"), weight, (x, dt, a_log, b, c, d))
    want, theirs = _output_and_gradients(lambda *a: ssd.ssd_reference(*a)[0], weight, (x, dt, a_log, b, c, d))
    assert _scaled_gap(y, want) < 2e-5
    for position in (127, 128, 255, 256, 383):
        assert float(jnp.max(jnp.abs(y[:, position]))) > 1e-3 * float(jnp.max(jnp.abs(y[:, 0])))
    assert float(jnp.max(jnp.abs(ours[0][:, 0]))) > 0
    for name, got, ref in zip(NAMES[:5], ours, theirs):
        assert _scaled_gap(got, ref) < 1e-4, name


def test_a_fast_head_whose_chunk_decays_past_what_float32_holds():
    """Steps of 0.3 under a decay of 16 a unit step: -614 over a chunk, where
    exp(c_l) exp(-c_s) as two factors is 0 x inf. One exponential of the
    difference: outputs and gradients finite and the recurrence's."""
    x, dt, a_log, b, c, d = _inputs(256, heads=2, groups=1, **PUBLISHED)
    dt, a_log = jnp.full_like(dt, 0.3), jnp.log(jnp.full((2,), 16.0))
    assert float(ssd.log_decay_chunk_min(dt, a_log, 128)) < -500
    args = (x, dt, a_log, b, c, d)
    weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    y, ours = _output_and_gradients(_scan("pallas"), weight, args)
    want, theirs = _output_and_gradients(lambda *a: ssd.ssd_reference(*a)[0], weight, args)
    assert bool(jnp.all(jnp.isfinite(y))) and _scaled_gap(y, want) < 2e-5
    for name, got, ref in zip(NAMES, ours, theirs):
        assert bool(jnp.all(jnp.isfinite(got))), name
        # a_log's is a sum of cancelling terms under exponents of 600, which float32
        # rounds at 4e-5: the XLA form reads 2.2e-4 of the recurrence's here, the kernels 3.1e-4
        assert _scaled_gap(got, ref) < (1e-3 if name == "a_log" else 1e-4), name


def test_the_rule_is_the_backend_the_shapes_and_the_mesh(monkeypatch):
    """Off a TPU the XLA form; on one the kernels at the sizes they tile and
    the XLA form elsewhere, said by `scan_plan`; never under a context mesh
    of several devices that nothing made manual; a kernel asked for by name
    at sizes it does not tile is refused."""
    cell = dict(chunk=128, heads=64, groups=8, head_dim=64, state=128)
    tests = dict(chunk=16, heads=8, groups=2, head_dim=8, state=16)
    assert ssd.resolve_scan_impl(**cell) == "xla_chunked"                       # this backend
    assert ssd.resolve_scan_impl("pallas", **cell) == "pallas"                  # interpreted, for tests
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd.resolve_scan_impl(**cell) == "pallas"
    assert ssd.scan_plan(8192, 128, heads=64, groups=8, head_dim=64, state=128) == {
        "ssm_scan_impl": "pallas", "ssm_chunk": 128, "ssm_scan_block_chunks": 1,
        "ssm_scan_kernels": 2, "ssm_scan_state_bytes": 8 * 64 * 128 * 4}
    for untiled in (tests, dict(cell, chunk=64), dict(cell, head_dim=32), dict(cell, state=64),
                    dict(cell, groups=64), dict(cell, heads=512, groups=8)):
        assert ssd.resolve_scan_impl(**untiled) == "xla_chunked", untiled
        with pytest.raises(ValueError, match="the kernels do not tile"):
            ssd.resolve_scan_impl("pallas", **untiled)
    said = ssd.scan_plan(48, 16, heads=8, groups=2, head_dim=8, state=16)
    assert (said["ssm_scan_impl"], said["ssm_scan_block_chunks"], said["ssm_scan_kernels"],
            said["ssm_scan_state_bytes"]) == ("xla_chunked", 3, 0, 0)
    with pytest.raises(ValueError, match="unknown scan implementation"):
        ssd.resolve_scan_impl("mosaic", **cell)
    mesh = jax.make_mesh((2, 4), ("fsdp", "tp"))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert ssd.resolve_scan_impl(**cell) == "xla_chunked"
    with jax.sharding.use_abstract_mesh(jax.make_mesh((1,), ("fsdp",)).abstract_mesh):
        assert ssd.resolve_scan_impl(**cell) == "pallas"


def _calls(jaxpr, name):
    """`pallas_call`s named `name` anywhere in `jaxpr`."""
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == "pallas_call" and eqn.params["name"] == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _calls(sub, name)
    return found


def test_a_differentiated_scan_is_two_kernels_and_a_checkpoint_may_keep_what_spares_the_second_forward():
    """`ssd_fwd` once and `ssd_bwd` once; under a checkpoint that keeps
    nothing the forward runs again, and with `ssm_scan_out` and
    `ssm_chunk_states` kept (the state that entered every chunk, narrowed as
    the read-out takes it) it does not."""
    args = _inputs(256, heads=2, groups=1, **PUBLISHED)
    policy = jax.checkpoint_policies.save_only_these_names("ssm_scan_out", "ssm_chunk_states")

    def loss(*a):
        return jnp.sum(_scan("pallas")(*a) ** 2)

    def kernels(fn):
        jaxpr = jax.make_jaxpr(jax.grad(fn, argnums=tuple(range(6))))(*args).jaxpr
        return _calls(jaxpr, "ssd_fwd"), _calls(jaxpr, "ssd_bwd")

    assert kernels(loss) == kernels(jax.checkpoint(loss, policy=policy)) == (1, 1)
    assert kernels(jax.checkpoint(loss)) == (2, 1)
    assert sum(kernels(loss)) == ssd.scan_plan(
        256, 128, heads=2, groups=1, head_dim=64, state=128, implementation="pallas")["ssm_scan_kernels"]
    kept = [str(shape) for shape, _ in jax._src.ad_checkpoint.saved_residuals(
        jax.checkpoint(loss, policy=policy), *args)]
    assert "float32[1,2,128,128]" in kept and "float32[1,256,128]" in kept, kept
    # the forward that is not differentiated writes no states
    plain = jax.make_jaxpr(_scan("pallas"))(*args).jaxpr
    assert _calls(plain, "ssd_fwd") == 1 and "128,128]" not in str([v.aval for v in plain.outvars])
    np.testing.assert_array_equal(np.asarray(_scan("pallas")(*args)),
                                  np.asarray(jax.vjp(_scan("pallas"), *args)[0]))
