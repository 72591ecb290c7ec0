"""ops/ssd.py's kernels, interpreted. The scan's (`ssd_fwd`, `ssd_bwd`):
against the recurrence taken one position at a time (`ssd_reference`) AND
against the XLA chunked form, outputs and every argument's gradient, at the
published head shapes (P 64, N 128, chunk 128); what a checkpoint around a
differentiated scan keeps. The gated norm's (`ssm_gate_norm_fwd`,
`ssm_gate_norm_bwd`): against the XLA form AND a float32 reference written
here, outputs and the gradients of y, z and scale. The convolution's
(`ssm_conv_fwd`, `ssm_conv_bwd`): against the XLA form, outputs and the
gradients of x, w and b, over several tiles of rows and sequences, at a
column offset of a wider array. The one rule that chooses each one's form.
The whole file takes about a minute alone (the rule at the
top of conftest.py): two or three chunks, a few tiles of rows a case."""

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
    """(y, every argument's gradient of sum(weight y)), one compilation."""
    def objective(*a):
        y = scan(*a)
        return jnp.sum(weight * y.astype(jnp.float32)), y

    grads, y = jax.jit(jax.grad(objective, argnums=range(len(args)), has_aux=True))(*args)
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


SCAN_CELL = dict(chunk=128, heads=64, groups=8, head_dim=64, state=128)
NORM_CELL = dict(rows=2 * 8192, inner=4096, groups=8)
CONV_CELL = dict(seq=8192, channels=6144, taps=4, offset=4096)
RULES = {
    # resolver, the XLA form's name, the cell's sizes, sizes the kernels do not tile
    "scan": (ssd.resolve_scan_impl, "xla_chunked", SCAN_CELL,
             (dict(chunk=16, heads=8, groups=2, head_dim=8, state=16), dict(SCAN_CELL, chunk=64),
              dict(SCAN_CELL, head_dim=32), dict(SCAN_CELL, state=64), dict(SCAN_CELL, groups=64),
              dict(SCAN_CELL, heads=512, groups=8))),
    # a group of 64 or 192 lanes is no whole number of 128-lane tiles; 8,200 rows have no divisor that
    # is a multiple of a bfloat16 tile's 16 sublanes; 4,096 features are not 3 groups
    "gate-norm": (ssd.resolve_gate_norm_impl, "xla", NORM_CELL,
                  (dict(NORM_CELL, inner=512), dict(NORM_CELL, inner=1536), dict(NORM_CELL, rows=8200),
                   dict(NORM_CELL, groups=3), dict(rows=96, inner=32, groups=2))),
    # 6,000 channels, column 4,000 or an output of 1,000 are no whole lane tiles; 8,200 rows a sequence have no
    # divisor that is a multiple of 16; 18 taps reach past the one strip before a tile, and one tap is no
    # convolution; a step takes every channel, at most 8,192
    "convolution": (ssd.resolve_conv_impl, "xla", CONV_CELL,
                    (dict(CONV_CELL, channels=6000), dict(CONV_CELL, offset=4000), dict(CONV_CELL, seq=8200),
                     dict(CONV_CELL, taps=18), dict(CONV_CELL, taps=1), dict(seq=48, channels=96, taps=4),
                     dict(CONV_CELL, channels=16384), dict(CONV_CELL, splits=(4096, 1000, 1048)))),
}


@pytest.mark.parametrize("which", sorted(RULES))
def test_the_rule_is_the_backend_the_shapes_and_the_mesh(monkeypatch, which):
    """ONE rule for the scan's, the gated norm's and the convolution's kernels (`ssd._resolve`):
    off a TPU the XLA form; on one the kernels at the sizes they tile and
    the XLA form elsewhere, said by the plan; never under a context mesh of
    several devices that nothing made manual; a kernel asked for by name at
    sizes it does not tile is refused."""
    resolve, plain, cell, untiled_sizes = RULES[which]
    assert resolve(**cell) == plain                                 # this backend
    assert resolve("pallas", **cell) == "pallas"                    # interpreted, for tests
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve(**cell) == "pallas"
    for untiled in untiled_sizes:
        assert resolve(**untiled) == plain, untiled
        with pytest.raises(ValueError, match="the kernels do not tile"):
            resolve("pallas", **untiled)
    with pytest.raises(ValueError, match=f"unknown {which.replace('-', 'd ')} implementation"):
        resolve("mosaic", **cell)
    mesh = jax.make_mesh((2, 4), ("fsdp", "tp"))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert resolve(**cell) == plain
    with jax.sharding.use_abstract_mesh(jax.make_mesh((1,), ("fsdp",)).abstract_mesh):
        assert resolve(**cell) == "pallas"


def test_the_plans_say_what_the_rule_chose(monkeypatch):
    tiny = ssd.scan_plan(48, 16, heads=8, groups=2, head_dim=8, state=16)
    assert (tiny["ssm_scan_impl"], tiny["ssm_scan_block_chunks"], tiny["ssm_scan_kernels"],
            tiny["ssm_scan_state_bytes"]) == ("xla_chunked", 3, 0, 0)
    assert ssd.gate_norm_plan(**NORM_CELL) == {"ssm_gate_norm_impl": "xla", "ssm_gate_norm_rows": 0}
    assert ssd.conv_plan(**CONV_CELL) == {"ssm_conv_impl": "xla", "ssm_conv_rows": 0}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd.scan_plan(8192, 128, heads=64, groups=8, head_dim=64, state=128) == {
        "ssm_scan_impl": "pallas", "ssm_chunk": 128, "ssm_scan_block_chunks": 1,
        "ssm_scan_kernels": 2, "ssm_scan_state_bytes": 8 * 64 * 128 * 4}
    assert ssd.gate_norm_plan(**NORM_CELL) == {"ssm_gate_norm_impl": "pallas", "ssm_gate_norm_rows": ssd._NORM_ROWS}
    # 2 x 200 rows: the largest divisor that is a multiple of 16 sublanes and at most `_NORM_ROWS`
    assert ssd.gate_norm_plan(400, 4096, 8)["ssm_gate_norm_rows"] == 80
    assert ssd.gate_norm_plan(8200, 4096, 8) == {"ssm_gate_norm_impl": "xla", "ssm_gate_norm_rows": 0}
    assert ssd.conv_plan(**CONV_CELL) == {"ssm_conv_impl": "pallas", "ssm_conv_rows": ssd._CONV_ROWS}
    # a sequence of 400: a tile is rows of ONE sequence, the largest divisor that is a multiple of 16
    assert ssd.conv_plan(400, 6144, 4, 4096) == {"ssm_conv_impl": "pallas", "ssm_conv_rows": 80}
    assert ssd.conv_plan(8200, 6144, 4, 4096) == {"ssm_conv_impl": "xla", "ssm_conv_rows": 0}


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



# ----------------------------------------------------------- the gated norm


def _norm_reference(y, z, scale, groups, eps):
    """The gate, then an RMS norm a group, written out in float32."""
    y, z, scale = (t.astype(jnp.float32) for t in (y, z, scale))
    gated = y * z / (1.0 + jnp.exp(-z))
    by_group = gated.reshape(*gated.shape[:2], groups, -1)
    normed = by_group / jnp.sqrt(jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
    return normed.reshape(gated.shape) * scale


def _norm_inputs(batch, seq, inner, dtype, wide=0):
    """(y, z, scale, the objective's weights): z `wide` features wider than y where asked."""
    keys = jax.random.split(jax.random.PRNGKey(50), 4)
    return (jax.random.normal(keys[0], (batch, seq, inner), dtype),
            jax.random.normal(keys[1], (batch, seq, inner + wide), dtype),
            1.0 + 0.2 * jax.random.normal(keys[2], (inner,)), jax.random.normal(keys[3], (batch, seq, inner)))


NORM_TOLERANCE = {jnp.float32: 2e-6, jnp.bfloat16: 1e-2}      # bfloat16: one rounding of the output, 2^-8


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("groups, width, wide", [(8, 512, 192), (2, 256, 0)],
                         ids=["8-groups-of-512-in-the-projection", "2-groups-of-256"])
def test_norm_kernels_equal_the_xla_form_and_the_reference_outputs_and_gradients(monkeypatch, groups, width, wide, dtype):
    """2 x 48 rows in three tiles of 32: the scale's cotangent is summed over
    the grid in its block. At the published 8 groups of 512 z is the
    in-projection's output as the mixer hands it over, WIDER than y with the
    gate first: its cotangent is the gate's and zeros."""
    monkeypatch.setattr(ssd, "_NORM_ROWS", 32)
    inner = groups * width
    y, z, scale, weight = _norm_inputs(2, 48, inner, dtype, wide)
    norm = lambda impl: lambda *a: ssd.gated_group_norm(*a, groups=groups, eps=1e-5, implementation=impl)  # noqa: E731
    out, ours = _output_and_gradients(norm("pallas"), weight, (y, z, scale))
    plain, plains = _output_and_gradients(norm("xla"), weight, (y, z, scale))
    want, theirs = _output_and_gradients(
        lambda y, z, scale: _norm_reference(y, z[..., :inner], scale, groups, 1e-5), weight,
        (y.astype(jnp.float32), z.astype(jnp.float32), scale))
    assert out.dtype == dtype and out.shape == y.shape
    assert _scaled_gap(out, want) < NORM_TOLERANCE[dtype]
    assert _scaled_gap(out, plain.astype(jnp.float32)) < NORM_TOLERANCE[dtype]
    for name, got, plain, ref in zip(("y", "z", "scale"), ours, plains, theirs):
        assert got.shape == ref.shape and got.dtype == plain.dtype, name
        assert _scaled_gap(got, ref) < 2 * NORM_TOLERANCE[dtype], name
        assert _scaled_gap(got, plain.astype(jnp.float32)) < 2 * NORM_TOLERANCE[dtype], name
    assert not wide or float(jnp.max(jnp.abs(ours[1][..., inner:]))) == 0.0


def test_norm_kernels_take_a_row_count_that_is_no_multiple_of_the_tile():
    """1 x 400 rows under tiles of at most 256: five steps of 80 rows, the
    largest divisor that is a multiple of 16; 1 x 40 rows have none, and the
    kernels asked for by name are refused."""
    y, z, scale, weight = _norm_inputs(1, 400, 512, jnp.float32)
    assert ssd._norm_rows(400) == 80
    norm = lambda impl: lambda *a: ssd.gated_group_norm(*a, groups=2, eps=1e-6, implementation=impl)  # noqa: E731
    out, ours = _output_and_gradients(norm("pallas"), weight, (y, z, scale))
    plain, plains = _output_and_gradients(norm("xla"), weight, (y, z, scale))
    assert _scaled_gap(out, plain) < 2e-6
    for name, got, want in zip(("y", "z", "scale"), ours, plains):
        assert _scaled_gap(got, want) < 4e-6, name
    with pytest.raises(ValueError, match="the kernels do not tile 40 rows"):
        norm("pallas")(y[:, :40], z[:, :40], scale)
    np.testing.assert_array_equal(np.asarray(norm(None)(y[:, :40], z[:, :40], scale)),
                                  np.asarray(norm("xla")(y[:, :40], z[:, :40], scale)))


def test_norm_kernels_asked_for_by_name_at_a_width_they_do_not_tile_are_refused():
    """Groups of 64 lanes (the tiny trees') are no whole 128-lane tiles: with
    nothing asked the XLA form, bit for bit what ops/layers.rmsnorm gives on
    the view by groups; by name refused, never swapped."""
    y, z, scale, _ = _norm_inputs(2, 16, 128, jnp.float32)
    with pytest.raises(ValueError, match="the kernels do not tile 32 rows of 128 features in 2 groups"):
        ssd.gated_group_norm(y, z, scale, groups=2, eps=1e-5, implementation="pallas")
    with pytest.raises(ValueError, match="unknown gated norm implementation"):
        ssd.gated_group_norm(y, z, scale, groups=2, eps=1e-5, implementation="mosaic")
    from ray_tpu.ops import rmsnorm
    gated = y * jax.nn.silu(z)
    want = rmsnorm(gated.reshape(2, 16, 2, 64), scale.reshape(2, 64), eps=1e-5).reshape(2, 16, 128)
    np.testing.assert_array_equal(np.asarray(ssd.gated_group_norm(y, z, scale, groups=2, eps=1e-5)), np.asarray(want))


def test_a_differentiated_norm_is_two_kernels_that_keep_nothing_of_their_own():
    """`ssm_gate_norm_fwd` once and `ssm_gate_norm_bwd` once; what the
    backward kernel reads is the forward's three arguments, so a checkpoint
    runs the forward kernel again only where something reads its OUTPUT (the
    mixer's out-projection does: its weight gradient)."""
    y, z, scale, _ = _norm_inputs(1, 32, 512, jnp.bfloat16)

    def norm(*a):
        return ssd.gated_group_norm(*a, groups=2, eps=1e-5, implementation="pallas").astype(jnp.float32)

    def kernels(fn):
        jaxpr = jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2)))(y, z, scale).jaxpr
        return _calls(jaxpr, "ssm_gate_norm_fwd"), _calls(jaxpr, "ssm_gate_norm_bwd")

    linear, square = lambda *a: jnp.sum(norm(*a)), lambda *a: jnp.sum(norm(*a) ** 2)       # noqa: E731
    assert kernels(linear) == kernels(square) == kernels(jax.checkpoint(linear)) == (1, 1)
    assert kernels(jax.checkpoint(square)) == (2, 1)
    kept = [str(shape) for shape, _ in jax._src.ad_checkpoint.saved_residuals(linear, y, z, scale)]
    assert sorted(kept) == ["bfloat16[1,32,512]"] * 2 + ["float32[1,512]"], kept      # y, z and the scale


# ---------------------------------------------------------- the convolution


def _conv_inputs(batch, seq, wide, channels, dtype, taps=4):
    """(x, w, b, the objective's weights): x `wide` features, the convolution's are `channels` of them."""
    keys = jax.random.split(jax.random.PRNGKey(52), 4)
    return (jax.random.normal(keys[0], (batch, seq, wide), dtype), 0.5 * jax.random.normal(keys[1], (channels, taps)),
            0.1 * jax.random.normal(keys[2], (channels,)), jax.random.normal(keys[3], (batch, seq, channels)))


def _conv(implementation, offset=0):
    return lambda *a: ssd.causal_conv1d(*a, offset=offset, implementation=implementation)


CONV_TOLERANCE = {jnp.float32: 2e-6, jnp.bfloat16: 1e-2}      # bfloat16: one rounding of the output or of dx, 2^-8


def _conv_gradients_agree(ours, plains, dtype):
    """x's gradient in x's dtype, w's and b's in float32."""
    for name, got, want in zip(("x", "w", "b"), ours, plains):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        limit = 2 * CONV_TOLERANCE[dtype if name == "x" else jnp.float32]
        assert _scaled_gap(got, want.astype(jnp.float32)) < limit, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("wide, offset, channels, lanes", [(640, 128, 256, 128), (384, 0, 384, 512)],
                         ids=["xbc-inside-the-projection", "the-whole-array-in-one-lane-chunk"])
def test_conv_kernels_equal_the_xla_form_outputs_and_gradients(monkeypatch, wide, offset, channels, lanes, dtype):
    """Two sequences of 96 rows in three tiles of 32: a tile's first rows read
    the strip before it and its last d pre the strip after it, dw and db are
    summed over the six steps in their blocks. Read at column 128 of an
    array of 640 (two column blocks of 128 a step), the columns around xBC
    get a zero cotangent."""
    monkeypatch.setattr(ssd, "_CONV_ROWS", 32)
    monkeypatch.setattr(ssd, "_CONV_LANES", lanes)
    x, w, b, weight = _conv_inputs(2, 96, wide, channels, dtype)
    out, ours = _output_and_gradients(_conv("pallas", offset), weight, (x, w, b))
    plain, plains = _output_and_gradients(_conv("xla", offset), weight, (x, w, b))
    assert out.dtype == dtype and out.shape == (2, 96, channels)
    assert _scaled_gap(out, plain.astype(jnp.float32)) < CONV_TOLERANCE[dtype]
    _conv_gradients_agree(ours, plains, dtype)
    around = np.ones(wide, bool)
    around[offset:offset + channels] = False
    assert not np.asarray(ours[0].astype(jnp.float32))[..., around].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_conv_kernels_write_x_b_and_c_as_an_output_each_and_take_a_cotangent_each(monkeypatch, dtype):
    """The mixer's call in small: xBC at column 256 of an array of 1,024, cut
    into 512 + 128 + 128 (a lane chunk lies inside one block of x and one
    output): three outputs and three cotangents, the XLA form's slices."""
    monkeypatch.setattr(ssd, "_CONV_ROWS", 32)
    x, w, b, _ = _conv_inputs(2, 64, 1024, 768, dtype)
    splits = (512, 128, 128)
    weights = [jax.random.normal(jax.random.PRNGKey(i), (2, 64, width)) for i, width in enumerate(splits)]

    def both(implementation):
        def objective(*a):
            parts = ssd.causal_conv1d(*a, offset=256, splits=splits, implementation=implementation)
            return sum(jnp.sum(weight * part.astype(jnp.float32)) for weight, part in zip(weights, parts)), parts
        return jax.jit(jax.grad(objective, argnums=(0, 1, 2), has_aux=True))(x, w, b)

    ours, parts = both("pallas")
    plains, plain = both("xla")
    whole = ssd.causal_conv1d(x, w, b, offset=256, implementation="xla")
    assert [part.shape for part in parts] == [(2, 64, width) for width in splits]
    assert _scaled_gap(jnp.concatenate(plain, axis=-1), whole.astype(jnp.float32)) < CONV_TOLERANCE[dtype]
    for got, want in zip(parts, plain):
        assert got.dtype == dtype and _scaled_gap(got, want.astype(jnp.float32)) < CONV_TOLERANCE[dtype]
    _conv_gradients_agree(ours, plains, dtype)
    with pytest.raises(ValueError, match=r"splits \(512, 128\) do not add up to the 768 channels"):
        ssd.causal_conv1d(x, w, b, offset=256, splits=(512, 128))


def test_conv_kernels_read_zeros_before_a_sequence_and_not_the_rows_before_it_in_memory():
    """The first rows of a sequence's FIRST tile see zeros: position 0 reads
    the last tap alone; the second sequence of a batch does not see the
    first's last rows, nor a tile the columns beside xBC; and an earlier
    input's gradient does not reach past its sequence."""
    x, w, b, _ = _conv_inputs(2, 32, 384, 128, jnp.float32)
    conv = _conv("pallas", 128)
    out = conv(x, w, b)
    xbc = x[..., 128:256]
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(jax.nn.silu(b + w[:, 3] * xbc[:, 0])), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[:, 1]), np.asarray(jax.nn.silu(b + w[:, 3] * xbc[:, 1] + w[:, 2] * xbc[:, 0])),
                               atol=1e-6)
    moved = conv(x.at[0].add(1.0).at[..., :128].add(1.0).at[..., 256:].add(1.0), w, b)
    np.testing.assert_array_equal(np.asarray(moved[1]), np.asarray(out[1]))
    assert float(jnp.max(jnp.abs(moved[0] - out[0]))) > 0
    # the last rows of the first sequence: their gradient comes from their own sequence alone
    dx = jax.grad(lambda x: jnp.sum(conv(x, w, b)[1]))(x)
    assert not np.asarray(dx[0]).any() and np.asarray(dx[1, :, 128:256]).all()


def test_conv_kernels_take_a_sequence_that_is_no_multiple_of_the_tile_and_refuse_what_does_not_tile():
    """1 x 400 rows under tiles of at most 256: five steps of 80 rows, the
    largest divisor that is a multiple of 16; 40 rows have none and 100
    channels are no lane tile: by name refused, never swapped, and with
    nothing asked the XLA form's bits."""
    x, w, b, weight = _conv_inputs(1, 400, 128, 128, jnp.float32)
    assert ssd._conv_rows(400) == 80
    out, ours = _output_and_gradients(_conv("pallas"), weight, (x, w, b))
    plain, plains = _output_and_gradients(_conv("xla"), weight, (x, w, b))
    assert _scaled_gap(out, plain) < 2e-6
    for name, got, want in zip(("x", "w", "b"), ours, plains):
        assert _scaled_gap(got, want) < 4e-6, name
    with pytest.raises(ValueError, match="the kernels do not tile sequences of 40 with 128 channels at column 0 under 4 taps"):
        _conv("pallas")(x[:, :40], w, b)
    with pytest.raises(ValueError, match="the kernels do not tile sequences of 400 with 100 channels"):
        _conv("pallas")(x, w[:100], b[:100])
    with pytest.raises(ValueError, match="unknown convolution implementation"):
        _conv("mosaic")(x, w, b)
    np.testing.assert_array_equal(np.asarray(_conv(None)(x[:, :40], w, b)), np.asarray(_conv("xla")(x[:, :40], w, b)))


def test_a_differentiated_convolution_is_two_kernels_that_keep_nothing_of_their_own():
    """`ssm_conv_fwd` once and `ssm_conv_bwd` once; what the backward kernel
    reads is the forward's three arguments (x as wide as it came), so a
    checkpoint runs the forward kernel again only where something reads its
    OUTPUT (the scan does)."""
    x, w, b, _ = _conv_inputs(1, 32, 256, 128, jnp.bfloat16)

    def conv(*a):
        return _conv("pallas", 128)(*a).astype(jnp.float32)

    def kernels(fn):
        """(forward, backward) calls: every site binds the ONE primitive `ssm_conv`, whose lowering builds
        the kernel out of line; with the outputs' cotangents after x, w and b it is the backward kernel."""
        def binds(jaxpr):
            found = [len(eqn.invars) > 3 for eqn in jaxpr.eqns if eqn.primitive is ssd.ssm_conv_p]
            for eqn in jaxpr.eqns:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    found += binds(sub)
            return found
        found = binds(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2)))(x, w, b).jaxpr)
        return found.count(False), found.count(True)

    linear, square = lambda *a: jnp.sum(conv(*a)), lambda *a: jnp.sum(conv(*a) ** 2)       # noqa: E731
    assert kernels(linear) == kernels(square) == kernels(jax.checkpoint(linear)) == (1, 1)
    assert kernels(jax.checkpoint(square)) == (2, 1)
    kept = [str(shape) for shape, _ in jax._src.ad_checkpoint.saved_residuals(linear, x, w, b)]
    assert sorted(kept) == ["bfloat16[1,32,256]", "float32[1,128]", "float32[4,128]"], kept    # x, the bias, the taps
