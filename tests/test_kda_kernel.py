"""ops/kda.py's kernels, interpreted (`kda_fwd`, `kda_bwd`), fed the mixer's
own arguments (`kda_rule`: the convolution's q and k, the gate's input, the
two logits a head [beta | the norm's gate], `A_log`, the bias and the norm's
scale) on the fused contract (PR 63: y, the rule's output normed a head under
the head's gate, flat): against the recurrence taken one
position at a time on what `rule_arguments` makes of them (`kda_reference`)
followed by the plain norm AND
against the XLA form, outputs, the least cumulative log-decay and all eight
gradients, at the published head shapes (key and value heads of 128, chunk 64,
sub-block 16), over tests/test_kda.py's four cases and a head whose q is all
zeros; a state that crosses every chunk border in VMEM scratch; a whole chunk
at the gate's bound; the one rule that chooses the form, its refusals and the
plan; what a differentiated call binds and what a checkpoint around it keeps.
The whole file takes about a minute alone (the rule at the top of
conftest.py): two or three chunks of two heads a case."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda
from tests.test_kda import _raw

NAMES = ("q", "k", "v", "f", "beta_gate", "a_log", "dt_bias", "norm_scale")
EPS, NORM_EPS = 1e-6, 1e-5
# the gate's input with a rate of 1 and no bias: -5 sigmoid(8) = -4.998 a position with a slope left for the
# gradients, -5 sigmoid(-12) = -3e-5, -5 sigmoid(-6.2126) = -0.01
CASES = {"mixed_gates": {}, "at_the_bound": {"f": 8.0}, "no_decay": {"f": -12.0},
         "same_keys": {"same_keys": True, "f": -6.2126}, "a_zero_query_head": {"zero_query_head": True}}
CELL = dict(chunk=64, d_k=128, d_v=128, lower_bound=-5.0)


def _rule(implementation):
    return lambda *t: kda.kda_rule(*t, eps=EPS, norm_eps=NORM_EPS, chunk=64, implementation=implementation)


def _recurrence(q, k, v, f, beta_gate, a_log, bias, scale):
    """The recurrence on what `rule_arguments` makes of the mixer's, then the plain norm a head under the
    head's gate: (y, the least cumulative log-decay a chunk)."""
    heads = a_log.shape[0]
    made = kda.rule_arguments(q, k, v, f, beta_gate[..., :heads], a_log, bias, lower_bound=-5.0, eps=EPS)
    return (kda.gated_head_norm(kda.kda_reference(*made)[0], beta_gate[..., heads:], scale, NORM_EPS),
            kda.log_decay_chunk_min(made[3], 64))


def _output_and_gradients(rule, weight, args):
    """(y, the least cumulative log-decay, all eight gradients of sum(weight y)), one compilation."""
    def objective(*t):
        out, least = rule(*t)
        return jnp.sum(weight * out.astype(jnp.float32)), (out, least)

    grads, (out, least) = jax.jit(jax.grad(objective, argnums=range(8), has_aux=True))(*args)
    return out, least, grads


def _rms_gap(got, want):
    """The root mean square of the difference over that of what is wanted."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / (np.sqrt(np.mean(want ** 2)) + 1e-30))


def _sum_gap(got, want, terms, over):
    """The same for a gradient that is a SUM of `terms` over the axes `over`, against the root of the sum of
    its terms' squares: the sum itself may cancel to nothing (the gate's two parameters' at the bound do)."""
    got, want, terms = (np.asarray(t, np.float64) for t in (got, want, terms))
    spread = np.sqrt(np.sum(terms ** 2, axis=over)).reshape(want.shape)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(spread ** 2)))


# float32: sums in another order than the recurrence's (the XLA form reads up to 4e-6 here, the gate's gradients
# at the bound 5e-5: sums of cancelling terms). bfloat16: the operands' own rounding, in every form (the XLA
# form reads 0.003-0.015 of the recurrence here; `same_keys` is the 0.015).
TOLERANCE = {jnp.float32: 2e-5, jnp.bfloat16: 2.5e-2}
# `A_log`'s and the bias's gradients, sums over the sequence of f's cotangent (times f + bias), by `_sum_gap`.
# float32: every form reads 3e-6 to 1.5e-5 of the recurrence and 3e-4 at the bound, where the terms' errors do
# not cancel as the terms do (the two forms 6e-4 apart). bfloat16: 0.004-0.03, `same_keys` 0.05-0.085; a
# gradient twice what it should be reads 0.19
SUMS_TOLERANCE = {jnp.float32: 1e-3, jnp.bfloat16: 0.125}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_equal_the_recurrence_and_the_xla_form_outputs_and_gradients(case, dtype):
    """Two heads of 128 over two chunks of 64: the state crosses a border in
    VMEM scratch, its cotangent crosses it back; the kernels make the norms,
    the log-decay and beta themselves and transpose them, norm the float32 o
    where they have it and take y's cotangent back through that norm from the
    o `kda_fwd` kept, the two gate parameters' and the norm's scale's gradients
    from float32 sums a channel."""
    args = _raw(3, s=128, dtype=dtype, **CASES[case])
    exact = tuple(t.astype(jnp.float32) for t in args)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, 128, 2 * 128))
    out, least, ours = _output_and_gradients(_rule("pallas"), weight, args)
    plain, plain_least, plains = _output_and_gradients(_rule("xla_chunked"), weight, args)
    want, want_least, theirs = _output_and_gradients(_recurrence, weight, exact)
    assert out.dtype == dtype and out.shape == weight.shape and bool(jnp.all(jnp.isfinite(out)))
    assert _rms_gap(out, want) < TOLERANCE[dtype]
    assert _rms_gap(out, plain) < TOLERANCE[dtype]
    assert _rms_gap(plain, want) < TOLERANCE[dtype]       # the XLA form is the recurrence followed by the plain norm
    # the kernel's second output is `log_decay_chunk_min` of the log-decay it never writes
    assert float(least) == pytest.approx(float(want_least), rel=1e-5) == pytest.approx(float(plain_least), rel=1e-5)
    if case == "a_zero_query_head":
        assert float(jnp.max(jnp.abs(out[..., :128]))) == 0.0 and float(jnp.max(jnp.abs(ours[0][..., :128]))) > 0
    for name, got, plain, ref in zip(NAMES[:5] + NAMES[7:], ours[:5] + ours[7:], plains[:5] + plains[7:],
                                     theirs[:5] + theirs[7:]):
        assert got.shape == ref.shape and got.dtype == plain.dtype and bool(jnp.all(jnp.isfinite(got))), name
        loose = 5 if name == "f" else 1
        assert _rms_gap(got, ref) < loose * TOLERANCE[dtype], name
        assert _rms_gap(got, plain) < loose * TOLERANCE[dtype], name
        # the XLA form norms o's ROUNDING to the activations' dtype, one rounding more than the kernels: in
        # bfloat16 `same_keys` reads 0.026 for q where the kernels read 0.021
        assert _rms_gap(plain, ref) < 1.5 * loose * TOLERANCE[dtype], name
    # the two parameters' gradients: f's cotangent, summed in float32 before anything is rounded
    d_f = theirs[3].reshape(1, 128, 2, 128)
    shifted = (exact[3] + exact[6]).reshape(1, 128, 2, 128)
    for at, terms, over in ((5, d_f * shifted, (0, 1, 3)), (6, d_f, (0, 1))):
        got, plain, ref = ours[at], plains[at], theirs[at]
        assert got.shape == ref.shape and got.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(got))), NAMES[at]
        assert _sum_gap(got, ref, terms, over) < SUMS_TOLERANCE[dtype], NAMES[at]
        assert _sum_gap(got, plain, terms, over) < SUMS_TOLERANCE[dtype], NAMES[at]


@pytest.mark.parametrize("heads", [8, 12], ids=["the-cells-block-of-8-heads", "two-blocks-of-6-heads"])
def test_the_fused_contract_at_the_cells_head_block_and_at_a_smaller_one(heads):
    """The kernels against the XLA form on the fused contract, y and every
    cotangent (q, k, v, f, `beta_gate` whole, `A_log`, the bias, the scale),
    with a grid step of 8 heads as the `train-ling3flash-4k` cell's and with
    two blocks of 6: a block's heads pick their two logits out of the lanes
    of ONE (64, 2 H) block, beta's at [0, H) and the gate's at [H, 2 H), and
    hand their cotangents back as one; the scale's gradient adds up over
    the chunks, the blocks and the heads."""
    assert kda.kda_plan(64, "pallas", heads=heads, d_k=128, d_v=128)["kda_heads_per_step"] == min(heads, 6 + 2 * (heads == 8))
    args = _raw(7, s=128, h=heads)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, 128, heads * 128))
    out, least, ours = _output_and_gradients(_rule("pallas"), weight, args)
    plain, plain_least, plains = _output_and_gradients(_rule("xla_chunked"), weight, args)
    assert out.shape == plain.shape == weight.shape and _rms_gap(out, plain) < 2e-5
    assert float(least) == pytest.approx(float(plain_least), rel=1e-5)
    for name, got, want in zip(NAMES, ours, plains):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert _rms_gap(got, want) < (1e-4 if name in ("f", "a_log", "dt_bias") else 2e-5), name
    # every head's gate logit moves y and no head reads another's
    assert float(jnp.min(jnp.max(jnp.abs(ours[4]), axis=(0, 1)))) > 0


def test_a_state_crosses_every_chunk_border_in_scratch():
    """A slow gate (-0.002 a position) with keys and values at position 0
    ALONE: every later output is the read-out of the state the scratch
    carries, and every gradient reaches position 0 through it."""
    # two sequences: the scratch starts each from zero
    q, k, v, f, beta_gate, a_log, bias, scale = _raw(4, s=192, b=2, f=-7.8236)
    beta_gate = beta_gate.at[:, 1:, :2].set(-200.0)        # beta's sigmoid 0: nothing is written after position 0
    args = (q, k, v, f, beta_gate, a_log, bias, scale)
    weight = jnp.zeros((2, 192, 2 * 128)).at[:, 150:].set(1.0)        # the objective reads the last chunk only
    out, least, ours = _output_and_gradients(_rule("pallas"), weight, args)
    want, _, theirs = _output_and_gradients(_recurrence, weight, args)
    assert _rms_gap(out, want) < 2e-5 and float(least) == pytest.approx(-64 * 0.002, rel=1e-3)
    for position in (63, 64, 127, 128, 191):
        assert float(jnp.max(jnp.abs(out[:, position]))) > 1e-3 * float(jnp.max(jnp.abs(out[:, 0])))
    assert float(jnp.max(jnp.abs(ours[2][:, 0]))) > 0 and float(jnp.max(jnp.abs(ours[1][:, 0]))) > 0
    for name, got, ref in zip(NAMES, ours, theirs):
        assert _rms_gap(got, ref) < 1e-4, name


def test_a_whole_chunk_at_the_bound_underflows_and_stays_finite_in_the_kernels():
    # -5 a position (sigmoid(40) is 1) is -320 over a chunk and -80 over a sub-block, whose factors reach
    # e^{-40} and e^{40}
    args = _raw(5, s=192, f=40.0, dtype=jnp.bfloat16)
    (out, least), grads = jax.jit(jax.value_and_grad(
        lambda *t: (lambda o, least: (jnp.sum(jnp.square(o.astype(jnp.float32))), least))(*_rule("pallas")(*t)),
        argnums=range(8), has_aux=True))(*args)
    assert float(least) == pytest.approx(-320.0)
    assert np.isfinite(float(out)) and all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_the_rule_is_the_backend_the_shapes_and_the_mesh(monkeypatch):
    """ops/ssd's ONE rule (`ssd._resolve`): off a TPU the XLA form; on one the
    kernels at the sizes they tile and the XLA form elsewhere; never under a
    context mesh of several devices that nothing made manual; a kernel asked
    for by name at sizes it does not tile is refused by name."""
    assert kda.resolve_kda_impl(**CELL) == "xla_chunked"                    # this backend
    assert kda.resolve_kda_impl("pallas", **CELL) == "pallas"               # interpreted, for tests
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kda.resolve_kda_impl(**CELL) == "pallas"
    # a chunk of 32; heads of 64 or of 256 values; a gate whose sub-block of 16 reaches e^{-96}; the tiny cell
    for untiled in (dict(CELL, chunk=32), dict(CELL, d_k=64), dict(CELL, d_v=256), dict(CELL, lower_bound=-6.0),
                    dict(chunk=32, d_k=16, d_v=16, lower_bound=-5.0), dict()):
        assert kda.resolve_kda_impl(**untiled) == "xla_chunked", untiled
        with pytest.raises(ValueError, match="kda_rule: the kernels do not tile a chunk of"):
            kda.resolve_kda_impl("pallas", **untiled)
    with pytest.raises(ValueError, match="unknown kda implementation: 'mosaic'"):
        kda.resolve_kda_impl("mosaic", **CELL)
    with jax.sharding.use_abstract_mesh(jax.make_mesh((2, 4), ("fsdp", "tp")).abstract_mesh):
        assert kda.resolve_kda_impl(**CELL) == "xla_chunked"
    with jax.sharding.use_abstract_mesh(jax.make_mesh((1,), ("fsdp",)).abstract_mesh):
        assert kda.resolve_kda_impl(**CELL) == "pallas"


def test_a_kernel_asked_for_by_name_where_it_does_not_tile_is_refused_by_name():
    with pytest.raises(ValueError, match="the kernels do not tile a chunk of 64, key heads of 16 and value heads of 8"):
        kda.kda_rule(*_raw(1, s=64, d_k=16, d_v=8), eps=EPS, norm_eps=NORM_EPS, implementation="pallas")
    with pytest.raises(ValueError, match="under a gate whose lower bound is -8.0 a position"):
        kda.kda_rule(*_raw(1, s=64), eps=EPS, norm_eps=NORM_EPS, implementation="pallas", lower_bound=-8.0)
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        kda.kda_rule(*_raw(1, s=96), eps=EPS, norm_eps=NORM_EPS, implementation="pallas")


def test_the_plan_says_what_the_rule_chose(monkeypatch):
    sizes = dict(heads=32, d_k=128, d_v=128)
    # `kda_prologue`: which form makes the recurrence's arguments from the mixer's, the kernels in VMEM or XLA;
    # `kda_epilogue`: which norms o a head under the head's gate, `kda_fwd` where o is (and `kda_bwd` back) or XLA
    xla = {"kda_impl": "xla_chunked", "kda_chunk": 64, "kda_subchunk": 16, "kda_kernels": 0,
           "kda_heads_per_step": 0, "kda_state_bytes": 0, "kda_prologue": "xla", "kda_epilogue": "xla"}
    assert kda.kda_plan(64, **sizes) == xla
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kda.kda_plan(64, **sizes) == dict(xla, kda_impl="pallas", kda_kernels=2, kda_heads_per_step=8,
                                             kda_state_bytes=8 * 128 * 128 * 4, kda_prologue="kernel",
                                             kda_epilogue="kernel")
    # a grid step takes the largest divisor of the heads that is at most `_KERNEL_HEADS`
    assert [kda.kda_plan(64, heads=h, d_k=128, d_v=128)["kda_heads_per_step"] for h in (2, 12, 7)] == [2, 6, 7]
    assert kda.kda_plan(32, heads=4, d_k=16, d_v=16) == dict(xla, kda_chunk=32)
    assert kda.kda_plan(64, lower_bound=-6.0, **sizes) == xla


def _binds(fn, args):
    """(forward, backward) binds of the ONE primitive `kda`, whose lowering
    builds a kernel out of line: with the kept states and y's cotangent after
    the five arguments and the three rows a channel it is `kda_bwd`."""
    def binds(jaxpr):
        found = [len(eqn.invars) > 8 for eqn in jaxpr.eqns if eqn.primitive is kda.kda_p]
        for eqn in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += binds(sub)
        return found
    found = binds(jax.make_jaxpr(fn)(*args).jaxpr)
    return found.count(False), found.count(True)


def test_a_differentiated_call_is_two_kernels_and_a_checkpoint_may_keep_what_spares_the_second_forward():
    """`kda_fwd` once and `kda_bwd` once, which keeps nothing of its own
    (what it reads is the mixer's arguments, the states that entered the chunks,
    the o `kda_fwd` wrote beside y and y's cotangent); under a checkpoint that
    keeps nothing the forward runs again, and with `kda_chunk_out` (y, what the
    caller's next operation reads), `kda_chunk_states` and `kda_chunk_o` kept it
    does not, and no gradient changes by a bit."""
    args = _raw(2, s=128, dtype=jnp.bfloat16)
    policy = jax.checkpoint_policies.save_only_these_names("kda_chunk_out", "kda_chunk_states", "kda_chunk_o")

    def loss(*t):
        return jnp.sum(_rule("pallas")(*t)[0].astype(jnp.float32) ** 2)

    def kernels(fn):
        return _binds(jax.grad(fn, argnums=tuple(range(8))), args)

    assert kernels(loss) == kernels(jax.checkpoint(loss, policy=policy)) == (1, 1)
    assert kernels(jax.checkpoint(loss)) == (2, 1)
    assert sum(kernels(loss)) == kda.kda_plan(64, "pallas", heads=2, d_k=128, d_v=128)["kda_kernels"]
    kept = sorted(str(shape) for shape, _ in jax._src.ad_checkpoint.saved_residuals(
        jax.checkpoint(loss, policy=policy), *args))
    # the mixer's arguments as they came (q, k, v, f), y for the square's derivative and o for the norm's, the
    # float32 states that entered the two chunks
    assert "float32[1,2,2,128,128]" in kept and kept.count("bfloat16[1,128,256]") == 6, kept
    whole, under = (jax.jit(jax.grad(fn, argnums=tuple(range(8))))(*args)
                    for fn in (loss, jax.checkpoint(loss, policy=policy)))
    for name, a, b in zip(NAMES, whole, under):
        assert bool(jnp.all(a == b)), name
    # no decays, no inverse, and nothing the kernels make of their arguments: no float32 log-decay, no q or k a head
    made = ("64,64]", "float32[1,128,256]", ",2,128]")
    assert not [shape for shape in kept if any(part in shape for part in made)], kept
    # the forward that is not differentiated writes no states and no o
    plain = jax.make_jaxpr(_rule("pallas"))(*args).jaxpr
    (call,) = [eqn for eqn in plain.eqns if "custom_vjp" in eqn.primitive.name]
    assert _binds(_rule("pallas"), args) == (1, 0) and "128,128]" not in str([v.aval for v in call.outvars])
    assert [v.aval.shape for v in call.outvars] == [(1, 128, 256), (1, 1, 256)]


def test_the_kernels_are_named_and_hold_the_state_they_say():
    """The two `pallas_call`s behind the primitive carry the names a device
    trace prints, a grid over (sequence, block of heads, chunk) with the
    chunks sequential, and a float32 scratch of the plan's `kda_state_bytes`."""
    args = _raw(2, s=128, dtype=jnp.bfloat16, h=4)
    flat = [*args[:5], *kda._channel_rows(*args[5:])]

    def calls(fn, *operands):
        return [eqn for eqn in jax.make_jaxpr(fn)(*operands).jaxpr.eqns if eqn.primitive.name == "pallas_call"]

    call = lambda *t, **kw: kda._kda_call(*t, heads=4, lower_bound=-5.0, eps=EPS, norm_eps=NORM_EPS,  # noqa: E731
                                          interpret=True, **kw)
    (forward,) = calls(lambda *t: call(*t, keep_states=True), *flat)
    states = jnp.zeros((1, 2, 4, 128, 128), jnp.float32)
    (backward,) = calls(lambda *t: call(*t, keep_states=False), *flat, states, flat[2], flat[2])
    plan = kda.kda_plan(64, "pallas", heads=4, d_k=128, d_v=128)
    for eqn, name in ((forward, "kda_fwd"), (backward, "kda_bwd")):
        assert eqn.params["name"] == name and eqn.params["grid_mapping"].grid == (1, 1, 2)
        mosaic = eqn.params["compiler_params"]["mosaic_tpu"]
        assert mosaic.dimension_semantics == ("parallel", "parallel", "arbitrary")
        assert mosaic.vmem_limit_bytes == kda._VMEM_LIMIT
    # y, the least cumulative log-decay a channel, the states that entered the two chunks, o
    assert plan["kda_state_bytes"] == 4 * 128 * 128 * 4 and [v.aval.shape for v in forward.outvars] == [
        (1, 128, 512), (1, 1, 512), (1, 2, 4, 128, 128), (1, 128, 512)]
    # dq, dk, dv, df as the arguments came; the two logits' a head a column, the step's heads' beta's then their
    # gates'; the gate's two sums and the norm's scale's a channel
    assert [(v.aval.shape, str(v.aval.dtype)) for v in backward.outvars] == [((1, 128, 512), "bfloat16")] * 4 + [
        ((1, 1, 128, 8), "float32")] + [((1, 1, 512), "float32")] * 3
