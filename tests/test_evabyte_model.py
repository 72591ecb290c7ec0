"""What the EvaByte family adds to the dense transformer and to the loss
(models/transformer.py, train/lm.py, ops/losses.py): the `eva` kind's leaves
and refusals, the (1 + scale) norm, the float32 stream, several next-byte
heads in the loss, dense and fused, against a loop over the heads, the forward
against the benchmark's plain reference, and what the trainer reports."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.models import forward, model_family  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.transformer import TransformerConfig  # noqa: E402
from ray_tpu.ops import losses  # noqa: E402
from ray_tpu.train import lm  # noqa: E402

HEADS, VOCAB = 8, 40


def tiny(**kw):
    base = dict(vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq=64, pos_emb="rope",
                norm="rmsnorm", act="swiglu", use_bias=False, tie_embeddings=False, rope_theta=1e5,
                norm_eps=1e-5, eva_window=8, eva_chunk=2, norm_unit_offset=True,
                residual_fp32=True, pred_heads=HEADS, dtype=jnp.float32)
    return TransformerConfig(**dict(base, **kw))


def _tokens(batch=3, seq=24, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, VOCAB, (batch, seq + 1)), jnp.int32)


# ------------------------------------------------------------ the configuration


def test_the_eva_kind_adds_two_pooling_vectors_a_head_and_a_wide_head():
    mc = tiny()
    params = transformer.init_params(mc, jax.random.PRNGKey(0))
    axes = transformer.logical_axes(mc)
    assert params["blocks"]["eva_mu"].shape == params["blocks"]["eva_phi"].shape == (2, 2, 16)
    assert axes["blocks"]["eva_mu"] == axes["blocks"]["eva_phi"] == ("layers", "heads", "head_dim")
    assert params["lm_head"].shape == (32, HEADS * VOCAB)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x))
    # a (1 + scale) norm starts at 0, the pooling vectors at N(0, 0.02)
    for name in ("ln1_scale", "ln2_scale"):
        assert not np.asarray(params["blocks"][name]).any()
    assert not np.asarray(params["lnf_scale"]).any()
    assert 0.01 < float(jnp.std(params["blocks"]["eva_mu"])) < 0.03
    assert mc.stream_dtype == jnp.float32 and tiny(residual_fp32=False, dtype=jnp.bfloat16).stream_dtype == jnp.bfloat16
    # the plain families are as they were: no new leaf, a head as wide as the vocabulary, scales of 1
    plain = TransformerConfig(vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq=64,
                              tie_embeddings=False)
    plain_params = transformer.init_params(plain, jax.random.PRNGKey(0))
    assert "eva_mu" not in plain_params["blocks"] and plain_params["lm_head"].shape == (32, VOCAB)
    assert np.asarray(plain_params["blocks"]["ln1_scale"]).all()
    assert model_family(plain).plan(plain, 4, 32) == {}


@pytest.mark.parametrize("change, match", [
    (dict(eva_window=-8), "eva_window -8"),
    (dict(eva_window=8, eva_chunk=3), "multiple of eva_chunk"),
    (dict(eva_window=0), "eva_window 0"),
    (dict(n_kv_heads=1), "as many key-value heads"),
    (dict(pos_emb="learned"), "rotary positions"),
    (dict(qk_norm=True), "QK-norm"),
    (dict(pred_heads=0), "pred_heads 0"),
    (dict(tie_embeddings=True), "untied head"),
    (dict(norm="layernorm", use_bias=True), "norm_unit_offset"),
], ids=["negative-window", "window-not-whole-chunks", "no-window", "grouped-keys", "learned-positions", "qk-norm",
        "no-head", "tied-head", "layernorm-offset"])
def test_the_configuration_refuses_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        tiny(**change)


@pytest.mark.parametrize("where", ["decode_step", "prefill", "paged"])
def test_the_cached_serving_paths_refuse_the_kind_by_name(where):
    mc = tiny()
    params = jax.eval_shape(lambda k: transformer.init_params(mc, k), jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="eva attention is run by the training forward alone"):
        if where == "paged":
            from ray_tpu.serve.llm.paged import PagedConfig, init_paged_cache

            init_paged_cache(mc, PagedConfig(page_size=8, num_pages=4))
        elif where == "decode_step":
            transformer.decode_step(params, transformer.init_cache(mc, 1, 16), jnp.zeros((1,), jnp.int32),
                                    jnp.zeros((1,), jnp.int32), mc)
        else:
            transformer.prefill(params, jnp.zeros((1, 8), jnp.int32), jnp.ones((1,), jnp.int32),
                                transformer.init_cache(mc, 1, 16), mc)
    with pytest.raises(NotImplementedError, match="several next-token heads"):
        transformer._no_eva(tiny(eva_window=0, eva_chunk=0), "the dense cache")


# ------------------------------------------------------ the norm and the stream


def test_the_unit_offset_norm_multiplies_by_one_plus_scale():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    want = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5) * (1.0 + g)
    got = transformer._norm(x, g, None, "rmsnorm", 1e-5, unit_offset=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    # at its start it is the plain norm with a scale of 1
    np.testing.assert_allclose(
        np.asarray(transformer._norm(x, jnp.zeros(32), None, "rmsnorm", 1e-5, unit_offset=True)),
        np.asarray(transformer._norm(x, jnp.ones(32), None, "rmsnorm", 1e-5)), rtol=1e-6)


def test_the_stream_is_float32_between_bfloat16_sublayers():
    mc = tiny(dtype=jnp.bfloat16)
    params = transformer.init_params(mc, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda w: w[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    tables = transformer.rope_frequencies(mc.head_dim, mc.max_seq, mc.rope_theta)
    y = transformer._block(x, lp, mc, tables, None)
    assert y.dtype == jnp.float32
    hidden = transformer.forward_hidden(params, _tokens(2, 16)[:, :-1], mc)
    assert hidden.dtype == jnp.bfloat16        # the final norm hands the head the compute dtype
    # the same block on a bfloat16 stream rounds every sum to 8 bits: the float32 stream does not
    rounded = transformer._block(x.astype(jnp.bfloat16), lp, mc.replace(residual_fp32=False), tables, None)
    assert rounded.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(y - y.astype(jnp.bfloat16).astype(jnp.float32)))) > 0


def test_the_forward_is_the_plain_references():
    """Program (the plain masked form and the interpreted kernels) against
    benchmark/reference/evabyte_ref.py on the seeded weights, float32."""
    from benchmark.reference import evabyte_ref

    mc = tiny()
    params = transformer.init_params(mc, jax.random.PRNGKey(3))
    params["blocks"]["ln1_scale"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (2, 32))
    tokens = _tokens(2, 32, seed=1)[:, :-1]
    with jax.default_matmul_precision("highest"):
        want = evabyte_ref.forward_logits(params, tokens, rope_theta=1e5, norm_eps=1e-5, window=8, chunk=2,
                                          pred_heads=HEADS)
        for impl in ("xla", "pallas"):
            got = jax.jit(lambda p, t: forward(p, t, mc.replace(attn_impl=impl)))(params, tokens)
            assert got.shape == (2, 32, HEADS * VOCAB)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, err_msg=impl)


# -------------------------------------------------------- the next-byte heads


def _loop_over_heads(logits, tokens):
    """Head n, positions 0 .. S - 1 - n, against tokens[t + 1 + n]: the mean a
    head, one head after the other."""
    s = tokens.shape[1] - 1
    per_head = []
    for n in range(HEADS):
        logp = jax.nn.log_softmax(logits[:, :s - n, n * VOCAB:(n + 1) * VOCAB].astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1 + n:1 + n + s - n, None], axis=-1)
        per_head.append(-jnp.mean(picked))
    return jnp.stack(per_head)


def test_targets_of_head_n_are_the_tokens_n_plus_one_ahead():
    tokens = _tokens(2, 12)
    targets, has = losses.multihead_targets(tokens, HEADS)
    assert targets.shape == has.shape == (2, 12, HEADS)
    for n in range(HEADS):
        np.testing.assert_array_equal(np.asarray(targets[:, :12 - n, n]), np.asarray(tokens[:, 1 + n:13]))
        assert np.asarray(has[:, :12 - n, n]).all() and not np.asarray(has[:, 12 - n:, n]).any()
        assert not np.asarray(targets[:, 12 - n:, n]).any()


@pytest.mark.parametrize("chunk", [0, 24, 8], ids=["dense", "fused-one-chunk", "fused-three-chunks"])
def test_the_eight_head_loss_is_the_loop_over_the_heads(chunk):
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 24, 32))
    head = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (32, HEADS * VOCAB))
    tokens = _tokens(3, 24)
    targets, has = losses.multihead_targets(tokens, HEADS)

    def ours(x, head):
        if chunk:
            return losses.fused_multihead_cross_entropy(x, head, targets, has, chunk=chunk)
        per_head = losses.multihead_cross_entropy(x @ head, targets, has)
        return jnp.mean(per_head), per_head

    def looped(x, head):
        per_head = _loop_over_heads(x @ head, tokens)
        return jnp.mean(per_head), per_head

    with jax.default_matmul_precision("highest"):
        (got, got_heads), got_grads = jax.value_and_grad(ours, argnums=(0, 1), has_aux=True)(x, head)
        (want, want_heads), want_grads = jax.value_and_grad(looped, argnums=(0, 1), has_aux=True)(x, head)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_heads), np.asarray(want_heads), rtol=1e-6)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7, rtol=1e-5)
    assert float(want_heads[0]) != float(want_heads[7])


def test_a_chunk_that_does_not_divide_the_sequence_is_refused():
    x, head = jnp.zeros((1, 24, 32)), jnp.zeros((32, HEADS * VOCAB))
    targets, has = losses.multihead_targets(_tokens(1, 24), HEADS)
    with pytest.raises(ValueError, match="not divisible by loss chunk 7"):
        losses.fused_multihead_cross_entropy(x, head, targets, has, chunk=7)


@pytest.mark.parametrize("chunk", [0, 8], ids=["dense", "fused"])
def test_lm_loss_reports_the_objective_the_next_bytes_and_the_last_heads(chunk):
    mc = tiny()
    params = transformer.init_params(mc, jax.random.PRNGKey(5))
    tokens = _tokens(2, 24, seed=2)
    with jax.default_matmul_precision("highest"):
        objective, scalars = lm.lm_loss(params, tokens, mc, chunk=chunk)
        per_head = _loop_over_heads(forward(params, tokens[:, :-1], mc), tokens)
    assert set(scalars) == {"loss", "num_tokens", "loss_next_byte", "loss_last_head"}
    np.testing.assert_allclose(float(objective), float(jnp.mean(per_head)), rtol=1e-6)
    np.testing.assert_allclose(float(scalars["loss"]), float(objective), rtol=0)
    np.testing.assert_allclose(float(scalars["loss_next_byte"]), float(per_head[0]), rtol=1e-6)
    np.testing.assert_allclose(float(scalars["loss_last_head"]), float(per_head[7]), rtol=1e-6)
    assert float(scalars["num_tokens"]) == 2 * 24
    with pytest.raises(NotImplementedError, match="no z-loss"):
        lm.lm_loss(params, tokens, mc, chunk=chunk, z_loss_coeff=1e-4)


def test_the_heads_rule_sees_every_heads_logits_and_the_float32_stream(monkeypatch):
    """`auto_loss_chunk` is asked about 8 x 40 logits a position, and the
    blocks' inputs are counted at the stream's 4 bytes a feature."""
    from ray_tpu.parallel import MeshSpec, build_mesh

    mc = tiny(remat=True)
    mesh = build_mesh(MeshSpec(fsdp=4), devices=jax.devices()[:4])
    opt = lm.default_optimizer(1e-3, total_steps=10)
    state, shardings = lm.abstract_train_state(mc, opt, mesh)
    asked = {}

    def chunk_rule(batch, seq, vocab, hbm_bytes=None, *, resident_bytes=0, step_bytes=0):
        asked.update(batch=batch, seq=seq, vocab=vocab, step_bytes=step_bytes)
        return seq

    monkeypatch.setattr(lm, "auto_loss_chunk", chunk_rule)
    step = lm.make_train_step(mc, opt, mesh, state_shardings=shardings)
    assert step.loss_chunk_for((4, 33), state) == 32
    assert (asked["batch"], asked["seq"], asked["vocab"]) == (1, 32, HEADS * VOCAB)
    gradients = sum(int(np.prod(sh.shard_shape(x.shape))) * 4
                    for x, sh in zip(jax.tree.leaves(state.params), jax.tree.leaves(shardings.params)))
    assert asked["step_bytes"] == gradients + 2 * 32 * 32 * 4       # two layers' inputs, a row of 32 float32
    bf16 = tiny(remat=True, residual_fp32=False)
    step = lm.make_train_step(bf16, opt, mesh, state_shardings=shardings)
    step.loss_chunk_for((4, 33), state)
    assert asked["step_bytes"] == gradients + 2 * 32 * 32 * 4       # float32 compute: the same 4 bytes


def test_block_costs_price_the_visible_keys_and_the_summaries():
    mc = tiny()
    costs = transformer.attention_costs(mc, 32, lambda weight: 1)
    q_width = 2 * 16
    visible = 8 // 2 + (32 - 8) // (2 * 2)        # half its window, and 6 of the 12 earlier summaries
    assert costs["flops"] == 2 * 32 * 3 * q_width + 4 * q_width * visible + 2 * q_width * 32
    names = [c.names for c in costs["candidates"]]
    assert names == [("attn_out", "attn_lse"), ("attn_residual",)]
    # the kept residual is a float32 row where the sublayers compute in bfloat16
    assert transformer.attention_costs(tiny(dtype=jnp.bfloat16), 32, lambda w: 1)["candidates"][1].width == 64
    assert costs["candidates"][1].width == 32


# ------------------------------------------------------------ what is reported


@pytest.fixture(scope="module")
def trained():
    """A tiny EvaByte trainer on the eight virtual devices (dp=2 x fsdp=4), two steps with
    cost accounting on: -> (its reports, the `train.init.step_fn` span's
    attributes, the step's operation table)."""
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import LMTrainer
    from ray_tpu.util import profiling, tracing

    peaks = pytest.MonkeyPatch()
    peaks.setitem(profiling.DEVICE_PEAKS, "cpu", (1e12, 100e9))
    peaks.setattr(profiling, "_program_ops", {})
    tracing.tracer().clear()
    trainer = LMTrainer(tiny(remat=True), mesh_spec=MeshSpec(dp=2, fsdp=4), total_steps=10, seed=1)
    batches = [{"tokens": np.asarray(_tokens(8, 32, seed=i))} for i in range(2)]
    reports = [trainer.train(iter([b]), num_steps=1, report_every=1) for b in batches]
    spans = tracing.tracer().spans(limit=10**6)
    attrs = next(s for s in spans if s["name"] == "train.init.step_fn")["attrs"]
    report_attrs = [s["attrs"] for s in spans if s["name"] == "train.report"]
    table = profiling.program_ops()["jit_step_under_mesh"]
    peaks.undo()
    tracing.tracer().clear()
    return reports, attrs, report_attrs, table


def test_the_trainer_reports_the_heads_losses_and_the_calls_tiles(trained):
    reports, attrs, report_attrs, _ = trained
    for report in reports:
        assert {"loss", "loss_next_byte", "loss_last_head", "grad_norm"} <= set(report)
        assert abs(report["loss"] - np.log(VOCAB)) < 0.1
    assert {"loss_next_byte", "loss_last_head"} <= set(report_attrs[0])
    assert (attrs["eva_impl"], attrs["eva_window"], attrs["eva_chunk"], attrs["eva_summaries"],
            attrs["eva_far_tiles"], attrs["pred_heads"]) == ("xla", 8, 2, 16, 6, HEADS)
    # the flash plan is the local call's: a window long
    assert attrs["attention_impl"] == "xla" and attrs["remat"] == "whole_block"


def test_the_steps_operations_lie_under_the_new_scopes_in_every_pass(trained):
    _, _, _, table = trained
    found = {(scope, pass_) for instances in table.values() for scopes, pass_, _ in instances
             for scope in scopes}
    for scope in ("attn.eva", "attn.eva.pool", "attn.eva.local", "attn.eva.far", "attn.eva.merge"):
        assert {(scope, "fwd"), (scope, "recompute"), (scope, "bwd")} <= found, scope
    assert {("head.multibyte", "fwd"), ("head.multibyte", "bwd")} <= found
    # nested: what is under a part is under the whole, the kernel's scope and the layer's kind
    for instances in table.values():
        for scopes, _, _ in instances:
            if "attn.eva.far" in scopes:
                assert {"attn.eva", "attn.kernel", "attn.full"} <= set(scopes)
            if "head.multibyte" in scopes:
                assert "head" in scopes
