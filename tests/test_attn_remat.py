"""What a recomputing step may keep of an attention sublayer beside the
kernel's output and the residual after it (PR 62): the kernel's operands as it
takes them, q, k and v after the bias, the norms and the rotation (`attn_q`,
`attn_k`, `attn_v`), of a gated layer the gate's logits (`attn_gate`) and, of
one with a QK-norm, q and k as the norm's backward reads them (`attn_q_proj`,
`attn_k_proj`), all or none (models/transformer.attention_costs:
`candidates_last`). Kept,
they change no loss and no gradient, and the backward pass repeats neither a
projection of the normed stream nor the rotation; `stack_costs` lists them
after every older candidate, so that at equal worth a byte the rule
(train/lm.auto_remat_saved) adds them to what a step kept and never swaps them
for it. (What the rule makes of them at the cells' published widths on a v5e:
tests/test_tpu_compile_cells*.py.)"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import model_family, transformer
from ray_tpu.models.configs import llama_tiny
from ray_tpu.ops import losses
from ray_tpu.train import lm
from ray_tpu.train.lm import lm_loss

from test_mixed_stack import seeded, tiny  # noqa: E402
from test_sequence_parallel import TOKENS, _step, _two_steps  # noqa: E402

OPERANDS = ("attn_q", "attn_k", "attn_v")
MIXED = OPERANDS + ("attn_gate", "attn_q_proj", "attn_k_proj")     # a gated layer with a QK-norm


def _dense(**kw):
    config = llama_tiny().replace(remat=True, **kw)
    return config, transformer.init_params(config, jax.random.PRNGKey(0)), OPERANDS


def _mixed():
    """`2 x (dS) | eS eF eS eS`: gated, QK-normed, rotary layers of both kinds
    (window, full) in a scanned run and in an unrolled one."""
    config = tiny()
    return config, seeded(config, 4), MIXED


STACKS = {
    "dense-three-matmuls": _dense,
    "dense-fused-qkv": functools.partial(_dense, fused_qkv=True),
    "dense-biases-no-rotation": functools.partial(
        _dense, use_bias=True, pos_emb="learned", norm="layernorm", act="gelu"),
    "mixed-window-and-full-gated": _mixed,
}


def _recomputed_in_proj(text) -> set:
    """The operations that lie in the scope `attn.proj` of a recomputed block,
    by the last part of their names: of a lowered function's text with its
    locations, or of a compiled one's (which also names what a `shard_map`
    holds by the pass that calls it)."""
    names = re.findall(r'(?:loc\(|op_name=)"([^"]*rematted_computation/[^"]*attn\.proj/[^"]*)"', text)
    return {name.rsplit("/", 1)[-1] for name in names}


@pytest.mark.parametrize("stack", list(STACKS))
def test_keeping_the_kernels_operands_changes_neither_loss_nor_gradients(stack):
    """Against the whole-block step; and with them kept the recomputed pass
    holds no matmul of the normed stream (the whole-block one does) and no
    rotation (its two halves' `split`, its `sub`), only the input norm."""
    config, params, names = STACKS[stack]()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, config.vocab_size)
    offered = {name for c in model_family(config).block_costs(config, 64)["candidates"] for name in c.names}
    assert set(names) <= offered and ("attn_gate" in offered) == ("attn_gate" in names)

    def value_and_gradients(saved):
        return jax.jit(jax.value_and_grad(lambda p: lm_loss(p, tokens, config, remat_saved=saved)[0]))

    whole, kept = value_and_gradients(()), value_and_gradients(names)
    (whole_loss, whole_grads), (kept_loss, kept_grads) = whole(params), kept(params)
    assert float(whole_loss) == float(kept_loss)
    for a, b in zip(jax.tree.leaves(whole_grads), jax.tree.leaves(kept_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
    was, now = (_recomputed_in_proj(fn.lower(params).as_text(debug_info=True)) for fn in (whole, kept))
    assert "dot_general" in was and "dot_general" not in now, (was, now)
    if config.pos_emb == "rope":
        assert {"split", "sub"} <= was and not {"split", "sub"} & now, (was, now)
    assert "rsqrt" in now       # the input norm stays: the backward's weight gradients read its output


def test_a_step_on_fsdp2_tp2_that_keeps_them_equals_the_one_device_whole_block_steps(monkeypatch):
    """The ring of `tp` permutes in front of q, k, v: on a device of a known
    size the rule keeps everything this tiny step offers, and two steps equal
    the one-device whole-block ones; the recomputed pass still hands the
    normed stream's piece round (the weight gradients read it) with no matmul
    over it."""
    want_metrics, want_params = _two_steps({}, 1)
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: 16 * 2 ** 30)
    step, state, _ = _step(dict(fsdp=2, tp=2), 4)
    plan = step.remat_plan_for(TOKENS.shape, state)
    assert plan["remat_saved"][-3:] == OPERANDS and {"mlp_up", "attn_residual"} <= set(plan["remat_saved"])
    metrics, params = _two_steps(dict(fsdp=2, tp=2), 4)
    np.testing.assert_allclose(metrics, want_metrics, atol=1e-4, rtol=1e-4)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(want_params)):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    again = _recomputed_in_proj(step.lower(state, {"tokens": jnp.asarray(TOKENS)}).compile().as_text())
    assert "ppermute" in again and not {"dot_general", "split", "sub"} & again, again


def test_stack_costs_merges_the_operands_of_two_attention_kinds_after_every_older_candidate():
    """Window and full layers of one stack offer ONE candidate of one width
    (q and the gate 4 heads of 8 each, k and v 2), written by every layer of
    both runs; it is worth its matmuls alone, sums nothing over `tp`, and
    stands behind the expert layers' candidates, which a later kind offers."""
    config = tiny()
    candidates = model_family(config).block_costs(config, 64)["candidates"]
    last = candidates[-1]
    assert last.names == MIXED and last.layers == (2, 4)
    # q and k twice: as the norm's backward pass reads them and as the kernel does
    assert last.width == 3 * 32 + 3 * 16 and last.flops == last.worth == 2 * config.d_model * (2 * 32 + 2 * 16)
    assert not last.tp_sum
    assert [c.names[0] for c in candidates].index("moe_shared_up") < len(candidates) - 1
    # a layer's FLOPs count the gate's projection once, as before
    dense = transformer.attention_costs(llama_tiny(), 64, lambda weight: 1)
    (operands,) = dense["candidates_last"]
    assert operands.names == OPERANDS and operands.width == 64 + 2 * 32
    assert [c.names[0] for c in dense["candidates"]] == ["attn_out", "attn_residual"]


def test_a_per_head_gate_adds_a_logit_a_head_and_tensor_parallelism_halves_the_width():
    config = llama_tiny().replace(attn_gate=True, attn_gate_per_head=True)
    (operands,) = transformer.attention_costs(config, 64, lambda weight: 1)["candidates_last"]
    assert operands.names == OPERANDS + ("attn_gate",) and operands.width == 64 + 2 * 32 + 4
    (halved,) = transformer.attention_costs(
        config, 64, lambda weight: 1 if weight == "stream" else 2)["candidates_last"]
    assert halved.width == 32 + 2 * 16 + 2 and halved.flops == 2 * config.d_model * halved.width


@pytest.mark.parametrize("room,want", [
    (2, ("mlp_up", "mlp_gate")),
    (3, ("mlp_up", "mlp_gate", "attn_q")),
    (1, ("mlp_up",)),
], ids=["room-for-two", "room-for-all", "room-for-one"])
def test_a_tie_at_equal_worth_a_byte_is_broken_for_the_older_candidate(room, want):
    """A matmul's output is worth `d_model` FLOPs a byte whichever matmul
    wrote it: on the tiny llama up, gate and the operands are 128 features
    each. With room for some of the three the rule keeps the ones it could
    keep before; given the same candidates in the other order it would not,
    which is why `stack_costs` lists the operands last."""
    config = llama_tiny()
    candidates = transformer.block_costs(config, 64)["candidates"]
    tied = [c for c in candidates if c.names[0] in ("mlp_up", "mlp_gate", "attn_q")]
    assert [c.names[0] for c in tied] == ["mlp_up", "mlp_gate", "attn_q"]
    assert len({(c.width, c.worth) for c in tied}) == 1 and tied[0].worth == 2 * config.d_model * 128

    def keep(offered):
        kept, held = lm.auto_remat_saved(
            tuple(offered), rows=1, itemsize=4, hbm_bytes=1000,
            peak_bytes=lambda kept: 0 if len(kept) <= room else 1000)
        assert held == config.n_layers * 4 * 128 * len(kept)
        return tuple(c.names[0] for c in kept)

    assert keep(tied) == want
    if room < 3:
        assert keep(tied[::-1]) == ("attn_q", "mlp_gate")[:room]
