"""What a recomputing step may keep of a HELD expert layer's buffer (PR 67):
the values the first pass of `moe._held_experts` writes and its backward pass
reads, each under its `checkpoint_name` (`moe.held_buffer_names`: the gathered
rows, the gate's and the up projection's outputs, the down projection's, the
slots' tables and the grouped matmuls' own), ONE candidate of
`mixed_stack._expert_costs`. Kept, they change no loss and no gradient by a
bit, the backward pass runs no grouped matmul of the forward again and
searches nothing of the first pass; on a v5e the rule takes the candidate at
`train-lfm2moe-8k`'s shapes, refuses it for room on the four cells whose chip
is full and for its worth where the buffer is mostly tile padding
(`train-ling3flash-4k`). (The whole tiny stack with the buffer kept:
tests/test_moe_remat.py.)"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core

from ray_tpu.models import moe, transformer
from ray_tpu.ops import grouped_matmul as gmm
from ray_tpu.ops import losses

from test_mixer_remat import EXPERT_CELLS, V5E_BYTES, _cell_plan  # noqa: E402

TOKENS, PUBLISHED, HELD, TOP_K, WIDTH = 64, 16, 4, 4, 128


def _block(expert_act, second_pass, tile, impl):
    """A block around one held layer (4 of 16 experts, top-4: a buffer of 128
    rows and a tile an expert) as a function of (h, gates, the expert
    weights), and its inputs. `second_pass`: half the tokens send all four
    choices here, 128 rows and their padding, one pass more than the buffer's."""
    config = moe.MoEConfig(d_model=WIDTH, d_ff=WIDTH, n_experts=PUBLISHED, top_k=TOP_K, held_experts=(0, HELD),
                           expert_act=expert_act, dtype=jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(7), 7)
    h, cotangent = jax.random.normal(keys[0], (TOKENS, WIDTH)), jax.random.normal(keys[6], (TOKENS, WIDTH))
    gates = jax.nn.softmax(jax.random.normal(keys[1], (TOKENS, TOP_K)))
    weights = tuple(0.3 * jax.random.normal(key, (HELD, WIDTH, WIDTH))
                    for key, _ in zip(keys[2:5], config.expert_weights))
    experts = jax.vmap(lambda key: jax.random.permutation(key, PUBLISHED)[:TOP_K])(jax.random.split(keys[5], TOKENS))
    if second_pass:
        here = jax.vmap(lambda key: jax.random.permutation(key, HELD))(jax.random.split(keys[5], TOKENS))
        experts = jnp.where(jnp.arange(TOKENS)[:, None] < 3 * TOKENS // 4, here, HELD + experts % (PUBLISHED - HELD))

    def block(h, gates, weights):
        out, report = moe._held_experts(h, gates, experts, weights, config, tile, impl)
        # linear in the output, as the residual stream is: the backward pass reads no value of it
        return jnp.sum(out * cotangent), report["moe_passes"]

    return config, block, (h, gates, weights)


def _graded(block, saved):
    return jax.value_and_grad(transformer.checkpoint_block(block, saved), (0, 1, 2), has_aux=True)


@pytest.mark.parametrize("second_pass", [False, True], ids=["one-pass", "second-pass"])
@pytest.mark.parametrize("expert_act", ["swiglu", "relu2"])
def test_a_block_that_keeps_the_buffer_gives_the_whole_blocks_loss_and_gradients_to_the_bit(expert_act, second_pass):
    """Gated and not, one pass and a routing that takes a second: the kept
    values are the arrays the forward pass wrote, and the backward pass reads
    them where it read their recomputed twins."""
    config, block, inputs = _block(expert_act, second_pass, 1, "xla")
    names = moe.held_buffer_names(config)
    assert (moe.BUFFER_GATE in names) == (expert_act == "swiglu") and len(names) == len(config.expert_weights) + 3
    ((whole, passes), whole_grads), ((kept, _), kept_grads) = (
        jax.jit(_graded(block, saved))(*inputs) for saved in ((), names))
    assert float(passes) == (2 if second_pass else 1)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(kept))
    for a, b in zip(jax.tree.leaves(whole_grads), jax.tree.leaves(kept_grads), strict=True):
        assert np.asarray(a).any()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _sub_jaxprs(params):
    for value in params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(item, (jax_core.ClosedJaxpr, jax_core.Jaxpr)):
                yield getattr(item, "jaxpr", item)


def _first_pass_calls(jaxpr, found=None):
    """The kernels and the jitted library calls of `jaxpr` by name, those
    behind a `cond` (the later passes: computed again from their inputs,
    whatever is kept) left out."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("pallas_call", "pjit", "jit"):
            found[eqn.params["name"]] += 1
        elif eqn.primitive.name == "moe_rows_sum":
            found["moe_rows_sum"] += 1
        if eqn.primitive.name != "cond":
            for sub in _sub_jaxprs(eqn.params):
                _first_pass_calls(sub, found)
    return found


@pytest.mark.parametrize("expert_act", ["swiglu", "relu2"])
def test_with_the_buffer_kept_the_backward_pass_runs_no_forward_matmul_and_no_search_of_the_first_pass(
        monkeypatch, expert_act):
    """The layer as a TPU runs it (the `moe_gmm_*` and `moe_rows_sum` kernels,
    interpreted, 8-row tiles), differentiated through a checkpoint: the
    backward pass's own jaxpr (`remat2`), the later passes' `cond` aside,
    holds three `moe_gmm_fwd` calls a gated expert (two one that is not), the
    slots' and the tiles' searches, the rows' sort and the gathers where the
    block keeps nothing, and none of them where it keeps the buffer beside
    the routing: what is left are the transposes. The counter that says the
    mechanism engaged."""
    real = gmm.grouped_matmul
    monkeypatch.setattr(moe, "grouped_matmul", lambda *a, **kw: real(*a, **dict(kw, interpret=True)))
    config, block, inputs = _block(expert_act, False, 8, "pallas")
    matmuls = len(config.expert_weights)

    def backward_calls(saved):
        jaxpr = jax.make_jaxpr(_graded(block, saved))(*inputs).jaxpr
        (backward,) = (eqn for eqn in jaxpr.eqns if eqn.primitive.name == "remat2")
        return _first_pass_calls(backward.params["jaxpr"])

    whole, kept = backward_calls(()), backward_calls((moe.ROUTING, *moe.held_buffer_names(config)))
    transposes = {"moe_gmm_dlhs": matmuls, "moe_gmm_drhs": matmuls}
    assert {name: whole[name] for name in ("moe_gmm_fwd", *transposes)} == {"moe_gmm_fwd": matmuls, **transposes}
    # a slot's expert, the kernel's bounds, the experts' ends, a row tile's group a matmul
    assert whole["searchsorted"] == 3 + matmuls and whole["argsort"] == 1 and whole["_take"] >= 2
    assert {name: kept[name] for name in ("moe_gmm_fwd", *transposes)} == {"moe_gmm_fwd": 0, **transposes}
    assert kept["searchsorted"] == kept["argsort"] == 0
    # the combine's transpose gathers the output's cotangent a slot; the dispatch's is the row-sum kernel
    assert kept["moe_rows_sum"] == whole["moe_rows_sum"] == 1
    with_buffer, without = (jax.jit(_graded(block, saved))(*inputs)
                            for saved in ((moe.ROUTING, *moe.held_buffer_names(config)), ()))
    for a, b in zip(jax.tree.leaves(with_buffer), jax.tree.leaves(without), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# a step's (batch, sequence) and what the first pass's buffer holds there: the slots (twice the even share of
# the T k rows, then a 256-row tile a held expert) and a slot's features in bfloat16: the gathered row, (gate,)
# up, the output, and 8 bytes of its row and gate
LFM2 = ("lfm2-8b-a1b-train-1chip", 2, 8192)
BUFFERS = {
    # 8 of 32 experts, top-4 of 16,384 tokens: 32,768 rows at twice the even share and 8 tiles
    "lfm2moe": (LFM2, 2 * 16384 * 4 * 8 // 32 + 8 * 256, 2048 + 2 * 1792 + 2048 + 4),
    "trinity": (EXPERT_CELLS["trinity"], 2 * 16384 * 8 * 16 // 128 + 16 * 256, 2048 + 2 * 1024 + 2048 + 4),
    "smallthinker": (EXPERT_CELLS["smallthinker"], 2 * 16384 * 6 * 8 // 64 + 8 * 256, 2560 + 2 * 768 + 2560 + 4),
    "glm47flash": (EXPERT_CELLS["glm47flash"], 2 * 16384 * 4 * 8 // 64 + 8 * 256, 2048 + 2 * 1536 + 2048 + 4),
    # squared-ReLU experts: no gate
    "nemotron3nano": (EXPERT_CELLS["nemotron3nano"], 2 * 16384 * 6 * 8 // 128 + 8 * 256, 2688 + 1856 + 2688 + 4),
    # 8 of 512, top-8 of 4,096 tokens: 1,024 rows at twice the even share, and twice as many in the tiles
    "ling3flash": (EXPERT_CELLS["ling3flash"], 2 * 4096 * 8 * 8 // 512 + 8 * 256, 2560 + 2 * 768 + 2560 + 4),
}


@pytest.mark.parametrize("cell", list(BUFFERS))
def test_a_v5e_keeps_the_buffer_where_the_chip_has_room_and_the_matmuls_are_worth_their_copies(monkeypatch, cell):
    """The candidate at the six held cells' published widths, as a TPU runs
    them: its width is the first pass's slots, tile padding included, over the
    device's tokens; it spares the experts' matmuls at an even routing and is
    worth their time at the kernels' share of the peak less a copy in and out
    of every value a kernel wrote. `train-lfm2moe-8k` (a chip 70% full, 32,768
    of 34,816 slots hold rows at twice the even share) keeps it, and the kept
    bytes are the slots'; the four cells whose estimate has no room for it
    keep what they kept; on `train-ling3flash-4k` 3,072 slots stand for 512
    rows and the worth is negative: never tried, whatever the room."""
    from ray_tpu.models import mixed_stack

    (_, batch, seq, *_), slots, features = BUFFERS[cell]
    costs, plan, estimates, config = _cell_plan(monkeypatch, BUFFERS[cell][0], V5E_BYTES)
    rows = batch * seq
    assert costs == mixed_stack.block_costs(config, seq, tokens=seq)     # a device's tokens default to one sequence
    costs = mixed_stack.block_costs(config, seq, tokens=rows)
    by_name = {c.names[0]: c for c in costs["candidates"]}
    buffer = by_name[moe.BUFFER_IN]
    assert buffer.names == moe.held_buffer_names(config) and buffer.layers == by_name[moe.ROUTING].layers
    assert slots == moe.held_buffer_rows(config, rows, 256) + config.n_experts_held * 256
    assert buffer.width == -(-slots * features // rows) and not buffer.tp_sum
    matmuls = (2 * config.d_model * len(config.expert_weights) * config.d_ff * config.top_k * config.n_experts_held
               // config.n_experts)
    assert buffer.flops == matmuls
    written = features - 4 - config.d_model
    assert buffer.worth == int(matmuls / 0.77 - 2 * slots * written * 2 / rows * 350)
    ceiling = (1 - losses.HBM_FREE_FRACTION) * V5E_BYTES
    kept = set(plan["remat_saved"])
    tried = {names: estimate for names, estimate in estimates.items() if moe.BUFFER_IN in names}
    if cell == "lfm2moe":
        assert set(buffer.names) <= kept and all(estimate <= ceiling for estimate in tried.values())
        others = sum(sum(c.layers) * rows * c.width * 2 for c in costs["candidates"]
                     if c is not buffer and c.names[0] in kept)
        assert plan["remat_saved_bytes"] - others == 4 * rows * buffer.width * 2
        assert 0 <= 4 * rows * buffer.width * 2 - 4 * slots * features * 2 < 4 * rows * 2
        assert set(buffer.names) <= set(plan["remat_saved_by_run"][1])
        assert not set(buffer.names) & set(plan["remat_saved_by_run"][0])     # the dense layer's run
    else:
        assert not set(buffer.names) & kept and kept == EXPERT_CELLS[cell][4]
        if cell == "ling3flash":
            assert buffer.worth < 0 and not tried
        else:
            assert buffer.worth > 0 and tried and all(estimate > ceiling for estimate in tried.values())
