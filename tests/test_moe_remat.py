"""What a recomputing step may keep of an expert layer and of a delta-rule
mixer's tail (PR 60): the routing under ONE name (`moe.ROUTING`: the router's
float32 logits, the chosen experts, their scores, the sorted rows' order and
each held expert's end), the shared expert's gate and up, the stream after
the mixer's out-projection (`kda_residual`) and, since PR 63 made the norm a
head under the head's gate the rule's own last step, the rule's output as
the out-projection reads it with the chunks' states and o (`kda_chunk_out`,
`kda_chunk_states`, `kda_chunk_o`: there is no `kda_gate_norm_out` any more). Kept, a group
changes no loss and no gradient, and
what it stands for is gone from the backward pass's recomputation. (What
`block_costs` lists at the five cells' published widths and what the rule
keeps of it on a v5e: tests/test_mixer_remat.py. Since PR 67 the held layer's
buffer is two more cases. Eight cases of ~20 s: the file holds nothing else,
tests/conftest.py's rule.)"""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import model_family, moe
from ray_tpu.train.lm import lm_loss

from test_ling3flash_model import gated, tiny_ling  # noqa: E402

# what the rule could keep of this stack before PR 60
BEFORE = ("kda_in_proj", "kda_chunk_out", "kda_chunk_states", "kda_chunk_o", "attn_out", "attn_lse", "attn_residual",
          "attn_latent_kv", "attn_latent_k_rope")
SHARED = ("moe_shared_gate", "moe_shared_up")
RULE = ("kda_chunk_out", "kda_chunk_states", "kda_chunk_o")
# the held layer's buffer (PR 67; tests/test_moe_buffer_remat.py holds the layer alone and the rule's choice)
BUFFER = ("moe_buffer_in", "moe_buffer_gate", "moe_buffer_up", "moe_buffer_out", "moe_buffer_slots", "moe_gmm_tiles")
# (the names kept, the routing, the operations of the lowered step that the backward pass loses with them: a
# layer BODY counts once, the scan's `eK eL` and the unrolled `eK`: three expert layers, two delta-rule mixers)
GROUPS = {
    # the router's matmul a layer, every top-k (the two of the group limit and the choice: all that are left are
    # the forward's nine) and the rows' sort
    "routing": ((moe.ROUTING,), "as-routed", {"dot_general": 3, "top_k": 9, "sort": 3}),
    # every choice on a held expert: twice the buffer, so the layer's `all_passes` takes its second pass
    "routing-second-pass": ((moe.ROUTING,), "every-choice", {"dot_general": 3, "top_k": 9, "sort": 3}),
    "shared-expert": (SHARED, "as-routed", {"dot_general": 6}),
    "kda-residual": (("kda_residual",), "as-routed", {"dot_general": 2}),
    # the rule's gated, normed output with the states that entered its chunks and o, beside all else BEFORE had:
    # the walk over the chunks and every decay of the two mixer bodies leave the recomputed pass
    "kda-rule-and-norm": (RULE, "as-routed", {"while": 2, "exponential": 16}),
    "all": ((moe.ROUTING, *SHARED, "kda_residual"), "every-choice", {"dot_general": 11, "top_k": 9, "sort": 3}),
    # the first pass's three grouped matmuls a layer body (`ragged_dot` here) and the search for a slot's expert;
    # a routing that takes a second pass keeps the first's and computes the second again, as before
    "buffer": (BUFFER, "as-routed", {"dot_general": 9, "while": 3}),
    "buffer-second-pass": (BUFFER, "every-choice", {"dot_general": 9, "while": 3}),
}


def _stack(routing):
    """`2 x (eK eL) | eK`: expert layers and delta-rule mixers in a scanned run
    and in an unrolled one; 32 sigmoid-routed experts in 4 groups of which 2
    are kept, a selection bias, top-4, 8 held beside a shared expert."""
    config = tiny_ling(first_layer=0, n_layers=5, n_dense_layers=0, global_attn_every=2)
    params = gated(config, 3)
    if routing == "every-choice":
        params = jax.tree_util.tree_map_with_path(
            lambda path, w: (jnp.broadcast_to(jnp.where(jnp.arange(w.shape[-1]) < 8, 10.0, 0.0), w.shape)
                             if "expert_bias" in jax.tree_util.keystr(path) else w), params)
    return config, params


def _operations(lowered) -> collections.Counter:
    return collections.Counter(re.findall(r"= \"?(?:stablehlo|chlo)\.([a-z_]+)", lowered.as_text()))


@pytest.mark.parametrize("group", list(GROUPS))
def test_keeping_a_group_changes_neither_loss_nor_gradients_and_spares_its_recomputation(group):
    names, routing, spared = GROUPS[group]
    config, params = _stack(routing)
    costs = model_family(config).block_costs(config, 64)
    assert [(run["scanned"], run["layers"]) for run in costs["runs"]] == [(True, 4), (False, 1)]
    assert set(names) <= {name for c in costs["candidates"] for name in c.names}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, config.vocab_size)
    loss = functools.partial(lm_loss, config=config)

    def value_and_gradients(saved):
        return jax.jit(jax.value_and_grad(lambda p: loss(p, tokens, remat_saved=saved), has_aux=True))

    # a group that BEFORE holds already is kept against BEFORE without it
    without = tuple(name for name in BEFORE if name not in names)
    before, kept = value_and_gradients(without), value_and_gradients(without + names)
    ((before_loss, scalars), before_grads), ((kept_loss, _), kept_grads) = before(params), kept(params)
    assert float(scalars["moe_passes"]) == (2 if routing == "every-choice" else 1)
    assert float(before_loss) == float(kept_loss)
    for a, b in zip(jax.tree.leaves(before_grads), jax.tree.leaves(kept_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
    forward = _operations(jax.jit(lambda p: loss(p, tokens)[0]).lower(params))
    was, now = _operations(before.lower(params)), _operations(kept.lower(params))
    assert {name: was[name] - now[name] for name in spared} == spared
    if moe.ROUTING in names:
        # what is left selects and sorts in the forward pass alone (whose sorts the lowering may outline as one)
        assert now["top_k"] == forward["top_k"] == 9 and was["sort"] == 2 * now["sort"] == 6
