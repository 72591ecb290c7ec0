"""Configuration files -> what the program and the yardstick need.

A configuration file holds the published keys of its source at the top
level (as run: a key named in `reduced` carries the reduced value and
`published` the original), and nested groups for what the benchmark
sets itself (`engine` or `trainer`, `dtype`, `program`, `sizing`,
`assumed`, `departures`, and `share` where the chip holds its share of
each layer). What depends on the model's family, the names of its keys
among it, is in benchmark/adapters/<model_type>.py.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, Optional

# What `reduced` may name, and nothing else (the model-configs guide, section
# 4): the depth, or a COUNT of which this chip holds its share. Each is a
# count because changing it changes how many there are of a thing and not the
# shape of any one of them; a width (hidden, intermediate, expert, head, latent,
# state or projection size, a rank, the experts a token is sent to) is never cut,
# so no such key is here and every key that is not here is refused by name.
DEPTH_KEYS = {
    "num_hidden_layers": "whole blocks, each at its published shapes; those left out lie on further chips",
    "n_layer": "GPT-2's name for the same",
    "num_layers": "the same",
}
# A share key names its ROLE, and the floor hangs on the role (`SHARE_ROLES`): the
# routed experts are counted under five names in the catalog beside the guide, and a
# synonym without the floor would be a way round it. A closed list in the benchmark's
# own file: an adapter or a configuration file (both arrive with a `model_config` PR)
# cannot declare a key a count.
SHARE_KEYS = {
    "num_experts": "experts",
    "n_routed_experts": "experts",
    "num_local_experts": "experts",
    "moe_num_experts": "experts",
    "moe_num_primary_experts": "experts",
    "vocab_size": "vocabulary",
}
# role -> why it is a count; its floor is below and in `check_reduced`
SHARE_ROLES = {
    "experts": "routed experts held, each a whole expert of the published width; the router keeps its published outputs",
    "vocabulary": "rows of the embedding and columns of the head held, each of the published hidden size",
}
# Not here, so refused by name: every count that is not whole routed experts of the
# published width (`n_shared_experts`, `num_shared_experts`, `zero_expert_num`, the
# experts a token is sent to under any name: `num_experts_per_tok`,
# `num_experts_per_token`, `experts_top_k`, `moe_num_active_primary_experts`, and the
# expert-group keys), every other vocabulary-like key (`unpadded_vocab_size`, n-gram and
# embedding-table sizes), and heads of any kind. Heads held as a share (attention,
# key-value, linear-attention or state-space heads) are not admitted yet: they want a
# floor and a rule that keeps query and key-value heads in step, and a `benchmark` PR
# adds them with both, under the names the configuration that needs them uses.
# The guide's floors for a share, by role: what is left is still the model.
MIN_EXPERTS_HELD = 8
MIN_VOCAB_SHARE = 8     # at least 1/8 of the published vocabulary


def check_reduced(conf: Dict[str, Any], name: str = "configuration") -> None:
    """Refuse, with the key in the message, a `reduced` that names anything
    but the depth or a count held as this chip's share (the lists above), or
    two keys of one role. A share key needs the file's `share` group, which
    states over how many chips each layer is divided and how; the published
    count must be a whole multiple of the held one, at most that many times
    it; the floor is its role's."""
    published = conf.get("published", {})
    share = conf.get("share")
    named: Dict[str, str] = {}
    for key in conf["reduced"]:
        if key not in DEPTH_KEYS and key not in SHARE_KEYS:
            raise ValueError(
                f"{name}: `reduced` names {key!r}, which is neither the depth nor a count held as "
                f"this chip's share ({', '.join(sorted(SHARE_KEYS))}): a width is never cut")
        if key not in published or published[key] == conf.get(key):
            raise ValueError(f"{name}: `reduced` names {key!r}, but `published` does not give "
                             "another value for it than the one run")
        if key in DEPTH_KEYS:
            continue
        role = SHARE_KEYS[key]
        if role in named:
            raise ValueError(f"{name}: `reduced` names {named[role]!r} and {key!r}, two keys of "
                             f"the role {role!r}: a file counts them under one")
        named[role] = key
        if (not isinstance(share, dict) or not isinstance(share.get("chips_sharing_a_layer"), int)
                or share["chips_sharing_a_layer"] < 2 or not share.get("how")):
            raise ValueError(
                f"{name}: `reduced` names the share key {key!r}, but the file has no `share` group "
                'that states {"chips_sharing_a_layer": <2 or more>, "how": "..."}')
        held, whole, chips = conf[key], published[key], share["chips_sharing_a_layer"]
        if held < 1 or whole % held or whole // held > chips:
            raise ValueError(
                f"{name}: {key!r} holds {held} of the published {whole}: the published count must be "
                f"a whole multiple of the held one, at most chips_sharing_a_layer = {chips} times it")
        if role == "experts" and held < MIN_EXPERTS_HELD:
            raise ValueError(f"{name}: {key!r} holds {held} routed experts; the floor is {MIN_EXPERTS_HELD}")
        if role == "vocabulary" and held * MIN_VOCAB_SHARE < whole:
            raise ValueError(f"{name}: {key!r} holds {held} of {whole} rows; the floor is "
                             f"1/{MIN_VOCAB_SHARE} of the published vocabulary")


def load_config(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        conf = json.load(f)
    for key in ("source", "model_type", "reduced", "chips"):
        if key not in conf:
            raise ValueError(f"{os.path.basename(path)}: missing key {key!r}")
    check_reduced(conf, os.path.basename(path))
    return conf


def adapter(conf: Dict[str, Any]):
    try:
        return importlib.import_module(f"benchmark.adapters.{conf['model_type']}")
    except ModuleNotFoundError:
        raise ValueError(f"model_type {conf['model_type']!r}: no adapter "
                         "(benchmark/adapters/) and no reference") from None


def transformer_config(conf: Dict[str, Any]):
    """The program's model configuration for a configuration file."""
    import jax.numpy as jnp

    dtypes = conf.get("dtype", {})
    return adapter(conf).program_config(
        conf,
        dtype=jnp.dtype(dtypes.get("compute", "bfloat16")),
        param_dtype=jnp.dtype(dtypes.get("params", "float32")),
        **conf.get("program", {}),
    )


def shape_numbers(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the cost functions in roofline.py take."""
    return adapter(conf).shapes(conf)


def _family_says(conf: Dict[str, Any], what: str) -> Any:
    """The adapter's own function of that name on the file, None where the
    family's adapter has no such function."""
    own = getattr(adapter(conf), what, None)
    return None if own is None else own(conf)


def expert_layer(conf: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """The sizes of the family's routed expert layer, by the adapter's own
    `expert_layer(conf)`: `held` experts here of the `published` ones, the
    experts `per_token`, the `hidden` size and ONE expert's `width`. None
    where the family's adapter has no such function (it has no expert layer):
    the readers that price one then have nothing to read. Beside `shapes`
    and not in it: `shapes` is what `roofline.train_flops_per_token` takes."""
    return _family_says(conf, "expert_layer")


def attention_window(conf: Dict[str, Any]) -> Optional[int]:
    """The keys a query of a windowed attention layer sees, by the adapter's
    own `attention_window(conf)`; None where the family has no such function
    or the configuration no window."""
    return _family_says(conf, "attention_window")


def train_flops_per_token(conf: Dict[str, Any], seq: int) -> float:
    """Operations the forward and backward passes REQUIRE per trained token
    of this configuration, at sequence length `seq`: what `mfu` multiplies
    the rate by. It is the adapter's own `train_flops_per_token(conf, seq)`
    where the family's adapter has one, and the homogeneous dense block's
    count (`roofline.train_flops_per_token` over `adapter.shapes`)
    otherwise.

    What an adapter's function must count: two operations a weight of every
    matmul a token passes through (of its experts only the ACTIVE ones, and
    of those only the share held here; a shared expert whole; the head over
    the vocabulary held here), plus the required operations of the
    attention scores or of the recurrence (4 x head size a causally visible
    key and head; for a linear-attention layer, what its state update and
    read-out need a token), all times three for forward plus backward.
    Recomputation, padding and what a kernel visits and masks do not count.
    The function is kept with the benchmark (model-configs guide, 3.6), where
    a PR that claims a gain cannot change it."""
    from . import roofline

    family = adapter(conf)
    own = getattr(family, "train_flops_per_token", None)
    if own is not None:
        return float(own(conf, seq))
    return roofline.train_flops_per_token(seq=seq, **family.shapes(conf))
