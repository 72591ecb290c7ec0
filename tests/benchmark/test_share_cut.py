"""What a configuration's `reduced` may name (`model_config.check_reduced`:
the depth, or a count held as this chip's share, with the guide's floors),
on a toy share-cut file and its malformed variants; and `mfu`'s seam, the
family's own count of what a token requires."""

import json
import os

import numpy as np
import pytest

from bench_helpers import ROOT, load
from benchmark import model_config, roofline, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "share_cut", "configs", "toy-share-olmoe.json")


# the names the catalog beside the guide counts the routed experts under (45, 23, 6, 3 and 1
# of its 88 rows): one role, one floor
EXPERT_COUNT_NAMES = ["num_experts", "n_routed_experts", "num_local_experts", "moe_num_experts",
                      "moe_num_primary_experts"]


def _renamed(conf, old, new):
    """The file with the count `old` under the name `new`: at the top level,
    in `published` and in `reduced`."""
    conf = {new if k == old else k: v for k, v in conf.items()}
    conf["published"] = {new if k == old else k: v for k, v in conf["published"].items()}
    conf["reduced"] = [new if k == old else k for k in conf["reduced"]]
    return conf


def _variant(tmp_path, experts_as="num_experts", **changes):
    conf = _renamed(load(TOY), "num_experts", experts_as)
    for key, value in changes.items():
        if value is None:
            conf.pop(key)
        elif isinstance(value, dict) and isinstance(conf.get(key), dict):
            conf[key] = dict(conf[key], **value)
        else:
            conf[key] = value
    path = tmp_path / "variant.json"
    path.write_text(json.dumps(conf))
    return str(path)


@pytest.mark.parametrize("experts_as", EXPERT_COUNT_NAMES)
def test_share_cut_file_loads_and_its_batches_stay_inside_the_slice(tmp_path, experts_as):
    """Under each name the routed experts are counted by; `num_experts` is the
    committed file itself."""
    conf = model_config.load_config(TOY if experts_as == "num_experts"
                                    else _variant(tmp_path, experts_as))
    assert conf["reduced"] == [experts_as, "vocab_size"]
    assert model_config.SHARE_KEYS[experts_as] == "experts"
    assert (conf[experts_as], conf["published"][experts_as]) == (8, 16)
    assert (conf["vocab_size"], conf["published"]["vocab_size"]) == (256, 512)
    assert conf["share"]["chips_sharing_a_layer"] == 2
    spec = {"batch": 8, "seq": 32, "zipf_a": 1.1}
    batches = traffic.lm_batches(spec, 2**31 + 31, conf["vocab_size"])
    ids = np.concatenate([next(batches)["tokens"].ravel() for _ in range(20)])
    assert ids.min() >= 0 and ids.max() < conf["vocab_size"]
    # the slice is used, not a corner of it: the Zipf law's tail reaches most of its rows
    assert len(np.unique(ids)) > conf["vocab_size"] // 2


MALFORMED = {
    "hidden_size_in_reduced": ("hidden_size", dict(
        hidden_size=64, published={"hidden_size": 128},
        reduced=["num_experts", "vocab_size", "hidden_size"])),
    "intermediate_size_in_reduced": ("intermediate_size", dict(
        intermediate_size=32, published={"intermediate_size": 64},
        reduced=["num_experts", "vocab_size", "intermediate_size"])),
    "four_experts_held": ("num_experts", dict(
        num_experts=4, share={"chips_sharing_a_layer": 4})),
    "a_sixteenth_of_the_vocabulary": ("vocab_size", dict(
        vocab_size=32, share={"chips_sharing_a_layer": 16})),
    "share_key_and_no_share_group": ("num_experts", dict(share=None)),
    "held_count_does_not_divide_the_published": ("num_experts", dict(num_experts=10)),
    "more_shares_than_chips_sharing_a_layer": ("vocab_size", dict(vocab_size=128)),
    "experts_per_token_in_reduced": ("num_experts_per_tok", dict(
        published={"num_experts_per_tok": 4},
        reduced=["num_experts", "vocab_size", "num_experts_per_tok"])),
    "a_key_nobody_thought_of": ("rope_theta", dict(
        published={"rope_theta": 500000}, reduced=["num_experts", "vocab_size", "rope_theta"])),
    "reduced_key_not_in_published": ("num_hidden_layers", dict(
        reduced=["num_experts", "vocab_size", "num_hidden_layers"])),
    "two_keys_of_one_role": ("n_routed_experts", dict(
        n_routed_experts=8, published={"n_routed_experts": 16},
        reduced=["num_experts", "n_routed_experts", "vocab_size"]), "two keys of the role"),
}
# the floor of 8 held hangs on the role: four held is refused under every name of the count
MALFORMED.update({
    f"four_experts_held_as_{name}": (name, dict(
        experts_as=name, share={"chips_sharing_a_layer": 4}, **{name: 4}), "the floor is 8")
    for name in EXPERT_COUNT_NAMES[1:]})
# counts that are not whole routed experts of the published width, the other vocabulary-like
# keys, heads of any kind, a window: not admitted under any family's name for them
NOT_ADMITTED = ["n_shared_experts", "num_shared_experts", "zero_expert_num",
                "num_experts_per_token", "experts_top_k", "moe_num_active_primary_experts",
                "n_group", "topk_group", "num_expert_groups", "unpadded_vocab_size",
                "engram_vocab_size", "ngram_vocab_size_base", "num_attention_heads",
                "num_key_value_heads", "moe_ffn_hidden_size", "sliding_window_size"]
MALFORMED.update({
    f"{name}_in_reduced": (name, dict(
        published={name: 4}, reduced=["num_experts", "vocab_size", name], **{name: 2}),
        "neither the depth nor a count")
    for name in NOT_ADMITTED})


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_a_malformed_share_cut_is_refused_with_the_key_in_the_message(tmp_path, case):
    key, changes, *why = MALFORMED[case]       # `why`: the words of the refusal, where a case gives them
    with pytest.raises(ValueError, match=repr(key) + ".*" + "".join(why)):
        model_config.load_config(_variant(tmp_path, **changes))


@pytest.mark.parametrize("suffix", ["_dim", "_rank", "_width", "_size"])
def test_no_allowed_key_is_a_width(suffix):
    allowed = set(model_config.DEPTH_KEYS) | set(model_config.SHARE_KEYS)
    assert not {k for k in allowed if k.endswith(suffix)} - {"vocab_size"}
    assert not allowed & {"hidden_size", "intermediate_size", "moe_intermediate_size",
                          "num_experts_per_tok", "head_dim"}
    assert not allowed & set(NOT_ADMITTED)
    assert set(model_config.SHARE_KEYS) == set(EXPERT_COUNT_NAMES) | {"vocab_size"}
    assert all(model_config.DEPTH_KEYS.values()) and all(model_config.SHARE_KEYS.values())
    # every share key has a role, and every role says why it is a count
    assert set(model_config.SHARE_KEYS.values()) == set(model_config.SHARE_ROLES)
    assert all(model_config.SHARE_ROLES.values())


@pytest.mark.parametrize("name, seq", [("gpt2-small-train-1chip", 1024),
                                       ("mistral-7b-v0.3-train-4chip", 1024),
                                       ("olmoe-1b-7b-train-1chip", 4096)])
def test_mfu_asks_the_family_and_the_shipped_cells_read_the_same_to_the_last_bit(name, seq):
    conf = model_config.load_config(os.path.join(ROOT, "benchmark", "configs", name + ".json"))
    family = model_config.adapter(conf)
    assert not hasattr(family, "train_flops_per_token")      # no shipped adapter has its own
    assert model_config.train_flops_per_token(conf, seq) == roofline.train_flops_per_token(
        seq=seq, **model_config.shape_numbers(conf))


def test_an_adapters_own_count_is_the_one_mfu_takes(monkeypatch):
    from benchmark.adapters import olmoe
    from benchmark.readers import mfu

    conf = model_config.load_config(TOY)
    monkeypatch.setattr(olmoe, "train_flops_per_token", lambda conf, seq: 1000.0 * seq,
                        raising=False)
    assert model_config.train_flops_per_token(conf, 32) == 32000.0
    ctx = {"conf": conf, "traffic": {"seq": 32}, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "window": {"tokens": 197e12 / 32000.0, "t0": 0.0, "t1": 2.0}}
    assert mfu.read(ctx) == pytest.approx(50.0)
