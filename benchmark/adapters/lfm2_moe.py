"""`lfm2_moe` family (LiquidAI/LFM2-8B-A1B): pre-norm blocks whose mixer is,
by the published `layer_types` list (no rule on the index gives it), a gated
short convolution ("conv": `conv_L_cache` causal depthwise taps a channel of
`hidden_size`, no bias, no activation, gated before and after by two thirds of
one in-projection) or grouped-query attention with an RMSNorm a head on q and
k and then rotary positions over the whole head ("full_attention");
`num_dense_layers` dense SwiGLU layers before sigmoid-routed expert layers
with a selection bias, gates renormalised over the chosen, no shared expert; a
tied head. A configuration may hold one chip's share of every layer:
`num_experts` experts of the published ones (the first), `vocab_size` rows of
the published vocabulary, and `num_hidden_layers` published layers in a row
from `program.first_layer` (a stage of a pipeline). `layer_types` stays whole,
as published: the layers run are its entries from `program.first_layer`."""

import functools

from ..reference import lfm2_moe_ref

MIXERS = {"conv": "sconv", "full_attention": "full"}


def _published(conf, key):
    return conf.get("published", {}).get(key, conf[key])


def held_experts(conf):
    """(first, last) of the published experts the file's `num_experts` are,
    None where it holds them all."""
    held = conf["num_experts"]
    return None if held == _published(conf, "num_experts") else (0, held)


def first_layer(conf):
    """The published index of the first layer that is run."""
    return int(conf.get("program", {}).get("first_layer", 0))


def layers_run(conf):
    """The published `layer_types` entries of the layers that are run, in order."""
    return list(conf["layer_types"][first_layer(conf):first_layer(conf) + conf["num_hidden_layers"]])


def _assumed(conf, key, default):
    return conf.get("assumed", {}).get(key, default)


def _check(conf):
    """Refuse, by the key's name, what neither the program nor the reference runs."""
    published = _published(conf, "num_hidden_layers")
    if len(conf["layer_types"]) != published:
        raise ValueError(f"lfm2_moe: layer_types has {len(conf['layer_types'])} entries for the "
                         f"{published} published layers: the list stays whole")
    unknown = set(conf["layer_types"]) - set(MIXERS)
    if unknown:
        raise ValueError(f"lfm2_moe: layer_types names {sorted(unknown)}: {sorted(MIXERS)} are what the "
                         "program and the reference run")
    last = first_layer(conf) + conf["num_hidden_layers"]
    if first_layer(conf) < 0 or last > published:
        raise ValueError(f"lfm2_moe: first_layer and num_hidden_layers name layers {first_layer(conf)}.."
                         f"{last - 1} of the {published} that layer_types lists")
    if conf["conv_bias"]:
        raise ValueError("lfm2_moe: conv_bias true: the short convolution has no bias in the program "
                         "and in the reference")
    if conf["conv_L_cache"] < 2:
        raise ValueError(f"lfm2_moe: conv_L_cache {conf['conv_L_cache']}: the op runs two taps or more")
    for key, run in (("norm_topk_prob", True), ("use_expert_bias", True)):
        if conf[key] != run:
            raise ValueError(f"lfm2_moe: {key} {conf[key]!r} is not what the program and the "
                             f"reference run ({run!r})")
    if not _assumed(conf, "tie_embedding", True):
        raise ValueError("lfm2_moe: assumed.tie_embedding false: the head is the embedding's matrix in "
                         "the program and in the reference")
    if _assumed(conf, "route_norm_eps", lfm2_moe_ref.ROUTE_NORM_EPS) != lfm2_moe_ref.ROUTE_NORM_EPS:
        raise ValueError(f"lfm2_moe: assumed.route_norm_eps is not the reference's "
                         f"{lfm2_moe_ref.ROUTE_NORM_EPS}")
    if conf["hidden_size"] % conf["num_attention_heads"]:
        raise ValueError("lfm2_moe: hidden_size is no whole number of num_attention_heads heads")


def program_config(conf, **common):
    from ray_tpu.models.mixed_stack import MixedStackConfig

    _check(conf)
    common["frozen_leaves"] = tuple(common.get("frozen_leaves", ()))
    if not hasattr(MixedStackConfig, "mixer_kinds"):
        raise ValueError("lfm2_moe: this program's mixed stack has no gated short-convolution mixer, no "
                         "list of mixer kinds, no rotary full layer and no tied head (they arrive with PR 61)")
    return MixedStackConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"], n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["moe_intermediate_size"], d_ff_dense=conf["intermediate_size"],
        max_seq=conf["max_position_embeddings"], pos_emb="rope", norm="rmsnorm", act="swiglu",
        use_bias=False, tie_embeddings=True, rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["norm_eps"]), qk_norm_per_head=True, attn_full_rope=True,
        mixer_kinds=tuple(MIXERS[kind] for kind in layers_run(conf)), sconv_taps=conf["conv_L_cache"],
        n_dense_layers=conf["num_dense_layers"],
        n_experts=_published(conf, "num_experts"), held_experts=held_experts(conf),
        top_k=conf["num_experts_per_tok"], norm_topk_prob=True,
        route_scale=float(conf["routed_scaling_factor"]), router_score="sigmoid",
        router_select_bias=True, route_norm_eps=lfm2_moe_ref.ROUTE_NORM_EPS,
        shared_expert_width=0, router_aux_coeff=0.0, **common,
    )


def shapes(conf):
    """The sizes the attention cost functions take (`flash_fwd_roofline` reads
    the heads and the head size of the attention layers' kernel); `d_ff` is
    the ACTIVE width of an expert layer a token HERE. The stack is not
    homogeneous, so the required work of a token is `train_flops_per_token`
    below."""
    held_share = conf["num_experts"] / _published(conf, "num_experts")
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_q_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=int(conf["num_experts_per_tok"] * held_share * conf["moe_intermediate_size"]),
        vocab=conf["vocab_size"], gated_mlp=True,
    )


def expert_layer(conf):
    """`model_config.expert_layer`: the routed experts held here of the
    published ones, the experts a token is sent to, the hidden size and one
    expert's width."""
    return {"held": conf["num_experts"], "published": _published(conf, "num_experts"),
            "per_token": conf["num_experts_per_tok"], "hidden": conf["hidden_size"],
            "width": conf["moe_intermediate_size"]}


def short_conv_layer(conf):
    """The sizes of the family's gated short convolution, for the reader that
    prices it (benchmark/sconv_cost): the conv layers that are run, their
    channels and a channel's taps."""
    return {"layers": layers_run(conf).count("conv"), "channels": conf["hidden_size"],
            "taps": conf["conv_L_cache"]}


def train_flops_per_token(conf, seq):
    """Operations a trained token REQUIRES here (model_config.
    train_flops_per_token's docstring): two a weight of every matmul it passes
    through, times three, plus what the mixers need beside their matmuls.

    A conv layer: W_in (three projections of the hidden size) and W_out, and
    2 x taps + 1 a channel for the two gates, the taps' products and their sum
    (7 at three taps). An attention layer: the q, k, v and output projections
    and 4 x head size a head over the (S + 1) / 2 keys a query sees on
    average. A dense MLP: three matrices. An expert layer: the router and
    `num_experts_per_tok` x held / published routed experts (no shared one).
    The head over the vocabulary held here (the tied matrix: the embedding's
    lookup is no matmul)."""
    m = conf["hidden_size"]
    conv = 2.0 * (m * 3 * m + m * m) + (2.0 * conf["conv_L_cache"] + 1.0) * m
    q_heads, kv_heads = conf["num_attention_heads"], conf["num_key_value_heads"]
    d = m // q_heads
    attention = (2.0 * (m * (q_heads + 2 * kv_heads) * d + q_heads * d * m)
                 + 4.0 * d * q_heads * (seq + 1) / 2.0)
    published = _published(conf, "num_experts")
    routed = conf["num_experts_per_tok"] * conf["num_experts"] / published
    experts = 2.0 * (m * published + routed * 3 * m * conf["moe_intermediate_size"])
    dense = 2.0 * 3 * m * conf["intermediate_size"]
    kinds = layers_run(conf)
    forward = (kinds.count("conv") * conv + kinds.count("full_attention") * attention
               + sum(dense if first_layer(conf) + i < conf["num_dense_layers"] else experts
                     for i in range(len(kinds)))
               + 2.0 * m * conf["vocab_size"])
    return 3.0 * forward


def _arch(conf):
    _check(conf)
    return {"layer_types": tuple(layers_run(conf)), "first_layer": first_layer(conf),
            "num_dense_layers": conf["num_dense_layers"], "rope_theta": float(conf["rope_theta"]),
            "norm_eps": float(conf["norm_eps"]), "top_k": conf["num_experts_per_tok"],
            "route_scale": float(conf["routed_scaling_factor"]), "held_experts": held_experts(conf),
            "frozen_leaves": tuple(conf.get("program", {}).get("frozen_leaves", ()))}


def reference_logits(params, tokens, conf):
    return lfm2_moe_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a row's share
    of the mean cross entropy (no auxiliary loss: the file's `departures`),
    one row at a time."""
    return {"part": functools.partial(lfm2_moe_ref.objective_part, total_tokens=total_tokens,
                                      **_arch(conf)),
            "stats": None, "rows_at_a_time": 1}
