"""`nemotron_h` family (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): a stack
whose layers are ONE mixer each behind one RMSNorm, by the characters of
`hybrid_override_pattern`: "M" a Mamba-2 state-space mixer (`mamba_num_heads`
heads of `mamba_head_dim`, a state of `ssm_state_size`, B and C in `n_groups`
groups, a causal depthwise convolution of `conv_kernel`, the scan's
`chunk_size`), "*" causal attention without positions (`num_attention_heads`
/ `num_key_value_heads` heads of `head_dim`), "E" sigmoid-routed experts that
are NOT gated (relu(up)^2, two matrices) beside a shared one; untied head. A
configuration may hold one chip's share of every layer: `n_routed_experts`
experts of the published ones (the first), `vocab_size` rows of the published
vocabulary, and the first `num_hidden_layers` layers of the pattern."""

import functools

from ..reference import nemotron_h_ref


def _published(conf, key):
    return conf.get("published", {}).get(key, conf[key])


def held_experts(conf):
    """(first, last) of the published experts the file's `n_routed_experts`
    are, None where it holds them all."""
    held = conf["n_routed_experts"]
    return None if held == _published(conf, "n_routed_experts") else (0, held)


def pattern(conf):
    """The kinds of the layers that are run, one character a layer."""
    return conf["hybrid_override_pattern"][:conf["num_hidden_layers"]]


def _check(conf):
    """Refuse, by the key's name, what neither the program nor the reference runs."""
    whole = conf["hybrid_override_pattern"]
    if len(whole) < conf["num_hidden_layers"]:
        raise ValueError(f"nemotron_h: hybrid_override_pattern has {len(whole)} characters for "
                         f"num_hidden_layers = {conf['num_hidden_layers']}")
    if set(pattern(conf)) - set("ME*"):
        raise ValueError("nemotron_h: hybrid_override_pattern names a layer that is not M, E or * "
                         f"({sorted(set(pattern(conf)) - set('ME*'))}: '-' is a dense MLP layer, "
                         "which neither the program nor the reference runs)")
    if conf["n_group"] != 1 or conf["topk_group"] != 1:
        raise ValueError("nemotron_h: n_group and topk_group other than 1 (group-limited routing) "
                         "are not what the program and the reference run")
    for key in ("mamba_proj_bias", "attention_bias", "mlp_bias", "use_bias", "tie_word_embeddings"):
        if conf[key]:
            raise ValueError(f"nemotron_h: {key} is not what the program and the reference run")
    if not conf["use_conv_bias"]:
        raise ValueError("nemotron_h: use_conv_bias false: the convolution has a bias in both")
    if conf["mamba_hidden_act"] != "silu":
        raise ValueError(f"nemotron_h: mamba_hidden_act {conf['mamba_hidden_act']!r}: silu is what is run")
    if conf["mlp_hidden_act"] != "relu2":
        raise ValueError(f"nemotron_h: mlp_hidden_act {conf['mlp_hidden_act']!r}: relu2 (a non-gated "
                         "expert, relu(up)^2) is what is run")
    if conf["sliding_window"] is not None:
        raise ValueError("nemotron_h: a sliding_window is not what is run: every causal key")
    if not conf["norm_topk_prob"]:
        raise ValueError("nemotron_h: norm_topk_prob false: gates renormalised over the chosen are "
                         "what is run")
    if conf["mamba_num_heads"] % conf["n_groups"]:
        raise ValueError("nemotron_h: mamba_num_heads is no multiple of n_groups")


def program_config(conf, **common):
    from ray_tpu.models.mixed_stack import MixedStackConfig

    _check(conf)
    common["frozen_leaves"] = tuple(common.get("frozen_leaves", ()))
    if not hasattr(MixedStackConfig, "layer_pattern"):
        raise ValueError("nemotron_h: this program's mixed stack has no one-sublayer layers, no "
                         "state-space mixer and no non-gated expert (they arrive with PR 48)")
    return MixedStackConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], layer_pattern=pattern(conf),
        n_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        d_head=conf["head_dim"],
        ssm_heads=conf["mamba_num_heads"], ssm_head_dim=conf["mamba_head_dim"],
        ssm_state=conf["ssm_state_size"], ssm_groups=conf["n_groups"],
        ssm_conv_kernel=conf["conv_kernel"], ssm_chunk=conf["chunk_size"],
        ssm_dt_min=float(conf["time_step_min"]), ssm_dt_max=float(conf["time_step_max"]),
        ssm_dt_floor=float(conf["time_step_floor"]),
        d_ff=conf["moe_intermediate_size"], d_ff_dense=conf["intermediate_size"],
        max_seq=conf["max_position_embeddings"], pos_emb="rope", norm="rmsnorm", act="swiglu",
        use_bias=False, tie_embeddings=False, rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["layer_norm_epsilon"]),
        n_experts=_published(conf, "n_routed_experts"), held_experts=held_experts(conf),
        top_k=conf["num_experts_per_tok"], norm_topk_prob=True,
        route_scale=float(conf["routed_scaling_factor"]), router_score="sigmoid",
        router_select_bias=True, expert_act="relu2",
        shared_expert_width=conf["n_shared_experts"] * conf["moe_shared_expert_intermediate_size"],
        router_aux_coeff=0.0, **common,
    )


def shapes(conf):
    """The sizes the attention cost functions take (`flash_fwd_roofline` reads
    the heads and the head size of the `*` layers); `d_ff` is the ACTIVE width
    of an expert layer a token HERE: its shared expert and the held share of
    its routed ones. The stack is not homogeneous, so the required work of a
    token is `train_flops_per_token` below."""
    held_share = conf["n_routed_experts"] / _published(conf, "n_routed_experts")
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_q_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        d_ff=int(conf["n_shared_experts"] * conf["moe_shared_expert_intermediate_size"]
                 + conf["num_experts_per_tok"] * held_share * conf["moe_intermediate_size"]),
        vocab=conf["vocab_size"], gated_mlp=False,
    )


def expert_layer(conf):
    """`model_config.expert_layer`: the routed experts held here of the
    published ones, the experts a token is sent to, the hidden size and one
    expert's width."""
    return {"held": conf["n_routed_experts"], "published": _published(conf, "n_routed_experts"),
            "per_token": conf["num_experts_per_tok"], "hidden": conf["hidden_size"],
            "width": conf["moe_intermediate_size"]}


def state_space_layer(conf):
    """The sizes of the family's state-space mixer, for the readers that
    price its scan (benchmark/ssd_cost): the `M` layers that are run, the
    heads, a head's features, a feature's state, the groups B and C come in,
    the convolution's taps and the published chunk."""
    return {"layers": pattern(conf).count("M"), "heads": conf["mamba_num_heads"],
            "head_dim": conf["mamba_head_dim"], "state": conf["ssm_state_size"],
            "groups": conf["n_groups"], "conv_kernel": conf["conv_kernel"],
            "chunk": conf["chunk_size"]}


def train_flops_per_token(conf, seq):
    """Operations a trained token REQUIRES here (model_config.
    train_flops_per_token's docstring): two a weight of every matmul it passes
    through, times three, plus what the mixers need beside their matmuls.

    An `M` layer: W_in and W_out; 2 x taps a convolved feature; the
    recurrence, 5 x heads x head features x state a token (the decay, the
    update and the read-out of the state, whatever chunking the program uses)
    and 2 x inner for D. A `*` layer: the q, k, v and output projections and
    4 x head size a head over the (S + 1) / 2 keys a query sees on average. An
    `E` layer: the router, the shared expert whole (two matrices) and
    `num_experts_per_tok` x held / published routed experts. The head over the
    vocabulary held here."""
    m = conf["hidden_size"]
    heads, p, n, groups = (conf["mamba_num_heads"], conf["mamba_head_dim"], conf["ssm_state_size"],
                           conf["n_groups"])
    inner, conv = heads * p, heads * p + 2 * groups * n
    mamba = (2.0 * (m * (inner + conv + heads) + inner * m) + 2.0 * conf["conv_kernel"] * conv
             + 5.0 * heads * p * n + 2.0 * inner)
    q_heads, kv_heads, d = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    attention = (2.0 * (m * (q_heads + 2 * kv_heads) * d + q_heads * d * m)
                 + 4.0 * d * q_heads * (seq + 1) / 2.0)
    published = _published(conf, "n_routed_experts")
    routed = conf["num_experts_per_tok"] * conf["n_routed_experts"] / published
    experts = 2.0 * (m * published
                     + 2 * m * conf["n_shared_experts"] * conf["moe_shared_expert_intermediate_size"]
                     + routed * 2 * m * conf["moe_intermediate_size"])
    kinds = pattern(conf)
    forward = (kinds.count("M") * mamba + kinds.count("*") * attention + kinds.count("E") * experts
               + 2.0 * m * conf["vocab_size"])
    return 3.0 * forward


def _arch(conf):
    _check(conf)
    return {"pattern": pattern(conf), "ssm_groups": conf["n_groups"],
            "ssm_state": conf["ssm_state_size"], "norm_eps": float(conf["layer_norm_epsilon"]),
            "top_k": conf["num_experts_per_tok"],
            "route_scale": float(conf["routed_scaling_factor"]), "held_experts": held_experts(conf),
            "frozen_leaves": tuple(conf.get("program", {}).get("frozen_leaves", ()))}


def reference_logits(params, tokens, conf):
    return nemotron_h_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a row's share
    of the mean cross entropy (no auxiliary loss: the file's `departures`),
    one row at a time."""
    return {"part": functools.partial(nemotron_h_ref.objective_part, total_tokens=total_tokens,
                                      **_arch(conf)),
            "stats": None, "rows_at_a_time": 1}
