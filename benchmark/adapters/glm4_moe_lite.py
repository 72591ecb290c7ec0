"""`glm4_moe_lite` family (zai-org/GLM-4.7-Flash): latent attention (q from
a normed latent of `q_lora_rank`; the keys' part without positions and the
values from one normed latent of `kv_lora_rank` all heads share; rotary
positions on `qk_rope_head_dim` of a head's `qk_nope_head_dim +
qk_rope_head_dim` features, the keys' rotary part one head given to all),
`first_k_dense_replace` dense layers before sigmoid-routed expert layers
beside a shared expert, plain pre-norm blocks, untied head, and
`num_nextn_predict_layers` multi-token prediction modules in the training
objective. A configuration may hold one chip's share of every layer:
`n_routed_experts` experts of the published ones (the first), and
`vocab_size` rows of the published vocabulary."""

import functools

from ..reference import glm4_moe_lite_ref


def _published(conf, key):
    return conf.get("published", {}).get(key, conf[key])


def held_experts(conf):
    """(first, last) of the published experts the file's `n_routed_experts`
    are, None where it holds them all."""
    held = conf["n_routed_experts"]
    return None if held == _published(conf, "n_routed_experts") else (0, held)


def head_dim(conf):
    return conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]


def _check(conf):
    """Refuse, by the key's name, what neither the program nor the reference runs."""
    if conf["n_group"] != 1 or conf["topk_group"] != 1:
        raise ValueError("glm4_moe_lite: n_group and topk_group other than 1 (group-limited "
                         "routing) are not what the program and the reference run")
    if conf.get("rope_scaling") is not None:
        raise ValueError("glm4_moe_lite: a rope_scaling is not what the program and the "
                         "reference run: unscaled rotary positions")
    if conf["v_head_dim"] != head_dim(conf):
        raise ValueError(f"glm4_moe_lite: v_head_dim {conf['v_head_dim']} is not qk_nope_head_dim + "
                         f"qk_rope_head_dim = {head_dim(conf)}: the flash kernels take one head size")
    if conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise ValueError("glm4_moe_lite: num_key_value_heads other than num_attention_heads: the "
                         "key-value latent comes up to every query head's own keys and values")
    if conf["partial_rotary_factor"] != 1 or conf["attention_bias"] or conf["hidden_act"] != "silu":
        raise ValueError("glm4_moe_lite: partial_rotary_factor 1 (of the rotary part), no "
                         "attention_bias and hidden_act silu are what is run")
    if conf["topk_method"] != "noaux_tc" or not conf["norm_topk_prob"]:
        raise ValueError("glm4_moe_lite: topk_method noaux_tc (sigmoid scores, a selection bias) and "
                         "norm_topk_prob (gates renormalised over the chosen) are what is run")
    if conf["num_nextn_predict_layers"] != 1:
        raise ValueError("glm4_moe_lite: num_nextn_predict_layers other than 1: one multi-token "
                         "prediction module is what the program and the reference run")


def program_config(conf, **common):
    from ray_tpu.models.mixed_stack import MixedStackConfig

    _check(conf)
    common["frozen_leaves"] = tuple(common.get("frozen_leaves", ()))
    if not hasattr(MixedStackConfig, "kv_lora_rank"):
        raise ValueError("glm4_moe_lite: this program's mixed stack has no latent attention kind "
                         "and no multi-token prediction module (they arrive with PR 44)")
    return MixedStackConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=head_dim(conf),
        q_lora_rank=conf["q_lora_rank"], kv_lora_rank=conf["kv_lora_rank"],
        qk_rope_dim=conf["qk_rope_head_dim"], v_head_dim=conf["v_head_dim"],
        d_ff=conf["moe_intermediate_size"], d_ff_dense=conf["intermediate_size"],
        max_seq=conf["max_position_embeddings"], pos_emb="rope", norm="rmsnorm", act="swiglu",
        use_bias=False, tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), norm_eps=float(conf["rms_norm_eps"]),
        n_dense_layers=conf["first_k_dense_replace"],
        n_experts=_published(conf, "n_routed_experts"), held_experts=held_experts(conf),
        top_k=conf["num_experts_per_tok"], norm_topk_prob=True,
        route_scale=float(conf["routed_scaling_factor"]), router_score="sigmoid",
        router_select_bias=True,
        shared_expert_width=conf["n_shared_experts"] * conf["moe_intermediate_size"],
        router_aux_coeff=0.0, mtp_modules=conf["num_nextn_predict_layers"],
        mtp_loss_weight=float(conf["assumed"]["mtp_loss_weight"]), **common,
    )


def shapes(conf):
    """The sizes the attention cost functions take (`flash_fwd_roofline`
    reads the heads and the head size: the kernels see `num_attention_heads`
    query and key-value heads of qk_nope + qk_rope features); `d_ff` is the
    ACTIVE width of an expert layer a token HERE: its shared expert and the
    held share of its routed ones. The stack is not homogeneous, so the
    required work of a token is `train_flops_per_token` below."""
    held_share = conf["n_routed_experts"] / _published(conf, "n_routed_experts")
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_q_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=head_dim(conf),
        d_ff=int((conf["n_shared_experts"] + conf["num_experts_per_tok"] * held_share)
                 * conf["moe_intermediate_size"]),
        vocab=conf["vocab_size"], gated_mlp=True,
    )


def expert_layer(conf):
    """`model_config.expert_layer`: the routed experts held here of the
    published ones, the experts a token is sent to, the hidden size and one
    expert's width."""
    return {"held": conf["n_routed_experts"], "published": _published(conf, "n_routed_experts"),
            "per_token": conf["num_experts_per_tok"], "hidden": conf["hidden_size"],
            "width": conf["moe_intermediate_size"]}


def train_flops_per_token(conf, seq):
    """Operations a trained token REQUIRES here (model_config.
    train_flops_per_token's docstring): two a weight of every matmul it
    passes through, 4 x head size a visible key and head, times three.

    A layer's latent attention: the two down-projections, the two
    up-projections, the output projection, and the scores over the (S + 1) /
    2 keys a query sees on average. Its MLP: a dense layer's three matrices,
    or the router, the shared expert whole and `num_experts_per_tok` x held /
    published routed experts. The `num_hidden_layers` layers run, and the
    multi-token prediction module: `eh_proj`, one more layer of the expert
    kind, and a second pass of the head over the vocabulary held here (the
    module is counted on all S positions: the one of a sequence that has no
    target is 1 / S of 16% of the count)."""
    m, d, heads = conf["hidden_size"], head_dim(conf), conf["num_attention_heads"]
    q_rank, kv_rank, rope = conf["q_lora_rank"], conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    attention = (m * q_rank + q_rank * heads * d + m * (kv_rank + rope)
                 + kv_rank * heads * (conf["qk_nope_head_dim"] + conf["v_head_dim"])
                 + heads * conf["v_head_dim"] * m)
    scores = 4.0 * heads * d * (seq + 1) / 2.0
    expert = 3 * m * conf["moe_intermediate_size"]
    published = _published(conf, "n_routed_experts")
    routed = conf["num_experts_per_tok"] * conf["n_routed_experts"] / published
    experts = m * published + (conf["n_shared_experts"] + routed) * expert
    dense = conf["first_k_dense_replace"]
    modules = conf["num_nextn_predict_layers"]
    expert_layers = conf["num_hidden_layers"] - dense + modules
    weights = ((dense + expert_layers) * attention + dense * 3 * m * conf["intermediate_size"]
               + expert_layers * experts + modules * 2 * m * m
               + (1 + modules) * m * conf["vocab_size"])
    return 3.0 * (2.0 * weights + (dense + expert_layers) * scores)


def _arch(conf):
    _check(conf)
    return {"num_dense_layers": conf["first_k_dense_replace"],
            "qk_rope_dim": conf["qk_rope_head_dim"], "rope_theta": float(conf["rope_theta"]),
            "norm_eps": float(conf["rms_norm_eps"]), "top_k": conf["num_experts_per_tok"],
            "route_scale": float(conf["routed_scaling_factor"]), "held_experts": held_experts(conf),
            "frozen_leaves": tuple(conf.get("program", {}).get("frozen_leaves", ()))}


def reference_logits(params, tokens, conf):
    return glm4_moe_lite_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a row's share
    of the objective (the main cross entropy plus the module's, weighted; no
    auxiliary loss: the file's `departures`), one row at a time."""
    return {"part": functools.partial(
                glm4_moe_lite_ref.objective_part, total_tokens=total_tokens,
                mtp_loss_weight=float(conf["assumed"]["mtp_loss_weight"]), **_arch(conf)),
            "stats": None, "rows_at_a_time": 1}
