"""OLMoE family (pre-norm, RMSNorm, QK-norm, rope, MHA, a router over
SwiGLU experts with top-k gates, untied head)."""

import functools

from ..reference import olmoe_ref


def program_config(conf, **common):
    from ray_tpu.models.moe import MoEConfig

    if conf["hidden_size"] != conf["num_attention_heads"] * conf["assumed"]["head_dim"]:
        raise ValueError("head_dim must be hidden_size / num_attention_heads")
    return MoEConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_ff=conf["intermediate_size"],
        max_seq=conf["max_position_embeddings"], pos_emb="rope",
        norm="rmsnorm", act="swiglu", use_bias=conf["attention_bias"],
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), norm_eps=float(conf["rms_norm_eps"]),
        qk_norm=conf["assumed"]["qk_norm"],
        n_experts=conf["num_experts"], top_k=conf["num_experts_per_tok"],
        norm_topk_prob=conf["norm_topk_prob"],
        router_aux_coeff=float(conf["assumed"]["router_aux_loss_coef"]), **common,
    )


def shapes(conf):
    """The sizes `roofline.train_flops_per_token` takes. `d_ff` is the
    ACTIVE width, experts per token x one expert's: what a token's forward
    and backward require. The router's hidden_size x num_experts weights
    (0.13 M a layer against 67 M) are left out."""
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_q_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["assumed"]["head_dim"],
        d_ff=conf["num_experts_per_tok"] * conf["intermediate_size"],
        vocab=conf["vocab_size"], gated_mlp=True,
    )


def expert_layer(conf):
    """`model_config.expert_layer`: every published expert is held; an
    expert's width is this family's `intermediate_size`. No layer has a
    window, so there is no `attention_window`."""
    return {"held": conf["num_experts"], "published": conf["num_experts"],
            "per_token": conf["num_experts_per_tok"], "hidden": conf["hidden_size"],
            "width": conf["intermediate_size"]}


def _arch(conf):
    return {"top_k": conf["num_experts_per_tok"], "norm_topk_prob": conf["norm_topk_prob"],
            "rope_theta": float(conf["rope_theta"]), "norm_eps": float(conf["rms_norm_eps"])}


def reference_logits(params, tokens, conf):
    return olmoe_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a block of
    rows' share of the objective (cross entropy plus the published 0.01
    times the load-balancing loss, whose load shares are the whole batch's:
    `stats`), one 4,096-token row at a time."""
    arch = _arch(conf)
    return {"part": functools.partial(
                olmoe_ref.objective_part, total_tokens=total_tokens,
                router_aux_loss_coef=float(conf["assumed"]["router_aux_loss_coef"]), **arch),
            "stats": lambda params, tokens: olmoe_ref.expert_shares(params, tokens[:, :-1], **arch),
            "rows_at_a_time": 1}
