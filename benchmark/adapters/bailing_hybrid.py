"""`bailing_hybrid` family (inclusionAI/Ling-3.0-flash): pre-norm blocks whose
mixer is Kimi Delta Attention (`num_attention_heads` heads of `head_dim` key and
value features, a decay a channel bounded at `kda_lower_bound`, a causal
depthwise convolution of `short_conv_kernel_size` on q, k and v) in every layer
but the LAST of each `layer_group_size`, which has latent attention (no q latent:
`q_lora_rank` null; keys and values from one latent of `kv_lora_rank`; q/k heads
of `qk_nope_head_dim` + `qk_rope_head_dim`, values of `v_head_dim`, narrower;
rotary positions on the rope part), both under ONE sigmoid gate a head;
`first_k_dense_replace` dense SwiGLU layers before sigmoid-routed expert layers
with group-limited selection (`n_group` groups, `topk_group` kept) beside a
shared expert; untied head. A configuration may hold one chip's share of every
layer: `num_experts` experts of the published ones (the first), `vocab_size` rows
of the published vocabulary, and `num_hidden_layers` published layers in a row
from `program.first_layer` (a stage of a pipeline: the index rules count from
the published index)."""

import functools

from ..reference import bailing_hybrid_ref


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
    """(mixer, mlp) of every layer that is run, in order."""
    return [bailing_hybrid_ref.layer_kind(i, layer_group_size=conf["layer_group_size"],
                                          first_k_dense_replace=conf["first_k_dense_replace"])
            for i in range(first_layer(conf), first_layer(conf) + conf["num_hidden_layers"])]


# key -> the one value that the program and the reference run
_AS_RUN = {
    "q_lora_rank": None, "rope_scaling": None, "hidden_act": "silu", "score_function": "sigmoid",
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
    "moe_router_enable_expert_bias": True, "kda_safe_gate": True, "no_kda_lora": True,
    "use_kda_lora": False, "gated_attention_proj_granularity_type": "head_wise", "linear_silu": True,
    "use_qk_norm": True, "use_bias": False, "use_qkv_bias": False, "group_norm_size": 1,
    "num_kv_heads_for_linear_attn": 0, "mtp_loss_scaling_factor": 0, "use_nGPT": False,
    "value_norm": False, "up_proj_norm": False, "scale_router_input": False, "use_mla_nope": False,
    "tie_word_embeddings": False,
}


def _check(conf):
    """Refuse, by the key's name, what neither the program nor the reference runs."""
    for key, run in _AS_RUN.items():
        if conf[key] != run:
            raise ValueError(f"bailing_hybrid: {key} {conf[key]!r} is not what the program and the "
                             f"reference run ({run!r})")
    if conf["qk_head_dim"] != conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]:
        raise ValueError("bailing_hybrid: qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
    if conf["rotary_dim"] != conf["qk_rope_head_dim"]:
        raise ValueError("bailing_hybrid: rotary_dim is not qk_rope_head_dim: the rope part rotates whole")
    if conf["v_head_dim"] > conf["qk_head_dim"]:
        raise ValueError("bailing_hybrid: v_head_dim wider than qk_head_dim: values are padded up to "
                         "the keys' width, never cut")
    if conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise ValueError("bailing_hybrid: num_key_value_heads other than num_attention_heads: the "
                         "key-value latent comes up to every query head's own keys and values")
    if conf["kda_lower_bound"] >= 0:
        raise ValueError("bailing_hybrid: kda_lower_bound must be negative: the gate's log-decay lies "
                         "between it and 0")
    last = first_layer(conf) + conf["num_hidden_layers"]
    if last > _published(conf, "num_hidden_layers"):
        raise ValueError(f"bailing_hybrid: layers {first_layer(conf)}..{last - 1} of "
                         f"{_published(conf, 'num_hidden_layers')} published ones")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(conf[key][first_layer(conf):last]):
            raise ValueError(f"bailing_hybrid: {key} is not 0 (off) in a layer that is run: a clamped "
                             "SwiGLU is not what the program and the reference run")


def program_config(conf, **common):
    from ray_tpu.models.mixed_stack import MixedStackConfig

    _check(conf)
    common["frozen_leaves"] = tuple(common.get("frozen_leaves", ()))
    if not hasattr(MixedStackConfig, "kda_heads"):
        raise ValueError("bailing_hybrid: this program's mixed stack has no delta-rule mixer, no latent "
                         "attention without a q latent and no group-limited routing (they arrive with PR 55)")
    return MixedStackConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"], n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        d_head=conf["qk_head_dim"], q_lora_rank=0, kv_lora_rank=conf["kv_lora_rank"],
        qk_rope_dim=conf["qk_rope_head_dim"], v_head_dim=conf["v_head_dim"],
        attn_gate=True, attn_gate_per_head=True, global_attn_every=conf["layer_group_size"],
        kda_heads=conf["num_attention_heads"], kda_head_dim=conf["head_dim"],
        kda_conv_kernel=conf["short_conv_kernel_size"],
        kda_gate_lower_bound=float(conf["kda_lower_bound"]),
        d_ff=conf["moe_intermediate_size"], d_ff_dense=conf["intermediate_size"],
        max_seq=conf["max_position_embeddings"], pos_emb="rope", norm="rmsnorm", act="swiglu",
        use_bias=False, tie_embeddings=False, rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), n_dense_layers=conf["first_k_dense_replace"],
        n_experts=_published(conf, "num_experts"), held_experts=held_experts(conf),
        top_k=conf["num_experts_per_tok"], norm_topk_prob=True,
        route_scale=float(conf["routed_scaling_factor"]), router_score="sigmoid",
        router_select_bias=True, route_groups=conf["n_group"], route_groups_kept=conf["topk_group"],
        shared_expert_width=conf["num_shared_experts"] * conf["moe_shared_expert_intermediate_size"],
        router_aux_coeff=0.0, **common,
    )


def shapes(conf):
    """The sizes the attention cost functions take (`flash_fwd_roofline` reads
    the heads and the head size of the ONE latent layer's kernel). `head_dim`
    is (qk_head_dim + v_head_dim) / 2 = 160: the scores need 2 x 192 and the
    weighted values 2 x 128 operations a visible key and head, and k with v,
    q with the output cross HBM as 192 + 128 features a row: 4 x 160 and 2 x
    160, what `roofline.attention_cost` counts of one head size. The zeros
    the kernels are padded with are no required work. `d_ff` is the ACTIVE
    width of an expert layer a token HERE. The stack is not homogeneous, so
    the required work of a token is `train_flops_per_token` below."""
    held_share = conf["num_experts"] / _published(conf, "num_experts")
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_q_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=(conf["qk_head_dim"] + conf["v_head_dim"]) // 2,
        d_ff=int(conf["num_shared_experts"] * conf["moe_shared_expert_intermediate_size"]
                 + conf["num_experts_per_tok"] * held_share * conf["moe_intermediate_size"]),
        vocab=conf["vocab_size"], gated_mlp=True,
    )


def expert_layer(conf):
    """`model_config.expert_layer`: the routed experts held here of the
    published ones, the experts a token is sent to, the hidden size and one
    expert's width."""
    return {"held": conf["num_experts"], "published": _published(conf, "num_experts"),
            "per_token": conf["num_experts_per_tok"], "hidden": conf["hidden_size"],
            "width": conf["moe_intermediate_size"]}


def delta_rule_layer(conf):
    """The sizes of the family's delta-rule mixer, for the readers that price
    its recurrence (benchmark/kda_cost): the KDA layers that are run, the
    heads, a head's key and value features."""
    return {"layers": sum(mixer == "kda" for mixer, _ in layers_run(conf)),
            "heads": conf["num_attention_heads"], "head_dim": conf["head_dim"]}


def train_flops_per_token(conf, seq):
    """Operations a trained token REQUIRES here (model_config.
    train_flops_per_token's docstring): two a weight of every matmul it passes
    through, times three, plus what the mixers need beside their matmuls.

    A KDA layer: W_in (four projections of H D), W_bg (two logits a head) and
    W_out; 2 x taps a convolved feature of q, k and v; and the recurrence, a
    token and head with a state of D x D (whatever chunking the program uses):
    the decay a channel (D^2 multiplications), what the decayed state returns
    for k (2 D^2), the rank-one correction (2 D^2: the outer product and its
    addition) and the read-out for q (2 D^2), 7 D^2 in all. The latent layer:
    W_q, W_kv_a, W_kv_b, the gate's H logits and W_o, and 4 x (qk_head_dim +
    v_head_dim) / 2 a head over the (S + 1) / 2 keys a query sees on average. A
    dense MLP: three matrices. An expert layer: the router, the shared expert
    whole and `num_experts_per_tok` x held / published routed experts. The
    head over the vocabulary held here. The multi-token prediction module is
    not run (its loss has the published weight 0) and not counted."""
    m, heads, d = conf["hidden_size"], conf["num_attention_heads"], conf["head_dim"]
    inner = heads * d
    kda = (2.0 * (m * (4 * inner + 2 * heads) + inner * m)
           + 2.0 * conf["short_conv_kernel_size"] * 3 * inner + 7.0 * heads * d * d)
    qk, v, rank, rope = conf["qk_head_dim"], conf["v_head_dim"], conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    latent = (2.0 * (m * heads * qk + m * (rank + rope) + rank * heads * (conf["qk_nope_head_dim"] + v)
                     + m * heads + heads * v * m)
              + 4.0 * heads * (qk + v) / 2.0 * (seq + 1) / 2.0)
    published = _published(conf, "num_experts")
    routed = conf["num_experts_per_tok"] * conf["num_experts"] / published
    experts = 2.0 * (m * published
                     + 3 * m * conf["num_shared_experts"] * conf["moe_shared_expert_intermediate_size"]
                     + routed * 3 * m * conf["moe_intermediate_size"])
    dense = 2.0 * 3 * m * conf["intermediate_size"]
    kinds = layers_run(conf)
    forward = (sum(kda if mixer == "kda" else latent for mixer, _ in kinds)
               + sum(dense if mlp == "dense" else experts for _, mlp in kinds)
               + 2.0 * m * conf["vocab_size"])
    return 3.0 * forward


def _arch(conf):
    _check(conf)
    return {"first_layer": first_layer(conf), "layer_group_size": conf["layer_group_size"],
            "first_k_dense_replace": conf["first_k_dense_replace"],
            "qk_rope_dim": conf["qk_rope_head_dim"], "rope_theta": float(conf["rope_theta"]),
            "norm_eps": float(conf["rms_norm_eps"]), "kda_lower_bound": float(conf["kda_lower_bound"]),
            "top_k": conf["num_experts_per_tok"], "route_scale": float(conf["routed_scaling_factor"]),
            "n_group": conf["n_group"], "topk_group": conf["topk_group"],
            "held_experts": held_experts(conf),
            "frozen_leaves": tuple(conf.get("program", {}).get("frozen_leaves", ()))}


def reference_logits(params, tokens, conf):
    return bailing_hybrid_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a row's share
    of the mean cross entropy (no auxiliary loss: the file's `departures`),
    one row at a time."""
    return {"part": functools.partial(bailing_hybrid_ref.objective_part, total_tokens=total_tokens,
                                      **_arch(conf)),
            "stats": None, "rows_at_a_time": 1}
