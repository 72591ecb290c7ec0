"""`afmoe` family (arcee-ai/Trinity-Mini): window and global gated-attention
layers mixed by a rule on the layer's index, sandwich RMSNorms, QK-norm a
head, dense layers before sigmoid-routed expert layers beside a shared
expert, untied head. A configuration may hold one chip's share of every
layer: `num_experts` experts of the published `published.num_experts` (the
first ones), `vocab_size` rows of the published vocabulary."""

import functools

from ..reference import afmoe_ref


def _published(conf, key):
    return conf.get("published", {}).get(key, conf[key])


def layer_types(conf, depth=None):
    """`sliding_attention` / `full_attention` a layer, by the rule the
    published `layer_types` list follows."""
    every = conf["global_attn_every_n_layers"]
    return ["full_attention" if (i + 1) % every == 0 else "sliding_attention"
            for i in range(conf["num_hidden_layers"] if depth is None else depth)]


def held_experts(conf):
    """(first, last) of the published experts the file's `num_experts` are,
    None where it holds them all."""
    return None if conf["num_experts"] == _published(conf, "num_experts") else (0, conf["num_experts"])


def program_config(conf, **common):
    from ray_tpu.models.mixed_stack import MixedStackConfig

    if conf["score_func"] != "sigmoid" or conf["n_group"] != 1 or conf["topk_group"] != 1:
        raise ValueError("afmoe: sigmoid scores and no group limit are what the program runs")
    listed = conf.get("layer_types")
    if listed is not None and listed != layer_types(conf, len(listed)):
        raise ValueError("afmoe: `layer_types` departs from the global_attn_every_n_layers rule")
    common["frozen_leaves"] = tuple(common.get("frozen_leaves", ()))
    return MixedStackConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=conf["head_dim"],
        d_ff=conf["moe_intermediate_size"], d_ff_dense=conf["intermediate_size"],
        max_seq=conf["max_position_embeddings"], pos_emb="rope", norm="rmsnorm", act="swiglu",
        use_bias=False, tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), norm_eps=float(conf["rms_norm_eps"]),
        qk_norm_per_head=True, attn_gate=True, sandwich_norm=True,
        scale_embedding=conf["mup_enabled"],
        sliding_window=conf["sliding_window"], global_attn_every=conf["global_attn_every_n_layers"],
        n_dense_layers=conf["num_dense_layers"],
        n_experts=_published(conf, "num_experts"), held_experts=held_experts(conf),
        top_k=conf["num_experts_per_tok"], norm_topk_prob=conf["route_norm"],
        route_scale=float(conf["route_scale"]), router_score=conf["score_func"],
        router_select_bias=True,
        shared_expert_width=conf["num_shared_experts"] * conf["moe_intermediate_size"],
        router_aux_coeff=0.0, **common,
    )


def shapes(conf):
    """The sizes the attention cost functions take (`flash_fwd_roofline`
    reads the heads and the head size); `d_ff` is the ACTIVE width of an
    expert layer a token HERE: its shared expert and the held share of its
    routed ones. The stack is not homogeneous, so the required work of a
    token is `train_flops_per_token` below and not roofline.py's count."""
    held_share = conf["num_experts"] / _published(conf, "num_experts")
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_q_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        d_ff=int((conf["num_shared_experts"] + conf["num_experts_per_tok"] * held_share)
                 * conf["moe_intermediate_size"]),
        vocab=conf["vocab_size"], gated_mlp=True,
    )


def expert_layer(conf):
    """`model_config.expert_layer`: the routed experts held here of the
    published ones, the experts a token is sent to, the hidden size and one
    expert's width."""
    return {"held": conf["num_experts"], "published": _published(conf, "num_experts"),
            "per_token": conf["num_experts_per_tok"], "hidden": conf["hidden_size"],
            "width": conf["moe_intermediate_size"]}


def attention_window(conf):
    """`model_config.attention_window`: the keys a sliding layer's query sees."""
    return conf["sliding_window"]


def train_flops_per_token(conf, seq):
    """Operations a trained token REQUIRES here (model_config.
    train_flops_per_token's docstring): two a weight of every matmul it
    passes through, 4 x head size a visible key and head, times three.

    Per layer: q, k, v, the gate and the output projection; the scores over
    the keys a query sees on average, (S + 1) / 2 on a full layer and
    sum_t min(t + 1, W) / S on a sliding one; a dense layer's three matrices,
    or the router, the shared expert whole and `num_experts_per_tok` x held /
    published routed experts. The head over the vocabulary held here."""
    m, d = conf["hidden_size"], conf["head_dim"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    window = min(conf["sliding_window"], seq)
    visible = {"full_attention": (seq + 1) / 2.0,
               "sliding_attention": (window * (window + 1) / 2.0 + (seq - window) * window) / seq}
    projections = m * d * (3 * hq + 2 * hkv)
    expert = 3 * m * conf["moe_intermediate_size"]
    routed = conf["num_experts_per_tok"] * conf["num_experts"] / _published(conf, "num_experts")
    experts = m * _published(conf, "num_experts") + (conf["num_shared_experts"] + routed) * expert
    weights = scores = 0.0
    for index, kind in enumerate(layer_types(conf)):
        weights += projections + (3 * m * conf["intermediate_size"]
                                  if index < conf["num_dense_layers"] else experts)
        scores += 4.0 * hq * d * visible[kind]
    return 3.0 * (2.0 * (weights + m * conf["vocab_size"]) + scores)


def _arch(conf):
    return {"global_attn_every": conf["global_attn_every_n_layers"],
            "num_dense_layers": conf["num_dense_layers"], "sliding_window": conf["sliding_window"],
            "rope_theta": float(conf["rope_theta"]), "norm_eps": float(conf["rms_norm_eps"]),
            "top_k": conf["num_experts_per_tok"], "route_scale": float(conf["route_scale"]),
            "held_experts": held_experts(conf),
            "frozen_leaves": tuple(conf.get("program", {}).get("frozen_leaves", ()))}


def reference_logits(params, tokens, conf):
    return afmoe_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a row's share
    of the mean cross entropy (no auxiliary loss: the file's `departures`),
    one row at a time."""
    return {"part": functools.partial(afmoe_ref.objective_part, total_tokens=total_tokens,
                                      **_arch(conf)),
            "stats": None, "rows_at_a_time": 1}
