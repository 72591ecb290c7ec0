"""`smallthinker` family (PowerInfer/SmallThinker-21BA3B-Instruct): every
layer an expert layer whose router reads the tensor the layer's attention
takes, ReGLU experts with no shared one, softmax gates over the chosen, a
window and rotary positions on three layers of every four and every causal
key and no positions on the FIRST of the four, plain pre-norm blocks, untied
head. A configuration may hold one chip's share of every layer:
`moe_num_primary_experts` experts of the published ones (the first), and
`vocab_size` rows of the published vocabulary."""

import functools

from ..reference import smallthinker_ref

GLOBAL_ATTN_EVERY = 4    # the period both layout lists follow: [0, 1, 1, 1] x 13


def _published(conf, key):
    return conf.get("published", {}).get(key, conf[key])


def layout(depth):
    """`sliding_window_layout` and `rope_layout` alike, by the rule the
    published lists follow: 0 (every causal key, no positions) for the first
    layer of a period, 1 (the window, rotary positions) for the others."""
    return [0 if i % GLOBAL_ATTN_EVERY == 0 else 1 for i in range(depth)]


def held_experts(conf):
    """(first, last) of the published experts the file's
    `moe_num_primary_experts` are, None where it holds them all."""
    held = conf["moe_num_primary_experts"]
    return None if held == _published(conf, "moe_num_primary_experts") else (0, held)


def _check(conf):
    for key in ("sliding_window_layout", "rope_layout"):
        listed = conf.get(key)
        if listed is not None and list(listed) != layout(len(listed)):
            raise ValueError(f"smallthinker: `{key}` departs from the [0, 1, 1, 1] rule")
    if not conf["moe_primary_router_apply_softmax"]:
        raise ValueError("smallthinker: softmax scores over the chosen experts are what the "
                         "program and the reference run (moe_primary_router_apply_softmax)")
    if not conf["norm_topk_prob"] or conf.get("rope_scaling") is not None:
        raise ValueError("smallthinker: gates renormalised over the chosen and unscaled rotary "
                         "positions are what the program and the reference run")


def program_config(conf, **common):
    from ray_tpu.models.mixed_stack import MixedStackConfig

    _check(conf)
    common["frozen_leaves"] = tuple(common.get("frozen_leaves", ()))
    return MixedStackConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=conf["head_dim"],
        d_ff=conf["moe_ffn_hidden_size"], max_seq=conf["max_position_embeddings"],
        pos_emb="rope", norm="rmsnorm", act="swiglu", use_bias=False,
        tie_embeddings=conf["tie_word_embeddings"], rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        sliding_window=conf["sliding_window_size"], global_attn_every=GLOBAL_ATTN_EVERY,
        global_attn_first=True, n_dense_layers=0, router_input="attention",
        n_experts=_published(conf, "moe_num_primary_experts"), held_experts=held_experts(conf),
        top_k=conf["moe_num_active_primary_experts"], norm_topk_prob=True,
        router_score="softmax", expert_act="reglu", router_aux_coeff=0.0, **common,
    )


def shapes(conf):
    """The sizes the attention cost functions take (`flash_fwd_roofline`
    reads the heads and the head size); `d_ff` is the ACTIVE width of an
    expert layer a token HERE: the held share of its routed experts. The
    stack is not homogeneous, so the required work of a token is
    `train_flops_per_token` below and not roofline.py's count."""
    held_share = conf["moe_num_primary_experts"] / _published(conf, "moe_num_primary_experts")
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_q_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        d_ff=int(conf["moe_num_active_primary_experts"] * held_share * conf["moe_ffn_hidden_size"]),
        vocab=conf["vocab_size"], gated_mlp=True,
    )


def expert_layer(conf):
    """`model_config.expert_layer`: the routed experts held here of the
    published ones, the experts a token is sent to, the hidden size and one
    expert's width."""
    return {"held": conf["moe_num_primary_experts"],
            "published": _published(conf, "moe_num_primary_experts"),
            "per_token": conf["moe_num_active_primary_experts"], "hidden": conf["hidden_size"],
            "width": conf["moe_ffn_hidden_size"]}


def attention_window(conf):
    """`model_config.attention_window`: the keys a sliding layer's query sees."""
    return conf["sliding_window_size"]


def train_flops_per_token(conf, seq):
    """Operations a trained token REQUIRES here (model_config.
    train_flops_per_token's docstring): two a weight of every matmul it
    passes through, 4 x head size a visible key and head, times three.

    Per layer: q, k, v and the output projection; the router;
    `moe_num_active_primary_experts` x held / published routed experts; the
    scores over the keys a query sees on average, (S + 1) / 2 on a full layer
    and sum_t min(t + 1, W) / S on a sliding one. The head over the vocabulary
    held here."""
    m, d = conf["hidden_size"], conf["head_dim"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    window = min(conf["sliding_window_size"], seq)
    visible = {0: (seq + 1) / 2.0,
               1: (window * (window + 1) / 2.0 + (seq - window) * window) / seq}
    published = _published(conf, "moe_num_primary_experts")
    routed = conf["moe_num_active_primary_experts"] * conf["moe_num_primary_experts"] / published
    layer = (m * d * (2 * hq + 2 * hkv) + m * published
             + routed * 3 * m * conf["moe_ffn_hidden_size"])
    kinds = layout(conf["num_hidden_layers"])
    scores = sum(4.0 * hq * d * visible[kind] for kind in kinds)
    return 3.0 * (2.0 * (len(kinds) * layer + m * conf["vocab_size"]) + scores)


def _arch(conf):
    _check(conf)
    return {"global_attn_every": GLOBAL_ATTN_EVERY, "sliding_window": conf["sliding_window_size"],
            "rope_theta": float(conf["rope_theta"]), "norm_eps": float(conf["rms_norm_eps"]),
            "top_k": conf["moe_num_active_primary_experts"], "held_experts": held_experts(conf),
            "frozen_leaves": tuple(conf.get("program", {}).get("frozen_leaves", ())),
            # the precision the stream is held in where the routing is decided
            "router_reads": conf.get("dtype", {}).get("compute", "bfloat16")}


def reference_logits(params, tokens, conf):
    return smallthinker_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a row's share
    of the mean cross entropy (no auxiliary loss: the file's `departures`),
    one row at a time."""
    return {"part": functools.partial(smallthinker_ref.objective_part, total_tokens=total_tokens,
                                      **_arch(conf)),
            "stats": None, "rows_at_a_time": 1}
