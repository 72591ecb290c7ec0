"""`evabyte` family (EvaByte/EvaByte, 6.5B): a byte-level dense decoder
(pre-norm, RMSNorm that multiplies by 1 + scale, rotary positions over the
whole head, as many key-value heads as query heads, SwiGLU, no biases, an
untied head) whose attention is EVA (`attention_class` "eva": exact inside a
window of `window_size` positions, one learned summary a chunk of `chunk_size`
of everything before the window, one softmax over both), whose residual
stream is added in float32 (`fp32_skip_add`), and whose ONE head matrix is
`num_pred_heads` next-byte heads of `vocab_size` columns each, with float32
logits (`fp32_logits`)."""

import functools

from ..reference import evabyte_ref


def _check(conf):
    """Refuse, by the key's name, what neither the program nor the reference runs."""
    if conf["attention_class"] != "eva":
        raise ValueError(f"evabyte: attention_class {conf['attention_class']!r}: eva is what is run")
    if conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise ValueError("evabyte: num_key_value_heads differs from num_attention_heads: grouped keys "
                         "are not what is run (a chunk's summary is a head's own)")
    if conf["hidden_size"] % conf["num_attention_heads"]:
        raise ValueError("evabyte: hidden_size is no multiple of num_attention_heads")
    if conf["window_size"] % conf["chunk_size"]:
        raise ValueError("evabyte: window_size is no multiple of chunk_size")
    if conf["num_chunks"] is not None:
        raise ValueError("evabyte: num_chunks is set: a chunk of chunk_size positions is what is run")
    for key in ("attention_bias", "tie_word_embeddings", "fp32_ln"):
        if conf[key]:
            raise ValueError(f"evabyte: {key} is not what the program and the reference run")
    for key in ("norm_add_unit_offset", "fp32_skip_add", "fp32_logits"):
        if not conf[key]:
            raise ValueError(f"evabyte: {key} false is not what the program and the reference run")
    if conf["hidden_act"] != "silu":
        raise ValueError(f"evabyte: hidden_act {conf['hidden_act']!r}: silu (SwiGLU) is what is run")
    if conf["rope_scaling"] is not None:
        raise ValueError("evabyte: rope_scaling is not what is run: plain rotary positions")
    if conf["num_pred_heads"] < 1:
        raise ValueError("evabyte: num_pred_heads must be at least 1")


def head_dim(conf):
    return conf["hidden_size"] // conf["num_attention_heads"]


def program_config(conf, **common):
    from ray_tpu.models.transformer import TransformerConfig

    _check(conf)
    if "eva_window" not in TransformerConfig.__dataclass_fields__:
        raise ValueError("evabyte: this program's transformer has no `eva` attention, no (1 + scale) "
                         "norm, no float32 residual and no next-byte heads (they arrive with PR 51)")
    return TransformerConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_ff=conf["intermediate_size"],
        max_seq=conf["max_position_embeddings"], pos_emb="rope", norm="rmsnorm", act="swiglu",
        use_bias=False, tie_embeddings=False, rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), eva_window=conf["window_size"],
        eva_chunk=conf["chunk_size"], norm_unit_offset=True, residual_fp32=True,
        pred_heads=conf["num_pred_heads"], **common,
    )


def shapes(conf):
    """The sizes the homogeneous block's cost functions take; the head is
    every next-byte head's columns. The attention is not the causal one those
    functions price, so a token's required work is `train_flops_per_token`
    below."""
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_q_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=head_dim(conf), d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"] * conf["num_pred_heads"], gated_mlp=True,
    )


def eva_layer(conf):
    """The sizes of the family's attention, for the readers that price it
    (benchmark/eva_cost): the layers that are run, the heads, a head's
    features, the window and the chunk."""
    return {"layers": conf["num_hidden_layers"], "heads": conf["num_attention_heads"],
            "head_dim": head_dim(conf), "window": conf["window_size"], "chunk": conf["chunk_size"]}


def train_flops_per_token(conf, seq):
    """Operations a trained byte REQUIRES here (model_config.
    train_flops_per_token's docstring): two a weight of every matmul it
    passes through, 4 x head size a visible key or summary and head, the
    pooling, all times three.

    A layer: the q, k, v and output projections and the three matrices of the
    SwiGLU; the scores over the (w + 1) / 2 keys of its own window a query
    sees on average and the (w / c) x (S / w - 1) / 2 summaries of the
    windows before it; the pooling, 8 x head size a head (two dot products
    with a learned vector and two weighted sums, of which a key is in one
    chunk). Once a position, the head over every next-byte head's columns."""
    m, heads, d = conf["hidden_size"], conf["num_attention_heads"], head_dim(conf)
    window, chunk = conf["window_size"], conf["chunk_size"]
    weights = 4 * m * heads * d + 3 * m * conf["intermediate_size"]
    visible = (window + 1) / 2.0 + (window // chunk) * (seq / window - 1) / 2.0
    layer = 2.0 * weights + 4.0 * d * heads * visible + 8.0 * d * heads
    head = 2.0 * m * conf["vocab_size"] * conf["num_pred_heads"]
    return 3.0 * (conf["num_hidden_layers"] * layer + head)


def _arch(conf):
    _check(conf)
    return {"rope_theta": float(conf["rope_theta"]), "norm_eps": float(conf["rms_norm_eps"]),
            "window": conf["window_size"], "chunk": conf["chunk_size"],
            "pred_heads": conf["num_pred_heads"]}


def reference_logits(params, tokens, conf):
    return evabyte_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a row's share
    of the objective (the mean over the next-byte heads of their mean cross
    entropies), nothing of the whole batch beforehand, one row at a time."""
    return {"part": functools.partial(evabyte_ref.objective_part, total_tokens=total_tokens,
                                      **_arch(conf)),
            "stats": None, "rows_at_a_time": 1}
