"""Mistral family (pre-norm, RMSNorm, rope, GQA, SwiGLU, untied head)."""

import functools

from ..reference import transformer_ref


def program_config(conf, **common):
    from ray_tpu.models.transformer import TransformerConfig

    if conf["hidden_size"] != conf["num_attention_heads"] * conf["head_dim"]:
        raise ValueError("head_dim must be hidden_size / num_attention_heads")
    return TransformerConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_ff=conf["intermediate_size"],
        max_seq=conf["max_position_embeddings"], pos_emb="rope",
        norm="rmsnorm", act="swiglu", use_bias=False,
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), **common,
    )


def shapes(conf):
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_q_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"], gated_mlp=True,
    )


def _arch(conf):
    # the norm epsilon is the one the PROGRAM computes with (see the file's
    # `departures`): the comparison is of arithmetic
    return {"family": "mistral", "rope_theta": float(conf["rope_theta"]),
            "norm_eps": float(conf["departures"]["program_norm_eps"])}


def reference_logits(params, tokens, conf):
    return transformer_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a block of
    rows' share of the objective, nothing of the whole batch beforehand,
    and 4 rows of float32 activations at a time."""
    return {"part": functools.partial(
                transformer_ref.objective_part, total_tokens=total_tokens, **_arch(conf)),
            "stats": None, "rows_at_a_time": 4}
