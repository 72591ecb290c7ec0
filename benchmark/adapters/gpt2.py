"""GPT-2 family (pre-norm, LayerNorm, learned positions, MHA with biases,
tanh GELU, tied head)."""

import functools

from ..reference import transformer_ref


def program_config(conf, **common):
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=conf["vocab_size"], d_model=conf["n_embd"],
        n_layers=conf["n_layer"], n_heads=conf["n_head"],
        d_ff=4 * conf["n_embd"], max_seq=conf["n_positions"],
        pos_emb="learned", norm="layernorm", act="gelu", use_bias=True,
        tie_embeddings=True, **common,
    )


def shapes(conf):
    return dict(
        n_layers=conf["n_layer"], d_model=conf["n_embd"], n_q_heads=conf["n_head"],
        n_kv_heads=conf["n_head"], head_dim=conf["n_embd"] // conf["n_head"],
        d_ff=4 * conf["n_embd"], vocab=conf["vocab_size"], gated_mlp=False,
    )


def _arch(conf):
    return {"family": "gpt2", "norm_eps": float(conf["layer_norm_epsilon"])}


def reference_logits(params, tokens, conf):
    return transformer_ref.forward_logits(params, tokens, **_arch(conf))


def reference_steps(conf, total_tokens):
    """What `reference/train_ref.follow` needs of this family: a block of
    rows' share of the objective, nothing of the whole batch beforehand,
    and 4 rows of float32 activations at a time."""
    return {"part": functools.partial(
                transformer_ref.objective_part, total_tokens=total_tokens, **_arch(conf)),
            "stats": None, "rows_at_a_time": 4}
