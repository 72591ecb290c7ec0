"""The plain reference against the program's own forward on seeded
weights at a tiny size (float32: they must agree to rounding)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import model_config
from benchmark.reference import transformer_ref

from bench_helpers import TINY


@pytest.mark.parametrize("name", ["tiny-mistral-serve", "tiny-gpt2-train"])
def test_reference_equals_the_programs_forward(name):
    from ray_tpu.models import forward, init_params

    conf = model_config.load_config(os.path.join(TINY, "configs", name + ".json"))
    mc = model_config.transformer_config(conf)
    params = init_params(mc, jax.random.PRNGKey(3))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, mc.vocab_size, size=(2, 24)), jnp.int32)
    ours = model_config.adapter(conf).reference_logits(params, tokens, conf)
    theirs = forward(params, tokens, mc).astype(jnp.float32)
    assert ours.shape == theirs.shape == (2, 24, mc.vocab_size)
    assert float(jnp.max(jnp.abs(ours - theirs))) < 1e-4


def test_a_wrong_epsilon_or_theta_is_seen():
    """Tight enough to notice: the published Mistral epsilon (1e-5) against
    the program's (1e-6) moves the logits by more than float32 rounding."""
    from ray_tpu.models import init_params

    conf = model_config.load_config(os.path.join(TINY, "configs", "tiny-mistral-serve.json"))
    mc = model_config.transformer_config(conf)
    params = init_params(mc, jax.random.PRNGKey(3))
    tokens = jnp.asarray([[5, 9, 200, 17, 3, 42, 8, 1]], jnp.int32)
    arch = {"family": "mistral", "rope_theta": conf["rope_theta"],
            "norm_eps": conf["departures"]["program_norm_eps"]}
    base = transformer_ref.forward_logits(params, tokens, **arch)
    assert float(jnp.max(jnp.abs(base - model_config.adapter(conf).reference_logits(params, tokens, conf)))) == 0
    eps = transformer_ref.forward_logits(params, tokens, **dict(arch, norm_eps=1e-5))
    theta = transformer_ref.forward_logits(params, tokens, **dict(arch, rope_theta=500.0))
    assert float(jnp.max(jnp.abs(base - eps))) > 1e-4
    assert float(jnp.max(jnp.abs(base - theta))) > 1e-4


def test_reference_loss_is_the_mean_next_token_cross_entropy():
    from ray_tpu.models import init_params

    conf = model_config.load_config(os.path.join(TINY, "configs", "tiny-gpt2-train.json"))
    mc = model_config.transformer_config(conf)
    params = init_params(mc, jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 256, size=(3, 17)), jnp.int32)
    arch = {"family": "gpt2", "norm_eps": conf["layer_norm_epsilon"]}
    logits = transformer_ref.forward_logits(params, tokens[:, :-1], **arch)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    want = -np.mean([logp[b, s, int(tokens[b, s + 1])] for b in range(3) for s in range(16)])
    assert transformer_ref.loss(params, tokens, **arch) == pytest.approx(float(want), rel=1e-5)
    assert transformer_ref.loss(params, tokens, rows_at_a_time=1, **arch) == pytest.approx(float(want), rel=1e-5)
    assert model_config.adapter(conf).reference_loss(params, tokens, conf) == pytest.approx(float(want), rel=1e-5)


def test_a_family_without_an_adapter_is_refused():
    with pytest.raises(ValueError, match="no adapter"):
        model_config.adapter({"model_type": "olmoe"})


def test_probe_is_judged_where_margins_allow_and_never_vacuously():
    import numpy as np

    from benchmark import check

    tail = np.zeros((4, 6), np.float32)
    tail[0, 2] = 3.0            # clear winner: judged
    tail[1, 5] = 2.0            # clear winner: judged
    tail[2, 1], tail[2, 4] = 1.0, 0.8    # a near tie: counted, not judged
    tail[3, 0] = 0.1            # a near tie
    problems, counts = check.judge_probe(tail, [2, 5, 4, 3], min_judged=2)
    assert problems == []
    assert counts == {"probe_tokens_judged": 2, "probe_tokens": 4, "probe_tokens_equal": 2}
    problems, _ = check.judge_probe(tail, [2, 1, 4, 3], min_judged=2)
    assert len(problems) == 1 and "positions [1]" in problems[0]
    problems, _ = check.judge_probe(tail, [2, 5, 4, 3], min_judged=3)
    assert len(problems) == 1 and "only 2 of 4" in problems[0]
    flat, _ = check.judge_probe(np.zeros((4, 6), np.float32), [0, 0, 0, 0], min_judged=1)
    assert len(flat) == 1 and "only 0 of 4" in flat[0]
