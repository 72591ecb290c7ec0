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
    # the differentiable form the first steps' reference takes: two of the three rows' share of it
    share, ce_sum = transformer_ref.objective_part(params, tokens[:2], total_tokens=3 * 16, **arch)
    rows = -np.sum([logp[b, s, int(tokens[b, s + 1])] for b in range(2) for s in range(16)])
    assert float(ce_sum) == pytest.approx(float(rows), rel=1e-5)
    assert float(share) == pytest.approx(float(rows) / 48, rel=1e-5)


def test_a_family_without_an_adapter_is_refused():
    with pytest.raises(ValueError, match="no adapter"):
        model_config.adapter({"model_type": "no-such-family"})


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


_TRAINER = {"learning_rate": 3e-4, "total_steps": 1000, "warmup_steps": 100, "end_lr_ratio": 0.1,
            "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "grad_clip": 1.0}


@pytest.mark.parametrize("config", [
    "gpt2-small-train-1chip", "mistral-7b-v0.3-train-4chip", "olmoe-1b-7b-train-1chip"])
def test_a_cells_trainer_group_states_the_optimizer_the_program_runs(config):
    """The reference's AdamW takes its numbers from the configuration file;
    they are `train/lm.default_optimizer`'s, which `LMTrainer` runs when it
    is given none: the same schedule, and the same two updates of a leaf."""
    import optax

    from bench_helpers import ROOT
    from benchmark.reference import train_ref
    from ray_tpu.train.lm import default_optimizer

    t = model_config.load_config(os.path.join(ROOT, "benchmark", "configs", config + ".json"))["trainer"]
    assert {k: t[k] for k in _TRAINER} == _TRAINER
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, t["learning_rate"], 100, max(t["total_steps"], 101), t["learning_rate"] * 0.1)
    for count in (0, 1, 2, 99, 100, 101, 550, 999, 1000, 5000):
        assert train_ref.learning_rate(count, t) == pytest.approx(float(schedule(count)), rel=1e-5, abs=1e-12)

    rng = np.random.default_rng(5)
    params = {"a": jnp.asarray(rng.normal(size=(7, 5)), jnp.float32), "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32)}
    grads = [jax.tree.map(lambda x: jnp.asarray(rng.normal(size=x.shape) * scale, jnp.float32), params)
             for scale in (3.0, 0.01)]       # the first is clipped, the second is not
    optimizer = default_optimizer(t["learning_rate"], total_steps=t["total_steps"])
    theirs, state = params, optimizer.init(params)
    ours, kept = params, []
    for count, g in enumerate(grads):
        updates, state = optimizer.update(g, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        kept.append(train_ref._clipped(jax.tree.map(jnp.copy, g), t["grad_clip"]))
        ours = train_ref._adamw_update(jax.tree.map(jnp.copy, ours), kept, train_ref.learning_rate(count, t), t)
    assert float(jnp.max(jnp.abs(theirs["a"] - params["a"]))) > 0        # the second update moved it
    for name in params:
        assert float(jnp.max(jnp.abs(ours[name] - theirs[name]))) < 1e-7


def test_olmoe_objective_parts_add_up_to_the_whole_batchs_gradient():
    """A block of rows at a time, with the load shares of the whole batch
    taken beforehand, gives the gradient of `olmoe_ref.objective` on the
    whole batch at once: the load-balancing loss is no sum over rows."""
    from bench_helpers import ROOT
    from benchmark.reference import olmoe_ref, train_ref
    from ray_tpu.models import model_family

    conf = model_config.load_config(os.path.join(
        ROOT, "tests", "benchmark", "data", "tiny_olmoe", "configs", "tiny-olmoe-train.json"))
    mc = model_config.transformer_config(conf)
    params = model_family(mc).init_params(mc, jax.random.PRNGKey(4))
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, mc.vocab_size, size=(4, 17)), jnp.int32)
    arch = {"top_k": 2, "norm_topk_prob": False, "rope_theta": 10000.0, "norm_eps": 1e-5}
    (_, parts), whole = jax.value_and_grad(
        lambda p: olmoe_ref.objective(p, tokens, router_aux_loss_coef=0.01, **arch), has_aux=True)(params)
    steps = model_config.adapter(conf).reference_steps(conf, 4 * 16)
    loss, blocks = train_ref.BatchGradient(steps["part"], steps["stats"], 1)(params, tokens)
    assert loss == pytest.approx(float(parts["cross_entropy"]), abs=1e-5)
    for ours, theirs in zip(jax.tree.leaves(blocks), jax.tree.leaves(whole)):
        assert float(jnp.max(jnp.abs(ours - theirs))) < 1e-6 + 1e-4 * float(jnp.max(jnp.abs(theirs)))
