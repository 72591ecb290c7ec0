"""The comparison that decides `correct`, run after the window.

Tolerances, and why each is what it is:

LOGITS_REL_RMS = 0.05. The serving cells compute in bfloat16 (8 bits of
  mantissa) and return bfloat16 logits; through 16 layers of random
  weights the system's logits measured 2.0% to 2.9% RMS off the float32
  reference, relative to the logits' own RMS, over six seeds on the chip
  (PR 24, PERF.md Findings). 5% leaves room for another seed, and is far
  under what a lower precision gives: fp8 (3 bits of mantissa) or int8
  weights round thirty times coarser at every step.
MARGIN = 0.5 logits. A greedy token is only compared where the full
  forward's top two logits are further apart than the two paths can
  disagree by rounding: the largest absolute difference to the reference
  measured on the chip is 0.18 to 0.25, so two bfloat16 paths can differ
  by twice that. Closer calls are counted and reported, not judged. So
  that the comparison cannot pass with nothing compared, the
  configuration's `probe.min_judged` tokens at least must be judged:
  random weights give a margin above 0.5 at 6% to 25% of positions (2 to
  8 of 32 over five seeds on the chip, PR 24), so a probe of 128 tokens
  with `min_judged` 2 fails for want of margins about once in 300 runs.
TRAINING, since the review of PR 31: the comparison is of the step the window
  times, not of a program of the check's own. Once the window has closed,
  the peak has been read and the measured trainer is gone, the trainer is
  built again from the seed (its state is a pure function of the seed, its
  step the same program, fetched from the compile cache) and driven through
  the first FOLLOWED = 2 seeded batches by the window's own call,
  `LMTrainer.train`; then, its state freed, `reference/train_ref.follow`
  takes the same two steps in float32: the gradient of the family's
  reference objective in blocks of rows, a clip by the global norm, AdamW
  behind the warm-up, with the numbers the configuration's `trainer` group
  states. Two steps and not three: the reference keeps one copy of the
  parameters' size a step, and a third does not fit beside a 626 M-parameter
  model's activations on one chip. The check takes 17 s on `train-gpt2s`,
  33 s on `train-olmoe-64e-4k` and 64 s on `train-mistral7b-fsdp2tp2`, which
  is longer than the 38 s window (PERF.md row 30; my chip runs, PR 31).
  Five numbers, each with its limit:
  `first_loss_repeat_gap`, FIRST_LOSS_REPEAT_GAP = 1e-5: the rebuilt
    trainer's first loss against the MEASURED trainer's (`system.loss_first`):
    0 in every chip run made, sound or not (same program, same state,
    same batch), so it ties what is compared to what was timed.
  `loss_step1_gap`, `loss_step2_gap`, `probe.loss_gap`: each followed step's
    loss against the reference's. The lower precision hardly moves it at
    seeded weights (every target's loss is near ln(vocab)), so it is held
    against the fault it is there to catch, a part of the batch left out,
    at about three times the sound runs' largest.
  `first_gradient_worst_leaf_difference`, `probe.first_gradient_difference`:
    the first gradient as AdamW gets it (clipped; the program's is its
    first moment after one step over 1 - b1), a leaf at a time: the norm of
    the two sides' DIFFERENCE over the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but zero),
    by the worst leaf. The gap between the two NORMS of a leaf, which is
    what was to be compared, is at fault: on `train-olmoe-64e-4k` sound runs
    read 0.0009 to 0.0067 (the router's leaf swings) and the experts through
    float8 0.0018 to 0.0165. It is printed beside the others, with no limit.
  `change_worst_leaf_gap`, `probe.change_gap`: the gap between the norm of
    the parameters' change after the two steps (the first update's rate is
    zero: the warm-up starts there) and the reference's, over the
    reference's norm of that leaf or of the median leaf, by the worst leaf.
    The lower precision hardly moves it (AdamW's update is a gradient over
    its own size), so it is held against a step that returns its state
    unchanged, which reads 1, at about three times the sound runs' largest.
  Readings (my chip runs, PR 31; sound seeds / control seeds; the control is
  the program's step with weights through float8_e4m3 where the forward
  reads them and the gradient passed straight through,
  tests/benchmark/bench_helpers.float8_weights: the MLP or the experts
  alone, then every weight):
                          gpt2s (8 / 3)     olmoe (8 / 3)      mistral (4 / 2)
    loss gap, sound       0.00006-0.00064   0.00006-0.00126    0.00002-0.00045
      MLP / experts       0.0020-0.0168     0.0001-0.0030      0.0045-0.0112
      every weight        0.0019-0.0223     0.0023-0.0331
      limit               0.002             0.004              0.002
    gradient difference   0.0060-0.0071     0.0079-0.0111      0.0222-0.0260
      MLP / experts       0.261-0.272       0.0535-0.0571      0.294-0.311
      every weight        0.322-0.354       0.138-0.216
      limit               0.03              0.025              0.08
    change gap, sound     0.078-0.082       0.00013-0.00065    0.00010-0.00018
      limit               0.25              0.002              0.001
  GPT-2's change gap is its key bias's: softmax does not see a bias on the
  keys, so that leaf's gradient is rounding noise on both sides and AdamW
  makes a full-sized step of either noise; it read 0.078 to 0.083 in all 14
  runs, sound or not, and a sound run's other leaves at most 0.0003. Half the batch
  left out (`train-olmoe-64e-4k`, one seed) reads 0.190 on the gradient,
  0.0035 and 0.0152 on the losses and 0.220 on the change.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List

from . import model_config
from .traffic import lm_batches, rng_for, tokens as seeded_tokens

LOGITS_REL_RMS = 0.05
MARGIN = 0.5
FOLLOWED = 2
FIRST_LOSS_REPEAT_GAP = 1e-5


def serve_correct(system, conf: Dict[str, Any], seed: int, ctx: Dict[str, Any]) -> List[str]:
    """A probe request alone through the engine, then (the engine gone, its
    pool freed) the system's own full forward and the reference on the
    probe's prompt and answer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import forward

    problems: List[str] = []
    probe = conf["probe"]
    prompt = seeded_tokens(rng_for(seed, 99), probe["prompt_tokens"], conf["vocab_size"])
    answer = system.generate(prompt, probe["max_tokens"])
    if len(answer) != probe["max_tokens"]:
        return [f"probe: {len(answer)} tokens, asked {probe['max_tokens']}"]
    params, mc = system.params, system.mc
    system.shutdown()

    sequence = jnp.asarray([prompt + answer[:-1]], jnp.int32)
    logits = jax.jit(lambda p, t: forward(p, t, mc))(params, sequence)[0].astype(jnp.float32)
    ref = model_config.adapter(conf).reference_logits(params, sequence, conf)[0]
    rel_rms = relative_rms(logits, ref)
    max_abs = float(jnp.max(jnp.abs(logits - ref)))
    if not rel_rms <= LOGITS_REL_RMS:
        problems.append(f"system forward vs reference: relative RMS error {rel_rms:.4f} "
                        f"> {LOGITS_REL_RMS}")

    # row i of the tail predicts answer[i]
    probe_problems, counts = judge_probe(np.asarray(logits[len(prompt) - 1:]), answer,
                                         probe["min_judged"])
    ctx["checks"] = dict(counts, logits_rel_rms_vs_reference=rel_rms,
                         logits_max_abs_vs_reference=max_abs)
    return problems + probe_problems


def judge_probe(tail, answer: List[int], min_judged: int):
    """The engine's greedy tokens against the argmax of the full forward's
    logits `tail` (one row per answer token), judged only where the top
    two logits are more than MARGIN apart; fewer than `min_judged` judged
    tokens is a problem of its own."""
    import numpy as np

    order = np.argsort(tail, axis=-1)
    top, second = order[:, -1], order[:, -2]
    rows = np.arange(len(answer))
    judged = tail[rows, top] - tail[rows, second] > MARGIN
    wrong = [int(i) for i in rows[judged] if int(top[i]) != answer[i]]
    problems = []
    if int(judged.sum()) < min_judged:
        problems.append(f"probe: only {int(judged.sum())} of {len(answer)} tokens had a top-two "
                        f"margin above {MARGIN}; {min_judged} must be judged")
    if wrong:
        problems.append(f"probe: engine's greedy tokens differ from the full forward's at "
                        f"answer positions {wrong[:8]} (margins above {MARGIN})")
    return problems, {
        "probe_tokens_judged": int(judged.sum()), "probe_tokens": len(answer),
        "probe_tokens_equal": int(sum(int(top[i]) == answer[i] for i in rows)),
    }


def relative_rms(got, ref) -> float:
    """RMS of `got - ref` over the RMS of `ref`."""
    import jax.numpy as jnp

    return float(jnp.sqrt(jnp.mean((got - ref) ** 2) / jnp.mean(ref ** 2)))


def leaf_gaps(program: List[float], reference: List[float]) -> List[float]:
    """A leaf's |program - reference| against the reference's norm of that
    leaf or of the median leaf, whichever is larger: some leaves' gradients
    are all but zero."""
    floor = statistics.median(reference)
    return [abs(p - r) / max(r, floor) for p, r in zip(program, reference)]


def program_first_steps(system, conf: Dict[str, Any], batches) -> Dict[str, Any]:
    """The trainer, rebuilt from the seed once the measured one is gone (its
    state is a pure function of the seed, and its step the same program,
    fetched from the compile cache), driven through `batches` by the window's
    own call, `LMTrainer.train`, a step at a time: each step's loss, the
    first gradient as AdamW got it (its first moment after one step over
    1 - b1; kept on the host, the next step donates the state), the norm a
    leaf of the parameters' change, and `seeded_params()`, which makes the
    seeded parameters again."""
    import jax
    import optax

    from ray_tpu.models import model_family

    from .reference.train_ref import leaf_norms

    system.initial_params()
    trainer, mc = system.trainer, system.mc
    shardings = trainer.state_shardings.params
    names = [jax.tree_util.keystr(path)
             for path, _ in jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]]

    def seeded_params():
        key = jax.random.PRNGKey(system.seed & 0x7FFFFFFF)      # as TrainSystem._build seeds it
        return jax.jit(lambda k: model_family(mc).init_params(mc, k), out_shardings=shardings)(key)

    losses, first_moment = [], None
    for batch in batches:
        losses.append(float(trainer.train(iter([batch]), num_steps=1, report_every=1)["loss"]))
        if first_moment is None:
            first_moment = jax.device_get(
                jax.tree.leaves(optax.tree_utils.tree_get(trainer.state.opt_state, "mu")))
    change = leaf_norms(jax.jit(lambda p, p0: jax.tree.map(lambda a, b: a - b, p, p0))(
        trainer.state.params, seeded_params()))
    system.trainer = None
    scale = 1.0 / (1.0 - float(conf["trainer"]["b1"]))
    return {"losses": losses, "first_gradient": [m * scale for m in first_moment],
            "change_norms": change, "leaf_names": names, "seeded_params": seeded_params}


def train_readings(system, conf: Dict[str, Any]) -> Dict[str, Any]:
    """Every number `train_correct` compares, the program's first FOLLOWED
    steps against the reference's on the same seeded weights and batches,
    and beside them (`uncompared`) the readings that no limit is set on."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from .reference import train_ref

    source = lm_batches(system.traffic, system.seed, conf["vocab_size"])
    batches = [next(source) for _ in range(FOLLOWED)]
    program = program_first_steps(system, conf, batches)
    gc.collect()        # the program's state is gone before the reference's is made
    gradient = program.pop("first_gradient")
    gradient_norms = [float(np.sqrt(np.sum(np.square(g, dtype=np.float64)))) for g in gradient]
    difference_norms: List[float] = []

    def first_gradient_seen(reference_gradient):
        # a leaf at a time back onto the device, beside the reference's
        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        for ours, theirs in zip(gradient, jax.tree.leaves(reference_gradient)):
            difference_norms.append(float(norm(jax.device_put(ours, theirs.sharding), theirs)))
        gradient.clear()

    tokens = [jnp.asarray(b["tokens"]) for b in batches]
    total = int(tokens[0].shape[0] * (tokens[0].shape[1] - 1))
    reference = train_ref.follow(
        program["seeded_params"], tokens, conf["trainer"], first_gradient_seen=first_gradient_seen,
        **model_config.adapter(conf).reference_steps(conf, total))
    names = program["leaf_names"]
    floor = statistics.median(reference["first_gradient_norms"])
    differences = [d / max(r, floor)
                   for d, r in zip(difference_norms, reference["first_gradient_norms"])]
    norm_gaps = leaf_gaps(gradient_norms, reference["first_gradient_norms"])
    changes = leaf_gaps(program["change_norms"], reference["change_norms"])
    worst = lambda values: max(range(len(values)), key=values.__getitem__)     # noqa: E731
    readings = {"first_loss_repeat_gap": abs(program["losses"][0] - system.loss_first)}
    for i, (got, ref) in enumerate(zip(program["losses"], reference["losses"])):
        readings[f"loss_step{i + 1}_gap"] = abs(got - ref)
    readings["first_gradient_worst_leaf_difference"] = max(differences)
    readings["change_worst_leaf_gap"] = max(changes)
    return {"readings": readings, "uncompared": {
        "program_losses": program["losses"], "reference_losses": reference["losses"],
        "first_gradient_worst_leaf": names[worst(differences)],
        "first_gradient_median_leaf_difference": statistics.median(differences),
        "first_gradient_worst_leaf_norm_gap": max(norm_gaps),
        "change_worst_leaf": names[worst(changes)],
        "change_median_leaf_gap": statistics.median(changes)}}


def train_limits(conf: Dict[str, Any]) -> Dict[str, float]:
    probe = conf["probe"]
    limits = {"first_loss_repeat_gap": FIRST_LOSS_REPEAT_GAP,
              "first_gradient_worst_leaf_difference": float(probe["first_gradient_difference"]),
              "change_worst_leaf_gap": float(probe["change_gap"])}
    limits.update({f"loss_step{i + 1}_gap": float(probe["loss_gap"]) for i in range(FOLLOWED)})
    return limits


def train_correct(system, conf: Dict[str, Any], ctx: Dict[str, Any]) -> List[str]:
    """A loss that fell over the window, and the step's first FOLLOWED steps
    against the float32 reference's (`train_readings`), each number under
    its limit. Runs after the window has closed, the peak has been read and
    the measured trainer is gone."""
    import numpy as np

    problems: List[str] = []
    first, last = system.loss_first, ctx["window"]["loss_last"]
    if not (np.isfinite(first) and np.isfinite(last)):
        problems.append(f"loss not finite: first {first}, last {last}")
    elif not last < first:
        problems.append(f"loss did not fall on the seeded data: first {first}, last {last}")
    found, limits = train_readings(system, conf), train_limits(conf)
    for name, value in found["readings"].items():
        if not value <= limits[name]:
            problems.append(f"first {FOLLOWED} steps, program against reference: "
                            f"{name} {value:.6g} > {limits[name]}")
    ctx["checks"] = {"loss_first": first, "loss_last": last, **found["uncompared"],
                     **{name: {"value": value, "limit": limits[name]}
                        for name, value in found["readings"].items()}}
    return problems
