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
TRAIN_LOSS_ABS = 0.005. The loss is a mean over 24,576 targets near
  ln(vocab), about 10.8: bfloat16 compute against the float32 reference
  differed by 0.00003 to 0.0006 over three seeds on the chip (gpt2-small,
  PR 24); ten times the worst leaves room for another seed, and a model
  computed in a lower precision, or with one term of the mathematics left
  out, moves the first loss by far more.
"""

from __future__ import annotations

from typing import Any, Dict, List

from . import model_config
from .traffic import rng_for, tokens as seeded_tokens

LOGITS_REL_RMS = 0.05
MARGIN = 0.5
TRAIN_LOSS_ABS = 0.005


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
    rel_rms = float(jnp.sqrt(jnp.mean((logits - ref) ** 2) / jnp.mean(ref ** 2)))
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


def train_correct(system, conf: Dict[str, Any], ctx: Dict[str, Any]) -> List[str]:
    """The first step's loss against the reference loss of the same seeded
    weights and batch, and a loss that fell."""
    import jax.numpy as jnp
    import numpy as np

    problems: List[str] = []
    first, last = system.loss_first, ctx["window"]["loss_last"]
    if not (np.isfinite(first) and np.isfinite(last)):
        problems.append(f"loss not finite: first {first}, last {last}")
    elif not last < first:
        problems.append(f"loss did not fall on the seeded data: first {first}, last {last}")
    params0 = system.initial_params()
    batch = jnp.asarray(system.batches.first["tokens"])
    ref_loss = model_config.adapter(conf).reference_loss(params0, batch, conf)
    if not abs(first - ref_loss) <= TRAIN_LOSS_ABS:
        problems.append(f"first-step loss {first:.5f} vs reference {ref_loss:.5f}: "
                        f"differ by more than {TRAIN_LOSS_ABS}")
    ctx["checks"] = {"loss_first": first, "loss_last": last, "reference_loss_first": ref_loss}
    return problems
