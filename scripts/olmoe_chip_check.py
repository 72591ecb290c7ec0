"""OLMoE-1B-7B on the chip, outside any timed window (ISSUE 27, part 7).

    python -m scripts.olmoe_chip_check gmm      # grouped matmul: Pallas against ragged_dot
    python -m scripts.olmoe_chip_check logits   # the system's forward against the plain reference
    python -m scripts.olmoe_chip_check loss     # what the cell's `correct` compares, and a float8 reading of it
    python -m scripts.olmoe_chip_check sharded  # four chips: the layer per shard against one device

`gmm` times the forward and both backward products of one expert
projection at the cell's shapes (131,072 routed rows, 64 experts, 2048 x
1024) under each implementation, seeded multinomial group sizes. `logits`
runs the system's forward at the published widths on two seeded
4,096-token sequences against benchmark/reference/olmoe_ref.py. `loss`
computes the first-step cross entropy of one seeded 4,096-token sequence
as the cell's check does (system against reference, limit `TRAIN_LOSS_ABS`)
and again with the system's weights rounded to float8. `sharded` (chiprun
--chips 4) runs LMTrainer at the cell's configuration on fsdp=2 x tp=2 and
dp=2 x fsdp=2, where every chip runs the `moe_gmm_*` kernels on its own
rows, against the same weights on one device.

One JSON line per measurement, with the device it ran on. Seconds are
information about this run, not the benchmark's.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROWS, EXPERTS, D_MODEL, D_EXPERT = 131072, 64, 2048, 1024
# The limits of `logits`, each from two readings on the chip (my chip runs, PR
# 27, three seeds; PERF.md section 6): what bfloat16 compute gives, and what
# the same forward gives with its weights rounded to float8_e4m3 (the nearest
# precision below the configuration's), which has to fall outside.
# Where system and reference choose the same experts for a token, its logits
# measured 1.43% to 1.67% relative RMS off (attention 0.7%, the three chained
# expert matmuls with bfloat16 between them the rest). Over all tokens 3.8% to
# 4.1%: 0.50% to 0.56% of the (token, choice) pairs fall on another expert
# (bfloat16 inputs flip near-ties between the 8th and 9th probability), and
# a token with one expert swapped is ~50% off in its expert sum. The share of
# equal choices is reported, not required.
LOGITS_REL_RMS = 0.06
AGREE_REL_RMS = 0.03


def _device():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def _emit(**record):
    print(json.dumps(dict(record, device=_device())), flush=True)


def _time(fn, *args, repeats=5):
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def gmm() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.moe import dropless_layout
    from ray_tpu.ops.grouped_matmul import gmm_tile_rows, grouped_matmul

    rng = np.random.default_rng(0)
    experts = jnp.asarray(rng.integers(0, EXPERTS, size=(ROWS // 8, 8)), jnp.int32)
    key = jax.random.PRNGKey(0)
    flops = 3 * 2.0 * ROWS * D_MODEL * D_EXPERT
    for (k, n) in ((D_MODEL, D_EXPERT), (D_EXPERT, D_MODEL)):
        rhs = (0.02 * jax.random.normal(key, (EXPERTS, k, n), jnp.float32)).astype(jnp.bfloat16)
        for impl in ("xla", "pallas"):
            tile = gmm_tile_rows(impl)
            layout = dropless_layout(experts, EXPERTS, tile)
            lhs = jax.random.normal(key, (layout.slot_row.shape[0], k), jnp.bfloat16)

            def loss(lhs, rhs, impl=impl, tile=tile, sizes=layout.padded_sizes):
                out = grouped_matmul(lhs, rhs, sizes, tile_rows=tile, implementation=impl)
                return jnp.sum(out.astype(jnp.float32) ** 2)

            fwd = jax.jit(lambda l, r, impl=impl, tile=tile, sizes=layout.padded_sizes:
                          grouped_matmul(l, r, sizes, tile_rows=tile, implementation=impl))
            both = jax.jit(jax.grad(loss, argnums=(0, 1)))
            t_fwd, t_all = _time(fwd, lhs, rhs), _time(both, lhs, rhs)
            _emit(phase="gmm", impl=impl, k=k, n=n, rows=int(lhs.shape[0]),
                  fwd_ms=1e3 * t_fwd, fwd_bwd_ms=1e3 * t_all,
                  fwd_bwd_tflops=flops / t_all / 1e12)


def _system_choices(params, tokens, config):
    """The experts the system's router chooses, layer by layer, in the
    system's own arithmetic (its attention sublayer, its norm, its float32
    router)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import moe_mlp_sublayer
    from ray_tpu.models.transformer import _norm, attention_sublayer
    from ray_tpu.ops import rope_frequencies

    c = config
    x = params["wte"].astype(c.dtype)[tokens]
    rope = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    chosen = []
    for layer in range(c.n_layers):
        lp = jax.tree.map(lambda w: w[layer], params["blocks"])
        x = attention_sublayer(x, lp, c, rope, None)
        h = _norm(x, lp["ln2_scale"], None, c.norm, c.norm_eps)
        probs = jax.nn.softmax(jnp.einsum(
            "bsm,me->bse", h.astype(jnp.float32), lp["router"].astype(jnp.float32)), -1)
        chosen.append(jax.lax.top_k(probs, c.top_k)[1])
        x, _, _ = moe_mlp_sublayer(x, lp, c)
    return jnp.stack(chosen)


def _cell_config():
    from benchmark import model_config
    from benchmark.harness import BENCH_DIR

    return model_config.transformer_config(model_config.load_config(
        os.path.join(BENCH_DIR, "configs", "olmoe-1b-7b-train-1chip.json")))


def logits(seeds=(27, 28, 29)) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import olmoe_ref
    from ray_tpu.models import model_family, moe
    from ray_tpu.models.transformer import lm_head_weights

    mc = _cell_config()
    init = jax.jit(lambda key: model_family(mc).init_params(mc, key))
    forward = jax.jit(lambda p, t: jnp.einsum(
        "bse,ev->bsv", moe.forward_hidden(p, t, mc)[0], lm_head_weights(p, mc)))
    system_choices = jax.jit(lambda p, t: _system_choices(p, t, mc))
    arch = dict(top_k=mc.top_k, norm_topk_prob=mc.norm_topk_prob,
                rope_theta=mc.rope_theta, norm_eps=mc.norm_eps)
    failed = []
    for seed in seeds:
        params = init(jax.random.PRNGKey(seed))
        tokens = jnp.asarray(np.random.default_rng(seed).integers(
            0, mc.vocab_size, size=(2, mc.max_seq)), jnp.int32)
        system = forward(params, tokens).astype(jnp.float32)
        choices = system_choices(params, tokens)
        err = ref_sq = err_agree = ref_sq_agree = same = 0.0
        for row in range(tokens.shape[0]):      # a row at a time: its float32 scores are 1 GiB
            ref, _, ref_choices = olmoe_ref.forward(params, tokens[row: row + 1], **arch)
            # (token, choice) pairs on which both choose the same expert, order apart
            equal = jnp.any(choices[:, row, :, :, None] == ref_choices[:, 0, :, None, :], axis=-1)
            same += float(jnp.sum(equal))
            agree = jnp.all(equal, axis=(0, 2))     # tokens whose every choice is the reference's
            sq, ref_sq_row = jnp.sum((system[row] - ref[0]) ** 2, -1), jnp.sum(ref[0] ** 2, -1)
            err, ref_sq = err + float(jnp.sum(sq)), ref_sq + float(jnp.sum(ref_sq_row))
            err_agree += float(jnp.sum(jnp.where(agree, sq, 0.0)))
            ref_sq_agree += float(jnp.sum(jnp.where(agree, ref_sq_row, 0.0)))
        rel_rms, rel_rms_agree = (err / ref_sq) ** 0.5, (err_agree / ref_sq_agree) ** 0.5
        _emit(phase="logits", seed=seed, tokens=list(tokens.shape), logits_rel_rms=rel_rms,
              logits_rel_rms_where_choices_agree=rel_rms_agree,
              tolerance=LOGITS_REL_RMS, tolerance_where_choices_agree=AGREE_REL_RMS,
              equal_expert_choices_share=same / (mc.n_layers * tokens.size * mc.top_k),
              moe_plan=moe.moe_plan(mc, tokens.size))
        if not (rel_rms <= LOGITS_REL_RMS and rel_rms_agree <= AGREE_REL_RMS):
            failed.append(seed)
        if seed == seeds[0]:
            # the other reading: weights through float8_e4m3 must be outside the limit
            float8 = jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), params)
            low = forward(float8, tokens[:1]).astype(jnp.float32)
            ref = olmoe_ref.forward_logits(params, tokens[:1], **arch)
            low_rms = float(jnp.sqrt(jnp.sum((low - ref) ** 2) / jnp.sum(ref ** 2)))
            _emit(phase="logits.float8_weights", seed=seed, logits_rel_rms=low_rms,
                  tolerance=LOGITS_REL_RMS)
            if low_rms <= LOGITS_REL_RMS:
                failed.append(f"{seed}: float8 weights pass the limit")
    if failed:
        raise SystemExit(f"logits further from the reference than the limits for seeds {failed}")


def loss(seed=31) -> None:
    """The comparison that decides the cell's `correct`, on one sequence:
    the system's cross entropy at initial weights (chunked head, as the
    cell runs it) against the reference's, and the same with the system's
    weights through float8_e4m3. Reported, so that PERF.md can say how
    much of the expert layer a first-step loss sees."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.check import TRAIN_LOSS_ABS
    from benchmark.reference import olmoe_ref
    from ray_tpu.models import model_family
    from ray_tpu.train.lm import lm_loss

    mc = _cell_config()
    params = jax.jit(lambda key: model_family(mc).init_params(mc, key))(jax.random.PRNGKey(seed))
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, mc.vocab_size, size=(1, mc.max_seq + 1)), jnp.int32)
    system = jax.jit(lambda p: lm_loss(p, tokens, mc, chunk=min(512, mc.max_seq))[1]["loss"])
    reference = olmoe_ref.loss(params, tokens, rows_at_a_time=1, top_k=mc.top_k,
                               norm_topk_prob=mc.norm_topk_prob, rope_theta=mc.rope_theta,
                               norm_eps=mc.norm_eps)
    float8 = jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), params)
    _emit(phase="loss", seed=seed, tokens=list(tokens.shape), reference_loss=float(reference),
          system_loss=float(system(params)), system_loss_float8_weights=float(system(float8)),
          limit=TRAIN_LOSS_ABS)


def sharded(seed=33, steps=8) -> None:
    import itertools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import model_family
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train.lm import default_optimizer, lm_loss
    from ray_tpu.train.trainer import LMTrainer

    mc = _cell_config()
    tokens = np.random.default_rng(seed).integers(
        0, mc.vocab_size, size=(4, mc.max_seq + 1), dtype=np.int32)
    params = jax.jit(lambda key: model_family(mc).init_params(mc, key))(jax.random.PRNGKey(seed))
    one = jax.jit(lambda p, t: lm_loss(p, t, mc, chunk=min(512, mc.max_seq))[1])
    want = {k: float(v) for k, v in one(params, jnp.asarray(tokens)).items()}
    del params
    tol = 2e-2  # bfloat16 compute, loss near ln(vocab): three digits agree (chip_smoke's limit)
    failed = []
    for spec in (MeshSpec(fsdp=2, tp=2), MeshSpec(dp=2, fsdp=2)):
        trainer = LMTrainer(mc, mesh_spec=spec, seed=seed,
                            optimizer=default_optimizer(1e-3, warmup_steps=2, total_steps=100))
        lowered = trainer.step_fn.lower(trainer.state, {"tokens": jnp.asarray(tokens)}).as_text()
        kernels = {name: lowered.count(name) for name in ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs")}
        batches = itertools.repeat({"tokens": tokens})
        first = trainer.train(batches, num_steps=1, report_every=1)
        second = trainer.train(batches, num_steps=2, report_every=2)
        jax.block_until_ready(trainer.state)
        t0 = time.perf_counter()
        last = trainer.train(batches, num_steps=steps, report_every=steps)
        jax.block_until_ready(trainer.state)
        seconds = time.perf_counter() - t0
        attrs = trainer._step_fn_span.to_dict()["attrs"]
        _emit(phase="sharded", mesh=spec.describe(), one_device=want, tolerance=tol,
              first_step={k: first[k] for k in ("loss", "router_aux_loss", "moe_load_max_over_mean")},
              third_step_loss=second["loss"], last_loss=last["loss"],
              step_ms=1e3 * seconds / steps, tokens_per_s=steps * tokens[:, 1:].size / seconds,
              kernels_in_step_program=kernels,
              plan={k: attrs.get(k) for k in ("moe_impl", "moe_gmm_tile_rows", "loss_chunk", "attention_impl")})
        ok = (abs(first["loss"] - want["loss"]) <= tol
              and abs(first["router_aux_loss"] - want["router_aux_loss"]) <= tol
              and second["loss"] < first["loss"] and attrs.get("moe_impl") == "gmm_pallas"
              and all(kernels.values()))
        if not ok:
            failed.append(spec.describe())
        del trainer
    if failed:
        raise SystemExit(f"the sharded step differs from one device on {failed}")


def main(argv) -> int:
    phases = {"gmm": gmm, "logits": logits, "loss": loss, "sharded": sharded}
    for name in argv or ["gmm", "logits"]:
        phases[name]()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
