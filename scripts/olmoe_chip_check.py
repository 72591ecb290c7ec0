"""OLMoE-1B-7B on the chip, outside any timed window (ISSUE 27, part 7).

    python -m scripts.olmoe_chip_check gmm      # grouped matmul: Pallas against ragged_dot
    python -m scripts.olmoe_chip_check sharded  # four chips: the layer per shard against one device

`gmm` times the forward and both backward products of one expert
projection at the cell's shapes (131,072 routed rows, 64 experts, 2048 x
1024) under each implementation, seeded multinomial group sizes. (The
`logits` and `loss` phases went in PR 33: every run of the
`train-olmoe-64e-4k` cell compares more, its first two steps against the
float32 reference, `benchmark/check.train_correct`.) `sharded` (chiprun
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


def _cell_config():
    from benchmark import model_config
    from benchmark.harness import BENCH_DIR

    return model_config.transformer_config(model_config.load_config(
        os.path.join(BENCH_DIR, "configs", "olmoe-1b-7b-train-1chip.json")))


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
    phases = {"gmm": gmm, "sharded": sharded}
    for name in argv or ["gmm"]:
        phases[name]()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
