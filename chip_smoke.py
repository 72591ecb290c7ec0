"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

One process drives the main paths once, through the entry points a user
calls, at the full width of the models the repo advertises, and checks what
comes out. It is the only process that touches JAX (runtime tasks, serve
replicas and engine loops are threads of it), so it owns the chip.

Default (one chip), one JSON line per phase:

1. runtime  `ray_tpu.init()` sees the chip; a `num_tpus=1` task reduces on it.
2. train    `LMTrainer` on gpt2-small as the `train-gpt2s` cell configures it (bf16,
            batch 24 x seq 1024, scan_unroll=12): finite, falling loss;
            the attention kernels named in the step's program.
3. serve    `serve.run(build_llm_app(llama3-8b widths, paged=True))` at the
            deepest cut that fits the chip; mixed-length traffic, one
            request streamed; the ragged Pallas kernel in the tick program;
            greedy tokens equal to the gather reference on the same chip.
4. store    which object-store tier is live; the native arena built from
            native/objstore.cc (the .so is not in git) round-trips an array.

`--chips 4` runs only what exists across chips and what each is compared
with: a sharded train step on two real 4-device meshes against the
one-device loss, and tensor_parallel=4 serving against tensor_parallel=1
(tokens equal in float32; in bfloat16 the fork point is reported).

The last line is `{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}`. Without a TPU, with the wrong chip count, or when any phase
fails, it is `{"ok": false, ...}` and the exit code is not 0. Seconds
printed by the phases are information about this run, not a benchmark.
"""

from __future__ import annotations

import argparse
import collections
import gc
import itertools
import json
import re
import shutil
import sys
import time
import traceback

SEED = 0
# Pallas kernels carry stable names (pallas_call name=...); a lowered
# program lists the ones it contains.
_KERNEL_NAME = re.compile(r'kernel_name = "(\w+)"')
TRAIN_KERNELS = {
    "xla": set(),
    "pallas": {"flash_fwd", "flash_bwd_dkv_dq"},
}
RAGGED_KERNEL_NAME = "ragged_paged_attention"
GIB = float(1 << 30)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def device_record() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def settle() -> dict:
    """Collect garbage until device 0's bytes_in_use stops falling, and
    return its allocator counters ({} where the backend has none). Threads
    a phase stopped release what they hold a moment after teardown returns."""
    import jax

    device = jax.devices()[0]
    in_use = None
    for _ in range(30):
        gc.collect()
        stats = device.memory_stats() or {}
        if stats.get("bytes_in_use") == in_use:
            break
        in_use = stats.get("bytes_in_use")
        time.sleep(0.1)
    return stats


def memory_record() -> dict:
    stats = settle()
    return {
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use_after_phase": stats.get("bytes_in_use"),
    }


def kernels_in(lowered_text: str) -> dict:
    return dict(collections.Counter(_KERNEL_NAME.findall(lowered_text)))


class CompileCacheCounter:
    """Persistent-cache hits and misses, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def since(self, mark: tuple) -> dict:
        return {"hits": self.hits - mark[0], "misses": self.misses - mark[1]}

    def mark(self) -> tuple:
        return (self.hits, self.misses)


def seeded_tokens(n: int, vocab: int, seed: int) -> list:
    import numpy as np

    return [int(t) for t in np.random.default_rng(seed).integers(1, vocab, size=n)]


# ------------------------------------------------------------------ runtime


def phase_runtime(n_chips: int) -> dict:
    """`ray_tpu.init()` with no arguments must report the chips JAX holds,
    and a task that reserves one must compute on it. n_chips=0 (the CPU
    tests) runs the same task with no TPU reserved."""
    import ray_tpu

    ray_tpu.init()
    try:
        resources = ray_tpu.cluster_resources()
        check(
            resources.get("TPU", 0.0) == float(n_chips),
            f"cluster_resources() TPU={resources.get('TPU')} but JAX holds "
            f"{n_chips} chip(s): {resources}",
        )

        @ray_tpu.remote(num_tpus=min(1, n_chips))
        def reduce_on_device(n: int):
            import jax
            import jax.numpy as jnp

            x = jnp.arange(n, dtype=jnp.float32)
            return float(jnp.sum(x * x)), x.devices().pop().platform

        n = 1000
        value, platform = ray_tpu.get(reduce_on_device.remote(n), timeout=300)
        expect = (n - 1) * n * (2 * n - 1) / 6.0
        check(abs(value - expect) <= 1e-6 * expect,
              f"device reduction {value} != {expect}")
        return {
            "cluster_resources": {k: v for k, v in resources.items()
                                  if k.startswith(("TPU", "CPU"))},
            "task_platform": platform,
            "task_value": value,
        }
    finally:
        ray_tpu.shutdown()


# -------------------------------------------------------------------- train


def resolved_attention(config, seq: int, expect_impl: str) -> dict:
    """The attention implementation a train step resolves to at `seq`,
    which must be `expect_impl`, and how far its sub-tile walk engages."""
    from ray_tpu.ops.attention import attention_plan

    plan = attention_plan(seq, causal=config.causal,
                          implementation=config.attn_impl)
    check(plan["attention_impl"] == expect_impl,
          f"attention resolved to {plan['attention_impl']!r}, the phase names "
          f"{expect_impl!r}")
    return plan


def phase_train(config, *, batch: int, seq: int, steps: int, expect_impl: str,
                cache: CompileCacheCounter) -> dict:
    """LMTrainer on a repeated seeded batch: the first step (compile), then
    `steps` more. Loss must be finite and fall; the attention
    implementation the step resolved to must be `expect_impl` and its
    kernels must be the ones in the step's lowered program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.train.trainer import LMTrainer

    plan = resolved_attention(config, seq, expect_impl)
    impl = plan["attention_impl"]
    trainer = LMTrainer(config, learning_rate=3e-4, total_steps=1000, seed=SEED)
    tokens = np.random.default_rng(SEED).integers(
        0, config.vocab_size, size=(batch, seq + 1), dtype=np.int32
    )
    kernels = kernels_in(
        trainer.step_fn.lower(trainer.state, {"tokens": jnp.asarray(tokens)})
        .as_text()
    )
    check(set(kernels) == TRAIN_KERNELS[impl],
          f"step program holds kernels {kernels}, expected "
          f"{sorted(TRAIN_KERNELS[impl])} for {impl!r}")

    def run(n: int) -> tuple:
        t0 = time.perf_counter()
        metrics = trainer.train(
            itertools.repeat({"tokens": tokens}), num_steps=n, report_every=n
        )
        jax.block_until_ready(trainer.state)
        return metrics, time.perf_counter() - t0

    mark = cache.mark()
    first, first_s = run(1)
    first_cache = cache.since(mark)
    last, rest_s = run(steps)
    losses = [float(first["loss"]), float(last["loss"])]
    check(all(np.isfinite(losses)), f"loss not finite: {losses}")
    check(losses[1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    info = {
        "model_params": trainer.num_params,
        "batch": batch,
        "seq": seq,
        **plan,  # attention_impl and how far its sub-tile walk engages
        "kernels_in_step_program": kernels,
        "loss_first": losses[0],
        "loss_last": losses[1],
        "steps": 1 + steps,
        # information, not metrics: the first call holds the step's compile
        # and the report path's cost-analysis compile
        "first_step_seconds_incl_compile": round(first_s, 3),
        "steady_step_seconds": round(rest_s / steps, 4),
        "compile_cache_first_step": first_cache,
        "train_step_compile_was_cache_hit": (
            first_cache["hits"] > 0 and first_cache["misses"] == 0
        ),
    }
    if "mfu" in last:
        info["mfu_from_cost_analysis"] = round(float(last["mfu"]), 4)
    del trainer
    return info


# -------------------------------------------------------------------- serve


def serve_depth(config, paged, hbm_bytes: int) -> tuple:
    """The deepest cut of `config` (a multiple of 4 layers) whose serving
    state fits one chip: weights + the page pool (both sized by the
    model's own init functions, abstractly) + the tick programs' scratch
    (about 1 GiB in the compiled programs' memory_analysis at these
    widths), inside 80% of the device's memory — the rest is headroom for
    the allocator and for the transient of building the weights. Returns
    (n_layers, the arithmetic in GiB)."""
    import jax

    from ray_tpu.models import init_params
    from ray_tpu.serve.llm.paged import init_paged_cache

    def state_bytes(n_layers: int) -> tuple:
        cut = config.replace(n_layers=n_layers)
        trees = (
            jax.eval_shape(lambda: init_params(cut, jax.random.PRNGKey(0))),
            jax.eval_shape(lambda: init_paged_cache(cut, paged)),
        )
        return tuple(
            sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))
            for t in trees
        )

    (w1, p1), (w2, p2) = state_bytes(1), state_bytes(2)
    per_layer, pool_per_layer = w2 - w1, p2 - p1
    fixed = w1 - per_layer  # embedding, head, final norm
    budget = 0.8 * hbm_bytes
    scratch = 1 * GIB

    def need(n: int) -> float:
        return fixed + n * (per_layer + pool_per_layer) + scratch

    fits = [n for n in range(4, config.n_layers + 1, 4) if need(n) <= budget]
    check(bool(fits), f"not even 4 layers fit in {hbm_bytes / GIB:.1f} GiB")
    depth = fits[-1]
    return depth, {
        "hbm_gib": round(hbm_bytes / GIB, 2),
        "budget_gib": round(budget / GIB, 2),
        "embed_and_head_gib": round(fixed / GIB, 2),
        "weights_per_layer_gib": round(per_layer / GIB, 3),
        "pool_per_layer_gib": round(pool_per_layer / GIB, 3),
        "tick_scratch_gib": 1.0,
        "need_at_depth_gib": round(need(depth) / GIB, 2),
        "need_at_full_depth_gib": round(need(config.n_layers) / GIB, 2),
    }


def _payload(prompt: list, max_tokens: int) -> dict:
    return {"prompt_tokens": prompt, "max_tokens": max_tokens, "temperature": 0.0}


def _valid(tokens: list, max_tokens: int, vocab: int, what: str) -> None:
    check(len(tokens) == max_tokens,
          f"{what}: {len(tokens)} tokens, asked {max_tokens}")
    check(all(isinstance(t, int) and 0 <= t < vocab for t in tokens),
          f"{what}: token outside the vocabulary: {tokens}")


def serve_once(config, params, *, name: str, max_slots: int,
               tensor_parallel: int = 1, ragged_kernel: bool = True,
               traffic: tuple = (), probe: tuple, max_tokens: int) -> dict:
    """Deploy one paged LLM server through `serve.run`, answer `traffic`
    (prompt lengths sent together, the first one streamed) and then
    `probe` (one prompt length, alone, so its programs and numerics do not
    depend on what shared its ticks), and tear everything down. Returns
    what the engine said about itself and the probe's greedy tokens."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.config import cfg
    from ray_tpu.serve.llm import build_llm_app
    from ray_tpu.util import state

    vocab = config.vocab_size
    cfg.set(serve_ragged_kernel=ragged_kernel)
    ray_tpu.init()
    try:
        t0 = time.perf_counter()
        handle = serve.run(build_llm_app(
            config, name=name, max_slots=max_slots, params=params,
            paged=True, tensor_parallel=tensor_parallel,
        ))
        info = {}
        if traffic:
            prompts = [seeded_tokens(n, vocab, SEED + 1 + i)
                       for i, n in enumerate(traffic)]
            stream = handle.options(stream=True).stream_generate.remote(
                _payload(prompts[0], max_tokens)
            )
            refs = [handle.generate.remote(_payload(p, max_tokens))
                    for p in prompts[1:]]
            items = [ray_tpu.get(r, timeout=900) for r in stream]
            streamed = [it["token"] for it in items[:-1]]
            check(items[-1].get("done") is True, f"stream ended with {items[-1]}")
            _valid(streamed, max_tokens, vocab, "streamed request")
            for n, ref in zip(traffic[1:], refs):
                out = ray_tpu.get(ref, timeout=900)
                _valid(out["tokens"], max_tokens, vocab, f"request of {n}")
                check(out["usage"]["prompt_tokens"] == n, f"usage {out['usage']}")
            info["traffic_prompt_lengths"] = list(traffic)
            info["traffic_seconds_incl_compile"] = round(
                time.perf_counter() - t0, 2
            )
        t1 = time.perf_counter()
        out = ray_tpu.get(
            handle.generate.remote(
                _payload(seeded_tokens(probe, vocab, SEED + 99), max_tokens)
            ),
            timeout=900,
        )
        _valid(out["tokens"], max_tokens, vocab, "probe request")
        info["probe_prompt_length"] = probe
        info["probe_tokens"] = out["tokens"]
        info["probe_seconds"] = round(time.perf_counter() - t1, 2)
        (engine,) = state.engine_snapshot().values()  # the replica's
        info["attention_impl"] = engine["attention_impl"]
        metrics = ray_tpu.get(handle.metrics.remote(), timeout=60)
        info["engine"] = {
            k: metrics[k] for k in (
                "generated_tokens", "prefill_chunks", "mixed_ticks",
                "mixed_ticks_with_decode", "decode_blocks", "page_stalls",
            )
        }
        n_requests = len(traffic) + 1
        check(metrics["generated_tokens"] == n_requests * max_tokens,
              f"engine counted {metrics['generated_tokens']} tokens for "
              f"{n_requests} requests of {max_tokens}")
        return info
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        cfg.reset()
        # the engine (and its page pool) goes when its threads have wound
        # down; the next server needs that memory
        deadline = time.perf_counter() + 30
        while state.engine_snapshot() and time.perf_counter() < deadline:
            gc.collect()
            time.sleep(0.1)
        settle()
        check(not state.engine_snapshot(),
              f"engine still alive after teardown: {set(state.engine_snapshot())}")


def tick_program_kernels(config, params, max_slots: int) -> dict:
    """Kernels in the mixed-tick program, lowered from the same module-level
    builder and shapes the engine jits (paged_engine.build_mixed_step)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.paged import init_paged_cache
    from ray_tpu.serve.llm.paged_engine import (
        PagedEngineConfig, build_mixed_step, mixed_block_q,
    )

    pc = PagedEngineConfig(max_slots=max_slots).paged
    cache = jax.eval_shape(lambda: init_paged_cache(config, pc))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    mixed = jax.jit(build_mixed_step(
        config, pc.page_size, block_q=mixed_block_q(pc.chunk_tokens)
    ))
    lowered = mixed.lower(
        params, cache, i32(1 + max_slots, pc.max_pages_per_slot),
        i32(1, pc.chunk_pages), i32(1, pc.chunk_tokens), i32(1), i32(1),
        i32(max_slots), i32(max_slots), i32(max_slots),
    )
    return kernels_in(lowered.as_text())


def phase_serve(config, *, max_slots: int, traffic: tuple, probe: int,
                max_tokens: int, expect_impl: str) -> dict:
    """The paged server at `config`: mixed-length traffic with one request
    streamed, then a probe request alone, whose greedy tokens must equal
    the same request through the gather reference (serve_ragged_kernel
    off) on the same device. With `expect_impl` the ragged kernel, the
    mixed-tick program must hold one kernel call per layer."""
    import jax

    from ray_tpu.models import init_params
    from ray_tpu.ops.ragged_paged_attention import (
        RAGGED_KERNEL, RAGGED_REFERENCE,
    )

    params = init_params(config, jax.random.PRNGKey(SEED))
    kernels = tick_program_kernels(config, params, max_slots)
    want = {RAGGED_KERNEL_NAME: config.n_layers} if expect_impl == RAGGED_KERNEL else {}
    check(kernels == want,
          f"mixed-tick program holds kernels {kernels}, expected {want}")
    served = serve_once(
        config, params, name="smoke-llm", max_slots=max_slots,
        traffic=traffic, probe=probe, max_tokens=max_tokens,
    )
    check(served["attention_impl"] == expect_impl,
          f"engine runs {served['attention_impl']!r}, the phase names "
          f"{expect_impl!r}")
    check(served["engine"]["mixed_ticks_with_decode"] >= 1,
          f"no tick held prefill chunks and decode lanes together: "
          f"{served['engine']}")
    reference = serve_once(
        config, params, name="smoke-llm-ref", max_slots=max_slots,
        ragged_kernel=False, probe=probe, max_tokens=max_tokens,
    )
    check(reference["attention_impl"] == RAGGED_REFERENCE,
          f"reference server runs {reference['attention_impl']!r}")
    check(served["probe_tokens"] == reference["probe_tokens"],
          f"greedy tokens differ: {served['attention_impl']} "
          f"{served['probe_tokens']} vs reference {reference['probe_tokens']}")
    return {
        "n_layers": config.n_layers,
        "d_model": config.d_model,
        "heads": [config.n_heads, config.kv_heads, config.head_dim],
        "vocab": config.vocab_size,
        "kernels_in_mixed_tick_program": kernels,
        "tokens_equal_to_gather_reference": True,
        **served,
        "reference_probe_seconds": reference["probe_seconds"],
    }


# -------------------------------------------------------------------- store


def phase_store() -> dict:
    """Which object-store tier a default runtime runs, and the native arena:
    built here from native/objstore.cc, it must hold and return an array.
    A machine without g++ is named as such; a build that fails, or an arena
    that was asked for and did not come up, fails the phase."""
    import numpy as np

    import ray_tpu
    from ray_tpu.core.config import cfg
    from ray_tpu.core.runtime import get_runtime

    ray_tpu.init()
    try:
        default_tier = get_runtime().object_store.large_object_tier
    finally:
        ray_tpu.shutdown()
    info = {"default_tier": default_tier,
            "native_store_flag_default": bool(cfg.native_store)}
    if shutil.which("g++") is None:
        check(default_tier == "python", "native tier live without a compiler?")
        info["native_arena"] = "unavailable: g++ is not installed on this machine"
        return info
    cfg.set(native_store=True)
    ray_tpu.init()
    try:
        store = get_runtime().object_store
        check(store.large_object_tier == "native_arena",
              f"native_store asked for, tier is {store.large_object_tier!r}")
        value = np.random.default_rng(SEED).standard_normal(1 << 18)  # 2 MiB
        back = ray_tpu.get(ray_tpu.put(value))
        check(np.array_equal(back, value), "array changed through the arena")
        check(store.stats["shm_puts"] >= 1, f"arena never used: {store.stats}")
        info["native_arena"] = "built from native/objstore.cc, round trip ok"
        info["shm_puts"] = store.stats["shm_puts"]
    finally:
        ray_tpu.shutdown()
        cfg.reset()
    return info


# ------------------------------------------------------------------ 4 chips


def phase_train_sharded(config, mesh_specs, *, batch: int, seq: int,
                        expect_impl: str) -> dict:
    """One sharded train state per mesh, three steps each on one seeded
    batch. The first step's loss (initial weights) must match the loss of
    the same weights on ONE device with plain XLA attention; the third
    step's loss (after real updates) must match across the meshes. The
    weights must really be split: per-device bytes are read from the
    arrays' shards and from the compiled step's memory_analysis()."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import forward, init_params
    from ray_tpu.ops import cross_entropy_loss
    from ray_tpu.train.lm import default_optimizer
    from ray_tpu.train.trainer import LMTrainer

    plan = resolved_attention(config, seq, expect_impl)
    tokens = np.random.default_rng(SEED).integers(
        0, config.vocab_size, size=(batch, seq + 1), dtype=np.int32
    )
    # ---- one device, plain reference attention, two sequences at a time
    ref_config = config.replace(attn_impl="xla")
    params = init_params(config, jax.random.PRNGKey(SEED))
    ref_loss_fn = jax.jit(lambda p, t: cross_entropy_loss(
        forward(p, t[:, :-1], ref_config), t[:, 1:])[0])
    ref_loss = float(np.mean([
        float(ref_loss_fn(params, jnp.asarray(tokens[i:i + 2])))
        for i in range(0, batch, 2)
    ]))
    total_param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    del params
    settle()

    meshes = {}
    for spec in mesh_specs:
        # warmup 2: the third step's loss has seen two real updates
        trainer = LMTrainer(
            config, mesh_spec=spec, seed=SEED,
            optimizer=default_optimizer(1e-3, warmup_steps=2, total_steps=100),
        )
        per_device = collections.Counter()
        for leaf in jax.tree.leaves(trainer.state.params):
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] += shard.data.nbytes
        ways = spec.fsdp * spec.tp  # dp replicates, fsdp and tp split
        check(len(per_device) == spec.num_devices,
              f"weights live on devices {sorted(per_device)} only")
        check(max(per_device.values()) <= 1.05 * total_param_bytes / ways,
              f"weights not split {ways} ways: {dict(per_device)} of "
              f"{total_param_bytes}")
        batch_arrays = {"tokens": jnp.asarray(tokens)}
        lowered = trainer.step_fn.lower(trainer.state, batch_arrays)
        kernels = kernels_in(lowered.as_text())
        check(set(kernels) == TRAIN_KERNELS[expect_impl],
              f"{spec.describe()}: step holds kernels {kernels}, expected "
              f"{sorted(TRAIN_KERNELS[expect_impl])}")
        compiled = lowered.compile()
        memory = compiled.memory_analysis()
        text = compiled.as_text()
        losses = []
        for _ in range(3):
            metrics = trainer.train(
                itertools.repeat({"tokens": tokens}), num_steps=1,
                report_every=1,
            )
            losses.append(float(metrics["loss"]))
        jax.block_until_ready(trainer.state)
        check(all(np.isfinite(losses)), f"{spec.describe()}: losses {losses}")
        meshes[spec.describe()] = {
            "losses": losses,
            "param_bytes_per_device": dict(sorted(per_device.items())),
            "param_split_ways": ways,
            "step_argument_gib_per_device": round(
                memory.argument_size_in_bytes / GIB, 2),
            "step_temp_gib_per_device": round(memory.temp_size_in_bytes / GIB, 2),
            "kernels_in_step_program": kernels,
            "collectives_in_compiled_step": {
                op: text.count(f" {op}(") for op in
                ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")
            },
        }
        del trainer, compiled, lowered
        settle()
    tol = 2e-2  # bf16 compute, loss near ln(vocab): three digits agree
    firsts = {name: m["losses"][0] for name, m in meshes.items()}
    thirds = [m["losses"][2] for m in meshes.values()]
    for name, loss in firsts.items():
        check(abs(loss - ref_loss) <= tol,
              f"{name}: sharded loss {loss} vs one-device {ref_loss}")
    check(max(thirds) - min(thirds) <= tol,
          f"loss after two updates differs across meshes: {thirds}")
    check(all(t < f for t, f in zip(thirds, firsts.values())),
          f"loss did not fall: first {firsts}, third {thirds}")
    return {
        "n_layers": config.n_layers,
        "batch": batch,
        "seq": seq,
        **plan,  # attention_impl and how far its sub-tile walk engages
        "total_param_bytes": total_param_bytes,
        "one_device_reference_loss": ref_loss,
        "tolerance": tol,
        "meshes": meshes,
    }


def phase_serve_tp(config, *, tp: int, max_slots: int, probe: int,
                   max_tokens: int, expect_impl: str,
                   require_equal: bool = True) -> dict:
    """tensor_parallel=tp paged serving (the ragged kernel under shard_map
    when `expect_impl` is the kernel) against tensor_parallel=1 with the
    same weights, on the probe request's greedy tokens.

    Equality is required where the two layouts compute the same sums:
    float32 activations, where only the order of f32 additions differs.
    With bfloat16 activations every shard rounds its partial wo/w_down
    product to bf16 BEFORE the all-reduce, so logits differ in the third
    digit and a greedy chain over random weights forks at the first near
    tie (seen on the chip in PR 22: token 1 equal, fork at token 2) —
    there (`require_equal=False`) the length of the common prefix is
    reported and both servers must still answer."""
    import jax

    from ray_tpu.models import init_params

    params = init_params(config, jax.random.PRNGKey(SEED))
    param_devices = sorted({d.id for x in jax.tree.leaves(params)
                            for d in x.devices()})
    one = serve_once(config, params, name="smoke-tp1", max_slots=max_slots,
                     probe=probe, max_tokens=max_tokens)
    many = serve_once(config, params, name=f"smoke-tp{tp}", max_slots=max_slots,
                      tensor_parallel=tp, probe=probe, max_tokens=max_tokens)
    for served in (one, many):
        check(served["attention_impl"] == expect_impl,
              f"engine runs {served['attention_impl']!r}, the phase names "
              f"{expect_impl!r}")
    common = 0
    for a, b in zip(one["probe_tokens"], many["probe_tokens"]):
        if a != b:
            break
        common += 1
    if require_equal:
        check(common == max_tokens,
              f"greedy tokens differ: tp=1 {one['probe_tokens']} vs tp={tp} "
              f"{many['probe_tokens']}")
    return {
        "n_layers": config.n_layers,
        "activation_dtype": jax.numpy.dtype(config.dtype).name,
        "tensor_parallel": tp,
        "attention_impl": expect_impl,
        "tokens_required_equal": require_equal,
        "common_prefix_with_tensor_parallel_1": common,
        "of_tokens": max_tokens,
        "probe_tokens": many["probe_tokens"],
        # placement as found: init_params puts the full weights on the
        # default device before the engine reshards them
        "init_params_devices": param_devices,
        "tp1_probe_seconds": one["probe_seconds"],
        f"tp{tp}_probe_seconds": many["probe_seconds"],
    }


# --------------------------------------------------------------------- main


def run_phase(name: str, fn, *args, **kwargs) -> bool:
    """Run one phase and print its line. A failure is printed, with its
    traceback on stderr, and makes the run fail: no phase vanishes."""
    t0 = time.perf_counter()
    try:
        info = fn(*args, **kwargs)
        ok = True
    except Exception as exc:  # noqa: BLE001 - reported and counted, never dropped
        traceback.print_exc()
        info = {"error": f"{type(exc).__name__}: {exc}"[:2000]}
        ok = False
    emit({"phase": name, "ok": ok, **info, **memory_record(),
          "phase_seconds": round(time.perf_counter() - t0, 2)})
    return ok


def one_chip_phases(cache: CompileCacheCounter) -> list:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import get_config
    from ray_tpu.ops.ragged_paged_attention import RAGGED_KERNEL
    from ray_tpu.serve.llm.paged_engine import PagedEngineConfig

    results = [run_phase("runtime", phase_runtime, 1)]
    # gpt2-small as benchmark/configs/gpt2-small-train-1chip.json configures it
    results.append(run_phase(
        "train", phase_train, get_config("gpt2-small").replace(scan_unroll=12),
        batch=24, seq=1024, steps=7, expect_impl="pallas", cache=cache,
    ))
    # llama3-8b at its published widths, bf16 weights; depth is the one cut
    llama = get_config("llama3-8b").replace(param_dtype=jnp.bfloat16)
    max_slots = 8
    hbm = jax.devices()[0].memory_stats()["bytes_limit"]
    depth, why = serve_depth(
        llama, PagedEngineConfig(max_slots=max_slots).paged, hbm
    )
    emit({"phase": "serve_depth", "ok": True, "n_layers": depth,
          "of": llama.n_layers, "why": why})
    results.append(run_phase(
        "serve", phase_serve, llama.replace(n_layers=depth),
        max_slots=max_slots, traffic=(40, 600, 200), probe=300,
        max_tokens=12, expect_impl=RAGGED_KERNEL,
    ))
    results.append(run_phase("store", phase_store))
    return results


def four_chip_phases() -> list:
    import jax.numpy as jnp

    from ray_tpu.models import get_config
    from ray_tpu.ops.ragged_paged_attention import RAGGED_KERNEL
    from ray_tpu.parallel import MeshSpec

    llama = get_config("llama3-8b")
    return [
        # f32 weights and AdamW state as the trainer keeps them: 2 layers
        # is what dp=2 x fsdp=2 (weights split two ways) holds per chip
        run_phase(
            "train_sharded", phase_train_sharded, llama.replace(n_layers=2),
            [MeshSpec(fsdp=2, tp=2), MeshSpec(dp=2, fsdp=2)],
            batch=8, seq=1024, expect_impl="pallas",
        ),
        # float32 activations: the layouts compute the same sums, the
        # tokens must be equal
        run_phase(
            "serve_tensor_parallel", phase_serve_tp,
            llama.replace(n_layers=4, dtype=jnp.float32),
            tp=4, max_slots=8, probe=300, max_tokens=12,
            expect_impl=RAGGED_KERNEL,
        ),
        # bfloat16 as served: must run; where the chains fork is reported
        run_phase(
            "serve_tensor_parallel_bf16", phase_serve_tp,
            llama.replace(n_layers=4, param_dtype=jnp.bfloat16),
            tp=4, max_slots=8, probe=300, max_tokens=12,
            expect_impl=RAGGED_KERNEL, require_equal=False,
        ),
    ]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): the four one-chip phases. 4: only the "
             "cross-chip paths and what each is compared with.",
    )
    args = parser.parse_args(argv)
    try:
        import jax

        from ray_tpu.core.compile_cache import ensure_compile_cache
    except ImportError as exc:
        emit({"ok": False, "error": f"cannot import the program: {exc}"})
        return 2
    cache_dir = ensure_compile_cache()
    device = device_record()
    if device["platform"] != "tpu" or device["count"] != args.chips:
        emit({"ok": False, "device": device,
              "error": f"needs {args.chips} TPU chip(s); JAX found "
                       f"{device['count']} {device['platform']} device(s)"})
        return 1
    emit({"phase": "start", "chips": args.chips, "device": device,
          "jax": jax.__version__, "compile_cache_dir": cache_dir})
    cache = CompileCacheCounter()
    results = one_chip_phases(cache) if args.chips == 1 else four_chip_phases()
    emit({"phase": "compile_cache", "dir": cache_dir,
          "hits": cache.hits, "misses": cache.misses})
    ok = all(results)
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
