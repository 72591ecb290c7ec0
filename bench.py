"""Headline benchmark: GPT-2 124M training tokens/sec on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} that
names the device it ran on (`platform`, `device_kind`, `device_count`).
It measures the chip and nothing else: without a TPU it exits non-zero
and prints no result, and a sub-benchmark that fails fails the run.

The reference publishes no absolute tokens/sec (BASELINE.md — scalability
envelope only), so vs_baseline is measured MFU / 0.40: the ratio of this
framework's model-flops utilization to a 40% MFU reference point, which is
strong torch-GPU-stack territory for this model class. >1.0 beats it.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

from ray_tpu.util.profiling import device_labels

BATCH = 24  # best of 16/24/32 in the pre-PR-1 chip rounds (PERF_NOTES; record deleted); 32 hit HBM pressure
SEQ = 1024
WARMUP_STEPS = 3
MEASURE_STEPS = 20


def ring_kernel_bench() -> dict:
    """Fused-Pallas vs einsum ring-attention LOCAL BLOCK on the real
    chip (the long-context kernel claim, runnable single-chip: the ring
    collective is free under XLA; the per-step kernel is what differs).
    N iterations are chained inside one jit so per-call dispatch does not
    enter the per-block time."""
    from ray_tpu.ops.attention import flash_attention_with_lse

    b, h, s, d = 4, 8, 2048, 128
    n_iters = 40
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16) for kk in keys)

    def einsum_block(q, k, v):
        s_ = jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) / (d ** 0.5)
        m = jnp.max(s_, axis=-1, keepdims=True)
        p = jnp.exp(s_ - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) / l

    def chained(block):
        def f(q, k, v):
            def body(_, qq):
                return block(qq, k, v).astype(jnp.bfloat16)
            return jnp.sum(
                jax.lax.fori_loop(0, n_iters, body, q).astype(jnp.float32)
            )
        return jax.jit(f)

    fused = chained(lambda q, k, v: flash_attention_with_lse(q, k, v)[0])
    ein = chained(einsum_block)

    def bench(fn):
        jax.block_until_ready(fn(q, k, v))  # compile
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, k, v))
        return (time.perf_counter() - t0) / n_iters * 1e3

    fused_ms, ein_ms = bench(fused), bench(ein)
    return {
        "ring_fused_block_ms": round(fused_ms, 3),
        "ring_einsum_block_ms": round(ein_ms, 3),
        "ring_fused_speedup": round(ein_ms / fused_ms, 2),
    }


def attn_kernel_bench() -> dict:
    """Per-layer flash-attention microbench at the bench model's exact
    attention shape (B24 H12 S1024 D64 causal bf16) — the kernel the round-5
    trace showed 5-6x off roofline. Chained inside one jit, like the ring
    bench. Reports the kernel `resolve_attention_impl` picks for the shape
    and its distance to the matmul roofline."""
    from ray_tpu.ops.attention import flash_attention, resolve_attention_impl
    from ray_tpu.util import profiling as prof

    b, h, s, d = BATCH, 12, SEQ, 64
    n_fwd, n_bwd = 20, 8
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16) for kk in keys)
    impl = resolve_attention_impl()

    def chain(n):
        def f(q, k, v):
            def body(_, qq):
                return flash_attention(qq, k, v, causal=True).astype(jnp.bfloat16)
            return jnp.sum(
                jax.lax.fori_loop(0, n, body, q).astype(jnp.float32)
            )
        return f

    fwd = jax.jit(chain(n_fwd))
    grad = jax.jit(jax.value_and_grad(chain(n_bwd), argnums=(0, 1, 2)))

    def bench(fn):
        jax.block_until_ready(fn(q, k, v))  # compile
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, k, v))
        return time.perf_counter() - t0

    fwd_ms = bench(fwd) / n_fwd * 1e3
    grad_s = bench(grad)
    bwd_ms = max(grad_s / n_bwd * 1e3 - fwd_ms, 0.0)

    # matmul roofline: causal fwd = 2*B*H*S^2*D flops (QK^T + PV, half the
    # square), bwd = 2.5x fwd (s recompute + dv/dp/dk/dq)
    peak = prof.device_peaks(jax.devices()[0])["peak_flops"]
    fwd_flops = 2.0 * b * h * s * s * d
    roofline_ms = (fwd_flops + 2.5 * fwd_flops) / peak * 1e3
    measured_ms = fwd_ms + bwd_ms
    return {
        "attn_impl": impl,
        "attn_fwd_ms": round(fwd_ms, 3),
        "attn_bwd_ms": round(bwd_ms, 3),
        "attn_roofline_fraction": round(roofline_ms / max(measured_ms, 1e-9), 4),
    }


def _dp_sync_fields(n_params: int, n_dp: int) -> dict:
    """The data-parallel sync mode + per-replica wire bytes the current
    config flags imply, tracked in the BENCH line every round (0 bytes on
    the single-chip bench; the multichip dryrun exercises the real path)."""
    from ray_tpu.core.config import cfg
    from ray_tpu.parallel.collectives import dp_sync_bytes

    explicit = (cfg.dp_shard_update or cfg.dp_allreduce_dtype == "int8") and n_dp > 1
    mode = (
        cfg.dp_allreduce_dtype + ("+shard_update" if cfg.dp_shard_update else "")
        if explicit else "xla_psum"
    )
    return {
        "dp_sync_mode": mode,
        "dp_sync_bytes": dp_sync_bytes(
            n_params, n_dp, mode=cfg.dp_allreduce_dtype,
            shard_update=cfg.dp_shard_update, block=cfg.dp_quant_block,
        ),
    }


def _collect_telemetry(step, state, batch, n_steps: int = 5) -> dict:
    """Per-step latency histogram + node stats riding along with the
    headline number, so BENCH_*.json rounds carry telemetry instead of
    a single scalar. Separately-synced steps (outside the throughput
    window — a per-step device sync would skew it)."""
    from ray_tpu.core.stats import sample_process_rss_bytes, sample_tpu_stats
    from ray_tpu.util.metrics import get_or_create_histogram, registry

    hist = get_or_create_histogram(
        "raytpu_bench_step_seconds",
        "Wall-clock duration of individually synced benchmark steps.",
        boundaries=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0),
    )
    durations = []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        durations.append(time.perf_counter() - t0)
        hist.observe(durations[-1])
    ((_, data),) = hist.collect()
    return {
        "step_seconds": {
            "mean": round(sum(durations) / len(durations), 5),
            "min": round(min(durations), 5),
            "max": round(max(durations), 5),
            "count": data["count"],
            "buckets": [[b, c] for b, c in data["buckets"]],
        },
        "node": {
            "rss_bytes": sample_process_rss_bytes(),
            "tpu": sample_tpu_stats(),
        },
        # the full exposition is greppable from the round artifacts
        "metrics_names": sorted(
            {line.split(" ", 3)[2]
             for line in registry().prometheus_text().splitlines()
             if line.startswith("# TYPE ")}
        ),
    }


def _goodput_block(acct) -> dict:
    """The BENCH JSON `goodput` block: bucket seconds + goodput fraction
    from the same accountant/gauges the train controller publishes
    (util/goodput) — wall-time attribution rides every round."""
    report = acct.report()
    return {
        "wall_time_s": report["wall_time_s"],
        "buckets": {
            b: s for b, s in report["buckets"].items() if s > 0.0
        },
        "goodput_s": report["goodput_s"],
        "goodput_fraction": report["goodput_fraction"],
    }


def step_forensics_overhead_bench() -> dict:
    """Recorder overhead A/B (the train-side mirror of bench_serve's
    forensics bench): the SAME LMTrainer loop on the tiny model with the
    step-phase recorder off, then on at the default sampling rate.
    Emits the tokens/s ratio — the acceptance bar is >= 0.98, i.e. the
    sampled `block_until_ready` syncs plus the mark ring cost under 2%
    of throughput."""
    import numpy as np

    from ray_tpu.core.config import cfg
    from ray_tpu.models import get_config
    from ray_tpu.train import steplog
    from ray_tpu.train.trainer import LMTrainer

    n_steps = 64
    b, s = 8, 128
    config = get_config("gpt2-tiny")
    trainer = LMTrainer(config, learning_rate=1e-3, total_steps=4 + 2 * n_steps)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, config.vocab_size, size=(b, s + 1), dtype=np.int32)

    def run(tag: str) -> float:
        t0 = time.perf_counter()
        trainer.train(({"tokens": tokens} for _ in range(n_steps)),
                      num_steps=n_steps, report_every=n_steps,
                      run_name=f"bench-forensics-{tag}")
        jax.block_until_ready(trainer.state)
        return n_steps * b * s / (time.perf_counter() - t0)

    # warm the step compile AND the report path's cost-analysis cache so
    # both timed sides pay neither
    trainer.train(({"tokens": tokens} for _ in range(4)), num_steps=4,
                  report_every=2, run_name="bench-forensics-warmup")
    steplog.log().clear()
    cfg.set(train_step_log=False)
    try:
        off_tps = run("off")
        cfg.set(train_step_log=True)  # default sampling rate
        sample_every = cfg.step_log_sample_every
        on_tps = run("on")
        stats = steplog.log().stats()
    finally:
        cfg.reset()
    ratio = on_tps / off_tps
    return {
        "metric": "train_step_forensics_tokens_per_s_ratio",
        "value": round(ratio, 4),
        "unit": "ratio",
        **device_labels(),
        "within_2pct": ratio >= 0.98,
        # host-loop rates of the tiny model on `platform` above: they feed
        # the ratio and are not device throughput
        "tiny_loop_tokens_per_s_recorder_off": round(off_tps, 1),
        "tiny_loop_tokens_per_s_recorder_on": round(on_tps, 1),
        "sample_every": sample_every,
        "steps_per_side": n_steps,
        "marks_recorded": stats["buffered_marks"],
        "steps_indexed": stats["indexed_steps"],
    }


def main() -> None:
    from ray_tpu.core.compile_cache import ensure_compile_cache
    from ray_tpu.models import count_params, get_config
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import create_train_state, default_optimizer, make_train_step
    from ray_tpu.util import profiling as prof
    from ray_tpu.util.goodput import GoodputAccountant

    labels = device_labels()
    if labels["platform"] != "tpu":
        # a tokens/s/chip taken on a CPU is not a slower measurement, it is
        # a different quantity: refuse instead of printing it
        sys.exit(
            f"bench.py measures a TPU chip; JAX found {labels} — no result "
            "printed (CPU rehearsals: pytest, or chip_smoke.py's phases "
            "through tests/test_chip_smoke.py)"
        )
    ensure_compile_cache()

    acct = GoodputAccountant("bench")
    acct.begin("init")

    # full layer-unroll measured fastest on-chip at this size (+15% over
    # scan: XLA fuses/overlaps across layer boundaries)
    config = get_config("gpt2-small").replace(scan_unroll=12)
    devices = jax.devices()
    mesh = build_mesh(MeshSpec(), devices=devices[:1])
    opt = default_optimizer(3e-4, total_steps=1000)
    state, shardings = create_train_state(config, opt, jax.random.PRNGKey(0), mesh)
    step = make_train_step(config, opt, mesh, state_shardings=shardings)
    n_params = count_params(state.params)

    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0, config.vocab_size
        )
    }

    acct.begin("compile")  # warmup = compile + first dispatches
    for _ in range(WARMUP_STEPS):
        state, metrics = step(state, batch)
    jax.block_until_ready(state)

    acct.begin("step_compute")
    t0 = time.perf_counter()
    for _ in range(MEASURE_STEPS):
        state, metrics = step(state, batch)
    jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0
    acct.finish()

    tokens_per_sec = MEASURE_STEPS * BATCH * SEQ / elapsed
    step_time_s = elapsed / MEASURE_STEPS
    # Cost-analysis accounting (util/profiling): the compiled step's own
    # FLOPs/bytes over the measured step time, priced against the chip's
    # published peaks. Must run BEFORE _collect_telemetry (which donates
    # `state` away). Any sub-benchmark that raises fails the run: a line
    # with a silently missing part reads as a measurement it is not.
    cost = prof.step_cost(step, state, batch)
    roof = prof.roofline(cost, step_time_s)
    mfu = roof["mfu"]
    print(
        json.dumps(
            {
                "metric": "gpt2_124m_train_tokens_per_sec_per_chip",
                "value": round(tokens_per_sec, 1),
                "unit": "tokens/s",
                "vs_baseline": round(mfu / 0.40, 3),
                **labels,
                # auditability: which peak the MFU attribution used
                "peak_flops": cost.peak_flops,
                "mfu": round(mfu, 4),
                "batch": BATCH,
                "seq": SEQ,
                "profiling": {
                    "source": "cost_analysis",
                    "mfu": round(mfu, 4),
                    "flops_per_step": cost.total_flops,
                    "flops_per_token": round(cost.total_flops / (BATCH * SEQ), 1),
                    "roofline": {
                        "compute": round(mfu, 4),
                        "hbm": round(roof["hbm_fraction"], 4),
                        "bound": roof["bound"],
                    },
                    "top_cost_buckets": [[k, v] for k, v in cost.top_buckets(5)],
                },
                "goodput": _goodput_block(acct),
                # the recorder-overhead A/B tracked next to the headline
                "step_forensics": step_forensics_overhead_bench(),
                "telemetry": _collect_telemetry(step, state, batch),
                **ring_kernel_bench(),
                **attn_kernel_bench(),
                **_dp_sync_fields(n_params, mesh.shape.get("dp", 1)),
            }
        )
    )


if __name__ == "__main__":
    if "--step-forensics-overhead" in sys.argv[1:]:
        # standalone recorder A/B (one BENCH JSON line): a ratio of two
        # host-loop rates, runnable on CPU and labelled with its platform
        print(json.dumps(step_forensics_overhead_bench()))
    else:
        main()
