"""Open-loop serve benchmark: streaming throughput under Poisson load.

The BASELINE.md serve north-star, upgraded from a closed burst to an
OPEN-LOOP harness: requests arrive on a Poisson clock whether or not the
engine has kept up (closed loops hide queueing collapse — a slow server
sees a slow client), every request streams, and the prompt mix models a
production chat fleet: a configurable fraction of requests share one of
a few long system prompts (the prefix-cache workload), the rest are
unique.

Two phases run on identical workloads — prefix cache OFF (baseline) then
ON — and ONE JSON line reports both: p50/p99 TTFT, p50 TPOT, tokens/s
per chip, and the prefix-cache hit rate. vs_baseline is the tokens/s
ratio ON/OFF: what page-level KV reuse buys at this shared-prefix mix.

Every result line names the device (`platform`, `device_kind`,
`device_count`). Rates and latencies are device metrics only on a TPU:
without one the CPU profile still runs (a rehearsal of control flow and
counts) and its line says so — no `value`, no `*_per_chip`, every reading
nested under `cpu_rehearsal_readings`. Nothing is written into the
checkout. The whole bench is ONE process: --chaos and --openai replicas
are threads of it, so the process that imports jax here is the only one
that opens the chip.

Optional chaos: --chaos runs the same open-loop workload through a
2-replica serve deployment and kills one replica actor mid-run — the
controller restarts it and the router fails requests over, so the drill
passes when every request still completes.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import jax
import numpy as np

TTFT_TARGET_S = 0.5

# Workload/engine defaults per backend. The CPU profile (smoke runs,
# CI) stretches the tiny model's rope table to 512 so a
# production-length shared system prompt fits, and uses single-page
# prefill chunks so a prompt spans many chunk launches — prefill cost
# then scales with the tokens actually computed (as it does on TPU,
# where FLOPs track real tokens) instead of being one fixed-shape
# launch that hides what the prefix cache skipped.
_PROFILES = {
    "tpu": dict(model="gpt2-small", requests=192, rate=24.0,
                prompt_len=192, max_tokens=48, system_len=128,
                page_size=64, chunk_pages=4, decode_block_steps=24,
                pages=512, max_seq=0, slots=8),
    "cpu": dict(model="llama-tiny", requests=64, rate=500.0,
                prompt_len=368, max_tokens=4, system_len=352,
                page_size=16, chunk_pages=1, decode_block_steps=2,
                pages=768, max_seq=512, slots=16),
}

# The speculative drill is decode-bound (speculation only pays during
# decode), so it flips the workload shape: short prompts, long
# generations, prefix cache off in every phase.
_SPEC_PROFILES = {
    "tpu": dict(model="gpt2-small", requests=64, rate=24.0,
                prompt_len=64, max_tokens=48, system_len=32,
                page_size=64, chunk_pages=2, decode_block_steps=8,
                pages=512, max_seq=0, slots=8),
    "cpu": dict(model="llama-tiny", requests=32, rate=200.0,
                prompt_len=48, max_tokens=24, system_len=32,
                page_size=16, chunk_pages=1, decode_block_steps=2,
                pages=256, max_seq=0, slots=8),
}


def _emit_result(payload: dict) -> None:
    """Print the ONE result line, labelled with the device it ran on. Off
    TPU the line is re-shaped so that no reading sits under a device-metric
    name (`value`, `*_per_chip`, `tok/s/chip`)."""
    from ray_tpu.util.profiling import device_labels

    labels = device_labels()
    if labels["platform"] == "tpu":
        print(json.dumps({**payload, **labels}))
        return
    print(json.dumps({
        "metric": "cpu_rehearsal",
        "of": payload["metric"],
        **labels,
        "note": "no TPU: control flow and counts only; the readings below "
                "are host-clock times of XLA's CPU backend on a toy "
                "profile, not device metrics",
        "cpu_rehearsal_readings": {
            k: v for k, v in payload.items() if k not in ("metric", "unit")
        },
    }))


def _resolve_profile(args) -> None:
    table = _SPEC_PROFILES if args.speculative else _PROFILES
    name = "tpu" if jax.default_backend() == "tpu" else "cpu"
    if name == "cpu":
        print("bench_serve: no TPU backend — running the CPU rehearsal "
              "profile; its result line carries no device metric",
              file=sys.stderr)
    for key, value in table[name].items():
        if getattr(args, key) is None:
            setattr(args, key, value)


def _clamp_to_model(args) -> None:
    """--chaos/--openai deploy engines that keep the model's own
    max_seq (no --max-seq override reaches them), so shrink the
    workload to fit when the profile's prompts would overflow."""
    from ray_tpu.models import get_config

    cap = get_config(args.model).max_seq
    if args.prompt_len + args.max_tokens > cap:
        args.prompt_len = cap - args.max_tokens
        args.system_len = min(args.system_len, args.prompt_len // 2)


def _percentile(xs, q):
    xs = sorted(xs)
    if not xs:
        return 0.0
    return xs[min(len(xs) - 1, int(len(xs) * q))]


def _build_workload(args, vocab: int):
    """Deterministic request list: (arrival_offset_s, prompt). A
    shared_frac slice reuses one of n_system long system prompts with a
    unique tail; the rest are fully unique prompts of the same length."""
    rng = np.random.default_rng(0)
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    arrivals = np.cumsum(gaps)
    systems = [
        [int(t) for t in rng.integers(1, vocab, size=args.system_len)]
        for _ in range(args.n_system)
    ]
    tail_len = args.prompt_len - args.system_len
    requests = []
    for i in range(args.requests):
        if rng.random() < args.shared_frac:
            system = systems[int(rng.integers(len(systems)))]
            prompt = list(system) + [
                int(t) for t in rng.integers(1, vocab, size=tail_len)
            ]
        else:
            prompt = [int(t) for t in rng.integers(1, vocab, size=args.prompt_len)]
        requests.append((float(arrivals[i]), prompt))
    return requests, systems


def _drain(stream, rec):
    """Collector: stream tokens, recording first/last token wall time and
    the token ids themselves (the speculative drill replays them as
    drafts and cross-checks spec-on output exactness)."""
    n = 0
    toks = rec["toks"] = []
    try:
        for tok in stream:
            now = time.perf_counter()
            if n == 0:
                rec["first"] = now
            rec["last"] = now
            toks.append(tok)
            n += 1
    except Exception as exc:  # noqa: BLE001 - report, don't kill the bench
        rec["error"] = repr(exc)
    rec["tokens"] = n
    rec["ttft_engine"] = stream.ttft_s


def _run_open_loop(args, config, params, mesh, prefix_cache: bool,
                   spec_tokens: int = 0, proposer=None):
    from ray_tpu.serve.llm.paged import PagedConfig
    from ray_tpu.serve.llm.paged_engine import PagedEngineConfig, PagedLLMEngine

    engine = PagedLLMEngine(
        config, params,
        PagedEngineConfig(
            max_slots=args.slots,
            decode_block_steps=args.decode_block_steps,
            speculative_tokens=spec_tokens,
            speculative_proposer=proposer,
            precompile=True,  # no XLA compile ever lands inside a request
            paged=PagedConfig(
                page_size=args.page_size, num_pages=args.pages,
                max_pages_per_slot=max(
                    8, -(-(args.prompt_len + args.max_tokens) // args.page_size)
                ),
                chunk_pages=args.chunk_pages, prefix_cache=prefix_cache,
            ),
        ),
        mesh=mesh,
    )
    requests, systems = _build_workload(args, config.vocab_size)
    try:
        # Warm outside the timed window: compile/launch paths AND (when
        # the cache is on) the shared system prompts — a production cache
        # is measured warm; cold-start misses are a separate axis.
        engine.generate(requests[0][1][: args.prompt_len], max_tokens=4)
        for system in systems:
            engine.generate(system, max_tokens=1)
        recs = [dict() for _ in requests]
        threads = []
        t0 = time.perf_counter()
        for (offset, prompt), rec in zip(requests, recs):
            now = time.perf_counter() - t0
            if offset > now:
                time.sleep(offset - now)
            rec["submitted"] = time.perf_counter()
            stream = engine.submit(prompt, max_tokens=args.max_tokens)
            t = threading.Thread(target=_drain, args=(stream, rec), daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=900)
        elapsed = time.perf_counter() - t0
        stats = engine.stats()
    finally:
        engine.shutdown()

    errors = [r for r in recs if "error" in r]
    assert not errors, f"{len(errors)} request(s) failed: {errors[0]['error']}"
    total_tokens = sum(r["tokens"] for r in recs)
    assert total_tokens == args.requests * args.max_tokens, "short generation"
    ttfts = [r["ttft_engine"] for r in recs if r["ttft_engine"] is not None]
    tpots = [
        (r["last"] - r["first"]) / (r["tokens"] - 1)
        for r in recs if r["tokens"] > 1
    ]
    return {
        "tokens_per_s": total_tokens / elapsed,
        "p50_ttft_s": _percentile(ttfts, 0.50),
        "p99_ttft_s": _percentile(ttfts, 0.99),
        "p50_tpot_s": _percentile(tpots, 0.50),
        "prefix_hit_rate": stats.get("prefix_cache_hit_rate", 0.0),
        "prefix_cache_pages": stats.get("prefix_cache_pages", 0.0),
        "mixed_ticks": stats.get("mixed_ticks", 0.0),
        "decode_steps": stats.get("decode_steps", 0.0),
        "decode_tokens": stats.get("decode_tokens", 0.0),
        "spec_proposed": stats.get("spec_proposed", 0.0),
        "spec_acceptance_rate": stats.get("spec_acceptance_rate", 0.0),
        "spec_rollback_pages": stats.get("spec_rollback_pages", 0.0),
        "outputs": [r["toks"] for r in recs],
        "elapsed_s": elapsed,
    }


class _RingProposer:
    """Adversarial drill proposer: drafts a +1 token ring the greedy
    chain almost never follows, pinning acceptance near zero so every
    verify round pays rejection + rollback (the speculation-can't-stall
    worst case)."""

    def __init__(self, vocab: int):
        self.vocab = vocab

    def propose(self, context, k):
        return [(context[-1] + 1 + i) % self.vocab for i in range(k)]


def bench_speculative(args, config, params, mesh) -> None:
    """Three phases on the IDENTICAL open-loop workload, prefix cache
    off throughout (speculation is the only variable):

    1. spec OFF — records every request's greedy output;
    2. spec ON, replay drill — a ReplayProposer drafts from phase 1's
       recorded outputs, pinning acceptance ~1 (the templated/agentic
       upper bound) and shrinking verify launches per generated token;
    3. spec ON, adversarial drill — always-wrong drafts, acceptance ~0:
       output must STILL be exact and decode must not stall.

    Both spec phases are cross-checked token-for-token against phase 1
    (exactness is part of the bench, not just the test suite)."""
    from ray_tpu.serve.llm.speculative import ReplayProposer

    base = _run_open_loop(args, config, params, mesh, prefix_cache=False)
    requests, _ = _build_workload(args, config.vocab_size)
    replay = ReplayProposer({
        tuple(prompt): toks
        for (_, prompt), toks in zip(requests, base["outputs"])
    })
    spec = _run_open_loop(
        args, config, params, mesh, prefix_cache=False,
        spec_tokens=args.spec_tokens, proposer=replay,
    )
    adv = _run_open_loop(
        args, config, params, mesh, prefix_cache=False,
        spec_tokens=args.spec_tokens,
        proposer=_RingProposer(config.vocab_size),
    )
    assert spec["outputs"] == base["outputs"], "replay drill diverged"
    assert adv["outputs"] == base["outputs"], "adversarial drill diverged"

    def launches_per_token(run):
        return run["decode_steps"] / max(1.0, run["decode_tokens"])

    launch_reduction = launches_per_token(base) / max(
        1e-9, launches_per_token(spec)
    )
    assert spec["spec_acceptance_rate"] >= 0.6, spec["spec_acceptance_rate"]
    assert launch_reduction >= 1.8, launch_reduction
    n_chips = max(1, args.tp)
    _emit_result({
        "metric": "serve_speculative_tokens_per_s_per_chip",
        "value": round(spec["tokens_per_s"] / n_chips, 1),
        "unit": "tok/s/chip",
        # speculation speedup at replay (high-acceptance) drafts
        "vs_baseline": round(
            spec["tokens_per_s"] / max(1e-9, base["tokens_per_s"]), 3
        ),
        "spec_tokens": args.spec_tokens,
        "acceptance_rate": round(spec["spec_acceptance_rate"], 3),
        "launches_per_token": round(launches_per_token(spec), 4),
        "baseline_launches_per_token": round(launches_per_token(base), 4),
        "launch_reduction": round(launch_reduction, 3),
        "p50_tpot_s": round(spec["p50_tpot_s"], 5),
        "baseline_p50_tpot_s": round(base["p50_tpot_s"], 5),
        "adversarial_acceptance_rate": round(adv["spec_acceptance_rate"], 3),
        "adversarial_p50_tpot_s": round(adv["p50_tpot_s"], 5),
        "adversarial_rollback_pages": adv["spec_rollback_pages"],
        "outputs_exact": True,
        "requests": args.requests,
        "arrival_rate_req_s": args.rate,
        "prompt_len": args.prompt_len,
        "max_tokens": args.max_tokens,
        "page_size": args.page_size,
        "tp": args.tp,
    })


def bench_forensics(args, config, params, mesh) -> None:
    """Request-recorder overhead A/B: the IDENTICAL open-loop workload
    with the forensics recorder OFF (baseline) then ON. The recorder is
    a deque append under a lock per phase mark — the acceptance bar is
    tokens/s with the recorder on within 2% of off."""
    from ray_tpu.core.config import cfg
    from ray_tpu.serve import reqlog

    cfg.set(serve_request_log=False)
    try:
        off = _run_open_loop(args, config, params, mesh, prefix_cache=True)
    finally:
        cfg.reset()
    reqlog.log().clear()
    cfg.set(serve_request_log=True)
    try:
        on = _run_open_loop(args, config, params, mesh, prefix_cache=True)
        recorder = reqlog.log().stats()
    finally:
        cfg.reset()
    ratio = on["tokens_per_s"] / max(1e-9, off["tokens_per_s"])
    _emit_result({
        "metric": "serve_forensics_recorder_tokens_per_s_ratio",
        "value": round(ratio, 4),
        "unit": "fraction",
        # overhead budget: recorder-on throughput within 2% of off
        "vs_baseline": round(ratio, 4),
        "within_2pct": ratio >= 0.98,
        "tokens_per_s_recorder_on": round(on["tokens_per_s"], 1),
        "tokens_per_s_recorder_off": round(off["tokens_per_s"], 1),
        "p99_ttft_s_recorder_on": round(on["p99_ttft_s"], 4),
        "p99_ttft_s_recorder_off": round(off["p99_ttft_s"], 4),
        "marks_recorded": recorder["seq"],
        "requests_indexed": recorder["indexed_requests"],
        "requests": args.requests,
        "arrival_rate_req_s": args.rate,
        "prompt_len": args.prompt_len,
        "max_tokens": args.max_tokens,
        "tp": args.tp,
    })


def _preemption_drill(config, params) -> dict:
    """Lane-preemption acceptance sub-drill: one slot, a low-priority
    long decode, then a high-priority arrival. The victim must be
    parked (trimmed to its emitted frontier), the preemptor served, and
    the victim resumed TOKEN-EXACT — with every page refcount restored
    once both streams drain (prefix-shared pages survive untouched)."""
    from ray_tpu.serve.llm.paged import PagedConfig
    from ray_tpu.serve.llm.paged_engine import PagedEngineConfig, PagedLLMEngine

    num_pages = 64
    engine = PagedLLMEngine(
        config, params,
        PagedEngineConfig(
            max_slots=1, decode_block_steps=2, precompile=True,
            paged=PagedConfig(page_size=8, num_pages=num_pages,
                              max_pages_per_slot=8, chunk_pages=2),
        ),
    )
    try:
        rng = np.random.default_rng(7)
        victim_prompt = [int(t) for t in
                         rng.integers(1, config.vocab_size, size=16)]
        high_prompt = [int(t) for t in
                       rng.integers(1, config.vocab_size, size=16)]
        # greedy reference on the same engine — also warms the prefix
        # cache so the victim's first pages are SHARED (the park must
        # only drop refcounts on them, never corrupt the cached KV)
        reference = engine.generate(victim_prompt, max_tokens=24)
        victim = engine.submit(victim_prompt, max_tokens=24,
                               tenant="free", priority=0)
        victim_iter = iter(victim)
        first = next(victim_iter)  # victim is decoding before the preemptor
        high = engine.submit(high_prompt, max_tokens=6,
                             tenant="paid", priority=1)
        high_tokens = high.result(timeout=300)
        victim_tokens = [first] + list(victim_iter)
        # cache hit over the shared prefix must still reproduce reference
        replay = engine.generate(victim_prompt, max_tokens=24)
        deadline = time.perf_counter() + 30
        restored = False
        while time.perf_counter() < deadline and not restored:
            stats = engine.stats()
            restored = (stats["pages_free"] + stats["prefix_cache_pages"]
                        == num_pages - 1)
            if not restored:
                time.sleep(0.05)
        stats = engine.stats()
        assert stats["lane_preemptions"] >= 1, "drill never preempted"
        assert victim_tokens == reference, "victim resume not token-exact"
        assert replay == reference, "prefix-shared pages corrupted"
        assert len(high_tokens) == 6, "preemptor starved"
        assert restored, f"page refcounts not restored: {stats}"
        return {
            "lane_preemptions": stats["lane_preemptions"],
            "lane_resumes": stats["lane_resumes"],
            "preempted_pages": stats["preempted_pages"],
            "token_exact_resume": True,
            "pages_restored": True,
        }
    finally:
        engine.shutdown()


def bench_multitenant(args) -> None:
    """Adversarial multi-tenant overload drill on ONE paged engine: a
    flooding low-priority 'free' tenant (token-bucket quota, weight 1)
    against a paying 'paid' tenant (weight 4, priority 1, TTFT SLO) on
    a merged Poisson mix. Passes when the paying tenant's TTFT SLO
    attainment stays >= 0.95 while the flood is shed with TYPED
    BackPressureError 429s carrying honest Retry-After estimates — and
    the lane-preemption sub-drill resumes token-exact."""
    import dataclasses

    from ray_tpu.core.exceptions import BackPressureError
    from ray_tpu.models import get_config, init_params
    from ray_tpu.serve import tenancy
    from ray_tpu.serve.llm.paged import PagedConfig
    from ray_tpu.serve.llm.paged_engine import PagedEngineConfig, PagedLLMEngine

    config = get_config(args.model)
    if args.max_seq:
        config = dataclasses.replace(config, max_seq=args.max_seq)
    params = init_params(config, jax.random.PRNGKey(0))

    on_tpu = jax.default_backend() == "tpu"
    ttft_slo_s = TTFT_TARGET_S if on_tpu else 2.5
    paid_n, paid_rate = 24, 30.0
    free_n, free_rate = 96, 120.0
    prompt_len, max_tokens = 64, 8
    tenancy.reset()
    tenancy.set_tenant("paid", weight=4.0, priority=1,
                       ttft_slo_s=ttft_slo_s)
    tenancy.set_tenant("free", weight=1.0, priority=0,
                       quota_rps=30.0, quota_burst=12.0)

    engine = PagedLLMEngine(
        config, params,
        PagedEngineConfig(
            max_slots=8, decode_block_steps=args.decode_block_steps,
            precompile=True,
            paged=PagedConfig(
                page_size=16, num_pages=192,
                max_pages_per_slot=max(
                    8, -(-(prompt_len + max_tokens) // 16)
                ),
                chunk_pages=args.chunk_pages,
            ),
        ),
    )
    rng = np.random.default_rng(0)
    arrivals = sorted(
        [(float(t), "paid") for t in np.cumsum(
            rng.exponential(1.0 / paid_rate, size=paid_n))]
        + [(float(t), "free") for t in np.cumsum(
            rng.exponential(1.0 / free_rate, size=free_n))]
    )
    try:
        engine.generate([1] * prompt_len, max_tokens=2)  # warm compile
        recs, threads = [], []
        sheds = []
        t0 = time.perf_counter()
        for offset, tenant in arrivals:
            now = time.perf_counter() - t0
            if offset > now:
                time.sleep(offset - now)
            prompt = [int(t) for t in
                      rng.integers(1, config.vocab_size, size=prompt_len)]
            try:
                stream = engine.submit(
                    prompt, max_tokens=max_tokens, tenant=tenant,
                    priority=1 if tenant == "paid" else 0,
                )
            except BackPressureError as e:
                sheds.append((tenant, e.retry_after_s))
                continue
            rec = {"tenant": tenant, "submitted": time.perf_counter()}
            recs.append(rec)
            t = threading.Thread(target=_drain, args=(stream, rec),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=900)
        stats = engine.stats()
    finally:
        engine.shutdown()

    errors = [r for r in recs if "error" in r]
    assert not errors, f"{len(errors)} request(s) failed: {errors[0]['error']}"
    by_tenant = {}
    for r in recs:
        if r["ttft_engine"] is not None:
            by_tenant.setdefault(r["tenant"], []).append(r["ttft_engine"])
    paid_ttfts = by_tenant.get("paid", [])
    paid_attainment = (
        sum(1 for t in paid_ttfts if t <= ttft_slo_s) / len(paid_ttfts)
        if paid_ttfts else 0.0
    )
    # the flood MUST be shed, every shed typed with an honest estimate
    assert sheds, "flooding tenant was never shed"
    assert all(t == "free" for t, _ in sheds), "paying tenant was shed"
    assert all(r is not None and r > 0 for _, r in sheds), \
        "shed without a computed Retry-After"
    assert paid_attainment >= 0.95, (
        f"paid TTFT SLO attainment {paid_attainment:.3f} < 0.95 "
        f"(p99={_percentile(paid_ttfts, 0.99):.3f}s vs {ttft_slo_s}s)"
    )
    drill = _preemption_drill(config, params)
    tenancy.reset()
    _emit_result({
        "metric": "serve_multitenant_paid_slo_attainment",
        "value": round(paid_attainment, 4),
        "unit": "fraction",
        "ttft_slo_s": ttft_slo_s,
        "paid_requests": len(paid_ttfts),
        "paid_p50_ttft_s": round(_percentile(paid_ttfts, 0.50), 4),
        "paid_p99_ttft_s": round(_percentile(paid_ttfts, 0.99), 4),
        "free_admitted": len(by_tenant.get("free", [])),
        "free_p99_ttft_s": round(
            _percentile(by_tenant.get("free", []), 0.99), 4),
        "free_shed_typed_429": len(sheds),
        "shed_retry_after_s_max": round(max(r for _, r in sheds), 3),
        "engine_shed_total": stats.get("shed", 0.0),
        "lane_preemptions": drill["lane_preemptions"],
        "lane_resumes": drill["lane_resumes"],
        "preempted_pages": drill["preempted_pages"],
        "token_exact_resume": drill["token_exact_resume"],
        "pages_restored": drill["pages_restored"],
        "arrival_rate_req_s": {"paid": paid_rate, "free": free_rate},
        "quota": {"free_rps": 30.0, "free_burst": 12.0},
        "weights": {"paid": 4.0, "free": 1.0},
    })


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard the engine over a "
                         "tp mesh of this many devices (1 = single device)")
    ap.add_argument("--model", default=None,
                    help="default: gpt2-small on TPU, llama-tiny on CPU")
    ap.add_argument("--requests", type=int, default=None,
                    help="open-loop request count (default 192 TPU / 64 CPU)")
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate, req/s (default 24 TPU / "
                         "500 CPU — the CPU profile saturates the engine)")
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-tokens", type=int, default=None)
    ap.add_argument("--system-len", type=int, default=None,
                    help="shared system-prompt length (tokens)")
    ap.add_argument("--n-system", type=int, default=3,
                    help="number of distinct shared system prompts")
    ap.add_argument("--shared-frac", type=float, default=0.75,
                    help="fraction of requests using a shared system prompt")
    ap.add_argument("--slots", type=int, default=None,
                    help="engine lanes (default 8 TPU / 16 CPU)")
    ap.add_argument("--pages", type=int, default=None)
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV page size in tokens (default 64 TPU / 16 CPU)")
    ap.add_argument("--chunk-pages", type=int, default=None,
                    help="prefill chunk size in pages (default 4 TPU / 2 CPU)")
    ap.add_argument("--decode-block-steps", type=int, default=None,
                    help="decode steps per dispatched block (default 24 TPU "
                         "/ 4 CPU; must be < max-tokens for TPOT to be "
                         "measurable)")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="override the model's max_seq (rope models only "
                         "need this to extend the position table; 0 keeps "
                         "the model default). CPU default 512 so the tiny "
                         "model fits a production-length system prompt.")
    ap.add_argument("--speculative", action="store_true",
                    help="run the speculative-decoding drill: spec off vs "
                         "replay (high-acceptance) vs adversarial "
                         "(all-reject) on one decode-bound workload")
    ap.add_argument("--spec-tokens", type=int, default=3,
                    help="draft tokens per verify round in the "
                         "--speculative drill")
    ap.add_argument("--openai", action="store_true",
                    help="drive the workload through the OpenAI-compatible "
                         "HTTP endpoint (/v1/completions) instead of the "
                         "engine API")
    ap.add_argument("--chaos", action="store_true",
                    help="run through a 2-replica serve deployment and kill "
                         "one replica mid-run (recovery drill)")
    ap.add_argument("--forensics-overhead", action="store_true",
                    help="A/B the request-forensics recorder: the same "
                         "open-loop workload with reqlog off vs on; "
                         "reports the tokens/s ratio (budget: >= 0.98)")
    ap.add_argument("--multitenant", action="store_true",
                    help="run the multi-tenant overload drill: a flooding "
                         "quota-limited tenant vs a paying weighted/"
                         "prioritized tenant on one engine, plus the "
                         "lane-preemption sub-drill")
    args = ap.parse_args()
    from ray_tpu.core.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    _resolve_profile(args)
    if args.multitenant:
        bench_multitenant(args)
        return
    if args.openai:
        _clamp_to_model(args)
        bench_openai(args)
        return
    if args.chaos:
        _clamp_to_model(args)
        bench_chaos(args)
        return

    import dataclasses

    from ray_tpu.models import get_config, init_params

    config = get_config(args.model)
    if args.max_seq:
        config = dataclasses.replace(config, max_seq=args.max_seq)
    mesh = None
    if args.tp > 1:
        from ray_tpu.parallel import MeshSpec, build_mesh

        mesh = build_mesh(
            MeshSpec(tp=args.tp), devices=jax.devices()[: args.tp]
        )
    params = init_params(config, jax.random.PRNGKey(0))

    if args.speculative:
        bench_speculative(args, config, params, mesh)
        return
    if args.forensics_overhead:
        bench_forensics(args, config, params, mesh)
        return

    base = _run_open_loop(args, config, params, mesh, prefix_cache=False)
    cached = _run_open_loop(args, config, params, mesh, prefix_cache=True)
    n_chips = max(1, args.tp)
    _emit_result({
        "metric": "serve_open_loop_tokens_per_s_per_chip",
        "value": round(cached["tokens_per_s"] / n_chips, 1),
        "unit": "tok/s/chip",
        # prefix-cache speedup on the shared-prefix mix
        "vs_baseline": round(
            cached["tokens_per_s"] / max(1e-9, base["tokens_per_s"]), 3
        ),
        "p50_ttft_s": round(cached["p50_ttft_s"], 4),
        "p99_ttft_s": round(cached["p99_ttft_s"], 4),
        "p50_tpot_s": round(cached["p50_tpot_s"], 5),
        "prefix_hit_rate": round(cached["prefix_hit_rate"], 3),
        "mixed_ticks": cached["mixed_ticks"],
        "baseline_mixed_ticks": base["mixed_ticks"],
        "baseline_tokens_per_s": round(base["tokens_per_s"], 1),
        "baseline_p50_ttft_s": round(base["p50_ttft_s"], 4),
        "baseline_p99_ttft_s": round(base["p99_ttft_s"], 4),
        "requests": args.requests,
        "arrival_rate_req_s": args.rate,
        "shared_frac": args.shared_frac,
        "prompt_len": args.prompt_len,
        "system_len": args.system_len,
        "max_tokens": args.max_tokens,
        "page_size": args.page_size,
        "chunk_pages": args.chunk_pages,
        "tp": args.tp,
    })


def bench_chaos(args) -> None:
    """Open-loop workload against a 2-replica serve deployment with one
    replica killed mid-run: the drill passes when the controller restarts
    it, the router fails over, and EVERY request completes."""
    import ray_tpu
    from ray_tpu import serve as serve_mod
    from ray_tpu.serve import api as serve_api
    from ray_tpu.serve.llm import build_llm_app

    ray_tpu.init(detect_accelerators=True)
    handle = serve_mod.run(
        build_llm_app(args.model, name="bench-llm", num_replicas=2,
                      max_slots=args.slots, paged=True),
        name="bench-llm",
    )
    from ray_tpu.models import get_config as _get_config

    requests, _ = _build_workload(args, _get_config(args.model).vocab_size)
    results: dict = {}

    def post(i, prompt):
        try:
            out = ray_tpu.get(
                handle.generate.remote(
                    {"prompt_tokens": prompt, "max_tokens": args.max_tokens}
                ),
                timeout=900,
            )
            results[i] = len(out["tokens"])
        except Exception as exc:  # noqa: BLE001
            results[i] = repr(exc)

    try:
        post(-1, requests[0][1])  # warmup compiles
        threads = []
        kill_after = len(requests) // 2
        t0 = time.perf_counter()
        for i, (offset, prompt) in enumerate(requests):
            now = time.perf_counter() - t0
            if offset > now:
                time.sleep(offset - now)
            t = threading.Thread(target=post, args=(i, prompt), daemon=True)
            t.start()
            threads.append(t)
            if i == kill_after:
                state = serve_api._controller._states["bench-llm"]
                ray_tpu.kill(state.replicas[-1])
        for t in threads:
            t.join(timeout=900)
        elapsed = time.perf_counter() - t0
        completed = [v for v in results.values() if isinstance(v, int)]
        _emit_result({
            "metric": "serve_chaos_open_loop_req_per_s",
            "value": round(len(requests) / elapsed, 2),
            "unit": "req/s",
            "vs_baseline": round(len(completed) / (len(requests) + 1), 3),
            "completed": len(completed),
            "failed": len(results) - len(completed),
            "replica_killed": True,
            })
    finally:
        serve_mod.shutdown()
        ray_tpu.shutdown()


def bench_openai(args) -> None:
    """Same open-loop arrivals, driven through the OpenAI HTTP surface:
    measures the full ingress path (HTTP + schema translation + serve
    routing + engine). TTFT is not observable per-request without SSE
    timing, so this reports req/s and decode tok/s through the endpoint."""
    import urllib.request

    import ray_tpu
    from ray_tpu import serve as serve_mod
    from ray_tpu.serve.llm import serve_openai

    ray_tpu.init(detect_accelerators=True)
    frontend = serve_openai(
        model=args.model, paged=True, max_slots=args.slots,
        tensor_parallel=args.tp,
    )
    url = f"http://127.0.0.1:{frontend.port}/v1/completions"
    from ray_tpu.models import get_config as _get_config

    requests, _ = _build_workload(args, _get_config(args.model).vocab_size)

    def post(i, prompt, results):
        req = urllib.request.Request(
            url,
            data=json.dumps({
                "model": args.model, "prompt": prompt,
                "max_tokens": args.max_tokens, "temperature": 0.0,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=900) as r:
            results[i] = json.loads(r.read())

    try:
        results: dict = {}
        post(-1, requests[0][1], results)  # warmup compiles
        threads = []
        t0 = time.perf_counter()
        for i, (offset, prompt) in enumerate(requests):
            now = time.perf_counter() - t0
            if offset > now:
                time.sleep(offset - now)
            t = threading.Thread(target=post, args=(i, prompt, results))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=900)
        elapsed = time.perf_counter() - t0
        done = [results[i] for i in range(len(requests)) if i in results]
        assert len(done) == len(requests), f"only {len(done)} completed"
        assert all(
            r["usage"]["completion_tokens"] == args.max_tokens for r in done
        )
        _emit_result({
            "metric": "serve_openai_http_req_per_s",
            "value": round(len(requests) / elapsed, 2),
            "unit": "req/s",
            "vs_baseline": 0.0,
            "decode_tokens_per_s": round(
                len(requests) * args.max_tokens / elapsed, 1
            ),
                "tp": args.tp,
        })
    finally:
        frontend.stop()
        serve_mod.shutdown()
        ray_tpu.shutdown()


if __name__ == "__main__":
    main()
