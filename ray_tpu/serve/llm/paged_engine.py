"""Paged continuous-batching engine: vLLM-class serving, TPU-native.

Reference parity: the vLLM engine the reference rides
(/root/reference/python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_engine.py:254 — paged KV, chunked prefill, continuous batching).
TPU inversion (the ragged-paged-attention recipe from PAPERS.md):

- HBM holds one fixed PAGE POOL shared by all slots (paged.py); a slot's
  KV occupancy scales with its actual tokens, not max_seq — like vLLM,
  unlike the dense engine's (L, max_slots, H, max_seq, D) grid.
- Prefill is CHUNKED and interleaved: each engine tick runs at most one
  prompt chunk plus one decode block, so a long prompt delays running
  streams by one chunk's latency, never by its full length.
- Decode runs in BLOCKS of K fused decode+sample steps per device call
  (lax.scan), with sampled tokens staying ON DEVICE between blocks and
  results fetched through an async pipeline one block deep. The host
  never blocks on a device read in the dispatch path: a synchronous host
  read stalls the device pipeline once per token.
- Backpressure is physical: admission, prefill growth, and the K-step
  lookahead all wait on the page allocator; finished slots return pages.

Retirement (EOS / budget) is detected at emission, up to one block after
the fact; blocks already in flight for a retired slot write only into
pages that are either still owned or provably overwritten before they
become visible (pages fill strictly forward from row 0 and attention
masks rows beyond a slot's length), so late retirement never corrupts a
neighbor.
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer import TransformerConfig
from ...ops.ragged_paged_attention import RAGGED_KERNEL, resolve_ragged_impl
from .. import reqlog
from ..tenancy import FairQueue
from .engine import (
    ResponseStream,
    _Request,
    _charge_wait,
    _check_admission,
    _fail_all_requests,
    _finish_request_span,
    _hit_stop_sequence,
    _normalize_stop_sequences,
    _observe_tenant_ttft,
    _observe_tick,
    _register_engine_metrics,
    _reject_if_dead,
    _start_request_span,
    _tick_cost,
    _timeout_request,
)
from .paged import (
    PagedConfig,
    PageAllocator,
    PrefixCache,
    batched_chunk_prefill_step,
    copy_page,
    init_paged_cache,
    paged_decode_step,
    ragged_mixed_step,
)
from .speculative import NgramProposer, accept_speculative, filtered_scores


@dataclasses.dataclass
class PagedEngineConfig:
    max_slots: int = 8
    eos_id: int = -1
    decode_block_steps: int = 16  # K: fused decode+sample steps per dispatch
    max_inflight_blocks: int = 8  # device blocks outstanding before gating
    # admission bound on the submit queue: overflow raises a typed
    # BackPressureError instead of queueing unboundedly. 0 = auto
    # (8 x max_slots); negative disables the bound.
    max_queued_requests: int = 0
    # Compile every prefill bucket + both decode variants at construction
    # (vLLM pre-captures its batch-size graphs the same way). Off by
    # default: tests build many engines; serving/bench wants it on so the
    # first burst never pays a 20-40s XLA compile mid-request.
    precompile: bool = False
    # Speculative decoding: tokens drafted per verify round. None reads
    # the cfg.serve_speculative_tokens flag; 0 disables. When enabled the
    # decode path becomes draft-and-verify: each ready lane's pending
    # token plus up to this many drafts are scored in ONE ragged launch
    # (a q_len=K region, exactly a prefill chunk's shape), with exact
    # greedy acceptance at temperature 0 and exact rejection sampling
    # otherwise, and page rollback on rejection.
    speculative_tokens: Optional[int] = None
    speculative_ngram: int = 3  # default proposer's max n-gram
    # Optional DraftProposer (speculative.py protocol); None = n-gram
    # prompt-lookup self-drafting.
    speculative_proposer: Optional[Any] = None
    paged: PagedConfig = dataclasses.field(default_factory=PagedConfig)


# ------------------------------------------------------- jittable components
# Module-level builders so the TP AOT test can lower the exact programs the
# engine runs (at Llama-3-8B shapes) without instantiating an engine.


def _sample_plain(logits, key, temps):
    """temperature-only / greedy sampling — the common fast path."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)


def _sample_filtered(logits, key, temps, top_ks, top_ps):
    """Per-lane temperature + top-k + top-p (nucleus) sampling —
    vLLM SamplingParams parity. The filtering body lives in
    speculative.filtered_scores (the verify step scores drafts against
    the SAME filtered distribution, which is what makes speculative
    output exactly match plain sampling)."""
    greedy = jnp.argmax(logits, axis=-1)
    final = filtered_scores(logits, temps, top_ks, top_ps)
    sampled = jax.random.categorical(key, final, axis=-1)
    return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)


def build_decode_block(mc: TransformerConfig, page_size: int, K: int,
                       sample_fn, use_kernel=None, mesh=None):
    """K fused decode+sample steps; tokens never leave the device.
    Output row 0 is the INPUT token vector — a freshly prefilled
    lane's first sampled token rides along with its first block,
    so it never needs a fetch of its own (every materialization
    is a blocking device-to-host read). Two variants are
    compiled: plain (temperature only — no per-step vocab sort)
    and filtered (top-k/top-p); the dispatcher picks per block."""

    def _decode_block(params, cache, block_tables, tokens, positions,
                      key, temps, *filters):
        def body(carry, _):
            cache, toks_c, pos_c, key_c = carry
            logits, cache = paged_decode_step(
                params, cache, block_tables, toks_c, pos_c, mc,
                page_size=page_size, use_kernel=use_kernel, mesh=mesh,
            )
            key_c, sub = jax.random.split(key_c)
            nxt = sample_fn(logits, sub, temps, *filters)
            return (cache, nxt, pos_c + 1, key_c), nxt

        (cache, final, _, _), toks = jax.lax.scan(
            body, (cache, tokens, positions, key), None, length=K
        )
        toks = jnp.concatenate([tokens[None], toks], axis=0)  # (K+1, B)
        return toks, final, cache

    return _decode_block


def build_batched_chunk_fn(mc: TransformerConfig, page_size: int):
    def _batched_chunk(params, cache, page_rows, chunk_page_ids, tokens,
                       offsets, totals):
        return batched_chunk_prefill_step(
            params, cache, page_rows, chunk_page_ids, tokens, offsets, totals,
            mc, page_size=page_size,
        )

    return _batched_chunk


def mixed_block_q(chunk_tokens: int) -> int:
    """Ragged q-block size for a given prefill chunk length: 8 (the
    Mosaic-tileable size the kernel wants) whenever the chunk divides by
    it, else the largest power of two that does (tiny test configs — the
    XLA reference path handles any block_q)."""
    bq = 8
    while chunk_tokens % bq:
        bq //= 2
    return max(bq, 1)


def build_mixed_step(mc: TransformerConfig, page_size: int,
                     use_kernel=None, mesh=None, block_q: int = 8):
    """The single mixed tick: P prefill chunks + B decode lanes through
    one ragged-paged-attention program (replaces the split
    build_batched_chunk_fn + per-step decode dispatch for ticks that have
    prefill work; the K-step fused decode block remains the decode-only
    steady state)."""

    def _mixed(params, cache, page_rows, chunk_page_ids, tokens,
               offsets, totals, dec_tokens, dec_positions, dec_active):
        return ragged_mixed_step(
            params, cache, page_rows, chunk_page_ids, tokens, offsets,
            totals, dec_tokens, dec_positions, dec_active, mc,
            page_size=page_size, block_q=block_q, use_kernel=use_kernel,
            mesh=mesh,
        )

    return _mixed


def serving_shardings(model_config: TransformerConfig, mesh, rules=None):
    """(param shardings, KV-pool sharding, replicated) for TP serving.

    Reference parity: the reference serves TP via vLLM workers in a
    placement group (/root/reference/python/ray/llm/_internal/serve/
    deployments/llm/vllm/vllm_models.py:124 — one process per GPU,
    NCCL all-reduce per layer). TPU inversion: ONE program over a mesh;
    the same rule table train uses (Megatron split on heads/mlp/vocab)
    annotates the params and the page pool shards on the kv-head axis,
    and XLA inserts the collectives over ICI.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from ...models.transformer import logical_axes
    from ...parallel import default_rules
    from ...parallel.sharding import tree_specs

    tp = mesh.shape.get("tp", 1)
    if model_config.kv_heads % tp or model_config.n_heads % tp:
        raise ValueError(
            f"tp={tp} must divide kv_heads ({model_config.kv_heads}) and "
            f"n_heads ({model_config.n_heads})"
        )
    specs = tree_specs(logical_axes(model_config), rules or default_rules())
    param_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
    # flat pool layout (Hkv, L*P, ps, D): kv heads lead
    kv_spec = NamedSharding(
        mesh, PartitionSpec("tp", None, None, None)
    )
    cache_sh = {"k": kv_spec, "v": kv_spec}
    replicated = NamedSharding(mesh, PartitionSpec())
    return param_sh, cache_sh, replicated


@dataclasses.dataclass
class _PagedSlot:
    request: Optional[_Request] = None
    pages: List[int] = dataclasses.field(default_factory=list)
    position: int = 0          # next KV write index at DISPATCH time
    prefill_offset: int = 0    # prompt tokens already ingested
    stalled: bool = False      # waiting on a page
    # dispatch-side generation bookkeeping
    dispatch_remaining: int = 0
    done_dispatching: bool = False
    blocks_in_flight: int = 0
    awaiting_first: bool = False  # first token rides the next block's row 0
    # emission-side bookkeeping
    emit_remaining: int = 0
    finished_emit: bool = False
    # speculative decoding: the host-side token context the proposer
    # drafts from (prompt + every emitted token; seeded by the "first"
    # fetch), and the one-round-in-flight latch — a lane never has two
    # verify rounds outstanding, so rollback math stays race-free.
    spec_ctx: Optional[List[int]] = None
    spec_inflight: bool = False
    # lane preemption: a marked lane stops dispatching new blocks and is
    # parked (trimmed to its emitted frontier) once its in-flight blocks
    # drain — an actively pipelined lane is never quiescent at mark time
    preempt_pending: bool = False
    # observability: admit wall time, so the per-request engine.prefill
    # span covers chunked ingest end to end (chunks batch across lanes)
    prefill_t0: float = 0.0

    @property
    def free(self) -> bool:
        return self.request is None

    @property
    def prefilling(self) -> bool:
        return (
            self.request is not None
            and self.prefill_offset < len(self.request.prompt)
        )

    @property
    def decodable(self) -> bool:
        return (
            self.request is not None
            and not self.prefilling
            and not self.done_dispatching
            and not self.preempt_pending
            and self.dispatch_remaining > 0
        )


class PagedLLMEngine:
    """Continuous batching over a paged KV pool with chunked prefill and
    pipelined block decoding."""

    def __init__(
        self,
        model_config: TransformerConfig,
        params: Any,
        engine_config: Optional[PagedEngineConfig] = None,
        mesh: Any = None,
    ):
        """mesh: optional jax.sharding.Mesh with a 'tp' axis — params and
        the KV page pool shard across it (serving_shardings) and every
        prefill/decode program runs SPMD over the mesh. Host-side state
        (slots, block tables, allocator) is unchanged: page tables are
        replicated, exactly like vLLM's TP workers sharing one scheduler."""
        self.model_config = model_config
        self.params = params
        self.mesh = mesh
        self.config = engine_config or PagedEngineConfig()
        pc = self.config.paged
        if pc.max_pages_per_slot % pc.chunk_pages:
            raise ValueError(
                f"max_pages_per_slot ({pc.max_pages_per_slot}) must be a "
                f"multiple of chunk_pages ({pc.chunk_pages}): prefill grows "
                "page tables chunk-aligned"
            )
        if pc.chunk_pages > pc.num_pages - 1:
            raise ValueError(
                f"chunk_pages ({pc.chunk_pages}) exceeds the pool "
                f"({pc.num_pages - 1} allocatable pages)"
            )
        self.paged = pc
        self.cache = init_paged_cache(model_config, pc)
        self.allocator = PageAllocator(pc.num_pages)
        self.slots = [_PagedSlot() for _ in range(self.config.max_slots)]
        self.block_tables = np.zeros(
            (self.config.max_slots, pc.max_pages_per_slot), dtype=np.int32
        )
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._rid = itertools.count()
        self._stop = threading.Event()
        self._wake = threading.Event()
        # Device→host results flow through a dedicated DRAIN THREAD: a
        # host read blocks until the producing program finishes, so the
        # blocking np.asarray must never run on the dispatch thread.
        # Entries:
        #   ("first", (slot, request), (1,) arr)
        #   ("block", [(slot, request), ...], (K, B) arr)
        self._fetchq: "queue.Queue[Optional[Tuple[str, Any, jax.Array]]]" = queue.Queue()
        self._doneq: "queue.Queue[Tuple[str, Any, Any]]" = queue.Queue()
        self._inflight = 0  # fetch entries not yet emitted
        self.drain_log: List[Tuple[int, float]] = []  # (batch_size, seconds)

        mc = model_config
        ps = pc.page_size
        K = self.config.decode_block_steps

        def _scatter_tokens(tokens, lane_slots, sampled):
            """Thread freshly sampled first tokens into the engine token
            vector: lane_slots maps each batched-prefill lane to its slot
            index, with non-finishing lanes pointing past the end (their
            garbage samples drop)."""
            return tokens.at[lane_slots].set(sampled, mode="drop")

        def _take(tokens, idx):
            return tokens[idx][None]

        def _merge_tokens(old, new, mask):
            """Merge a decode block's final sampled tokens back into the
            engine token vector ONLY for lanes that were dispatched in
            that block. Excluded lanes (page-stalled mid-decode, still
            prefilling) keep their pending input token — the block
            sampled garbage for them (attention over the scratch page)
            and writing it back would silently corrupt their stream when
            they unstall."""
            return jnp.where(mask, new, old)

        def _dec_pack(old, new, mask):
            """Pack a mixed tick's decode samples for fetch + carry: row 0
            is the tick's INPUT tokens (a fresh lane's first sampled token
            rides there, like a decode block's row 0), row 1 the per-lane
            merged output (non-dispatched lanes keep their pending token —
            same invariant as _merge_tokens)."""
            merged = jnp.where(mask, new, old)
            return jnp.stack([old, merged]), merged

        # Kernel dispatch: auto (None) is resolve_ragged_impl's static
        # rule — the Pallas ragged kernel on a TPU backend at tileable
        # shapes, the XLA schedule-replay reference elsewhere. Under a TP
        # mesh the kernel call is shard_map-wrapped over the tp axis
        # inside ragged_paged_attention. `attention_impl` names what the
        # compiled programs run (snapshot() reports it).
        from ...core.config import cfg

        use_kernel = None if cfg.serve_ragged_kernel else False
        spec = self.config.speculative_tokens
        if spec is None:
            spec = int(cfg.serve_speculative_tokens)
        self.spec_tokens = max(0, int(spec))
        # verify width: the pending token + the drafts (row 0 of a verify
        # region re-scores the token whose KV write was deferred)
        self._spec_width = self.spec_tokens + 1
        self._proposer = None
        if self.spec_tokens:
            self._proposer = (
                self.config.speculative_proposer
                or NgramProposer(self.config.speculative_ngram)
            )
        bq = mixed_block_q(pc.chunk_tokens)
        self._block_q = bq
        self.attention_impl = resolve_ragged_impl(
            mc.head_dim, ps, bq, use_kernel=use_kernel
        )
        # decided once: every program below runs what attention_impl names
        use_kernel = self.attention_impl == RAGGED_KERNEL
        dec_plain = build_decode_block(mc, ps, K, _sample_plain, use_kernel,
                                       mesh=mesh)
        dec_filtered = build_decode_block(mc, ps, K, _sample_filtered,
                                          use_kernel, mesh=mesh)
        mixed = build_mixed_step(mc, ps, use_kernel, mesh, block_q=bq)
        _copy = lambda cache, s, d: copy_page(cache, s, d, n_layers=mc.n_layers)  # noqa: E731
        if mesh is not None:
            param_sh, cache_sh, rep = serving_shardings(mc, mesh)
            self.params = jax.device_put(params, param_sh)
            self.cache = jax.device_put(self.cache, cache_sh)
            common_in = (param_sh, cache_sh, rep, rep, rep, rep, rep)
            self._decode_block_plain = jax.jit(
                dec_plain, donate_argnums=(1,),
                in_shardings=common_in, out_shardings=(rep, rep, cache_sh),
            )
            self._decode_block_filtered = jax.jit(
                dec_filtered, donate_argnums=(1,),
                in_shardings=common_in + (rep, rep),
                out_shardings=(rep, rep, cache_sh),
            )
            self._mixed = jax.jit(
                mixed, donate_argnums=(1,),
                in_shardings=(param_sh, cache_sh) + (rep,) * 8,
                out_shardings=(rep, rep, cache_sh),
            )
            self._copy_page = jax.jit(
                _copy, donate_argnums=(0,),
                in_shardings=(cache_sh, rep, rep), out_shardings=cache_sh,
            )
            self._tokens_dev = jax.device_put(
                jnp.zeros((self.config.max_slots,), jnp.int32), rep
            )
        else:
            self._decode_block_plain = jax.jit(dec_plain, donate_argnums=(1,))
            self._decode_block_filtered = jax.jit(dec_filtered, donate_argnums=(1,))
            self._mixed = jax.jit(mixed, donate_argnums=(1,))
            self._copy_page = jax.jit(_copy, donate_argnums=(0,))
            self._tokens_dev = jnp.zeros((self.config.max_slots,), jnp.int32)
        def _spec_accept_pack(dec_logits, toks, counts, key, temps, tks, tps):
            """Accept/resample a verify round and pack the result for ONE
            small fetch: columns [:W] the emit-ordered tokens, column W the
            per-lane emitted count. Logits never cross to the host."""
            out, n = accept_speculative(
                dec_logits, toks, counts, key, temps, tks, tps
            )
            return jnp.concatenate([out, n[:, None]], axis=1)

        self._sample = jax.jit(_sample_filtered)
        self._spec_accept = jax.jit(_spec_accept_pack)
        self._scatter_tokens = jax.jit(_scatter_tokens, donate_argnums=(0,))
        self._take = jax.jit(_take)
        self._merge_tokens = jax.jit(_merge_tokens, donate_argnums=(0,))
        self._dec_pack = jax.jit(_dec_pack)
        self._key = jax.random.PRNGKey(0)
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.allocator, ps, pc.prefix_cache_pages)
            if pc.prefix_cache else None
        )
        # weighted-fair admit queue (replaces the old FIFO pending deque):
        # raw submits drain into per-(priority, tenant) SCFQ lanes; pops
        # come out in virtual-time order. Deferred admissions (page
        # stalls) and preempted lanes re-enter at the front of their lane
        # without a fresh virtual-time charge.
        self._fair = FairQueue()
        self.metrics: Dict[str, float] = {
            "generated_tokens": 0.0,
            "decode_steps": 0.0,
            "decode_blocks": 0.0,
            "prefill_chunks": 0.0,
            "ongoing": 0.0,
            "page_stalls": 0.0,
            "pages_in_use": 0.0,
            "shed": 0.0,
            "timeouts": 0.0,
            # batch-occupancy accounting (engine.py gauge registry)
            "batch_fill": 0.0,
            "tick_seconds": 0.0,
            "prefill_tokens": 0.0,
            "decode_tokens": 0.0,
            # prefix-cache counters (engine.py gauge registry mirrors
            # these as raytpu_engine_prefix_cache_*); zero when disabled
            "prefix_cache_hits": 0.0,
            "prefix_cache_misses": 0.0,
            "prefix_cache_evictions": 0.0,
            "prefix_cache_pages": 0.0,
            "prefix_cache_hit_rate": 0.0,
            "prefix_cache_cow": 0.0,
            "mixed_ticks": 0.0,
            # mixed ticks in which decode lanes rode along with the
            # prefill chunks (one launch held both kinds of work)
            "mixed_ticks_with_decode": 0.0,
            # speculative-decoding counters (engine.py gauge registry
            # mirrors these as raytpu_engine_spec_*); zero when disabled
            "spec_proposed": 0.0,
            "spec_accepted": 0.0,
            "spec_acceptance_rate": 0.0,
            "spec_rollback_pages": 0.0,
            # lane-preemption counters (multi-tenant overload protection)
            "lane_preemptions": 0.0,
            "lane_resumes": 0.0,
            "preempted_pages": 0.0,
        }
        self._tick_cost = None  # decode-block cost, set at first dispatch
        self.metrics_label = _register_engine_metrics(self, "paged")
        if self.config.precompile:
            self._precompile()
        self._drainer = threading.Thread(
            target=self._drain_worker, daemon=True, name="paged-llm-drain"
        )
        self._drainer.start()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="paged-llm-engine"
        )
        self._thread.start()

    def _precompile(self) -> None:
        """Trigger every XLA compile the serving loop can hit — each
        prefill bucket (1, 2, 4, ..., max_slots lanes) and both decode
        variants — with all-inactive inputs whose writes land only in the
        scratch page. Runs BEFORE the engine threads start, so no request
        ever pays a compile. Donated caches rebind as in the live loop."""
        pc = self.paged
        ms = self.config.max_slots
        ct, cp = pc.chunk_tokens, pc.chunk_pages
        spec = self.spec_tokens > 0
        dec_toks = (
            jnp.zeros((ms, self._spec_width), jnp.int32)
            if spec else self._tokens_dev
        )
        b = 1
        while True:
            logits, dec_logits, self.cache = self._mixed(
                self.params,
                self.cache,
                jnp.zeros((b + ms, pc.max_pages_per_slot), jnp.int32),
                jnp.zeros((b, cp), jnp.int32),     # scratch page only
                jnp.zeros((b, ct), jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), jnp.int32),        # totals 0: inactive
                dec_toks,
                jnp.zeros((ms,), jnp.int32),
                jnp.zeros((ms,), jnp.int32),       # no decode ride-alongs
            )
            self._key, sub = jax.random.split(self._key)
            self._sample(
                logits, sub, jnp.zeros((b,), jnp.float32),
                jnp.zeros((b,), jnp.int32), jnp.ones((b,), jnp.float32),
            )
            if b == 1:
                self._key, sub = jax.random.split(self._key)
                if spec:
                    self._spec_accept(
                        dec_logits, dec_toks, jnp.zeros((ms,), jnp.int32),
                        sub, jnp.zeros((ms,), jnp.float32),
                        jnp.zeros((ms,), jnp.int32),
                        jnp.ones((ms,), jnp.float32),
                    )
                    self._take(self._tokens_dev, 0)  # every first token
                else:
                    self._sample(
                        dec_logits, sub, jnp.zeros((ms,), jnp.float32),
                        jnp.zeros((ms,), jnp.int32),
                        jnp.ones((ms,), jnp.float32),
                    )
                    self._dec_pack(
                        self._tokens_dev, jnp.zeros((ms,), jnp.int32),
                        jnp.zeros((ms,), bool),
                    )
            if b >= ms:
                break
            b = min(b * 2, ms)
        if not spec:
            # spec mode never launches the fused decode blocks: the verify
            # tick (self._mixed, compiled above) IS its decode path
            zeros_bt = jnp.zeros((ms, pc.max_pages_per_slot), jnp.int32)
            pos = jnp.zeros((ms,), jnp.int32)
            temps = jnp.zeros((ms,), jnp.float32)
            self._key, sub = jax.random.split(self._key)
            _, _, self.cache = self._decode_block_plain(
                self.params, self.cache, zeros_bt, self._tokens_dev, pos,
                sub, temps
            )
            self._key, sub = jax.random.split(self._key)
            _, _, self.cache = self._decode_block_filtered(
                self.params, self.cache, zeros_bt, self._tokens_dev, pos,
                sub, temps, jnp.zeros((ms,), jnp.int32),
                jnp.ones((ms,), jnp.float32),
            )
        jax.block_until_ready(self.cache["k"])

    # ------------------------------------------------------------------- API

    def submit(
        self,
        prompt_tokens: List[int],
        max_tokens: int = 64,
        temperature: float = 0.0,
        *,
        top_k: int = 0,
        top_p: float = 1.0,
        stop_token_ids: Optional[List[int]] = None,
        stop_sequences: Optional[List[List[int]]] = None,
        deadline_ts: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
        request_id: Optional[str] = None,
    ) -> ResponseStream:
        limit = self.paged.max_slot_tokens
        if len(prompt_tokens) + max_tokens > limit:
            raise ValueError(
                f"prompt({len(prompt_tokens)}) + max_tokens({max_tokens}) "
                f"exceeds per-slot page capacity {limit}"
            )
        if not prompt_tokens:
            raise ValueError("empty prompt")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        tenant = tenant or "default"
        if request_id is None and reqlog.enabled():
            request_id = reqlog.new_request_id()
        _check_admission(self, deadline_ts, tenant, request_id=request_id)
        request = _Request(
            rid=next(self._rid),
            prompt=list(prompt_tokens),
            max_tokens=max_tokens,
            temperature=temperature,
            out=queue.Queue(),
            top_k=int(top_k),
            top_p=float(top_p),
            stop_token_ids=tuple(stop_token_ids or ()),
            stop_sequences=_normalize_stop_sequences(stop_sequences),
            deadline_ts=deadline_ts,
            tenant=tenant,
            priority=int(priority or 0),
            request_id=request_id,
        )
        _start_request_span(request, "paged")
        reqlog.mark(request_id, "engine.submitted", tenant=tenant,
                    prompt_tokens=len(request.prompt),
                    max_tokens=max_tokens)
        request.enqueued_at = time.perf_counter()
        self._queue.put(request)
        _reject_if_dead(self, request)
        self._wake.set()
        return ResponseStream(request)

    def generate(
        self, prompt_tokens: List[int], max_tokens: int = 64,
        temperature: float = 0.0, **sampling,
    ) -> List[int]:
        return self.submit(
            prompt_tokens, max_tokens, temperature, **sampling
        ).result()

    def stats(self) -> Dict[str, float]:
        """Point-in-time engine statistics: the metrics dict plus live
        allocator/prefix-cache state (the latter read fresh, not from the
        last loop tick)."""
        out = dict(self.metrics)
        out["pages_free"] = float(self.allocator.available)
        if self.prefix_cache is not None:
            for key, val in self.prefix_cache.stats().items():
                out[f"prefix_cache_{key}"] = val
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Live engine introspection (`state.engine_snapshot()` / the
        dashboard's /api/engines): the lane table, page-pool occupancy,
        prefix-cache chain heads, and per-tenant fair-queue depths. Read
        in place, point-in-time, lock-free — the loop thread mutates
        between field reads, and a forensics read must never stall the
        engine (a lane row may be a tick stale; that is fine)."""
        lanes: List[Dict[str, Any]] = []
        for idx, slot in enumerate(self.slots):
            request = slot.request
            lane: Dict[str, Any] = {"lane": idx, "free": request is None}
            if request is not None:
                lane.update(
                    rid=request.rid,
                    request_id=request.request_id,
                    tenant=request.tenant,
                    priority=request.priority,
                    prefilling=slot.prefilling,
                    stalled=slot.stalled,
                    preempt_pending=slot.preempt_pending,
                    position=slot.position,
                    prefill_offset=slot.prefill_offset,
                    pages=len(slot.pages),
                    blocks_in_flight=slot.blocks_in_flight,
                    dispatch_remaining=slot.dispatch_remaining,
                    emit_remaining=slot.emit_remaining,
                    generated=request.generated,
                    spec_inflight=slot.spec_inflight,
                )
            lanes.append(lane)
        pc = self.paged
        out: Dict[str, Any] = {
            "kind": "paged",
            "attention_impl": self.attention_impl,
            "lanes": lanes,
            "pages": {
                "total": pc.num_pages - 1,  # page 0 is scratch
                "free": self.allocator.available,
                "in_use": pc.num_pages - 1 - self.allocator.available,
            },
            "queue_depth": self._queue.qsize(),
            "fair_depths": self._fair.depths(),
            "inflight_blocks": self._inflight,
            "spec_tokens": self.spec_tokens,
        }
        if self.prefix_cache is not None:
            out["prefix_cache"] = dict(
                self.prefix_cache.stats(),
                chains=self.prefix_cache.chain_heads(),
            )
        return out

    def shutdown(self) -> None:
        self._stop.set()
        self._wake.set()
        self._fetchq.put(None)
        self._thread.join(timeout=10)
        self._drainer.join(timeout=10)

    # ------------------------------------------------------------- admission

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """Pool alloc with prefix-cache pressure relief: when the free
        list comes up short, evict cache-pinned pages (LRU, never pages a
        live slot shares) to cover the shortfall and retry once. Cached
        prefixes therefore never starve admissions or decode growth."""
        pages = self.allocator.alloc(n)
        if pages is None and self.prefix_cache is not None:
            if self.prefix_cache.evict(n - self.allocator.available) > 0:
                pages = self.allocator.alloc(n)
        return pages

    def _drain_submits(self) -> None:
        """Move raw submits into the weighted-fair admit queue: one
        per-(priority, tenant) SCFQ lane each (serve/tenancy.FairQueue),
        so admission order is virtual-time fair rather than FIFO."""
        while True:
            try:
                request = self._queue.get_nowait()
            except queue.Empty:
                return
            self._fair.push(request, request.tenant, request.priority)

    def _next_admissible(self) -> Optional[_Request]:
        """Next admissible request in weighted-fair order, shedding
        anything whose deadline expired while it queued — an expired
        request never consumes an admission slot ahead of a live one."""
        while True:
            candidate = self._fair.pop()
            if candidate is None:
                return None
            if (
                candidate.deadline_ts is not None
                and time.time() >= candidate.deadline_ts
            ):
                # expired while queued: fail fast, never take a slot
                self.metrics["timeouts"] = (
                    self.metrics.get("timeouts", 0.0) + 1
                )
                _timeout_request(candidate)
                candidate.out.put(None)
                continue
            return candidate

    def _preemption_enabled(self) -> bool:
        from ...core.config import cfg

        return bool(cfg.serve_lane_preemption)

    def _pick_victim(self, min_priority: int) -> Optional[int]:
        """Lowest-priority, largest-page-holding lane strictly below
        `min_priority` that can be preempted: not mid-prefill, not
        already finishing, not already marked. In-flight blocks do NOT
        disqualify — marking stops further dispatch and the park happens
        once the pipeline drains (``_sweep_pending_preemptions``)."""
        best = None
        for idx, slot in enumerate(self.slots):
            request = slot.request
            if (
                request is None
                or request.priority >= min_priority
                or slot.prefilling
                or slot.preempt_pending
                or slot.done_dispatching
                or slot.finished_emit
            ):
                continue
            rank = (request.priority, -len(slot.pages))
            if best is None or rank < best[0]:
                best = (rank, idx)
        return best[1] if best is not None else None

    def _request_preempt(self, idx: int) -> bool:
        """Preempt lane `idx`: park immediately when it is quiescent
        (no in-flight blocks — its emitted tokens equal its drained
        dispatch positions, so re-prefilling prompt+emitted reproduces
        the KV exactly), else mark it pending so dispatch stops feeding
        it and the drain sweep parks it. Returns True when the park
        happened NOW (pages already released)."""
        slot = self.slots[idx]
        if slot.blocks_in_flight == 0 and not slot.spec_inflight:
            self._park_lane(idx)
            return True
        slot.preempt_pending = True
        return False

    def _sweep_pending_preemptions(self) -> None:
        """Park every marked lane whose in-flight blocks have drained.
        A lane that finished (or dispatched its last block) while the
        mark was pending just unmarks — it retires on its own."""
        for idx, slot in enumerate(self.slots):
            if not slot.preempt_pending:
                continue
            if (
                slot.request is None
                or slot.finished_emit
                or slot.done_dispatching
            ):
                slot.preempt_pending = False
                continue
            if slot.blocks_in_flight == 0 and not slot.spec_inflight:
                self._park_lane(idx)

    def _park_lane(self, idx: int) -> int:
        """Preempt a decode lane: trim it to its emitted frontier and
        park the request back in the admit queue with the generated
        prefix folded into its prompt (PR 13's rollback-to-frontier
        guarantee taken to zero pages). Returns the pages released.

        Freeing `slot.pages` only drops THIS slot's refs: prefix-shared
        pages (refcount > 1 via the prefix cache or another lane) merely
        lose one holder and are never written or zeroed — the shared KV
        stays intact for everyone else. On re-admit the lane re-prefills
        prompt+generated (prefix-cache assisted), so a greedy stream
        resumes token-exact with its remaining emit budget; the consumer
        keeps every token already emitted and sees no seam."""
        from ...util.events import emit

        slot = self.slots[idx]
        request = slot.request
        freed = len(slot.pages)
        generated = list(request.gen_tokens)
        request.prompt = list(request.prompt) + generated
        request.max_tokens = slot.emit_remaining
        request.gen_tokens = []
        request.parked = True
        self.allocator.free(slot.pages)
        slot.pages = []
        slot.request = None
        slot.position = 0
        slot.prefill_offset = 0
        slot.stalled = False
        slot.dispatch_remaining = 0
        slot.done_dispatching = False
        slot.blocks_in_flight = 0
        slot.awaiting_first = False
        slot.emit_remaining = 0
        slot.finished_emit = False
        slot.spec_ctx = None
        slot.spec_inflight = False
        slot.preempt_pending = False
        self.block_tables[idx, :] = 0
        # parked lanes keep their place: front of their (priority, tenant)
        # lane, no fresh virtual-time charge
        self._fair.requeue(request, request.tenant, request.priority)
        # park wait charges into the preempt_wait TTFT bucket at resume
        request.enqueued_at = time.perf_counter()
        reqlog.mark(request.request_id, "engine.preempted",
                    tenant=request.tenant, lane=idx, pages=freed,
                    generated=len(generated))
        self.metrics["lane_preemptions"] += 1
        self.metrics["preempted_pages"] += float(freed)
        emit(
            "INFO",
            "serve",
            f"preempted decode lane slot={idx} rid={request.rid} "
            f"tenant={request.tenant} pages={freed}",
            kind="serve.lane_preempted",
            rid=request.rid,
            tenant=request.tenant,
            pages=freed,
        )
        return freed

    def _reclaim_pages(self, incoming: _Request, need: int) -> bool:
        """Page-pressure preemption: preempt strictly lower-priority
        lanes until the pages they hold (counting lanes already marked
        pending) cover `need`. Quiescent victims release immediately;
        pipelined ones release on the drain sweep a tick later — the
        caller's requeue keeps the incoming request's place meanwhile.
        Returns True when enough pages are free RIGHT NOW to retry."""
        expected = self.allocator.available + sum(
            len(s.pages) for s in self.slots if s.preempt_pending
        )
        while expected < need:
            victim = self._pick_victim(incoming.priority)
            if victim is None:
                break
            expected += len(self.slots[victim].pages)
            self._request_preempt(victim)
        return self.allocator.available >= need

    def _preempt_for_head(self) -> None:
        """High-priority admissions must not wedge behind low-priority
        long decodes: when every slot is busy and the fair head outranks
        an eligible lane, preempt one victim so the head seats as soon
        as the victim's pipeline drains (same tick when quiescent). One
        pending park at a time — never cascade victims for one head."""
        if not len(self._fair) or any(s.free for s in self.slots):
            return
        if any(s.preempt_pending for s in self.slots):
            return  # a park is already on the way for this wedge
        head = self._fair.peek()
        if head is None:
            return
        victim = self._pick_victim(head.priority)
        if victim is not None:
            self._request_preempt(victim)

    def _admit(self) -> None:
        from ...util.events import emit

        self._drain_submits()
        if self._preemption_enabled():
            self._sweep_pending_preemptions()
            self._preempt_for_head()
        for idx, slot in enumerate(self.slots):
            if not slot.free:
                continue
            if not len(self._fair):
                return
            request = self._next_admissible()
            if request is None:
                return
            # Prefix reuse: the longest cached page-aligned prefix of the
            # prompt arrives pre-filled (lookup takes this slot's refs);
            # only the tail still needs chunk prefill.
            hit: List[int] = (
                self.prefix_cache.lookup(request.prompt)
                if self.prefix_cache is not None else []
            )
            # hit pages can be chunk-misaligned, so cap fresh pages at the
            # block-table width (prefill tops up page-by-page from there)
            fresh_n = min(
                self.paged.chunk_pages,
                self.paged.max_pages_per_slot - len(hit),
            )
            pages = self._alloc_pages(fresh_n)
            if pages is None and self._preemption_enabled():
                if self._reclaim_pages(request, fresh_n):
                    pages = self._alloc_pages(fresh_n)
            if pages is None:
                if hit:
                    self.allocator.free(hit)
                # deferred admission keeps its place: front of its lane,
                # no fresh virtual-time charge
                self._fair.requeue(request, request.tenant, request.priority)
                self.metrics["page_stalls"] += 1
                if not request.stall_marked:
                    request.stall_marked = True
                    reqlog.mark(request.request_id, "engine.page_stall",
                                tenant=request.tenant, reason="admit",
                                need_pages=fresh_n)
                return
            request.stall_marked = False
            wait = _charge_wait(request)
            request.cached_tokens = len(hit) * self.paged.page_size
            if request.parked:
                request.parked = False
                self.metrics["lane_resumes"] += 1
                emit(
                    "INFO",
                    "serve",
                    f"resuming preempted lane rid={request.rid} "
                    f"tenant={request.tenant}",
                    kind="serve.lane_resumed",
                    rid=request.rid,
                    tenant=request.tenant,
                )
                reqlog.mark(request.request_id, "engine.resumed",
                            tenant=request.tenant, lane=idx, wait_s=wait,
                            hit_pages=len(hit))
            else:
                reqlog.mark(request.request_id, "engine.admitted",
                            tenant=request.tenant, lane=idx, wait_s=wait,
                            hit_pages=len(hit),
                            cached_tokens=request.cached_tokens)
            slot.request = request
            slot.pages = list(hit) + pages
            slot.position = 0
            slot.prefill_offset = len(hit) * self.paged.page_size
            slot.prefill_t0 = time.time()
            if request.span is not None:
                request.span.set_attribute(
                    "queue_s", time.perf_counter() - request.submitted_at
                )
            slot.stalled = False
            slot.dispatch_remaining = 0
            slot.done_dispatching = False
            slot.blocks_in_flight = 0
            slot.awaiting_first = False
            slot.emit_remaining = request.max_tokens
            slot.finished_emit = False
            slot.spec_ctx = None
            slot.spec_inflight = False
            slot.preempt_pending = False
            self.block_tables[idx, :] = 0
            self.block_tables[idx, : len(slot.pages)] = slot.pages

    # --------------------------------------------------------------- prefill

    def _ensure_private_page(self, idx: int, slot: _PagedSlot,
                             page_index: int) -> bool:
        """Copy-on-write guard before a decode write: if the page at the
        write frontier is shared (prefix cache pin or another slot), copy
        its KV stripes to a fresh page, swap the block table, and drop
        this slot's ref on the shared original. Page-granular sharing plus
        forward-only writes means the engine never organically writes a
        shared page today (lookup stops short of the first page a request
        writes); the guard makes that invariant enforced rather than
        assumed. Returns False (and stalls the lane) if no page is free
        for the copy."""
        if self.prefix_cache is None:
            return True
        page = slot.pages[page_index]
        if page <= 0 or self.allocator.refcount(page) <= 1:
            return True
        fresh = self._alloc_pages(1)
        if fresh is None:
            if not slot.stalled:
                slot.stalled = True
                self.metrics["page_stalls"] += 1
                reqlog.mark(slot.request.request_id, "engine.page_stall",
                            tenant=slot.request.tenant, reason="cow")
            return False
        self.cache = self._copy_page(
            self.cache, jnp.asarray(page, jnp.int32),
            jnp.asarray(fresh[0], jnp.int32),
        )
        self.allocator.free([page])
        slot.pages[page_index] = fresh[0]
        self.block_tables[idx, page_index] = fresh[0]
        self.metrics["prefix_cache_cow"] += 1
        reqlog.mark(slot.request.request_id, "engine.cow",
                    tenant=slot.request.tenant, page=page,
                    fresh_page=fresh[0])
        return True

    def _mixed_tick(self) -> bool:
        """THE mixed tick: one ragged-paged-attention device call ingests
        a chunk for EVERY prefilling slot AND advances every decodable
        lane one step. Prefill lanes pad to the next power of two (a
        handful of compiled programs covers every burst size); decode
        lanes ride along in the same launch instead of waiting behind the
        prefill backlog, so a burst of long prompts no longer freezes
        running streams for its whole duration (the split
        batched-chunk/decode-block dispatch it replaces preferred prefill
        for whole ticks at a time). Final chunks sample their first
        tokens on device, batched. Decode-only ticks return False and the
        K-step fused decode block (steady state) takes over."""
        ct = self.paged.chunk_tokens
        cp = self.paged.chunk_pages
        ps = self.paged.page_size
        maxp = self.paged.max_pages_per_slot
        ms = self.config.max_slots
        work: List[Tuple[int, int, int]] = []  # (slot_idx, offset, first_page)
        for idx, slot in enumerate(self.slots):
            if not slot.prefilling:
                continue
            offset = slot.prefill_offset
            first_page = offset // ps
            # a prefix hit can leave first_page chunk-misaligned, so the
            # chunk's page window may brush the block-table cap: grow only
            # to the cap — window pages past it stay scratch-mapped, and
            # only pad rows land there (real tokens always fit in maxp
            # pages by the submit() capacity check)
            need = min(first_page + cp, maxp) - len(slot.pages)
            if need > 0:
                extra = self._alloc_pages(need)
                if extra is None:
                    if not slot.stalled:
                        reqlog.mark(slot.request.request_id,
                                    "engine.page_stall",
                                    tenant=slot.request.tenant,
                                    reason="prefill_growth")
                    slot.stalled = True
                    self.metrics["page_stalls"] += 1
                    continue
                slot.pages.extend(extra)
                self.block_tables[idx, : len(slot.pages)] = slot.pages
            slot.stalled = False
            work.append((idx, offset, first_page))
        if not work:
            return False
        b = 1 << (len(work) - 1).bit_length()
        b = min(b, ms)
        tokens = np.zeros((b, ct), dtype=np.int32)
        page_rows = np.zeros((b + ms, maxp), dtype=np.int32)
        chunk_ids = np.zeros((b, cp), dtype=np.int32)  # inactive → scratch 0
        offsets = np.zeros((b,), dtype=np.int32)
        totals = np.zeros((b,), dtype=np.int32)  # 0 = inactive lane
        for lane, (idx, offset, first_page) in enumerate(work):
            slot = self.slots[idx]
            prompt = slot.request.prompt
            n_real = min(ct, len(prompt) - offset)
            self.metrics["prefill_tokens"] += float(n_real)
            reqlog.mark(slot.request.request_id, "engine.prefill_chunk",
                        tenant=slot.request.tenant, offset=offset,
                        tokens=n_real)
            tokens[lane, :n_real] = prompt[offset : offset + n_real]
            page_rows[lane] = self.block_tables[idx]
            window = slot.pages[first_page : first_page + cp]
            chunk_ids[lane, : len(window)] = window
            offsets[lane] = offset
            totals[lane] = offset + n_real
        # ---- decode ride-along: every decodable lane advances one step
        # (or, in speculative mode, one drafted verify round) in the same
        # launch (gated like a decode block: its fetch entry occupies an
        # inflight slot)
        spec = self.spec_tokens > 0
        dec_positions = np.zeros((ms,), dtype=np.int32)
        dec_active = np.zeros((ms,), dtype=np.int32)
        dec_temps = np.zeros((ms,), dtype=np.float32)
        dec_ks = np.zeros((ms,), dtype=np.int32)
        dec_ps = np.ones((ms,), dtype=np.float32)
        dec_tokens_np = (
            np.zeros((ms, self._spec_width), dtype=np.int32) if spec else None
        )
        dec_lanes: List[Tuple[int, _Request, bool]] = []
        spec_lanes: List[Tuple[int, _Request, int, int, int]] = []
        if self._inflight < self.config.max_inflight_blocks:
            if spec:
                spec_lanes = self._gather_spec_rounds(
                    page_rows, b, dec_tokens_np, dec_positions, dec_active,
                    dec_temps, dec_ks, dec_ps,
                )
            else:
                cap = self.paged.max_slot_tokens
                for i, slot in enumerate(self.slots):
                    if not slot.decodable:
                        continue
                    if slot.position + 1 > cap:
                        slot.done_dispatching = True
                        continue
                    pages_needed = slot.position // ps + 1
                    if pages_needed > len(slot.pages):
                        extra = self._alloc_pages(
                            pages_needed - len(slot.pages)
                        )
                        if extra is None:
                            if not slot.stalled:
                                slot.stalled = True
                                self.metrics["page_stalls"] += 1
                            continue
                        slot.pages.extend(extra)
                        self.block_tables[i, : len(slot.pages)] = slot.pages
                    if not self._ensure_private_page(
                        i, slot, slot.position // ps
                    ):
                        continue
                    slot.stalled = False
                    page_rows[b + i] = self.block_tables[i]
                    dec_positions[i] = slot.position
                    dec_active[i] = 1
                    dec_temps[i] = slot.request.temperature
                    dec_ks[i] = slot.request.top_k
                    dec_ps[i] = slot.request.top_p
                    dec_lanes.append((i, slot.request, slot.awaiting_first))
                    slot.awaiting_first = False
        logits, dec_logits, self.cache = self._mixed(
            self.params,
            self.cache,
            jnp.asarray(page_rows),
            jnp.asarray(chunk_ids),
            jnp.asarray(tokens),
            jnp.asarray(offsets),
            jnp.asarray(totals),
            jnp.asarray(dec_tokens_np) if spec else self._tokens_dev,
            jnp.asarray(dec_positions),
            jnp.asarray(dec_active),
        )
        self.metrics["mixed_ticks"] += 1
        if dec_lanes or spec_lanes:
            self.metrics["mixed_ticks_with_decode"] += 1
        if spec_lanes:
            self._finish_spec_dispatch(
                dec_logits, spec_lanes, dec_tokens_np, dec_active,
                dec_temps, dec_ks, dec_ps,
            )
        # ---- decode bookkeeping: sample, merge, and ship the pair of
        # token rows exactly like a K=1 decode block
        if dec_lanes:
            self._key, sub = jax.random.split(self._key)
            sampled = self._sample(
                dec_logits, sub, jnp.asarray(dec_temps),
                jnp.asarray(dec_ks), jnp.asarray(dec_ps),
            )
            stacked, merged = self._dec_pack(
                self._tokens_dev, sampled, jnp.asarray(dec_active == 1)
            )
            self._tokens_dev = merged
            _async_fetch(stacked)
            for i, request, _ in dec_lanes:
                slot = self.slots[i]
                reqlog.mark(request.request_id, "engine.decode_block",
                            tenant=request.tenant, steps=1)
                slot.position += 1
                slot.dispatch_remaining -= 1
                slot.blocks_in_flight += 1
                if slot.dispatch_remaining <= 0:
                    slot.done_dispatching = True
            self._inflight += 1
            self._fetchq.put(("block", dec_lanes, stacked))
            self.metrics["decode_blocks"] += 1
            self.metrics["decode_steps"] += 1
        # ---- prefill bookkeeping + batched first-token sampling
        lane_slots = np.full((b,), self.config.max_slots, dtype=np.int32)
        temps = np.zeros((b,), dtype=np.float32)
        top_ks = np.zeros((b,), dtype=np.int32)
        top_ps = np.ones((b,), dtype=np.float32)
        finished: List[Tuple[int, int]] = []
        for lane, (idx, offset, first_page) in enumerate(work):
            slot = self.slots[idx]
            slot.prefill_offset = int(totals[lane])
            slot.position = int(totals[lane])
            self.metrics["prefill_chunks"] += 1
            if not slot.prefilling:
                request = slot.request
                from ...util import tracing

                tracing.tracer().record_span(
                    "engine.prefill", slot.prefill_t0, time.time(),
                    parent=(request.span.context
                            if request.span is not None else None),
                    lane=f"engine:slot{idx}",
                    attrs={"rid": request.rid,
                           "prompt_tokens": len(request.prompt)},
                )
                finished.append((lane, idx))
                lane_slots[lane] = idx
                temps[lane] = request.temperature
                top_ks[lane] = request.top_k
                top_ps[lane] = request.top_p
                if self.prefix_cache is not None:
                    # publish every page the finished prompt fully covers
                    # (their KV is final: decode writes start past them)
                    self.prefix_cache.register(request.prompt, slot.pages)
        if finished:
            self._key, sub = jax.random.split(self._key)
            sampled = self._sample(
                logits, sub, jnp.asarray(temps), jnp.asarray(top_ks),
                jnp.asarray(top_ps),
            )
            self._tokens_dev = self._scatter_tokens(
                self._tokens_dev, jnp.asarray(lane_slots), sampled
            )
            for lane, idx in finished:
                slot = self.slots[idx]
                request = slot.request
                slot.dispatch_remaining = request.max_tokens - 1
                if slot.dispatch_remaining <= 0:
                    slot.done_dispatching = True
                if self.spec_tokens or slot.dispatch_remaining <= 0:
                    # spec mode drafts on the HOST, so the first token's
                    # value must round-trip before the first verify round
                    # can be proposed — fetch it now through the async
                    # pipeline ("first" seeds spec_ctx). Also the rare
                    # max_tokens=1 path, where no decode block will ever
                    # carry this lane's first token.
                    first_dev = self._take(self._tokens_dev, idx)
                    _async_fetch(first_dev)
                    self._inflight += 1
                    self._fetchq.put(("first", (idx, request), first_dev))
                else:
                    slot.awaiting_first = True
        return True

    # Historical name: drivers and tests tick prefill through it; it now
    # runs the full mixed tick (prefill chunks + decode ride-along).
    _prefill_tick = _mixed_tick

    # ---------------------------------------------------------------- decode

    def _dispatch_decode_block(self) -> bool:
        """Launch one K-step fused decode+sample block for every decodable
        lane. No host reads: results drain later via _drain()."""
        K = self.config.decode_block_steps
        ps = self.paged.page_size
        cap = self.paged.max_slot_tokens
        bt = np.zeros_like(self.block_tables)  # inactive lanes → scratch
        positions = np.zeros(len(self.slots), dtype=np.int32)
        temps = np.zeros(len(self.slots), dtype=np.float32)
        top_ks = np.zeros(len(self.slots), dtype=np.int32)
        top_ps = np.ones(len(self.slots), dtype=np.float32)
        lanes: List[Tuple[int, _Request]] = []
        useful_steps: Dict[int, int] = {}
        for i, slot in enumerate(self.slots):
            if not slot.decodable:
                continue
            # Only the USEFUL steps of a lane's final block need real
            # pages; overshoot steps (budget < K) write to unmapped block
            # table entries, i.e. the scratch page, and their sampled
            # tokens are dropped at emission.
            useful = min(K, slot.dispatch_remaining)
            if slot.position + useful > cap:
                # cannot fit the remaining budget before page capacity:
                # stop here and let emission retire the stream (possibly
                # short of max_tokens when budget brushes capacity)
                slot.done_dispatching = True
                continue
            pages_needed = (slot.position + useful - 1) // ps + 1
            if pages_needed > len(slot.pages):
                extra = self._alloc_pages(pages_needed - len(slot.pages))
                if extra is None:
                    if not slot.stalled:
                        slot.stalled = True
                        self.metrics["page_stalls"] += 1
                        reqlog.mark(slot.request.request_id,
                                    "engine.page_stall",
                                    tenant=slot.request.tenant,
                                    reason="decode_growth")
                    continue
                slot.pages.extend(extra)
                self.block_tables[i, : len(slot.pages)] = slot.pages
            # COW: every page this block will write must be privately held
            if not all(
                self._ensure_private_page(i, slot, pi)
                for pi in range(slot.position // ps, pages_needed)
            ):
                continue
            slot.stalled = False
            bt[i] = self.block_tables[i]
            positions[i] = slot.position
            temps[i] = slot.request.temperature
            top_ks[i] = slot.request.top_k
            top_ps[i] = slot.request.top_p
            useful_steps[i] = useful
            lanes.append((i, slot.request, slot.awaiting_first))
            slot.awaiting_first = False
        if not lanes:
            return False
        self._key, sub = jax.random.split(self._key)
        common = (
            self.params,
            self.cache,
            jnp.asarray(bt),
            self._tokens_dev,
            jnp.asarray(positions),
            sub,
            jnp.asarray(temps),
        )
        # all-plain batches (the common case) skip the per-step vocab sort
        if (top_ks > 0).any() or (top_ps < 1.0).any():
            toks, final, self.cache = self._decode_block_filtered(
                *common, jnp.asarray(top_ks), jnp.asarray(top_ps)
            )
        else:
            if self._tick_cost is None:
                # before the dispatch consumes the donated cache: price
                # the fused K-step decode block once
                self._tick_cost = _tick_cost(
                    self._decode_block_plain, *common
                ) or False
            toks, final, self.cache = self._decode_block_plain(*common)
        # Per-lane merge: lanes excluded from this dispatch keep their
        # pending token (see _merge_tokens docstring).
        mask = np.zeros(len(self.slots), dtype=bool)
        for i, _, _ in lanes:
            mask[i] = True
        self._tokens_dev = self._merge_tokens(
            self._tokens_dev, final, jnp.asarray(mask)
        )
        _async_fetch(toks)
        for i, request, _ in lanes:
            slot = self.slots[i]
            reqlog.mark(request.request_id, "engine.decode_block",
                        tenant=request.tenant, steps=useful_steps[i])
            slot.position += useful_steps[i]
            slot.dispatch_remaining -= K
            slot.blocks_in_flight += 1
            if slot.dispatch_remaining <= 0:
                slot.done_dispatching = True
        self._inflight += 1
        self._fetchq.put(("block", lanes, toks))
        self.metrics["decode_blocks"] += 1
        self.metrics["decode_steps"] += K
        return True

    # ---------------------------------------------------- speculative decode

    def _gather_spec_rounds(
        self,
        page_rows: np.ndarray,
        base: int,
        dec_tokens: np.ndarray,
        dec_positions: np.ndarray,
        dec_active: np.ndarray,
        dec_temps: np.ndarray,
        dec_ks: np.ndarray,
        dec_ps: np.ndarray,
    ) -> List[Tuple[int, _Request, int, int, int]]:
        """Fill one verify round per ready lane into the mixed-tick decode
        arrays: row 0 the lane's pending token (its KV write was deferred
        to this round), rows 1.. the proposer's drafts, dispatched as a
        q_len=count ragged region at positions position..position+count-1.
        Pages are grown to cover the whole round up front (COW-guarded);
        the drain side rolls back whatever rejection leaves unused. A lane
        needs spec_ctx (seeded by its "first" fetch) and at most one round
        in flight. Returns the dispatched (idx, request, dispatch_position,
        count) list."""
        ps = self.paged.page_size
        cap = self.paged.max_slot_tokens
        lanes: List[Tuple[int, _Request, int, int, int]] = []
        for i, slot in enumerate(self.slots):
            if (
                not slot.decodable
                or slot.spec_inflight
                or slot.spec_ctx is None
            ):
                continue
            # a round with c inputs emits at most c tokens and writes c KV
            # rows: cap the width by both budgets
            width = min(
                self._spec_width, cap - slot.position,
                slot.dispatch_remaining,
            )
            if width <= 0:
                slot.done_dispatching = True
                continue
            drafts: List[int] = []
            if width > 1 and self._proposer is not None:
                try:
                    drafts = list(
                        self._proposer.propose(slot.spec_ctx, width - 1)
                    )[: width - 1]
                except Exception:
                    drafts = []  # a broken proposer degrades to plain decode
            count = 1 + len(drafts)
            pre_pages = len(slot.pages)  # rollback floor: only pages this
            # round grows are ever trimmed back (admit-time spares stay)
            pages_needed = (slot.position + count - 1) // ps + 1
            if pages_needed > len(slot.pages):
                extra = self._alloc_pages(pages_needed - len(slot.pages))
                if extra is None:
                    if not slot.stalled:
                        slot.stalled = True
                        self.metrics["page_stalls"] += 1
                        reqlog.mark(slot.request.request_id,
                                    "engine.page_stall",
                                    tenant=slot.request.tenant,
                                    reason="spec_growth")
                    continue
                slot.pages.extend(extra)
                self.block_tables[i, : len(slot.pages)] = slot.pages
            # COW: every page this round may write must be privately held
            if not all(
                self._ensure_private_page(i, slot, pi)
                for pi in range(slot.position // ps, pages_needed)
            ):
                continue
            slot.stalled = False
            page_rows[base + i] = self.block_tables[i]
            dec_tokens[i, 0] = slot.spec_ctx[-1]
            if drafts:
                dec_tokens[i, 1:count] = drafts
            dec_positions[i] = slot.position
            dec_active[i] = count
            dec_temps[i] = slot.request.temperature
            dec_ks[i] = slot.request.top_k
            dec_ps[i] = slot.request.top_p
            slot.spec_inflight = True
            slot.blocks_in_flight += 1
            self.metrics["spec_proposed"] += float(len(drafts))
            lanes.append((i, slot.request, slot.position, count, pre_pages))
        return lanes

    def _finish_spec_dispatch(
        self,
        dec_logits: jax.Array,
        spec_lanes: List[Tuple[int, _Request, int, int, int]],
        dec_tokens: np.ndarray,
        dec_active: np.ndarray,
        dec_temps: np.ndarray,
        dec_ks: np.ndarray,
        dec_ps: np.ndarray,
    ) -> None:
        """Score the dispatched rounds on device (exact accept/resample)
        and ship ONE packed (tokens + counts) array through the async
        fetch pipeline — verify logits never cross to the host and the
        dispatch thread never blocks on a device read."""
        self._key, sub = jax.random.split(self._key)
        packed = self._spec_accept(
            dec_logits, jnp.asarray(dec_tokens), jnp.asarray(dec_active),
            sub, jnp.asarray(dec_temps), jnp.asarray(dec_ks),
            jnp.asarray(dec_ps),
        )
        _async_fetch(packed)
        self._inflight += 1
        self._fetchq.put(("spec", spec_lanes, packed))
        self.metrics["decode_blocks"] += 1
        self.metrics["decode_steps"] += 1  # one launch, however many tokens

    def _dispatch_spec_verify(self) -> bool:
        """Decode-only verify tick — the speculative steady state. One
        ragged launch scores every ready lane's drafted round; the single
        prefill lane is inactive (zero totals, scratch-mapped) so the call
        reuses the b=1 compiled bucket of the mixed step."""
        pc = self.paged
        ms = self.config.max_slots
        if self._inflight >= self.config.max_inflight_blocks:
            return False
        page_rows = np.zeros((1 + ms, pc.max_pages_per_slot), dtype=np.int32)
        dec_tokens = np.zeros((ms, self._spec_width), dtype=np.int32)
        dec_positions = np.zeros((ms,), dtype=np.int32)
        dec_active = np.zeros((ms,), dtype=np.int32)
        dec_temps = np.zeros((ms,), dtype=np.float32)
        dec_ks = np.zeros((ms,), dtype=np.int32)
        dec_ps = np.ones((ms,), dtype=np.float32)
        spec_lanes = self._gather_spec_rounds(
            page_rows, 1, dec_tokens, dec_positions, dec_active,
            dec_temps, dec_ks, dec_ps,
        )
        if not spec_lanes:
            return False
        _, dec_logits, self.cache = self._mixed(
            self.params,
            self.cache,
            jnp.asarray(page_rows),
            jnp.zeros((1, pc.chunk_pages), jnp.int32),
            jnp.zeros((1, pc.chunk_tokens), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            jnp.asarray(dec_tokens),
            jnp.asarray(dec_positions),
            jnp.asarray(dec_active),
        )
        self._finish_spec_dispatch(
            dec_logits, spec_lanes, dec_tokens, dec_active,
            dec_temps, dec_ks, dec_ps,
        )
        return True

    # -------------------------------------------------------------- emission

    def _drain_worker(self) -> None:
        """Dedicated thread that pays the device→host read latency.
        Everything queued is fetched in ONE jax.device_get batch: each
        separate read is a blocking transfer, N batched reads cost one,
        so backlog amortizes instead of serializing. FIFO order is
        preserved (a request's first token is
        enqueued before any of its decode blocks)."""
        while True:
            item = self._fetchq.get()
            if item is None:
                return
            batch = [item]
            while True:
                try:
                    nxt = self._fetchq.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    self._fetchq.put(None)  # re-post shutdown sentinel
                    break
                batch.append(nxt)
            # One fetch thread per entry: transfers overlap across threads
            # (a single device_get over pending computations serializes —
            # wait-compute then fetch, per array, each paying the RTT).
            all_vals: List[Any] = [None] * len(batch)
            errors: List[BaseException] = []

            def fetch(i: int, arr) -> None:
                try:
                    all_vals[i] = np.asarray(arr)
                except BaseException as exc:  # noqa: BLE001 - device boundary
                    errors.append(exc)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=fetch, args=(i, b[2]), daemon=True)
                for i, b in enumerate(batch)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            self.drain_log.append((len(batch), time.perf_counter() - t0))
            if len(self.drain_log) > 1000:
                del self.drain_log[:500]
            if errors:
                self._doneq.put(("error", errors[0], None))
                return
            for (kind, meta, _), vals in zip(batch, all_vals):
                self._doneq.put((kind, meta, vals))

    def _pump_completed(self, wait: bool = False) -> bool:
        """Emit every completed fetch. wait=True blocks briefly for one
        (used when nothing is dispatchable, so the loop makes progress)."""
        drained = False
        while True:
            try:
                timeout = 0.05 if (wait and not drained) else None
                entry = (
                    self._doneq.get(timeout=timeout)
                    if timeout is not None
                    else self._doneq.get_nowait()
                )
            except queue.Empty:
                return drained
            kind, meta, vals = entry
            if kind == "error":
                raise meta
            self._inflight -= 1
            drained = True
            if kind == "first":
                idx, request = meta
                token = int(vals[0])
                slot = self.slots[idx]
                if (
                    self.spec_tokens
                    and slot.request is request
                    and not slot.finished_emit
                ):
                    # seed the host-side draft context: everything the
                    # proposer may condition on (prompt + first token)
                    slot.spec_ctx = list(request.prompt) + [token]
                self._emit(idx, request, token, first=True)
                self._maybe_retire(idx, request)
            elif kind == "spec":
                self._complete_spec_round(meta, vals)
            else:
                # vals is (K+1, B): row 0 = the block's input tokens —
                # emitted only for lanes whose first token rides this block
                for k in range(vals.shape[0]):
                    for idx, request, fresh in meta:
                        if k == 0 and not fresh:
                            continue
                        self._emit(idx, request, int(vals[k, idx]), first=(k == 0))
                for idx, request, _ in meta:
                    slot = self.slots[idx]
                    if slot.request is request:
                        slot.blocks_in_flight -= 1
                    self._maybe_retire(idx, request)

    def _complete_spec_round(
        self, meta: List[Tuple[int, _Request, int, int, int]], vals: np.ndarray
    ) -> None:
        """Drain one verify round: emit the accepted prefix + the
        corrected/bonus token, advance the lane to the accepted frontier,
        and ROLL BACK pages speculated past it. vals is the packed
        (max_slots, W+1) array — columns [:W] emit-ordered tokens, column
        W the emitted count m (1 <= m <= count for live lanes).

        Rollback safety: the trimmed pages can never be shared. The round
        wrote positions >= dispatch_pos >= len(prompt) + 1, so the kept
        frontier keep = (new_pos-1)//ps + 1 strictly exceeds both the
        prefix-cache hit count (lookup caps at (len(prompt)-1)//ps pages)
        and everything register() publishes (len(prompt)//ps fully-covered
        pages) — trimmed indices are all fresh allocations this engine
        grew for speculated tokens, refcount 1, and free() returns them to
        the pool. Stale KV left in kept pages at rows [new_pos,
        dispatch_pos+count) is masked by every future launch's kv_len
        until the lane's forward writes overwrite it."""
        ps = self.paged.page_size
        for idx, request, dpos, count, pre_pages in meta:
            slot = self.slots[idx]
            m = int(vals[idx, -1])
            self.metrics["spec_accepted"] += float(max(0, m - 1))
            if slot.request is not request:
                continue  # retired mid-flight (deadline/EOS): pages freed
            slot.spec_inflight = False
            slot.blocks_in_flight -= 1
            new_pos = dpos + m
            slot.position = new_pos
            # free only pages THIS round grew past the accepted frontier
            # (admit-time spares below pre_pages stay mapped — trimming
            # them would churn the allocator every round on short prompts)
            keep = max((new_pos - 1) // ps + 1, pre_pages)
            rolled = 0
            if keep < len(slot.pages):
                trimmed = slot.pages[keep:]
                slot.pages = slot.pages[:keep]
                self.allocator.free(trimmed)
                self.block_tables[idx, keep:] = 0
                rolled = len(trimmed)
                self.metrics["spec_rollback_pages"] += float(rolled)
            reqlog.mark(request.request_id, "engine.spec_round",
                        tenant=request.tenant, proposed=count - 1,
                        accepted=m - 1, rollback_pages=rolled)
            slot.dispatch_remaining -= m
            if slot.dispatch_remaining <= 0:
                slot.done_dispatching = True
            emitted = [int(vals[idx, j]) for j in range(m)]
            if slot.spec_ctx is not None:
                slot.spec_ctx.extend(emitted)
            for tok in emitted:
                self._emit(idx, request, tok)
            self._maybe_retire(idx, request)

    def _emit(self, idx: int, request: _Request, token: int, first: bool = False) -> None:
        slot = self.slots[idx]
        if slot.request is not request or slot.finished_emit:
            return  # stale block for an already-retired stream
        if first and request.first_token_at is None:
            request.first_token_at = time.perf_counter()
            buckets = _observe_tenant_ttft(request)
            reqlog.mark(request.request_id, "engine.first_token",
                        tenant=request.tenant, **buckets)
        request.generated += 1
        request.out.put(token)
        # the resume ledger: a preempted lane folds these into its prompt
        request.gen_tokens.append(int(token))
        slot.emit_remaining -= 1
        self.metrics["generated_tokens"] += 1
        if not first:  # first tokens are the prefill's output
            self.metrics["decode_tokens"] += 1.0
        if (
            token == self.config.eos_id
            or token in request.stop_token_ids
            or _hit_stop_sequence(request, token)
            or slot.emit_remaining <= 0
        ):
            slot.finished_emit = True

    def _maybe_retire(self, idx: int, request: _Request) -> None:
        slot = self.slots[idx]
        if slot.request is not request:
            return
        if slot.finished_emit or (
            slot.done_dispatching and slot.blocks_in_flight == 0
        ):
            self._finish(idx, slot)

    def _finish(self, idx: int, slot: _PagedSlot) -> None:
        if slot.request is not None:
            if slot.request.span is not None:
                # span=None means the timeout path already sealed this
                # request with its own terminal mark
                reqlog.mark(slot.request.request_id, "engine.finished",
                            tenant=slot.request.tenant,
                            generated=slot.request.generated)
            _finish_request_span(slot.request)
            slot.request.out.put(None)
        self.allocator.free(slot.pages)
        slot.pages = []
        slot.request = None
        slot.stalled = False
        slot.dispatch_remaining = 0
        slot.blocks_in_flight = 0
        slot.finished_emit = False
        slot.spec_ctx = None
        slot.spec_inflight = False
        self.block_tables[idx, :] = 0

    # ------------------------------------------------------------------ loop

    def _deadline_sweep(self) -> None:
        """Evict slots whose request outlived its deadline: the stream
        fails with a typed RequestTimeoutError and the slot's pages
        return to the pool (late in-flight blocks for the evicted lane
        are benign — same guarantee as EOS retirement, module header)."""
        now = time.time()
        for idx, slot in enumerate(self.slots):
            request = slot.request
            if (
                request is None
                or slot.finished_emit
                or request.deadline_ts is None
                or now < request.deadline_ts
            ):
                continue
            self.metrics["timeouts"] = self.metrics.get("timeouts", 0.0) + 1
            _timeout_request(request)
            slot.finished_emit = True
            self._maybe_retire(idx, request)

    def _all_stalled_deadlock(self) -> Optional[int]:
        """Every occupied slot waits on an empty pool and nothing is in
        flight: truncate the largest page-holder rather than deadlock."""
        occupied = [(i, s) for i, s in enumerate(self.slots) if not s.free]
        if not occupied or self._inflight:
            return None
        if all(s.stalled or s.prefilling for _, s in occupied) and (
            self.allocator.available == 0
        ):
            return max(occupied, key=lambda t: len(t[1].pages))[0]
        return None

    def _loop(self) -> None:
        try:
            self._loop_inner()
        except BaseException as exc:  # noqa: BLE001 - engine death boundary
            self._death_cause = exc
            # queued fair-lane requests (deferred admissions included)
            # fail like freshly queued ones
            for request in self._fair.drain():
                self._queue.put(request)
            _fail_all_requests(self.slots, self._queue, exc)
            raise

    def _loop_inner(self) -> None:
        pc = self.paged
        while not self._stop.is_set():
            tick_t0 = time.perf_counter()
            self._admit()
            self._deadline_sweep()
            progressed = self._prefill_tick()
            # Prefer draining the prefill backlog before launching a decode
            # block: chunks are sub-millisecond, and grouping admissions
            # into ONE joint block minimizes device-to-host fetches.
            if not progressed and self._inflight < self.config.max_inflight_blocks:
                progressed |= (
                    self._dispatch_spec_verify()
                    if self.spec_tokens
                    else self._dispatch_decode_block()
                )
            if self.spec_tokens:
                # a spec lane is only dispatchable once its "first" fetch
                # has seeded the draft context and its previous round has
                # drained — otherwise the loop must WAIT on the drain
                # queue, not spin
                dispatchable = any(
                    s.prefilling
                    or (
                        s.decodable
                        and not s.spec_inflight
                        and s.spec_ctx is not None
                    )
                    for s in self.slots
                )
            else:
                dispatchable = any(
                    s.decodable or s.prefilling for s in self.slots
                )
            gated = self._inflight >= self.config.max_inflight_blocks
            progressed |= self._pump_completed(
                wait=self._inflight > 0 and (gated or not dispatchable)
            )
            # Safety sweep: a lane can become retirable outside any pending
            # block (e.g. the capacity gate fired with nothing in flight).
            for i, slot in enumerate(self.slots):
                if slot.request is not None and not slot.prefilling:
                    self._maybe_retire(i, slot.request)
            occupied = sum(1 for s in self.slots if not s.free)
            self.metrics["ongoing"] = (
                occupied + self._queue.qsize() + len(self._fair)
            )
            self.metrics["pages_in_use"] = float(
                pc.num_pages - 1 - self.allocator.available
            )
            self.metrics["batch_fill"] = occupied / max(len(self.slots), 1)
            if self.prefix_cache is not None:
                pcs = self.prefix_cache.stats()
                self.metrics["prefix_cache_hits"] = pcs["hits"]
                self.metrics["prefix_cache_misses"] = pcs["misses"]
                self.metrics["prefix_cache_evictions"] = pcs["evictions"]
                self.metrics["prefix_cache_pages"] = pcs["pages"]
                self.metrics["prefix_cache_hit_rate"] = pcs["hit_rate"]
            if self.spec_tokens:
                prop = self.metrics["spec_proposed"]
                self.metrics["spec_acceptance_rate"] = (
                    self.metrics["spec_accepted"] / prop if prop else 0.0
                )
            if progressed:
                _observe_tick(self, time.perf_counter() - tick_t0)
            if occupied == 0 and not self._inflight:
                self._wake.wait(timeout=0.02)
                self._wake.clear()
                continue
            if not progressed:
                victim = self._all_stalled_deadlock()
                if victim is not None:
                    self._finish(victim, self.slots[victim])
                else:
                    time.sleep(0.001)


def _async_fetch(arr: jax.Array) -> None:
    """Start the device→host transfer without blocking (falls back to a
    no-op where the runtime lacks copy_to_host_async; np.asarray later
    then pays the full read)."""
    start = getattr(arr, "copy_to_host_async", None)
    if start is not None:
        try:
            start()
        except Exception:
            pass
