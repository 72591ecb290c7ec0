"""Paged KV cache + chunked prefill: the TPU continuous-batching substrate.

Reference parity: vLLM's paged attention + chunked prefill, which the
reference rides via VLLMEngine (/root/reference/python/ray/llm/_internal/
serve/deployments/llm/vllm/vllm_engine.py:254). TPU inversion (the ragged
paged attention recipe from PAPERS.md): XLA needs static shapes, so

- the KV cache is one FLAT pool of pages, (Hkv, L*num_pages, page_size, D)
  — layer i owns page range [i*num_pages, (i+1)*num_pages) — shared by
  every slot; a host-side allocator hands out (layer-agnostic) page ids
  and a per-slot block table maps logical positions to pages. HBM no
  longer scales with max_slots × max_seq — concurrency is bounded by
  actual tokens, like vLLM;
- decode attention reads ONLY the pages a slot uses: on TPU via the Pallas
  paged-attention kernel (scalar-prefetched block tables drive the block
  index_map, so unused pages are never fetched); off-TPU via a gather+mask
  XLA reference with identical semantics;
- prefill is CHUNKED: prompts are ingested page-aligned chunk by chunk
  (one chunk per engine tick), each chunk attending to the pages written
  so far — so a long prompt never blocks running decodes for more than
  one chunk's latency, and every chunk reuses ONE compiled program
  (offset is a traced scalar, the chunk length is static).

Page 0 is reserved as a scratch page: idle decode lanes write there and
block-table rows default to it, so the fixed-shape decode program needs no
host-side compaction.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer import TransformerConfig, _no_eva, _no_latent_attention, _norm
from ...ops import apply_rope, rope_frequencies
from ...ops.ragged_paged_attention import (
    RAGGED_KERNEL,
    ragged_paged_attention,
    resolve_ragged_impl,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    page_size: int = 64
    num_pages: int = 256          # pool size (page 0 reserved as scratch)
    max_pages_per_slot: int = 16  # static block-table width
    chunk_pages: int = 4          # prefill chunk = chunk_pages * page_size
    # Prefix/KV-cache reuse (PrefixCache): requests sharing a page-aligned
    # prompt prefix reuse its KV instead of re-prefilling. Off by default —
    # retired prompts then PIN their pages (cache holds a ref) until pool
    # pressure evicts them, which changes allocator-accounting invariants
    # tests and capacity planning may rely on.
    prefix_cache: bool = False
    prefix_cache_pages: int = 0   # max cached pages; 0 = pool-pressure only

    @property
    def chunk_tokens(self) -> int:
        return self.chunk_pages * self.page_size

    @property
    def max_slot_tokens(self) -> int:
        return self.max_pages_per_slot * self.page_size


def init_paged_cache(
    model: TransformerConfig, paged: PagedConfig
) -> Dict[str, jax.Array]:
    """One FLAT page pool across layers: layer i owns pages
    [i*num_pages, (i+1)*num_pages). Folding the layer axis into the page
    axis is what keeps every cache access O(pages touched): updates are
    provably-aliasing dynamic_update_slices and reads are single gathers
    driven by per-layer-offset block tables — no per-layer slab ever
    materializes. (A (L, ...) leading axis forces XLA to either scan-
    double-buffer or slice out ~pool/L per layer per step; measured 8x
    decode slowdown at 512 pages.)"""
    _no_latent_attention(model, "the paged engine")
    _no_eva(model, "the paged engine")
    shape = (
        model.kv_heads,
        model.n_layers * paged.num_pages,
        paged.page_size,
        model.head_dim,
    )
    return {"k": jnp.zeros(shape, model.dtype), "v": jnp.zeros(shape, model.dtype)}


class PageAllocator:
    """Host-side REFCOUNTED free list over the page pool.

    Prefix caching means a physical page can back several block tables at
    once (N slots sharing a system prompt, plus the cache's own pin), so
    ownership is a count, not a set: `alloc` hands out pages at refcount 1,
    `share` adds a holder, and `free` drops one — the page returns to the
    free list only when the LAST holder lets go. Page 0 is the scratch
    page: never handed out, never refcounted, and `free`/`share` ignore it.
    """

    def __init__(self, num_pages: int):
        self._free = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        self._lock = threading.Lock()

    def alloc(self, n: int) -> Optional[List[int]]:
        with self._lock:
            if len(self._free) < n:
                return None
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._refs[p] = 1
            return pages

    def share(self, pages: Sequence[int]) -> None:
        """Add one holder to each page. Sharing a page that is not
        currently allocated is a caller bug and raises — silently
        resurrecting a freed page would corrupt whichever slot the free
        list hands it to next."""
        with self._lock:
            for p in pages:
                if p <= 0:
                    continue
                if p not in self._refs:
                    raise ValueError(f"share of unallocated page {p}")
                self._refs[p] += 1

    def free(self, pages: Sequence[int]) -> None:
        # Drop ONE holder per page. The double-free guard survives from the
        # pre-refcount allocator: a page with no live holders is ignored, so
        # a buggy caller can never put the same physical page on the free
        # list twice (which would hand it to two slots and corrupt both).
        with self._lock:
            for p in pages:
                if p > 0 and p in self._refs:
                    self._refs[p] -= 1
                    if self._refs[p] <= 0:
                        del self._refs[p]
                        self._free.append(p)

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._refs.get(page, 0)

    @property
    def available(self) -> int:
        with self._lock:
            return len(self._free)


# ---------------------------------------------------------------- prefix cache


def _chain_hash(prev: bytes, chunk: Sequence[int]) -> bytes:
    """Collision-resistant chain hash of page-aligned token chunks.

    KV for a page is a pure function of every token up to the page's end
    (causal attention), so keying page p by H(H(...), tokens of page p)
    makes a hit sufficient for reuse. blake2b rather than python hash():
    a tuple-hash collision would silently splice one prompt's KV into
    another request."""
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(np.asarray(chunk, dtype=np.int64).tobytes())
    return h.digest()


class PrefixCache:
    """Refcounted page-level prefix cache over the allocator.

    Maps the chain hash of each fully-prompt-covered page to the physical
    page holding its KV. The cache itself holds ONE reference per entry
    (the pin that keeps a finished request's prompt pages warm); every
    slot that reuses a page takes its own reference via `allocator.share`.
    Eviction (LRU, and only of pages whose sole holder is the cache) is
    driven by pool pressure: the engine calls `evict` when an alloc
    fails, so cached prefixes never starve admissions — but pages still
    referenced by live slots are pinned and survive the sweep.
    """

    def __init__(self, allocator: PageAllocator, page_size: int,
                 capacity_pages: int = 0):
        self.allocator = allocator
        self.page_size = page_size
        self.capacity_pages = capacity_pages  # 0 = bounded by pool pressure only
        self._entries: "OrderedDict[bytes, int]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def lookup(self, prompt: Sequence[int]) -> List[int]:
        """Longest cached page-aligned prefix of `prompt`, capped so at
        least one prompt token is always left to prefill (its logits seed
        sampling — vLLM caps its hit the same way). Matched pages get one
        reference taken FOR THE CALLER; the caller releases them through
        the normal refcounted free path when the slot retires."""
        ps = self.page_size
        max_reuse = max(0, (len(prompt) - 1) // ps)
        matched: List[int] = []
        digest = b""
        with self._lock:
            for p in range(max_reuse):
                digest = _chain_hash(digest, prompt[p * ps:(p + 1) * ps])
                page = self._entries.get(digest)
                if page is None:
                    break
                matched.append(page)
                self._entries.move_to_end(digest)
            self.hits += len(matched)
            self.misses += max_reuse - len(matched)
        if matched:
            self.allocator.share(matched)
        return matched

    def register(self, prompt: Sequence[int], pages: Sequence[int]) -> int:
        """Publish every page fully covered by `prompt` (KV already
        written by this slot's prefill). The cache takes its own reference
        per NEW entry; hashes already present keep their existing page.
        Returns the number of pages newly published."""
        ps = self.page_size
        full = len(prompt) // ps
        added = 0
        with self._lock:
            digest = b""
            for p in range(full):
                digest = _chain_hash(digest, prompt[p * ps:(p + 1) * ps])
                if digest in self._entries:
                    self._entries.move_to_end(digest)
                    continue
                if (
                    self.capacity_pages > 0
                    and len(self._entries) >= self.capacity_pages
                    and not self._evict_locked(1)
                ):
                    break
                page = pages[p]
                self.allocator.share([page])
                self._entries[digest] = page
                self._entries.move_to_end(digest)
                added += 1
        return added

    def evict(self, n: int) -> int:
        """Release up to n cache-pinned pages back toward the pool (LRU
        first, skipping pages live slots still hold)."""
        with self._lock:
            return self._evict_locked(n)

    def _evict_locked(self, n: int) -> int:
        dropped = 0
        for digest, page in list(self._entries.items()):
            if dropped >= n:
                break
            if self.allocator.refcount(page) != 1:
                continue  # pinned by a live slot: survives the sweep
            del self._entries[digest]
            self.allocator.free([page])
            self.evictions += 1
            dropped += 1
        return dropped

    def stats(self) -> Dict[str, float]:
        with self._lock:
            hits, misses = self.hits, self.misses
            return {
                "hits": float(hits),
                "misses": float(misses),
                "evictions": float(self.evictions),
                "pages": float(len(self._entries)),
                "hit_rate": hits / max(1, hits + misses),
            }

    def chain_heads(self, limit: int = 64) -> List[Dict[str, Any]]:
        """MRU-first view of the cached chain entries for engine
        introspection (`engine.snapshot()`): each row is one published
        page keyed by its blake2b chain-hash head, with its live
        refcount (1 = pinned only by the cache, >1 = shared by slots)."""
        with self._lock:
            rows = [
                {"digest": digest.hex(), "page": page}
                for digest, page in reversed(self._entries.items())
            ][:limit]
        for row in rows:
            row["refcount"] = self.allocator.refcount(row["page"])
        return rows


# ------------------------------------------------------------------ attention


def _gather_ref_attention(q, k_cache, v_cache, block_tables, lengths):
    """XLA reference paged attention. q (B, Hq, D); caches
    (Hkv, P, ps, D); block_tables (B, maxP); lengths (B,). Returns (B, Hq, D).
    Semantics ground truth for the Pallas kernel (and the CPU path)."""
    b, hq, d = q.shape
    hkv, _, ps, _ = k_cache.shape
    # (B, maxP, Hkv, ps, D) -> (B, Hkv, maxP*ps, D)
    k = jnp.swapaxes(k_cache[:, block_tables], 0, 1)
    v = jnp.swapaxes(v_cache[:, block_tables], 0, 1)
    k = k.reshape(b, hkv, -1, d)
    v = v.reshape(b, hkv, -1, d)
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    logits = jnp.einsum(
        "bhd,bhkd->bhk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(d)
    mask = jnp.arange(k.shape[2])[None, :] < lengths[:, None]
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", probs.astype(v.dtype), v)


def paged_attention(q, k_cache, v_cache, block_tables, lengths, *, page_size: int,
                    use_kernel: Optional[bool] = None, mesh=None,
                    interpret: bool = False):
    """Decode-step paged attention: the q_len == 1 case of the ragged
    kernel. Dispatch is `resolve_ragged_impl`'s static rule: the Pallas
    ragged kernel on a TPU backend at Mosaic-tileable shapes
    (head_dim % 128 == 0, page_size % 8 == 0 — Llama-class models), the
    gather reference elsewhere (CPU, tiny test configs, GPT-2's 64-wide
    heads).

    Tensor parallelism: the kernel path is `shard_map`-wrapped over the
    tp mesh axis inside `ragged_paged_attention` (GSPMD cannot partition
    a pallas_call, but both head axes divide by tp, so each shard runs
    the kernel on its local head group); pass `mesh`. The gather
    reference partitions cleanly on the kv-head axis under plain GSPMD."""
    b, hq, head_dim = q.shape
    block_q = 8
    impl = resolve_ragged_impl(
        head_dim, page_size, block_q, use_kernel=use_kernel,
        interpret=interpret,
    )
    if impl == RAGGED_KERNEL:
        # adapt (B, Hq, D) single-token lanes to the ragged layout: one
        # block_q-row region per lane, real row 0, q_len 1
        q_r = jnp.swapaxes(q, 0, 1)[:, :, None, :]  # (Hq, B, 1, D)
        q_r = jnp.pad(q_r, ((0, 0), (0, 0), (0, block_q - 1), (0, 0)))
        q_r = q_r.reshape(hq, b * block_q, head_dim)
        ones = jnp.ones((b,), jnp.int32)
        out = ragged_paged_attention(
            q_r, k_cache, v_cache,
            jnp.arange(b, dtype=jnp.int32), ones, ones, lengths,
            block_tables,
            block_q=block_q, max_q_blocks=1,
            use_kernel=True, interpret=interpret, mesh=mesh,
        )
        out = out.reshape(hq, b, block_q, head_dim)[:, :, 0, :]
        return jnp.swapaxes(out, 0, 1)  # (B, Hq, D)
    return _gather_ref_attention(q, k_cache, v_cache, block_tables, lengths)


# --------------------------------------------------------------- model passes


def batched_chunk_prefill_step(
    params: Params,
    cache: Dict[str, jax.Array],
    page_rows: jax.Array,       # (B, maxP) block tables of the batched slots
    chunk_page_ids: jax.Array,  # (B, chunk_pages) pages each chunk fills
    tokens: jax.Array,          # (B, C) chunks, right-padded
    offsets: jax.Array,         # (B,) tokens already ingested (page-aligned)
    total_lens: jax.Array,      # (B,) offset + real tokens this chunk
    config: TransformerConfig,
    *,
    page_size: int,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Ingest one prompt chunk for up to B slots in ONE device call —
    burst admissions prefill together instead of serializing TTFT
    (vLLM batches prefill chunks across sequences the same way;
    reference vllm_engine.py:254). Inactive lanes point their
    chunk_page_ids at the scratch page (0) with total_len 0: they burn
    lane FLOPs but write only garbage the attention masks off.

    Returns the LAST real token's logits per lane (B, V) — only the
    lanes finishing their prompt this tick sample from them.
    """
    c = config
    dt = c.dtype
    b, chunk = tokens.shape
    chunk_pages = chunk // page_size
    pos = offsets[:, None] + jnp.arange(chunk)[None, :]  # (B, C)
    x = params["wte"].astype(dt)[tokens]  # (B, C, E)
    if c.pos_emb == "learned":
        x = x + params["wpe"].astype(dt)[jnp.clip(pos, 0, c.max_seq - 1)]
        rope_tables = None
    else:
        rope_tables = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    flat_ids = chunk_page_ids.reshape(-1)  # (B*cp,) — scratch dups are fine

    # Unrolled layers over the FLAT page pool (see init_paged_cache):
    # page writes are per-page DUS (in place), reads gather only each
    # lane's tables shifted into the layer's page range.
    k_full, v_full = cache["k"], cache["v"]
    num_pages = k_full.shape[1] // c.n_layers
    zero = jnp.int32(0)
    for i in range(c.n_layers):
        lp = {name: w[i] for name, w in params["blocks"].items()}
        h = _norm(x, lp["ln1_scale"], lp.get("ln1_bias"), c.norm, c.norm_eps)
        q = jnp.einsum("bse,ehd->bhsd", h, lp["wq"].astype(dt))
        k = jnp.einsum("bse,ehd->bhsd", h, lp["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bhsd", h, lp["wv"].astype(dt))
        if c.use_bias:
            q = q + lp["bq"].astype(dt)[None, :, None, :]
            k = k + lp["bk"].astype(dt)[None, :, None, :]
            v = v + lp["bv"].astype(dt)[None, :, None, :]
        if rope_tables is not None:
            cos, sin = rope_tables
            q = apply_rope(q, cos, sin, pos)
            k = apply_rope(k, cos, sin, pos)
        # whole-page in-place writes: one DUS per (lane, chunk page)
        kp = (
            k.transpose(1, 0, 2, 3)
            .reshape(k.shape[1], b * chunk_pages, page_size, k.shape[-1])
            .astype(c.dtype)
        )
        vp = (
            v.transpose(1, 0, 2, 3)
            .reshape(v.shape[1], b * chunk_pages, page_size, v.shape[-1])
            .astype(c.dtype)
        )
        layer_flat = flat_ids + i * num_pages
        for j in range(b * chunk_pages):
            start = (zero, layer_flat[j], zero, zero)
            k_full = jax.lax.dynamic_update_slice(k_full, kp[:, j][:, None], start)
            v_full = jax.lax.dynamic_update_slice(v_full, vp[:, j][:, None], start)
        # per-lane gathered attention over each slot's own pages
        layer_rows = page_rows + i * num_pages  # (B, maxP)
        keys = jnp.swapaxes(k_full[:, layer_rows], 0, 1)  # (B, Hkv, maxP, ps, D)
        vals = jnp.swapaxes(v_full[:, layer_rows], 0, 1)
        keys = keys.reshape(b, keys.shape[1], -1, keys.shape[-1])
        vals = vals.reshape(b, vals.shape[1], -1, vals.shape[-1])
        hq, hkv = q.shape[1], keys.shape[1]
        if hq != hkv:
            keys = jnp.repeat(keys, hq // hkv, axis=1)
            vals = jnp.repeat(vals, hq // hkv, axis=1)
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", q, keys, preferred_element_type=jnp.float32
        ) / math.sqrt(q.shape[-1])
        key_pos = jnp.arange(keys.shape[2])
        causal = key_pos[None, None, :] <= pos[:, :, None]       # (B, C, S)
        valid = key_pos[None, None, :] < total_lens[:, None, None]
        logits = jnp.where((causal & valid)[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        attn = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vals.dtype), vals)
        out = jnp.einsum("bhsd,hde->bse", attn.astype(dt), lp["wo"].astype(dt))
        if c.use_bias:
            out = out + lp["bo"].astype(dt)
        x = x + out
        h = _norm(x, lp["ln2_scale"], lp.get("ln2_bias"), c.norm, c.norm_eps)
        up = jnp.einsum("bse,ef->bsf", h, lp["w_up"].astype(dt))
        if c.use_bias:
            up = up + lp["b_up"].astype(dt)
        if c.act == "swiglu":
            from ...ops import swiglu

            act = swiglu(jnp.einsum("bse,ef->bsf", h, lp["w_gate"].astype(dt)), up)
        else:
            from ...ops import gelu

            act = gelu(up)
        down = jnp.einsum("bsf,fe->bse", act, lp["w_down"].astype(dt))
        if c.use_bias:
            down = down + lp["b_down"].astype(dt)
        x = x + down
    x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), c.norm, c.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["wte"].T
    # vocab projection ONLY for each lane's last real token (B, E) @ (E, V)
    last = jnp.clip(total_lens - offsets - 1, 0, chunk - 1)
    x_last = x[jnp.arange(b), last]  # (B, E)
    logits = jnp.einsum("be,ev->bv", x_last, head.astype(dt))
    return logits, {"k": k_full, "v": v_full}


def ragged_mixed_step(
    params: Params,
    cache: Dict[str, jax.Array],
    page_rows: jax.Array,       # (P+B, maxP) tables: prefill lanes then decode
    chunk_page_ids: jax.Array,  # (P, cp) pages each prefill chunk fills
    prefill_tokens: jax.Array,  # (P, C) chunks, right-padded
    offsets: jax.Array,         # (P,) tokens already ingested (page-aligned)
    totals: jax.Array,          # (P,) offset + real tokens (0 = inactive)
    dec_tokens: jax.Array,      # (B,) or (B, Kd) decode input tokens
    dec_positions: jax.Array,   # (B,) decode write positions (first token)
    dec_active: jax.Array,      # (B,) int32 real tokens this tick (0..Kd)
    config: TransformerConfig,
    *,
    page_size: int,
    block_q: int = 8,
    use_kernel: Optional[bool] = None,
    mesh=None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """ONE device call for a mixed tick: P prefill chunks AND B decode
    lanes run through a single token-major transformer pass whose
    attention is one ragged-paged-attention launch per layer. This
    replaces the split batched_chunk_prefill_step + paged_decode_step
    dispatch: a tick with both kinds of work used to pay two compiled
    programs and two passes over the page pool.

    Token-major layout: T = P*C + B*R rows (R = ceil(Kd/block_q)*block_q).
    Prefill lane p owns rows [p*C, (p+1)*C) (C = chunk tokens, a multiple
    of block_q); decode lane b owns the R-row region at P*C + b*R with its
    dec_active[b] real tokens at rows 0.. — ONE token for plain decode,
    1 + drafts for a speculative verify round (the pending token plus the
    drafted continuation, scored causally in the same launch exactly like
    a prefill chunk). The ragged descriptor (q_lens = chunk fill / count /
    0, kv_lens = totals / position+count / 0) masks everything else off,
    so inactive lanes and pad rows burn FLOPs but write only to the
    scratch page (a pad row near capacity must NOT clamp its page-table
    gather onto the lane's own live page — it is explicitly routed to
    page 0).

    Returns (prefill last-token logits (P, V), decode logits — (B, V) for
    1-D dec_tokens, else (B, Kd, V) with row j scoring the token after
    input row j — and the updated cache).
    """
    c = config
    dt = c.dtype
    p_lanes, chunk = prefill_tokens.shape
    squeeze_dec = dec_tokens.ndim == 1
    if squeeze_dec:
        dec_tokens = dec_tokens[:, None]
    b_lanes, dec_width = dec_tokens.shape
    chunk_pages = chunk // page_size
    if chunk % block_q:
        raise ValueError(f"chunk tokens ({chunk}) must divide by block_q "
                         f"({block_q})")
    dec_blocks = -(-dec_width // block_q)
    dec_region = dec_blocks * block_q  # rows per decode lane
    dec_counts = dec_active.astype(jnp.int32)
    t_tokens = p_lanes * chunk + b_lanes * dec_region

    # ---- token-major embedding -------------------------------------------
    pre_pos = offsets[:, None] + jnp.arange(chunk)[None, :]     # (P, C)
    dec_pos_grid = dec_positions[:, None] + jnp.arange(dec_width)[None, :]
    dec_region_pos = jnp.zeros((b_lanes, dec_region), jnp.int32).at[
        :, :dec_width
    ].set(dec_pos_grid)
    positions = jnp.concatenate(
        [pre_pos.reshape(-1), dec_region_pos.reshape(-1)]
    )  # (T,)
    dec_region_tok = jnp.zeros((b_lanes, dec_region), jnp.int32).at[
        :, :dec_width
    ].set(dec_tokens)
    tokens = jnp.concatenate(
        [prefill_tokens.reshape(-1), dec_region_tok.reshape(-1)]
    )  # (T,)
    x = params["wte"].astype(dt)[tokens]  # (T, E)
    if c.pos_emb == "learned":
        x = x + params["wpe"].astype(dt)[jnp.clip(positions, 0, c.max_seq - 1)]
        rope_tables = None
    else:
        rope_tables = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    # ---- ragged descriptor (static regions, dynamic lengths) -------------
    cb = chunk // block_q
    starts = jnp.concatenate([
        jnp.arange(p_lanes, dtype=jnp.int32) * cb,
        p_lanes * cb + jnp.arange(b_lanes, dtype=jnp.int32) * dec_blocks,
    ])
    counts = jnp.concatenate([
        jnp.full((p_lanes,), cb, jnp.int32),
        jnp.full((b_lanes,), dec_blocks, jnp.int32),
    ])
    q_lens = jnp.concatenate([
        (totals - offsets).astype(jnp.int32),
        dec_counts,
    ])
    kv_lens = jnp.concatenate([
        totals.astype(jnp.int32),
        (dec_positions + dec_counts) * (dec_counts > 0),
    ])

    flat_ids = chunk_page_ids.reshape(-1)                 # (P*cp,)
    # per-(lane, token) page/row targets: token j of lane b lands at
    # position dec_positions[b] + j. Rows past dec_counts[b] (pad rows,
    # shrunken verify rounds) go to the scratch page — the gather index
    # is clamped so a lane near max_pages can't wrap, and the page is
    # forced to 0 so a clamped gather can't alias the lane's live KV.
    maxp = page_rows.shape[1]
    valid_tok = jnp.arange(dec_width)[None, :] < dec_counts[:, None]
    page_idx = jnp.clip(dec_pos_grid // page_size, 0, maxp - 1)
    gathered = page_rows[p_lanes + jnp.arange(b_lanes)[:, None], page_idx]
    dec_pages = jnp.where(valid_tok, gathered, 0)          # (B, Kd)
    dec_rows = jnp.where(valid_tok, dec_pos_grid % page_size, 0)

    k_full, v_full = cache["k"], cache["v"]
    num_pages = k_full.shape[1] // c.n_layers
    zero = jnp.int32(0)
    for i in range(c.n_layers):
        lp = {name: w[i] for name, w in params["blocks"].items()}
        h = _norm(x, lp["ln1_scale"], lp.get("ln1_bias"), c.norm, c.norm_eps)
        # heads-leading token-major projections: (T, E) @ (E, H, D) -> (H, T, D)
        q = jnp.einsum("te,ehd->htd", h, lp["wq"].astype(dt))
        k = jnp.einsum("te,ehd->htd", h, lp["wk"].astype(dt))
        v = jnp.einsum("te,ehd->htd", h, lp["wv"].astype(dt))
        if c.use_bias:
            q = q + lp["bq"].astype(dt)[:, None, :]
            k = k + lp["bk"].astype(dt)[:, None, :]
            v = v + lp["bv"].astype(dt)[:, None, :]
        if rope_tables is not None:
            cos, sin = rope_tables
            q = apply_rope(q[None], cos, sin, positions[None])[0]
            k = apply_rope(k[None], cos, sin, positions[None])[0]
        # prefill KV: whole-page DUS per (lane, chunk page), as in
        # batched_chunk_prefill_step
        layer_flat = flat_ids + i * num_pages
        kp = (
            k[:, : p_lanes * chunk]
            .reshape(k.shape[0], p_lanes * chunk_pages, page_size, k.shape[-1])
            .astype(c.dtype)
        )
        vp = (
            v[:, : p_lanes * chunk]
            .reshape(v.shape[0], p_lanes * chunk_pages, page_size, v.shape[-1])
            .astype(c.dtype)
        )
        for j in range(p_lanes * chunk_pages):
            start = (zero, layer_flat[j], zero, zero)
            k_full = jax.lax.dynamic_update_slice(k_full, kp[:, j][:, None], start)
            v_full = jax.lax.dynamic_update_slice(v_full, vp[:, j][:, None], start)
        # decode KV: per-(lane, token) row DUS at (page, row), as in
        # paged_decode_step; 2*B*Kd DUS per layer (Kd=1 for plain decode)
        for lane in range(b_lanes):
            for j in range(dec_width):
                row_idx = p_lanes * chunk + lane * dec_region + j
                upd_k = k[:, row_idx].astype(c.dtype)[:, None, None, :]
                upd_v = v[:, row_idx].astype(c.dtype)[:, None, None, :]
                start = (zero, dec_pages[lane, j] + i * num_pages,
                         dec_rows[lane, j], zero)
                k_full = jax.lax.dynamic_update_slice(k_full, upd_k, start)
                v_full = jax.lax.dynamic_update_slice(v_full, upd_v, start)
        # ONE ragged attention launch for every lane, prefill and decode
        attn = ragged_paged_attention(
            q, k_full, v_full, starts, counts, q_lens, kv_lens,
            page_rows + i * num_pages,
            block_q=block_q, max_q_blocks=max(cb, dec_blocks),
            use_kernel=use_kernel, mesh=mesh, interpret=interpret,
        )  # (Hq, T, D)
        out = jnp.einsum("htd,hde->te", attn.astype(dt), lp["wo"].astype(dt))
        if c.use_bias:
            out = out + lp["bo"].astype(dt)
        x = x + out
        h = _norm(x, lp["ln2_scale"], lp.get("ln2_bias"), c.norm, c.norm_eps)
        up = jnp.einsum("te,ef->tf", h, lp["w_up"].astype(dt))
        if c.use_bias:
            up = up + lp["b_up"].astype(dt)
        if c.act == "swiglu":
            from ...ops import swiglu

            act = swiglu(jnp.einsum("te,ef->tf", h, lp["w_gate"].astype(dt)), up)
        else:
            from ...ops import gelu

            act = gelu(up)
        down = jnp.einsum("tf,fe->te", act, lp["w_down"].astype(dt))
        if c.use_bias:
            down = down + lp["b_down"].astype(dt)
        x = x + down
    x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), c.norm, c.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["wte"].T
    # vocab projection ONLY for sample rows: each prefill lane's last real
    # token and each decode lane's Kd token rows (all of them — a verify
    # round needs every row's logits to score the drafted continuation)
    last = jnp.clip(totals - offsets - 1, 0, chunk - 1)
    pre_rows = jnp.arange(p_lanes) * chunk + last
    dec_rows_x = (
        p_lanes * chunk
        + (jnp.arange(b_lanes) * dec_region)[:, None]
        + jnp.arange(dec_width)[None, :]
    ).reshape(-1)
    x_sample = x[jnp.concatenate([pre_rows, dec_rows_x])]  # (P+B*Kd, E)
    logits = jnp.einsum("be,ev->bv", x_sample, head.astype(dt))
    dec_logits = logits[p_lanes:].reshape(b_lanes, dec_width, -1)
    if squeeze_dec:
        dec_logits = dec_logits[:, 0]
    return logits[:p_lanes], dec_logits, {"k": k_full, "v": v_full}


def copy_page(
    cache: Dict[str, jax.Array], src: jax.Array, dst: jax.Array,
    *, n_layers: int,
) -> Dict[str, jax.Array]:
    """Copy one logical page (every layer's stripe) src -> dst in the flat
    pool: the device half of copy-on-write divergence. Layer i's stripe
    lives at page + i*num_pages (see init_paged_cache)."""
    k_full, v_full = cache["k"], cache["v"]
    num_pages = k_full.shape[1] // n_layers
    zero = jnp.int32(0)
    for i in range(n_layers):
        s = src + i * num_pages
        d = dst + i * num_pages
        k_pg = jax.lax.dynamic_slice(
            k_full, (zero, s, zero, zero),
            (k_full.shape[0], 1, k_full.shape[2], k_full.shape[3]),
        )
        v_pg = jax.lax.dynamic_slice(
            v_full, (zero, s, zero, zero),
            (v_full.shape[0], 1, v_full.shape[2], v_full.shape[3]),
        )
        k_full = jax.lax.dynamic_update_slice(k_full, k_pg, (zero, d, zero, zero))
        v_full = jax.lax.dynamic_update_slice(v_full, v_pg, (zero, d, zero, zero))
    return {"k": k_full, "v": v_full}


def paged_decode_step(
    params: Params,
    cache: Dict[str, jax.Array],
    block_tables: jax.Array,  # (B, maxP) int32
    tokens: jax.Array,        # (B,) int32
    positions: jax.Array,     # (B,) int32 — write slot; length = position + 1
    config: TransformerConfig,
    *,
    page_size: int,
    use_kernel: Optional[bool] = None,
    mesh=None,
    interpret: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One continuous-batching decode step over the paged cache."""
    c = config
    dt = c.dtype
    b = tokens.shape[0]
    x = params["wte"].astype(dt)[tokens][:, None, :]  # (B, 1, E)
    if c.pos_emb == "learned":
        x = x + params["wpe"].astype(dt)[positions][:, None, :]
        rope_tables = None
    else:
        rope_tables = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    lengths = positions + 1
    page_ids = block_tables[jnp.arange(b), positions // page_size]  # (B,)
    rows = positions % page_size  # (B,)

    # Layers are UNROLLED (python loop) over the FLAT page pool (see
    # init_paged_cache): per-layer block tables are the slot's tables
    # shifted into layer i's page range, updates are per-lane DUS (in
    # place on the donated pool), reads gather only the table's pages.
    k_full, v_full = cache["k"], cache["v"]
    num_pages = k_full.shape[1] // c.n_layers
    for i in range(c.n_layers):
        lp = {name: w[i] for name, w in params["blocks"].items()}
        layer_tables = block_tables + i * num_pages
        layer_pages = page_ids + i * num_pages
        h = _norm(x, lp["ln1_scale"], lp.get("ln1_bias"), c.norm, c.norm_eps)
        q = jnp.einsum("bse,ehd->bhsd", h, lp["wq"].astype(dt))
        k = jnp.einsum("bse,ehd->bhsd", h, lp["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bhsd", h, lp["wv"].astype(dt))
        if c.use_bias:
            q = q + lp["bq"].astype(dt)[None, :, None, :]
            k = k + lp["bk"].astype(dt)[None, :, None, :]
            v = v + lp["bv"].astype(dt)[None, :, None, :]
        if rope_tables is not None:
            cos, sin = rope_tables
            pos2d = positions[:, None]
            q = apply_rope(q, cos, sin, pos2d)
            k = apply_rope(k, cos, sin, pos2d)
        # Write this token's K/V into each slot's current page/row with
        # per-lane dynamic_update_slice — the canonical in-place KV-cache
        # update (a scatter over mixed indices lowers to a transposing
        # scatter that copies pool-sized buffers; DUS provably aliases).
        # Cost model: 2*B DUS ops per (unrolled) layer, so trace/compile
        # time scales with B*L — paid once per batch bucket at engine
        # precompile, never per request. Worth it: execution went 762ms ->
        # 52ms per 24-step block at a 1.2GB pool on v5e.
        newk = k[:, :, 0, :].astype(c.dtype)  # (B, Hkv, D)
        newv = v[:, :, 0, :].astype(c.dtype)
        zero = jnp.int32(0)
        for lane in range(b):
            upd_k = newk[lane][:, None, None, :]  # (Hkv, 1, 1, D)
            upd_v = newv[lane][:, None, None, :]
            start = (zero, layer_pages[lane], rows[lane], zero)
            k_full = jax.lax.dynamic_update_slice(k_full, upd_k, start)
            v_full = jax.lax.dynamic_update_slice(v_full, upd_v, start)
        attn = paged_attention(
            q[:, :, 0, :], k_full, v_full, layer_tables, lengths,
            page_size=page_size, use_kernel=use_kernel, mesh=mesh,
            interpret=interpret,
        )[:, :, None, :]
        out = jnp.einsum("bhsd,hde->bse", attn.astype(dt), lp["wo"].astype(dt))
        if c.use_bias:
            out = out + lp["bo"].astype(dt)
        x = x + out
        h = _norm(x, lp["ln2_scale"], lp.get("ln2_bias"), c.norm, c.norm_eps)
        up = jnp.einsum("bse,ef->bsf", h, lp["w_up"].astype(dt))
        if c.use_bias:
            up = up + lp["b_up"].astype(dt)
        if c.act == "swiglu":
            from ...ops import swiglu

            gate = jnp.einsum("bse,ef->bsf", h, lp["w_gate"].astype(dt))
            act = swiglu(gate, up)
        else:
            from ...ops import gelu

            act = gelu(up)
        down = jnp.einsum("bsf,fe->bse", act, lp["w_down"].astype(dt))
        if c.use_bias:
            down = down + lp["b_down"].astype(dt)
        x = x + down
    x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), c.norm, c.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["wte"].T
    logits = jnp.einsum("bse,ev->bsv", x, head.astype(dt))[:, 0]
    return logits, {"k": k_full, "v": v_full}


def chunk_prefill_step(
    params: Params,
    cache: Dict[str, jax.Array],
    page_row: jax.Array,      # (maxP,) this slot's block table
    chunk_page_ids: jax.Array,  # (chunk_pages,) pages this chunk fills
    tokens: jax.Array,        # (1, C) the chunk, right-padded
    offset: jax.Array,        # () int32 — tokens already ingested (page-aligned)
    total_len: jax.Array,     # () int32 — offset + real tokens in this chunk
    config: TransformerConfig,
    *,
    page_size: int,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-slot chunk prefill: the B=1 case of
    batched_chunk_prefill_step (kept as the documented one-slot API).
    Returns the last real token's logits (1, V) and the updated pool."""
    return batched_chunk_prefill_step(
        params,
        cache,
        page_row[None],
        chunk_page_ids[None],
        tokens,
        jnp.reshape(offset, (1,)),
        jnp.reshape(total_len, (1,)),
        config,
        page_size=page_size,
    )
