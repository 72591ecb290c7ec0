"""Decoder-only transformer covering the GPT-2 and Llama families.

The reference serves these architectures through vLLM/torch model zoos
(/root/reference/python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_engine.py:254); training rides user torch code under Ray Train
(/root/reference/python/ray/train/torch/config.py:153). Here the models are
first-class and TPU-shaped:

- parameters are a plain pytree with a parallel tree of *logical axis names*
  (ray_tpu.parallel.sharding) — DP/FSDP/TP/SP/EP is a rule-table change,
  never a model change;
- ONE activation is constrained, and only where a step is traced under a
  context mesh with `tp` > 1 (ray_tpu.parallel.sequence_parallel): the
  residual stream between sublayers holds its sequence axis over `tp`
  (`constrain_stream` at the embedding's output and after each sublayer's
  residual add; whole sequences again on the final norm's output, for the
  head), the projections that read it gather it a piece at a time under
  their matmuls (`column_parallel`: `wq/wk/wv`, `w_up/w_gate`) and those that
  write it scatter their partial sums the same way (`row_parallel`: `wo`,
  `w_down`). With no mesh, a manual one or `tp` = 1 the three are the plain
  einsums and the lowered program is what it was;
- layers are stacked on a leading axis and executed with `lax.scan`, so
  compile time is O(1) in depth and remat is one `jax.checkpoint`;
- attention dispatches to the Pallas flash kernel on TPU (ray_tpu.ops);
- one config struct spans GPT-2 (learned pos, layernorm, gelu, tied head)
  and Llama (rope, rmsnorm, swiglu, GQA, untied) — family presets live in
  ray_tpu.models.configs.

Shapes: tokens (B, S) int32 → logits (B, S, V). Decode path carries a dense
KV cache (L, B, Hkv, max_seq, Dh) with per-example write positions, the
substrate for continuous batching in ray_tpu.serve.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import (
    apply_rope,
    flash_attention,
    flash_attention_kept,
    gelu,
    layernorm,
    rmsnorm,
    rope_frequencies,
    swiglu,
)
from ..ops.attention import attention_plan
from ..ops.eva import eva_attention, eva_plan
from ..parallel.sequence_parallel import column_parallel, constrain_stream, row_parallel

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: Optional[int] = None  # None → n_heads (MHA); < n_heads → GQA
    d_ff: int = 3072
    max_seq: int = 1024
    pos_emb: str = "learned"  # "learned" (GPT-2) | "rope" (Llama)
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    act: str = "gelu"  # "gelu" | "swiglu"
    use_bias: bool = True
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    param_dtype: Any = jnp.float32
    # recompute each block in the backward pass: all of it, or, under
    # train/lm.make_train_step, all but the candidates of `block_costs` that
    # the device has room for
    remat: bool = False
    attn_impl: Optional[str] = None  # None → pallas on TPU, xla elsewhere
    causal: bool = True  # False → bidirectional encoder (ViT, CLIP text off)
    fused_qkv: bool = False  # single [E, (Hq+2Hkv)·Dh] projection matmul
    scan_unroll: int = 1  # lax.scan unroll for the layer stack
    qk_norm: bool = False  # RMSNorm over the whole q and k projections (OLMoE)
    norm_eps: Optional[float] = None  # None → 1e-6 (rmsnorm) / 1e-5 (layernorm)
    # What follows is data of an architecture, each None / False for the
    # families above: a head size that is not d_model / n_heads; the QK-norm
    # taken over one head's features (one weight of head_dim for q, one for
    # k) and not over the whole projection; a sigmoid gate on the attention
    # output before its projection (`wg`, as wide as q); a norm on each
    # sublayer's OUTPUT before it joins the residual stream, beside the one on
    # its input ("sandwich"); the embedding scaled by sqrt(d_model).
    d_head: Optional[int] = None
    qk_norm_per_head: bool = False
    attn_gate: bool = False
    sandwich_norm: bool = False
    scale_embedding: bool = False
    # Latent attention (`kv_lora_rank` > 0; the mixed stack's third attention
    # kind): q comes up from a normed latent of `q_lora_rank`, the keys' part
    # without positions and the values come up, a head, from ONE normed latent
    # of `kv_lora_rank` that all heads share, and the last `qk_rope_dim` of a
    # head's `head_dim` features carry rotary positions: q's own, and for the
    # keys one head of `qk_rope_dim` projected beside the latent and given to
    # every head. `q_lora_rank` 0: no q latent, q comes from the normed stream
    # in one projection (`wq`). `v_head_dim` (None: `head_dim`): values narrower
    # than the keys; the kernels take one head size, so q, k and v are padded
    # with zeros to `kernel_head_dim`, which changes no score and no output
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    v_head_dim: Optional[int] = None
    # the output gate (`attn_gate`) as ONE logit a head, `wg` (d_model, heads),
    # and not one a feature
    attn_gate_per_head: bool = False
    # EVA attention (`eva_window` > 0; ops/eva.py: the EvaByte family): exact
    # causal attention inside a window of `eva_window` positions, one learned
    # summary a chunk of `eva_chunk` positions of everything before the window,
    # one softmax over both; a layer's leaves `eva_mu`, `eva_phi` (heads,
    # head_dim) are a head's two pooling vectors. 0: softmax attention over
    # every causal key (or a mixed stack's window)
    eva_window: int = 0
    eva_chunk: int = 0
    # Data of the same family, each False / 1 for the others: an RMSNorm that
    # multiplies by 1 + scale (scale starts at 0); the residual stream carried
    # and added in float32 whatever `dtype` the sublayers compute in; and
    # `pred_heads` next-token heads in the loss, head n a `vocab_size`-column
    # slice of ONE (d_model, pred_heads x vocab_size) head matrix that
    # predicts token t + 1 + n from position t (train/lm.lm_loss)
    norm_unit_offset: bool = False
    residual_fp32: bool = False
    pred_heads: int = 1

    def __post_init__(self):
        if self.eva_window or self.eva_chunk:
            if not (self.eva_chunk > 0 and self.eva_window > 0
                    and self.eva_window % self.eva_chunk == 0):
                raise ValueError(f"eva attention: eva_window {self.eva_window} must be a positive "
                                 f"multiple of eva_chunk {self.eva_chunk}")
            if not (self.causal and self.kv_heads == self.n_heads and self.pos_emb == "rope"
                    and not (self.latent_attention or self.qk_norm or self.qk_norm_per_head
                             or self.attn_gate or self.fused_qkv)):
                raise ValueError(
                    "eva attention: causal, rotary positions, as many key-value heads as query "
                    "heads and none of latent attention, QK-norm, an output gate or a fused "
                    "projection are what the program runs")
        if self.pred_heads < 1 or (self.pred_heads > 1 and self.tie_embeddings):
            raise ValueError(f"pred_heads {self.pred_heads}: at least 1, and more than one head "
                             "needs an untied head matrix")
        if self.norm_unit_offset and self.norm != "rmsnorm":
            raise ValueError("norm_unit_offset is an RMSNorm's (1 + scale)")
        if self.attn_gate_per_head and not self.attn_gate:
            raise ValueError("attn_gate_per_head is a form of attn_gate, which is off")
        if self.latent_attention:
            if not 0 < self.value_dim <= self.head_dim:
                raise ValueError(
                    f"latent attention: v_head_dim {self.v_head_dim} is not in (0, the q/k head size "
                    f"{self.head_dim}]: values are padded up to the keys' width, never cut")
            if not (self.q_lora_rank >= 0 and 0 < self.qk_rope_dim < self.head_dim
                    and self.qk_rope_dim % 2 == 0 and self.kv_heads == self.n_heads):
                raise ValueError(
                    "latent attention: q_lora_rank >= 0, an even qk_rope_dim under the head size "
                    "and as many key-value heads as query heads are what the program runs")
        elif self.v_head_dim not in (None, self.head_dim):
            raise ValueError(f"v_head_dim {self.v_head_dim}: values narrower than the keys are a "
                             "latent-attention layer's")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def value_dim(self) -> int:
        """The features of a head's values (and of the attention output a head)."""
        return self.head_dim if self.v_head_dim is None else self.v_head_dim

    @property
    def kernel_head_dim(self) -> int:
        """The one head size the attention kernels are handed: `head_dim`, or
        where the values are narrower than the keys the next multiple of 128
        lanes, to which q, k and v are padded with zeros."""
        return self.head_dim if self.value_dim == self.head_dim else -(-self.head_dim // 128) * 128

    @property
    def eva_attention(self) -> bool:
        return self.eva_window > 0

    @property
    def stream_dtype(self) -> Any:
        """The dtype of the residual stream: what a block takes and returns."""
        return jnp.float32 if self.residual_fp32 else self.dtype

    @property
    def rotary_dims(self) -> int:
        """The features of a head that rotate, which is the width of the
        rotary table's rows x 2: the whole head, or a latent head's rotary part."""
        return self.qk_rope_dim if self.latent_attention else self.head_dim

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------- init


def init_params(config: TransformerConfig, key: jax.Array) -> Params:
    """GPT-2-style init: N(0, 0.02), residual-out projections scaled by
    1/sqrt(2L). Block params are stacked on a leading layer axis for scan."""
    c = config
    _no_latent_attention(c, "transformer.init_params")
    pd = c.param_dtype
    dh = c.head_dim
    keys = jax.random.split(key, 16)
    std = 0.02
    res_std = std / math.sqrt(2 * c.n_layers)

    def normal(k, shape, s=std):
        # drawn in the parameter dtype: an f32 draw of one stacked bf16
        # weight at 8B widths is a multi-GiB transient on a 16 GB chip
        return s * jax.random.normal(k, shape, pd)

    L = c.n_layers
    # a norm's scale where it multiplies by (1 + scale) starts at 0
    norm_scale = jnp.zeros if c.norm_unit_offset else jnp.ones
    blocks: Params = {
        "ln1_scale": norm_scale((L, c.d_model), pd),
        "wq": normal(keys[0], (L, c.d_model, c.n_heads, dh)),
        "wk": normal(keys[1], (L, c.d_model, c.kv_heads, dh)),
        "wv": normal(keys[2], (L, c.d_model, c.kv_heads, dh)),
        "wo": normal(keys[3], (L, c.n_heads, dh, c.d_model), res_std),
        "ln2_scale": norm_scale((L, c.d_model), pd),
        "w_up": normal(keys[4], (L, c.d_model, c.d_ff)),
        "w_down": normal(keys[5], (L, c.d_ff, c.d_model), res_std),
    }
    if c.act == "swiglu":
        blocks["w_gate"] = normal(keys[6], (L, c.d_model, c.d_ff))
    if c.qk_norm:
        blocks["q_norm_scale"] = jnp.ones((L, c.n_heads * dh), pd)
        blocks["k_norm_scale"] = jnp.ones((L, c.kv_heads * dh), pd)
    if c.eva_attention:
        blocks["eva_mu"] = normal(keys[10], (L, c.n_heads, dh))
        blocks["eva_phi"] = normal(keys[11], (L, c.n_heads, dh))
    if c.norm == "layernorm":
        blocks["ln1_bias"] = jnp.zeros((L, c.d_model), pd)
        blocks["ln2_bias"] = jnp.zeros((L, c.d_model), pd)
    if c.use_bias:
        blocks["bq"] = jnp.zeros((L, c.n_heads, dh), pd)
        blocks["bk"] = jnp.zeros((L, c.kv_heads, dh), pd)
        blocks["bv"] = jnp.zeros((L, c.kv_heads, dh), pd)
        blocks["bo"] = jnp.zeros((L, c.d_model), pd)
        blocks["b_up"] = jnp.zeros((L, c.d_ff), pd)
        blocks["b_down"] = jnp.zeros((L, c.d_model), pd)

    params: Params = {
        "wte": normal(keys[7], (c.vocab_size, c.d_model)),
        "blocks": blocks,
        "lnf_scale": norm_scale((c.d_model,), pd),
    }
    if c.pos_emb == "learned":
        params["wpe"] = normal(keys[8], (c.max_seq, c.d_model), 0.01)
    if c.norm == "layernorm":
        params["lnf_bias"] = jnp.zeros((c.d_model,), pd)
    if not c.tie_embeddings:
        params["lm_head"] = normal(keys[9], (c.d_model, c.pred_heads * c.vocab_size))
    return params


def logical_axes(config: TransformerConfig) -> Params:
    """Logical-axis tree mirroring init_params output (sharding rule input)."""
    c = config
    blocks: Params = {
        "ln1_scale": ("layers", None),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "ln2_scale": ("layers", None),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if c.act == "swiglu":
        blocks["w_gate"] = ("layers", "embed", "mlp")
    if c.qk_norm:
        blocks["q_norm_scale"] = ("layers", None)
        blocks["k_norm_scale"] = ("layers", None)
    if c.eva_attention:
        blocks["eva_mu"] = ("layers", "heads", "head_dim")
        blocks["eva_phi"] = ("layers", "heads", "head_dim")
    if c.norm == "layernorm":
        blocks["ln1_bias"] = ("layers", None)
        blocks["ln2_bias"] = ("layers", None)
    if c.use_bias:
        blocks["bq"] = ("layers", "heads", "head_dim")
        blocks["bk"] = ("layers", "kv_heads", "head_dim")
        blocks["bv"] = ("layers", "kv_heads", "head_dim")
        blocks["bo"] = ("layers", None)
        blocks["b_up"] = ("layers", "mlp")
        blocks["b_down"] = ("layers", None)
    axes: Params = {
        "wte": ("vocab", "embed"),
        "blocks": blocks,
        "lnf_scale": (None,),
    }
    if c.pos_emb == "learned":
        axes["wpe"] = (None, "embed")
    if c.norm == "layernorm":
        axes["lnf_bias"] = (None,)
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# -------------------------------------------------------------------- forward


def _norm(x, scale, bias, kind, eps=None, unit_offset=False):
    """`eps` None keeps each norm's own default (ops/layers); `unit_offset`:
    an RMSNorm that multiplies by 1 + scale."""
    kw = {} if eps is None else {"eps": eps}
    if kind == "rmsnorm":
        return rmsnorm(x, 1.0 + scale.astype(jnp.float32) if unit_offset else scale, **kw)
    return layernorm(x, scale, bias, **kw)


def _block_norm(x, scale, bias, config):
    """A sublayer's norm of the stream `x`, in the dtype the sublayer computes
    in (the stream's own unless it is carried in float32)."""
    c = config
    return _norm(x, scale, bias, c.norm, c.norm_eps, c.norm_unit_offset).astype(c.dtype)


def _qk_norm(x: jax.Array, scale: jax.Array, eps: Optional[float]) -> jax.Array:
    """RMSNorm of a (B, H, S, D) projection over all its H x D features (the
    norm sits before the split into heads, as in OLMoE), scale (H x D,).
    Reduced in place over the two axes: a transpose to (B, S, H x D) and
    back costs 1.6% of the OLMoE cell's step (chip run, PR 27)."""
    _, h, _, d = x.shape
    kw = {} if eps is None else {"eps": eps}
    return rmsnorm(x, scale.reshape(1, h, 1, d), axis=(1, 3), **kw)


def attention_sublayer(
    x: jax.Array,
    lp: Params,
    config: TransformerConfig,
    rope_tables: Optional[Tuple[jax.Array, jax.Array]],
    positions: Optional[jax.Array],
    window: Optional[int] = None,
    remat_saved: Tuple[str, ...] = (),
) -> jax.Array:
    """Pre-norm causal self-attention + residual on (B, S, E). What differs
    between the layers of one stack comes as arguments: `rope_tables` (None:
    this layer encodes no positions) and `window` (None: every causal key).
    `remat_saved`: what the checkpoint around the block keeps. The sublayer
    is the scope `attn.window` or `attn.full`, by its kind, and inside it
    `attn.proj`, `attn.kernel` and `attn.out` (util/profiling.STEP_SCOPES)."""
    with jax.named_scope("attn.full" if window is None else "attn.window"):
        return _attention_sublayer(x, lp, config, rope_tables, positions, window, remat_saved)


def _latent_qkv(h, lp, config, rope_tables, positions):
    """q, k (B, H, S, head_dim) and v (B, H, S, value_dim) of a latent-attention
    layer from the normed stream h (B, S, E): the scope `attn.latent`. q =
    [q_nope | rot(q_rope)] a head from the normed q latent (or, with no q
    latent, from h in the one projection `wq`); k = [k_nope | rot(k_rope)] with
    k_nope and v a head from the normed key-value latent and the ONE rotary
    key head given to all of them. The two normed latents and the rotary key
    part are named (`attn_latent_*`): a checkpoint that keeps them recomputes
    the up-projections alone."""
    c = config
    dt = c.dtype
    nope = c.head_dim - c.qk_rope_dim
    kw = {} if c.norm_eps is None else {"eps": c.norm_eps}
    cos, sin = rope_tables
    with jax.named_scope("attn.latent"):
        if c.q_lora_rank:
            q_latent = rmsnorm(jnp.einsum("bse,er->bsr", h, lp["wq_a"].astype(dt)),
                               lp["q_a_norm_scale"], **kw)
            q_latent = checkpoint_name(q_latent, "attn_latent_q")
        kv_a = jnp.einsum("bse,er->bsr", h, lp["wkv_a"].astype(dt))
        kv_latent = checkpoint_name(
            rmsnorm(kv_a[..., :c.kv_lora_rank], lp["kv_a_norm_scale"], **kw), "attn_latent_kv")
        k_rope = checkpoint_name(kv_a[..., c.kv_lora_rank:], "attn_latent_k_rope")
        q = (jnp.einsum("bsr,rhd->bhsd", q_latent, lp["wq_b"].astype(dt)) if c.q_lora_rank
             else jnp.einsum("bse,ehd->bhsd", h, lp["wq"].astype(dt)))
        q = apply_rope(q, cos, sin, positions, rotary_dims=c.qk_rope_dim)
        # the up-projection's two parts as two matmuls of the split WEIGHT: the
        # values leave theirs as the kernel takes them
        wkv_b = lp["wkv_b"].astype(dt)
        k_nope = jnp.einsum("bsr,rhd->bhsd", kv_latent, wkv_b[..., :nope])
        v = jnp.einsum("bsr,rhd->bhsd", kv_latent, wkv_b[..., nope:])
        k_rope = apply_rope(k_rope[:, None], cos, sin, positions)      # one head: (B, 1, S, rope)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:-1], c.qk_rope_dim))], axis=-1)
    return q, k, v


def _attention_sublayer(x, lp, config, rope_tables, positions, window, remat_saved):
    c = config
    dt = c.dtype
    with jax.named_scope("attn.proj"):
        h = _block_norm(x, lp["ln1_scale"], lp.get("ln1_bias"), c)
        if c.latent_attention:
            q, k, v = _latent_qkv(constrain_stream(h, whole=True), lp, c, rope_tables, positions)
        elif c.fused_qkv:
            h = constrain_stream(h, whole=True)
            # one wide matmul beats three narrow ones on the MXU; the concat of
            # the (static) weights folds into the kernel at compile time
            wqkv = jnp.concatenate(
                [
                    lp["wq"].reshape(c.d_model, -1),
                    lp["wk"].reshape(c.d_model, -1),
                    lp["wv"].reshape(c.d_model, -1),
                ],
                axis=-1,
            ).astype(dt)
            qkv = jnp.einsum("bse,ef->bsf", h, wqkv)
            nq = c.n_heads * c.head_dim
            nkv = c.kv_heads * c.head_dim
            b_, s_, _ = qkv.shape
            q = qkv[..., :nq].reshape(b_, s_, c.n_heads, c.head_dim).transpose(0, 2, 1, 3)
            k = qkv[..., nq : nq + nkv].reshape(b_, s_, c.kv_heads, c.head_dim).transpose(0, 2, 1, 3)
            v = qkv[..., nq + nkv :].reshape(b_, s_, c.kv_heads, c.head_dim).transpose(0, 2, 1, 3)
        else:
            q, k, v = column_parallel(
                h, *(("bse,ehd->bhsd", lp[w]) for w in ("wq", "wk", "wv")))
        if c.use_bias:
            q = q + lp["bq"].astype(dt)[None, :, None, :]
            k = k + lp["bk"].astype(dt)[None, :, None, :]
            v = v + lp["bv"].astype(dt)[None, :, None, :]
        if c.qk_norm or c.qk_norm_per_head:
            # what a QK-norm's backward pass reads: q and k as the matmuls wrote them
            q, k = checkpoint_name(q, "attn_q_proj"), checkpoint_name(k, "attn_k_proj")
        if c.qk_norm:
            q = _qk_norm(q, lp["q_norm_scale"], c.norm_eps)
            k = _qk_norm(k, lp["k_norm_scale"], c.norm_eps)
        if c.qk_norm_per_head:
            kw = {} if c.norm_eps is None else {"eps": c.norm_eps}
            q = rmsnorm(q, lp["q_norm_scale"], **kw)
            k = rmsnorm(k, lp["k_norm_scale"], **kw)
        if rope_tables is not None and not c.latent_attention:
            cos, sin = rope_tables
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        # the kernel's operands as it takes them, after the bias, the norms and the rotation: with
        # the three (and a gate's logits) kept the backward pass repeats no projection of the normed stream
        q, k, v = (checkpoint_name(t, name) for t, name in ((q, "attn_q"), (k, "attn_k"), (v, "attn_v")))
    with jax.named_scope("attn.kernel"):
        if c.eva_attention:
            attn = eva_attention(q, k, v, lp["eva_mu"], lp["eva_phi"], window=c.eva_window,
                                 chunk=c.eva_chunk, implementation=c.attn_impl)
        else:
            attend = flash_attention_kept if "attn_lse" in remat_saved else flash_attention
            how = {}
            if c.kernel_head_dim != c.head_dim:
                # values narrower than the keys: one head size for the kernels, by zeros
                # that change no score (the scale stays the keys' own) and no output
                q, k, v = (jnp.pad(t, ((0, 0),) * 3 + ((0, c.kernel_head_dim - t.shape[-1]),))
                           for t in (q, k, v))
                how["sm_scale"] = 1.0 / math.sqrt(c.head_dim)
            attn = attend(q, k, v, causal=c.causal, window=window, implementation=c.attn_impl, **how)
            attn = attn[..., :c.value_dim]
    if c.attn_gate:
        with jax.named_scope("attn.proj"):
            if c.attn_gate_per_head:    # one logit a head
                gate = jnp.einsum("bse,eh->bhs", h, lp["wg"].astype(dt))[..., None]
            else:
                (gate,) = column_parallel(h, ("bse,ehd->bhsd", lp["wg"]))
            gate = checkpoint_name(gate, "attn_gate")
    with jax.named_scope("attn.out"):
        if c.attn_gate:
            attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
        out = row_parallel("bhsd,hde->bse", attn, lp["wo"])
        if c.use_bias:
            out = out + lp["bo"].astype(dt)
        if c.sandwich_norm:
            out = _norm(out, lp["ln1_post_scale"], lp.get("ln1_post_bias"), c.norm, c.norm_eps)
        return checkpoint_name(constrain_stream(x + out), "attn_residual")


def mlp_sublayer(x: jax.Array, lp: Params, config: TransformerConfig) -> jax.Array:
    """Pre-norm dense MLP + residual on (B, S, E): the scope `mlp`."""
    c = config
    dt = c.dtype
    with jax.named_scope("mlp"):
        h = _block_norm(x, lp["ln2_scale"], lp.get("ln2_bias"), c)
        # up and, where there is one, gate: one gather of h for both. Row-wise work alone reads
        # them before the down projection, so each stays the tuple of pieces the gather delivers
        # (one piece, the whole, wherever the stream is not sharded)
        wide = ("w_up", "w_gate") if c.act == "swiglu" else ("w_up",)
        acts = []
        for up, *gate in zip(*column_parallel(h, *(("bse,ef->bsf", lp[w]) for w in wide), ordered=False)):
            if c.use_bias:
                up = up + lp["b_up"].astype(dt)
            up = checkpoint_name(up, "mlp_up")
            acts.append(swiglu(checkpoint_name(gate[0], "mlp_gate"), up) if gate else gelu(up))
        down = row_parallel("bsf,fe->bse", acts, lp["w_down"], ordered=False)
        if c.use_bias:
            down = down + lp["b_down"].astype(dt)
        if c.sandwich_norm:
            down = _norm(down, lp["ln2_post_scale"], lp.get("ln2_post_bias"), c.norm, c.norm_eps)
        return constrain_stream(x + down)


def _block(
    x: jax.Array,
    lp: Params,
    config: TransformerConfig,
    rope_tables: Optional[Tuple[jax.Array, jax.Array]],
    positions: Optional[jax.Array],
    remat_saved: Tuple[str, ...] = (),
) -> jax.Array:
    """One transformer block on (B, S, E) activations (training/prefill)."""
    x = attention_sublayer(x, lp, config, rope_tables, positions, remat_saved=remat_saved)
    return mlp_sublayer(x, lp, config)


class RematCandidate(NamedTuple):
    """Values of a layer, under their `checkpoint_name`s, that a checkpoint
    policy may keep across the forward pass (all of `names` or none), and
    what keeping them is worth, a row (a token), layer and device."""

    names: Tuple[str, ...]
    width: int               # features held
    flops: int               # forward FLOPs that the backward need not repeat
    worth: int               # the time they take less what keeping moves, in FLOPs at the matmuls' rate
    # and with them a sum of such a row's partial results over `tp`: an all-reduce of the row where
    # every `tp` device holds the whole stream, a reduce-scatter into the kept part of it where the
    # stream's sequence lies over `tp` (half the bytes on the link for half the bytes kept at tp = 2)
    tp_sum: bool
    layers: Tuple[int, ...]  # the layers that write them, in each run of the stack


# What a kept attention output is worth beside a kept matmul output, both
# from chip runs (PERF.md section 6, PR 34). The flash kernels' share of the
# chip's peak, by which the scores they spare count for their time beside
# matmuls that run near it (`flash_fwd_roofline` 52.7 at S = 1,024 and 58.0
# at 8,192, `flash_win_fwd_roofline` 35.5: ledger, PR 33)
_FLASH_SHARE_OF_PEAK = {"full": 0.55, "window": 0.355}
# and FLOPs that take as long as moving one byte to keep it: the kernels
# write the lse a lane of 128 float32 a row and read it so, and the kept
# (B, H, S) is reshaped from and to that; the output is copied into the kept
# stack and out of it (a matmul's is written there as it is computed). On
# Mistral-7B's shard (S = 1,024, 16 heads of 128) keeping both spared 4.0 ms
# a step of `flash_fwd` and the step took 0.27 ms MORE: 4.27 ms for 2.42 GB
_KEPT_KERNEL_FLOPS_PER_BYTE = 350


def attention_costs(
    config: TransformerConfig, seq: int, split: Callable[[str], int], window: Optional[int] = None,
) -> Dict[str, Any]:
    """`attention_sublayer`'s part of `block_costs`, a layer: `flops`,
    `width` and `candidates` as there, and `candidates_last`, which
    `stack_costs` lists after every sublayer's `candidates`: the kernel's
    operands as it takes them, q, k and v after the bias, the norms and the
    rotation (`attn_q`, `attn_k`, `attn_v`) with, of a gated layer, the gate's
    logits (`attn_gate`) and, of one with a QK-norm, q and k as the matmuls
    wrote them, which the norm's backward pass reads (`attn_q_proj`,
    `attn_k_proj`), all or none: kept, the backward pass repeats no matmul of
    the normed stream, no norm a head and no rotation (a latent-attention
    layer keeps its latents instead and offers none of these)."""
    c = config
    if c.latent_attention:
        return _latent_attention_costs(c, seq, split)
    q_width, kv_width = c.n_heads * c.head_dim // split("wq"), c.kv_heads * c.head_dim // split("wk")
    out_proj = 2 * q_width * c.d_model
    # the scores' two matmuls over the keys a query sees
    if c.eva_attention:
        # half its window, and a summary a chunk of the windows before its own
        visible = c.eva_window // 2 + (seq - c.eva_window) // (2 * c.eva_chunk)
    else:
        visible = min(seq, window) if window else seq // 2 if c.causal else seq
    scores = 4 * q_width * visible
    heads, itemsize = c.n_heads // split("wq"), jnp.dtype(c.dtype).itemsize
    # a row of the residual stream on this device, in features of the activations' dtype:
    # its share where the sequence lies over `tp`
    stream = c.d_model * jnp.dtype(c.stream_dtype).itemsize // itemsize // split("stream")
    # q, k, v and a gate's logits, one matmul's output each; under a QK-norm q and k twice, as
    # its backward pass reads them and as the kernel does
    projected = q_width + 2 * kv_width + ((heads if c.attn_gate_per_head else q_width) if c.attn_gate else 0)
    qk_normed = c.qk_norm or c.qk_norm_per_head
    operands = ("attn_q", "attn_k", "attn_v", *(("attn_gate",) if c.attn_gate else ()),
                *(("attn_q_proj", "attn_k_proj") if qk_normed else ()))
    return {
        "flops": (2 * c.d_model * ((2 if c.attn_gate else 1) * q_width + 2 * kv_width)
                  + scores + out_proj),
        # the norm's output (gathered whole for the projections), the sublayer's
        # (and its own norm's) and the residual as the stream is held; q and the
        # attention output, (the gate and the gated output); k, v
        "width": (c.d_model + (2 if c.sandwich_norm else 1) * c.d_model // split("stream") + stream
                  + (4 if c.attn_gate else 2) * q_width + 2 * kv_width),
        "candidates": (
            _kept_kernel_candidate(scores, heads, q_width, itemsize, "window" if window else "full"),
            RematCandidate(("attn_residual",), stream, out_proj, out_proj, split("wq") > 1, ()),
        ),
        # Worth their matmuls alone, as `mlp_up` is: a matmul's output is worth `d_model` FLOPs a
        # byte whichever matmul wrote it, and listed last these are added to what a step kept
        # before them, never swapped for it. The row-wise work they spare with the matmuls (the
        # norms a head, the rotation, the `bse -> bhsd` layout: 4 of the 5.1 ms recomputed on
        # `train-lfm2moe-8k`, PERF.md section 6, PR 62) is NOT priced: priced, they would stand
        # over every other matmul's output and push one out on a full chip, and where there is
        # room the rule takes them as they are. (Under a QK-norm the same FLOPs buy more bytes, q
        # and k twice, and the candidate stands behind the matmuls' outputs by its worth alone.)
        # No sum over `tp` is spared: the ring's permutes are not one.
        "candidates_last": (
            RematCandidate(operands, projected + (q_width + kv_width if qk_normed else 0),
                           2 * c.d_model * projected, 2 * c.d_model * projected, False, ()),
        ),
    }


def _kept_kernel_candidate(scores: int, heads: int, q_width: int, itemsize: int, kind: str):
    """The attention kernel's output and lse as a candidate: what `scores`
    FLOPs take at the kernels' share of the peak, less what keeping moves."""
    lse_width = -(-4 * heads // itemsize)    # 4 bytes a head, in features of the activations' dtype
    moved = 2 * (heads * 128 * 4 + q_width * itemsize)
    return RematCandidate(
        ("attn_out", "attn_lse"), q_width + lse_width, scores,
        int(scores / _FLASH_SHARE_OF_PEAK[kind] - moved * _KEPT_KERNEL_FLOPS_PER_BYTE), False, ())


def _latent_attention_costs(config: TransformerConfig, seq: int, split: Callable[[str], int]):
    """`attention_costs` of a latent-attention layer: the five matmuls (the
    two down-projections whole on every device, the up-projections and the
    output's over the heads it holds; with no q latent, q's one projection),
    and beside the other kinds' two candidates the normed latents with the
    rotary key part: `q_lora_rank + kv_lora_rank + qk_rope_dim` features a
    token, after which the backward repeats the up-projections alone. The
    kernels' operands and output are `kernel_head_dim` wide a head (the head
    size, or values narrower than the keys padded up)."""
    c = config
    q_weight = "wq_b" if c.q_lora_rank else "wq"
    heads, itemsize = c.n_heads // split(q_weight), jnp.dtype(c.dtype).itemsize
    q_width, nope = heads * c.head_dim, c.head_dim - c.qk_rope_dim
    kernel_width, out_width = heads * c.kernel_head_dim, heads * c.value_dim
    latents = c.q_lora_rank + c.kv_lora_rank + c.qk_rope_dim
    down = 2 * c.d_model * latents
    up = 2 * ((c.q_lora_rank or c.d_model) * q_width + c.kv_lora_rank * heads * (nope + c.value_dim))
    out_proj = 2 * out_width * c.d_model
    scores = 2 * (q_width + out_width) * (seq // 2 if c.causal else seq)
    gate = 2 * c.d_model * (heads if c.attn_gate_per_head else out_width) if c.attn_gate else 0
    named = (("attn_latent_q",) if c.q_lora_rank else ()) + ("attn_latent_kv", "attn_latent_k_rope")
    return {
        "flops": down + up + scores + gate + out_proj,
        # the norm's output, the sublayer's, the residual; both latents before
        # and after their norms; q before and after its rotation, the keys'
        # part without positions, k, v and the attention output (the last four
        # as the kernels take them)
        "width": (c.d_model + 2 * c.d_model // split("stream") + 2 * latents + q_width
                  + 4 * kernel_width + heads * nope),
        "candidates": (
            _kept_kernel_candidate(scores, heads, kernel_width, itemsize, "full"),
            RematCandidate(("attn_residual",), c.d_model // split("stream"), out_proj, out_proj,
                           split(q_weight) > 1, ()),
            RematCandidate(named, latents, down, down, False, ()),
        ),
    }


def mlp_costs(config: TransformerConfig, split: Callable[[str], int],
              d_ff: Optional[int] = None) -> Dict[str, Any]:
    """`mlp_sublayer`'s part of `block_costs`, a layer of width `d_ff`
    (None: the configuration's)."""
    c = config
    wide = ("w_up", "w_gate") if c.act == "swiglu" else ("w_up",)
    d_ff = (d_ff or c.d_ff) // split("w_up")
    matmul = 2 * c.d_model * d_ff  # each of up, (gate,) down
    itemsize = jnp.dtype(c.dtype).itemsize
    stream = c.d_model * jnp.dtype(c.stream_dtype).itemsize // itemsize // split("stream")
    return {
        "flops": (len(wide) + 1) * matmul,
        # the down projection's output only feeds the next block's input,
        # which is kept; a norm on it reads it again in the backward pass
        "kept_anyway": 0 if c.sandwich_norm else matmul,
        # the devices that share a sequence of what the sublayer hands on
        "stream_split": split("stream"),
        # the norm's output (gathered whole), the MLP's (and its own norm's)
        # and the residual as the stream is held; up, (gate,) and the activation
        "width": (c.d_model + (2 if c.sandwich_norm else 1) * c.d_model // split("stream") + stream
                  + (len(wide) + 1) * d_ff),
        "candidates": tuple(
            RematCandidate((w.replace("w_", "mlp_"),), d_ff, matmul, matmul, False, ()) for w in wide),
    }


class StackRun(NamedTuple):
    """Layers in a row that the forward runs as one scan (`scanned`; its
    stacked gradients and kept values then live as long as the scan's
    backward) or one after the other, their parameters under `params` of the
    parameter tree: (layers of a kind, the kind's `attention_costs`, its
    MLP's costs) a kind. `period`: the layers one iteration of the scan runs
    (its body unrolls them)."""

    scanned: bool
    params: Tuple[Any, ...]
    kinds: Tuple[Tuple[int, Dict[str, Any], Dict[str, Any]], ...]
    period: int = 1


def stack_costs(runs: Sequence[StackRun]) -> Dict[str, Any]:
    """`block_costs` of a stack given as its runs: FLOPs summed over the
    stack; a run's layers, whether it is scanned, its widest kind's width and
    where its parameters are; a candidate once, with the layers of each run
    that write it and their mean FLOPs and worth."""
    flops = recomputed = 0
    merged: Dict[Tuple[str, ...], RematCandidate] = {}
    last = set()    # offered as a kind's `candidates_last`
    for r, run in enumerate(runs):
        for n, attention, mlp in run.kinds:
            flops += n * (attention["flops"] + mlp["flops"])
            recomputed += n * (attention["flops"] + mlp["flops"] - mlp.get("kept_anyway", 0))
            last.update(c.names for c in attention.get("candidates_last", ()))
            for c in (*attention["candidates"], *mlp["candidates"], *attention.get("candidates_last", ())):
                seen = merged.get(c.names, c._replace(layers=(0,) * len(runs), flops=0, worth=0))
                if (seen.width, seen.tp_sum) != (c.width, c.tp_sum):
                    raise ValueError(f"{c.names} differs between the kinds of one stack")
                before = sum(seen.layers)
                merged[c.names] = seen._replace(
                    layers=tuple(m + n * (i == r) for i, m in enumerate(seen.layers)),
                    flops=(before * seen.flops + n * c.flops) // (before + n),
                    worth=(before * seen.worth + n * c.worth) // (before + n))
    return {
        "flops": flops, "recomputed_flops": recomputed,
        "runs": tuple({"scanned": run.scanned, "params": run.params, "period": run.period,
                       "layers": sum(n for n, _, _ in run.kinds),
                       # a block's input is held as its layers' last sublayer hands the stream on
                       "stream_split": min(m.get("stream_split", 1) for _, _, m in run.kinds),
                       "width": max(a["width"] + m["width"] for _, a, m in run.kinds)}
                      for run in runs),
        # in the order the kinds give them, a mixer's before its MLP's, and every kind's `candidates_last`
        # behind those: the rule's sort is stable, so at equal worth a byte the first listed is tried first
        "candidates": tuple(sorted(merged.values(), key=lambda c: c.names in last)),
    }


def block_costs(
    config: TransformerConfig, seq: int, split: Callable[[str], int] = lambda weight: 1,
    tokens: Optional[int] = None,
) -> Dict[str, Any]:
    """What the stack's blocks cost a device for a row (a token) of an
    S-long sequence, for the rule that decides what a recomputing step keeps
    (train/lm.py): `flops` of their forward pass; `recomputed_flops`, the
    part a whole-block checkpoint runs again in the backward pass; `runs`,
    the stack as the backward pass walks it (here one scan: layers, the
    features of every activation one block writes, where its parameters
    are); `candidates`, the values named in the sublayers that a policy may
    keep: the MLP's up (and gate) projection, each on its own; the residual
    stream after the attention output projection (with it the backward needs
    neither that matmul again nor, under tensor parallelism, the sum of its
    partial results over `tp`: `RematCandidate.tp_sum`); the attention
    kernel's output with its lse (with both the backward does not run the
    forward kernel again); and, last, the kernel's operands q, k and v as it
    takes them (with a gate's logits and a QK-norm's inputs: with all of them
    the backward repeats no projection of the normed stream, no norm a head
    and no rotation; `attention_costs`). A candidate's `worth`
    is its FLOPs where a matmul is spared; the kernel's are priced by its
    time, less what keeping its results moves (`_FLASH_SHARE_OF_PEAK`,
    `_KEPT_KERNEL_FLOPS_PER_BYTE`): at S = 1,024 that leaves nothing, at
    8,192 it is the most valuable candidate a byte. `split(weight)` is the
    number of devices that share the output features of that block
    parameter's matmul (tensor parallelism), and `split("stream")` the
    devices that share one sequence of the residual stream between sublayers
    (parallel/sequence_parallel.stream_shards): the stream's rows, a kept
    `attn_residual` and a block's input are that share a device. `tokens`, a
    device's tokens a step, is every family's argument and sizes nothing here
    (mixed_stack.block_costs: a held expert layer's buffer)."""
    del tokens
    return stack_costs([StackRun(True, ("blocks",), (
        (config.n_layers, attention_costs(config, seq, split), mlp_costs(config, split)),))])


def plan(config: TransformerConfig, batch: int, seq: int) -> Dict[str, Any]:
    """What the dense family's own layers resolve to for a (batch, seq) step,
    for callers that report it (LMTrainer's `train.init.step_fn` span): an
    EVA model's flash calls (a window long, one a window: `attention_plan`
    of a window, over the caller's of the whole sequence), its windows,
    chunks and tile counts (ops/eva.eva_plan) and its next-token heads;
    nothing for the others."""
    c = config
    if not c.eva_attention:
        return {}
    return dict(attention_plan(c.eva_window, causal=c.causal, implementation=c.attn_impl,
                               head_dim=c.head_dim),
                **eva_plan(seq, window=c.eva_window, chunk=c.eva_chunk, head_dim=c.head_dim,
                           implementation=c.attn_impl), pred_heads=c.pred_heads)


def checkpoint_block(block_fn, saved: Tuple[str, ...] = ()):
    """`block_fn` recomputed in the backward pass but for the values whose
    `checkpoint_name` is in `saved` (none: the whole block)."""
    policy = jax.checkpoint_policies.save_only_these_names(*saved) if saved else None
    return jax.checkpoint(block_fn, policy=policy)


def forward_hidden(
    params: Params,
    tokens: jax.Array,
    config: TransformerConfig,
    *,
    positions: Optional[jax.Array] = None,
    remat_saved: Tuple[str, ...] = (),
) -> jax.Array:
    """Forward up to (but excluding) the LM head: (B, S) → (B, S, E).
    The chunked fused-loss path (ops/losses.py
    fused_linear_cross_entropy) consumes this so the full logits tensor
    never materializes. Where `config.remat`, `remat_saved` names the
    candidates of `block_costs` that are kept across the forward pass."""
    c = config
    dt = c.dtype
    _, s = tokens.shape
    with jax.named_scope("embed"):
        x = params["wte"].astype(dt)[tokens]
        if c.pos_emb == "learned":
            if positions is None:
                x = x + params["wpe"].astype(dt)[None, :s]
            else:
                x = x + params["wpe"].astype(dt)[positions]
        # whole sequences first, as the lookup in a table split over `tp` leaves them
        # (one all-reduce); its device's rows of them are then a slice
        x = constrain_stream(constrain_stream(x.astype(c.stream_dtype), whole=True))
    rope_tables = None
    if c.pos_emb != "learned":
        rope_tables = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    def block_fn(carry, lp):
        return _block(carry, lp, c, rope_tables, positions, remat_saved), None

    if c.remat:
        block_fn = checkpoint_block(block_fn, remat_saved)
    x, _ = jax.lax.scan(block_fn, x, params["blocks"], unroll=c.scan_unroll)

    with jax.named_scope("head"):
        return constrain_stream(_block_norm(x, params["lnf_scale"], params.get("lnf_bias"), c), whole=True)


def lm_head_weights(params: Params, config: TransformerConfig) -> jax.Array:
    """(E, V) output projection — tied to wte unless a separate lm_head
    exists; (E, pred_heads x V) for a model of several next-token heads."""
    head = params.get("lm_head", None)
    if head is None:
        head = params["wte"].T
    return head.astype(config.dtype)


def forward(
    params: Params,
    tokens: jax.Array,
    config: TransformerConfig,
    *,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence forward (training / prefill): (B, S) → (B, S, V);
    (B, S, pred_heads x V) for a model of several next-token heads, head n's
    logits the n-th V columns."""
    x = forward_hidden(params, tokens, config, positions=positions)
    return jnp.einsum("bse,ev->bsv", x, lm_head_weights(params, config))


# --------------------------------------------------------------------- decode


def _no_qk_norm(config: TransformerConfig) -> None:
    if config.qk_norm:
        raise NotImplementedError(
            "qk_norm is applied by attention_sublayer (training, full-sequence "
            "forward) only: the cached decode paths do not carry it yet")


def _no_latent_attention(config: TransformerConfig, where: str) -> None:
    if config.latent_attention:
        raise NotImplementedError(
            f"latent attention is run by the mixed stack's training forward alone: {where} has "
            "neither its seven leaves nor a cache of latent rows (the absorbed decode form)")
    if getattr(config, "layer_pattern", ""):
        raise NotImplementedError(
            f"a patterned stack (state-space `ssm` layers, one sublayer a layer) is run by the mixed "
            f"stack's training forward alone: {where} has neither a state-space mixer's leaves nor a "
            "cache of its convolution's window and its state")


def _no_eva(config: TransformerConfig, where: str) -> None:
    """Refuse, by name, what the EvaByte family adds and the cached paths lack."""
    if config.eva_attention:
        raise NotImplementedError(
            f"eva attention is run by the training forward alone: {where} has no cache of a "
            "window's keys and values beside one summary pair a chunk of everything before it")
    if config.pred_heads > 1 or config.norm_unit_offset or config.residual_fp32:
        raise NotImplementedError(
            f"several next-token heads, a (1 + scale) norm and a float32 residual stream are run "
            f"by the training forward alone: {where} reads one head and adds in the compute dtype")


def init_cache(
    config: TransformerConfig, batch: int, max_seq: Optional[int] = None
) -> Params:
    """Dense KV cache: k/v of shape (L, B, Hkv, S, Dh) in the compute dtype."""
    c = config
    s = max_seq or c.max_seq
    shape = (c.n_layers, batch, c.kv_heads, s, c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def _decode_attention(q, k_cache, v_cache, lengths):
    """Single-step attention against the cache. q (B, H, 1, Dh); cache
    (B, Hkv, S, Dh); lengths (B,) = #valid cache slots per example."""
    b, hq, _, dh = q.shape
    hkv = k_cache.shape[1]
    if hq != hkv:
        k_cache = jnp.repeat(k_cache, hq // hkv, axis=1)
        v_cache = jnp.repeat(v_cache, hq // hkv, axis=1)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k_cache, preferred_element_type=jnp.float32
    ) / math.sqrt(dh)
    mask = jnp.arange(k_cache.shape[2])[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v_cache.dtype), v_cache)


def decode_step(
    params: Params,
    cache: Params,
    tokens: jax.Array,
    positions: jax.Array,
    config: TransformerConfig,
) -> Tuple[jax.Array, Params]:
    """One autoregressive step for continuous batching.

    tokens (B,) int32; positions (B,) int32 — per-example write slot (also
    the rope position). Returns (logits (B, V), updated cache). Examples at
    different sequence positions coexist in one batch: each writes its own
    cache row at its own position.
    """
    c = config
    _no_qk_norm(c)
    _no_latent_attention(c, "the dense cache")
    _no_eva(c, "the dense cache")
    dt = c.dtype
    b = tokens.shape[0]
    x = params["wte"].astype(dt)[tokens][:, None, :]  # (B, 1, E)
    if c.pos_emb == "learned":
        x = x + params["wpe"].astype(dt)[positions][:, None, :]
        rope_tables = None
    else:
        rope_tables = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    lengths = positions + 1

    def write_at(cache_bhsd, new_bh1d):
        # scatter each example's new row at its own position
        def one(cache_hsd, new_h1d, pos):
            return jax.lax.dynamic_update_slice(cache_hsd, new_h1d, (0, pos, 0))

        return jax.vmap(one)(cache_bhsd, new_bh1d, positions)

    def block_fn(x, scanned):
        lp, k_cache, v_cache = scanned
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
        k_cache = write_at(k_cache, k.astype(c.dtype))
        v_cache = write_at(v_cache, v.astype(c.dtype))
        attn = _decode_attention(q, k_cache, v_cache, lengths)
        out = jnp.einsum("bhsd,hde->bse", attn.astype(dt), lp["wo"].astype(dt))
        if c.use_bias:
            out = out + lp["bo"].astype(dt)
        x = x + out
        h = _norm(x, lp["ln2_scale"], lp.get("ln2_bias"), c.norm, c.norm_eps)
        up = jnp.einsum("bse,ef->bsf", h, lp["w_up"].astype(dt))
        if c.use_bias:
            up = up + lp["b_up"].astype(dt)
        if c.act == "swiglu":
            act = swiglu(jnp.einsum("bse,ef->bsf", h, lp["w_gate"].astype(dt)), up)
        else:
            act = gelu(up)
        down = jnp.einsum("bsf,fe->bse", act, lp["w_down"].astype(dt))
        if c.use_bias:
            down = down + lp["b_down"].astype(dt)
        return x + down, (k_cache, v_cache)

    x, (new_k, new_v) = jax.lax.scan(block_fn, x, (params["blocks"], cache["k"], cache["v"]))
    x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), c.norm, c.norm_eps)
    head = params.get("lm_head", None)
    if head is None:
        head = params["wte"].T
    logits = jnp.einsum("bse,ev->bsv", x, head.astype(dt))[:, 0]
    return logits, {"k": new_k, "v": new_v}


def prefill(
    params: Params,
    tokens: jax.Array,
    lengths: jax.Array,
    cache: Params,
    config: TransformerConfig,
) -> Tuple[jax.Array, Params]:
    """Prompt ingestion: run the full-sequence path once, stash K/V into the
    cache, return last-valid-token logits. tokens (B, S) right-padded;
    lengths (B,) true prompt lengths."""
    c = config
    _no_qk_norm(c)
    _no_latent_attention(c, "the dense cache")
    _no_eva(c, "the dense cache")
    dt = c.dtype
    b, s = tokens.shape
    x = params["wte"].astype(dt)[tokens]
    if c.pos_emb == "learned":
        x = x + params["wpe"].astype(dt)[None, :s]
        rope_tables = None
    else:
        rope_tables = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    def block_fn(x, scanned):
        lp, k_cache, v_cache = scanned
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
            q = apply_rope(q, cos, sin, None)
            k = apply_rope(k, cos, sin, None)
        # write the first S slots of the cache; padded tail is masked by
        # `lengths` at decode time
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(c.dtype), (0, 0, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(c.dtype), (0, 0, 0, 0)
        )
        attn = flash_attention(q, k, v, causal=True, implementation=c.attn_impl)
        out = jnp.einsum("bhsd,hde->bse", attn, lp["wo"].astype(dt))
        if c.use_bias:
            out = out + lp["bo"].astype(dt)
        x = x + out
        h = _norm(x, lp["ln2_scale"], lp.get("ln2_bias"), c.norm, c.norm_eps)
        up = jnp.einsum("bse,ef->bsf", h, lp["w_up"].astype(dt))
        if c.use_bias:
            up = up + lp["b_up"].astype(dt)
        if c.act == "swiglu":
            act = swiglu(jnp.einsum("bse,ef->bsf", h, lp["w_gate"].astype(dt)), up)
        else:
            act = gelu(up)
        down = jnp.einsum("bsf,fe->bse", act, lp["w_down"].astype(dt))
        if c.use_bias:
            down = down + lp["b_down"].astype(dt)
        return x + down, (k_cache, v_cache)

    x, (new_k, new_v) = jax.lax.scan(
        block_fn, x, (params["blocks"], cache["k"], cache["v"])
    )
    x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), c.norm, c.norm_eps)
    head = params.get("lm_head", None)
    if head is None:
        head = params["wte"].T
    logits = jnp.einsum("bse,ev->bsv", x, head.astype(dt))
    last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, {"k": new_k, "v": new_v}
