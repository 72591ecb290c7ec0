"""A decoder whose layers are not all alike: a stack described as data.

The dense and MoE families (transformer.py, moe.py) scan one block over a
stack of identical layers. Here the configuration says, a layer, its
attention kind ("sliding": a causal window with rotary positions; "full":
every causal key and no positional encoding; "latent", every layer of a
configuration with `kv_lora_rank`: every causal key, q from a low-rank
latent, keys and values from one latent all heads share, rotary positions
on a part of the head) and its MLP kind ("dense": one
SwiGLU; "experts": a routed expert layer, beside a shared expert where the
configuration has one), by two rules on the layer's index: one layer of every
`global_attn_every` is full (the last of its period, or the first where
`global_attn_first`), the first `n_dense_layers` are dense. Which leaves a
layer has is data too (`_layer_shapes`: the output gate, the QK-norm, the
norms on a sublayer's output, the selection bias and the shared expert exist
where the configuration's flags say so), as is the tensor an expert layer's
router reads (`router_input`). `stack_runs` groups the layers into runs
of a repeated period of kinds; the forward scans each run over its repeats
and unrolls the period inside the scan's body, so compile time grows with
the period and not with the depth, and parameters are stacked a run: run r,
position p of its period is one dict of leaves with a leading axis of the
run's repeats.

The block is written once, from the parts the two other families own:
`transformer.attention_sublayer` (head size, QK-norm a head, rotary or none,
window, output gate, post-norm: data of the configuration and arguments of
the call), `transformer.mlp_sublayer`, `moe.moe_mlp`. Where `config.remat`
every block is recomputed in the backward pass but for what the step keeps of
it (`block_costs` names what it may: the attention kernel's output and lse and
the residual after the output projection in every layer, a latent layer's
latents, gate and up in the dense ones, a state-space or delta-rule mixer's
in-projection and its scan's or rule's output with the chunks' states; of an
expert layer its routing, the shared expert's gate and up and the held
experts' buffer as the first pass wrote it).

A configuration with `mtp_modules` has, beside the stack, one multi-token
prediction module (`params["mtp"]`, `mtp_hidden`): the stack's output and
the embedding of the NEXT token, each through a norm of its own, joined by
`eh_proj`, through one more block of the expert-layer kind and a final norm,
to the shared head, which then predicts the token after next. The objective
that uses it is train/lm.lm_loss's.

A layer need not be both sublayers. A configuration with a `layer_pattern`
(one character a layer) gives each layer ONE mixer behind one norm, x +
mixer(norm(x)): "M", a Mamba-2 state-space mixer (`_ssm_sublayer`: an
in-projection to a gate z, the convolved x, B, C and a step a head; a causal
depthwise convolution; the chunked selective scan of ops/ssd; the gate and a
norm a group; an out-projection: leaves `ssm_*` of its own); "*", full
attention without positions; "E", an expert layer. Its kind is then
`LayerKind("ssm", "none")`, `("full", "none")` or `("none", "experts")`.

A configuration with `kda_heads` mixes a fifth kind of mixer into the index
rules: every layer that is not the one of its period of `global_attn_every`
is "kda", Kimi Delta Attention (`_kda_sublayer`: q, k, v and a decay a channel
from one projection, a causal depthwise convolution and silu on q, k and v,
L2-normalised q and k, the chunked delta rule of ops/kda, an RMS norm a head
under ONE sigmoid gate a head, an out-projection: leaves `kda_*` of its own;
no positions), and the period's one layer is "latent" where the configuration
has `kv_lora_rank`, else "full". `first_layer` is the published index of the
first layer that is run: the two index rules count from it.

A configuration with `mixer_kinds` is told its mixers' kinds as data, one a
layer that is run (a published `layer_types` list that no index rule gives),
and only its MLPs' kinds by `n_dense_layers` and `first_layer`. The sixth kind
of mixer is such a list's: "sconv", a gated short convolution (`_sconv_sublayer`:
[B | C | X] from one projection, C * conv(B * X) with `sconv_taps` causal
depthwise taps a channel, no bias and no activation, ops/short_conv; an
out-projection: leaves `sconv_*` of its own; no positions, no state beyond
`sconv_taps` - 1 rows). Where `attn_full_rope`, a "full" layer has rotary
positions over the whole head (after its QK-norm). Where `tie_embeddings`, the
tree has no `lm_head` and the head reads `wte` (transformer.lm_head_weights).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import kda, rope_frequencies, short_conv, ssd
from ..ops.attention import attention_plan
from ..ops.grouped_matmul import gmm_tile_rows
from .moe import (
    _HELD_BUFFER_SHARES,
    ROUTING,
    MoEConfig,
    held_buffer_names,
    held_buffer_rows,
    load_max_over_mean,
    moe_mlp,
    moe_plan,
)
from .transformer import (
    _KEPT_KERNEL_FLOPS_PER_BYTE,
    Params,
    RematCandidate,
    StackRun,
    _norm,
    attention_costs,
    attention_sublayer,
    checkpoint_block,
    mlp_costs,
    mlp_sublayer,
    stack_costs,
)


@dataclasses.dataclass(frozen=True)
class MixedStackConfig(MoEConfig):
    """`d_ff` is one routed expert's width (as in MoEConfig), `d_ff_dense`
    the width of a dense layer's MLP."""

    sliding_window: int = 2048
    global_attn_every: int = 4     # layer i is full iff (i + 1) % this == 0 ...
    global_attn_first: bool = False  # ... or, the full layer FIRST in its period, iff i % this == 0
    n_dense_layers: int = 0        # the leading layers whose MLP is dense
    d_ff_dense: int = 0
    # what an expert layer's router reads: "mlp", the normed tensor its
    # experts read, or "attention", the tensor the layer's ATTENTION sublayer
    # takes (the layer's input, before that sublayer's norm): the routing is
    # then decided before attention has run
    router_input: str = "mlp"
    # the deviation the embedding's rows start at. Blocks that put no norm on
    # a sublayer's output add every branch to the stream at its own size: at
    # 0.02 an untrained attention layer's output (the mean of the values, all
    # but the same vector for every query) outgrows the token's embedding
    # within a layer, and every token then chooses the same experts
    # (POST_NORM_GAIN's finding, for the family that has such norms)
    embedding_std: float = 0.02
    # the deviation a router's weights start at. A scale changes no choice of
    # experts (top-k keeps its order) and sets how sharp the gates over the
    # chosen are, and with them what a row that changes its LAST expert
    # between two precisions moves: the last gate, a tenth of the row's weight
    # at logits of deviation 1, a fiftieth at 3
    router_std: float = 0.02
    # a layer's leaves that the step reads as constants: they get no gradient
    # and so are not trained. What one chip's share of a layer names when it
    # cannot form a leaf's whole gradient: the router's needs the outputs of
    # all the chosen experts, and the absent ones' lie on other chips
    frozen_leaves: Tuple[str, ...] = ()
    # multi-token prediction modules after the stack (0 or 1) and the weight
    # of their loss in the objective: loss + mtp_loss_weight x mtp_loss
    mtp_modules: int = 0
    mtp_loss_weight: float = 0.0
    # The layers' kinds as a string, one character a layer, each layer then ONE
    # sublayer (`PATTERN_KINDS`: "M" a state-space mixer, "*" full attention
    # without positions, "E" an expert layer); "": the two index rules above,
    # every layer attention and then an MLP. A pattern may be longer than
    # `n_layers` (a published one of which the first layers are run)
    layer_pattern: str = ""
    # the state-space mixer (Mamba-2): heads of `ssm_head_dim` features with a
    # state of `ssm_state` a feature, B and C in `ssm_groups` groups of heads, a
    # causal depthwise convolution of `ssm_conv_kernel` taps over x, B and C,
    # the scan's chunk, and the range a head's step starts in (log-uniform in
    # [min, max], no smaller than the floor)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # the published index of the first layer that is run (a chip's stage of a
    # pipeline): layer i of the stack is layer `first_layer` + i of the two
    # index rules (`global_attn_every`, `n_dense_layers`)
    first_layer: int = 0
    # Kimi Delta Attention (`kda_heads` > 0): the layers that are not their
    # period's one are "kda", heads of `kda_head_dim` key and value features
    # with a state of kda_head_dim^2 a head, a causal depthwise convolution of
    # `kda_conv_kernel` taps over q, k and v, the delta rule's chunk, and the
    # gate's lower bound: a channel's log-decay a position is
    # `kda_gate_lower_bound` x sigmoid(exp(A_log) (f + dt_bias)). A_log and
    # dt_bias start as a state-space mixer's do (`ssm_dt_*`)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_chunk: int = kda.CHUNK
    kda_gate_lower_bound: float = -5.0
    # The mixers' kinds as data, one of `MIXER_KINDS` a layer that is run ((): the
    # index rules or the pattern above): a published list that no rule on the
    # index gives. The MLPs' kinds stay `n_dense_layers`' and `first_layer`'s
    mixer_kinds: Tuple[str, ...] = ()
    # a "full" layer with rotary positions over the whole head (False: it encodes none)
    attn_full_rope: bool = False
    # the gated short convolution's causal depthwise taps a channel (an "sconv" mixer)
    sconv_taps: int = 3

    @property
    def ssm_conv_width(self) -> int:
        """The features the convolution runs over: x, then B and C a group."""
        return self.ssm_heads * self.ssm_head_dim + 2 * self.ssm_groups * self.ssm_state

    def __post_init__(self):
        super().__post_init__()
        unknown = set(self.layer_pattern) - set(PATTERN_KINDS)
        if unknown or (self.layer_pattern and len(self.layer_pattern) < self.n_layers):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: at least n_layers = {self.n_layers} "
                             f"characters of {sorted(PATTERN_KINDS)} are what the program runs")
        if self.layer_pattern and (self.mtp_modules or self.latent_attention or self.kda_heads
                                   or self.first_layer):
            raise ValueError("layer_pattern: a patterned stack has no latent attention, no delta-rule "
                             "mixer, no multi-token prediction module and starts at its first layer")
        if self.kda_heads and not (self.kda_head_dim > 0 and self.kda_gate_lower_bound < 0
                                   and not self.mtp_modules):
            raise ValueError("kda_heads: kda_head_dim, a negative kda_gate_lower_bound and no multi-token "
                             "prediction module are what the program runs")
        if self.first_layer < 0:
            raise ValueError(f"first_layer {self.first_layer}: a published layer's index")
        if self.mixer_kinds and (len(self.mixer_kinds) != self.n_layers or set(self.mixer_kinds) - set(MIXER_KINDS)):
            raise ValueError(f"mixer_kinds {self.mixer_kinds!r}: one of {MIXER_KINDS} for each of the "
                             f"n_layers = {self.n_layers} layers is what the program runs")
        if self.mixer_kinds and (self.layer_pattern or self.kda_heads or self.latent_attention
                                 or self.mtp_modules):
            raise ValueError("mixer_kinds: a stack told its mixers' kinds has no pattern, no delta-rule "
                             "mixer, no latent attention and no multi-token prediction module")
        if "sconv" in self.mixer_kinds and self.sconv_taps < 2:
            raise ValueError(f"sconv_taps {self.sconv_taps}: a short convolution has two taps or more")
        if "M" in self.layer_pattern[:self.n_layers] and not (
                self.ssm_heads > 0 and self.ssm_head_dim > 0 and self.ssm_state > 0
                and self.ssm_groups > 0 and self.ssm_heads % self.ssm_groups == 0):
            raise ValueError("layer_pattern has a state-space layer: ssm_heads (a multiple of "
                             "ssm_groups), ssm_head_dim and ssm_state must be given")
        if self.mtp_modules not in (0, 1):
            raise ValueError(f"mtp_modules {self.mtp_modules}: one multi-token prediction module "
                             "is what the program runs")
        if self.router_input not in ("mlp", "attention"):
            raise ValueError(f"unknown router input: {self.router_input!r}")
        kinds = layer_kinds(self)
        known = {name for kind in (*kinds, *(LayerKind(kinds[0].attention, mlp) for mlp in ("dense", "experts")))
                 for name in _layer_shapes(self, kind)}
        if not set(self.frozen_leaves) <= known:
            raise ValueError(f"frozen_leaves {sorted(set(self.frozen_leaves) - known)}: "
                             f"no layer has such a leaf")


class LayerKind(NamedTuple):
    """The sublayers a layer has: its mixer first, then its MLP."""

    # "sliding" | "full" | "latent" | "ssm" (a state-space mixer) | "kda" (a delta-rule one)
    # | "sconv" (a gated short convolution) | "none"
    attention: str
    mlp: str        # "dense" | "experts" | "none"

    @property
    def code(self) -> str:
        return ({"dense": "d", "experts": "e", "none": "-"}[self.mlp]
                + {"sliding": "S", "full": "F", "latent": "L", "ssm": "M", "kda": "K", "sconv": "C",
                   "none": "-"}[self.attention])

    @property
    def sublayers(self) -> int:
        return (self.attention != "none") + (self.mlp != "none")


# what a `mixer_kinds` list may name
MIXER_KINDS = ("sliding", "full", "sconv")
# a `layer_pattern`'s characters: each a layer of one sublayer
PATTERN_KINDS = {"M": LayerKind("ssm", "none"), "*": LayerKind("full", "none"),
                 "E": LayerKind("none", "experts")}


class Run(NamedTuple):
    """`repeats` times the period of `kinds`, layers in a row."""

    kinds: Tuple[LayerKind, ...]
    repeats: int


def layer_kinds(config: MixedStackConfig) -> List[LayerKind]:
    c = config
    if c.layer_pattern:
        return [PATTERN_KINDS[character] for character in c.layer_pattern[:c.n_layers]]
    if c.mixer_kinds:
        return [LayerKind(mixer, "dense" if c.first_layer + i < c.n_dense_layers else "experts")
                for i, mixer in enumerate(c.mixer_kinds)]
    full_at = 0 if c.global_attn_first else c.global_attn_every - 1
    # the period's one layer, and the others
    one = "latent" if c.latent_attention else "full"
    others = "kda" if c.kda_heads else one if c.latent_attention else "sliding"
    return [LayerKind(one if i % c.global_attn_every == full_at else others,
                      "dense" if i < c.n_dense_layers else "experts")
            for i in range(c.first_layer, c.first_layer + c.n_layers)]


def stack_runs(kinds: List[LayerKind]) -> List[Run]:
    """The layers as runs of a repeated period of whole kinds. The stack is
    cut where the MLP kind changes between two layers that both have an MLP
    (the parameters' shapes do). A piece is its shortest period repeated, then
    what is left over as one more run of a single repeat. A piece that no
    period tiles is, where it STARTS with a period of two layers or more
    repeated in a row, that run (the period that covers most layers, the
    shortest of those that cover as many) and then what follows it by the
    same rule; else one run of a single repeat (a layer repeated at the start
    of such a piece stays unrolled, as before PR 48: the shipped trees keep
    their layout). `MEMEM*EME`: `ME` twice, then `M*EME` once."""
    runs: List[Run] = []
    start = 0
    while start < len(kinds):
        end = start + 1
        while end < len(kinds) and ("none" in (kinds[end].mlp, kinds[end - 1].mlp)
                                    or kinds[end].mlp == kinds[end - 1].mlp):
            end += 1
        while start < end:
            piece = kinds[start:end]

            def repeats_of(period):
                n = 1
                while piece[n * period:(n + 1) * period] == piece[:period]:
                    n += 1
                return n

            halves = range(1, len(piece) // 2 + 1)
            # the shortest period that tiles the piece, a tail shorter than it aside
            period = next((p for p in halves if repeats_of(p) == len(piece) // p), None)
            if period is None:
                _, period = max(((repeats_of(p) * p, -p) for p in halves[1:] if repeats_of(p) > 1),
                                default=(0, -len(piece)))
                period = -period
            repeats = repeats_of(period)
            runs.append(Run(tuple(piece[:period]), repeats))
            start += repeats * period
    return runs


# ----------------------------------------------------------------------- init


# What the norm on a sublayer's OUTPUT starts at: the gain LayerScale puts on
# a residual branch (the other families scale the projection into the
# residual stream by 1/sqrt(2L); under a norm that scale is undone). At 1 the
# norm takes an untrained attention layer's output, the mean of the values
# over up to `sliding_window` keys and all but the same vector for every
# query, up to the size of the token's own embedding, layer after layer: the
# router's input is then half that common vector and most tokens choose the
# same experts before a step is taken. Measured at the published widths
# (PERF.md section 6, PR 33): the common vector's share of the router's input
# 0.44-0.48 at 1, 0.16-0.23 at 0.29, 0.06-0.09 at 0.125; training on tokens
# drawn independently brings it back the faster the larger the gain (largest
# expert's rows over the mean after 46 steps: 7.6 at 0.05, 5.1 at 0.01),
# while under 0.03 a token's copies route alike to the last bit, so that one
# near-tie between bfloat16 and float32 moves all of them at once.
POST_NORM_GAIN = 0.03


def _layer_shapes(config: MixedStackConfig, kind: LayerKind) -> Dict[str, Tuple[Tuple[int, ...], Any, Any]]:
    """name -> (shape, how it is initialised, logical axes) of one layer's
    leaves: those the configuration's flags give it, in one fixed order.
    "post": the norm on a sublayer's output. "into_residual": a projection
    into the residual stream, which takes the other families' 1/sqrt(2 L)
    scale (one over the root of the sublayers the stack has) where no such
    norm follows it (under one the scale is undone). A layer has the norms of
    the sublayers it has: `ln1_scale` before its mixer, `ln2_scale` before
    its MLP."""
    c = config
    dh, m = c.head_dim, c.d_model
    into_residual = "normal" if c.sandwich_norm else "into_residual"
    held, shared = c.n_experts_held, c.shared_expert_width
    dense, experts = kind.mlp == "dense", kind.mlp == "experts"
    gated = "we_gate" in c.expert_weights
    ssm, delta, sconv = kind.attention == "ssm", kind.attention == "kda", kind.attention == "sconv"
    attention = kind.attention not in ("ssm", "kda", "sconv", "none")
    latent, plain = kind.attention == "latent", attention and kind.attention != "latent"
    q_rank, kv_rank, rope, dv = c.q_lora_rank, c.kv_lora_rank, c.qk_rope_dim, c.value_dim
    heads, inner, conv = c.ssm_heads, c.ssm_heads * c.ssm_head_dim, c.ssm_conv_width
    kda_inner = c.kda_heads * c.kda_head_dim
    gate = ((m, c.n_heads), ("embed", "heads")) if c.attn_gate_per_head else (
        (m, c.n_heads, dh), ("embed", "heads", "head_dim"))
    # (name, whether this layer has the leaf, its shape, initialisation and axes)
    leaves = [
        ("ln1_scale", attention or ssm or delta or sconv, (m,), "ones", (None,)),
        ("ln1_post_scale", attention and c.sandwich_norm, (m,), "post", (None,)),
        ("ln2_scale", dense or experts, (m,), "ones", (None,)),
        ("ln2_post_scale", (dense or experts) and c.sandwich_norm, (m,), "post", (None,)),
        # a latent layer with no q latent has q's ONE projection, as a plain layer has
        ("wq", plain or (latent and not q_rank), (m, c.n_heads, dh), "normal", ("embed", "heads", "head_dim")),
        ("wk", plain, (m, c.kv_heads, dh), "normal", ("embed", "kv_heads", "head_dim")),
        ("wv", plain, (m, c.kv_heads, dh), "normal", ("embed", "kv_heads", "head_dim")),
        # a latent layer: the down-projections and the latents' norms whole on
        # every device of a tensor-parallel group, the up-projections over heads
        ("wq_a", latent and q_rank > 0, (m, q_rank), "normal", ("embed", None)),
        ("q_a_norm_scale", latent and q_rank > 0, (q_rank,), "ones", (None,)),
        ("wq_b", latent and q_rank > 0, (q_rank, c.n_heads, dh), "normal", (None, "heads", "head_dim")),
        ("wkv_a", latent, (m, kv_rank + rope), "normal", ("embed", None)),
        ("kv_a_norm_scale", latent, (kv_rank,), "ones", (None,)),
        ("wkv_b", latent, (kv_rank, c.n_heads, dh - rope + dv), "normal", (None, "heads", "head_dim")),
        ("wg", attention and c.attn_gate, gate[0], "normal", gate[1]),
        ("wo", attention, (c.n_heads, dv, m), into_residual, ("heads", "head_dim", "embed")),
        ("q_norm_scale", attention and c.qk_norm_per_head, (dh,), "ones", (None,)),
        ("k_norm_scale", attention and c.qk_norm_per_head, (dh,), "ones", (None,)),
        # a state-space mixer: [z | x B C | dt] in one projection, whole on every
        # device; what is a head's carries the heads' axis (whole here too: no
        # rule of parallel/sharding names it yet)
        ("ssm_in", ssm, (m, inner + conv + heads), "normal", ("embed", None)),
        ("ssm_conv_w", ssm, (conv, c.ssm_conv_kernel), "conv", (None, None)),
        ("ssm_conv_b", ssm, (conv,), "zeros", (None,)),
        ("ssm_dt_bias", ssm, (heads,), "ssm_dt_bias", ("ssm_heads",)),
        ("ssm_a_log", ssm, (heads,), "ssm_a_log", ("ssm_heads",)),
        ("ssm_d", ssm, (heads,), "ones", ("ssm_heads",)),
        ("ssm_norm_scale", ssm, (heads, c.ssm_head_dim), "ones", ("ssm_heads", None)),
        ("ssm_out", ssm, (heads, c.ssm_head_dim, m), into_residual, ("ssm_heads", "head_dim", "embed")),
        # a delta-rule mixer: [q | k | v | f] in one projection and [beta | gate], one
        # logit a head each, in another (64 columns beside 16,384 would leave the wide one
        # no whole number of lane tiles), whole on every device as a state-space mixer's
        ("kda_in", delta, (m, 4 * kda_inner), "normal", ("embed", None)),
        ("kda_bg", delta, (m, 2 * c.kda_heads), "normal", ("embed", None)),
        ("kda_conv_w", delta, (3 * kda_inner, c.kda_conv_kernel), "conv", (None, None)),
        ("kda_a_log", delta, (c.kda_heads,), "ssm_a_log", ("ssm_heads",)),
        ("kda_dt_bias", delta, (kda_inner,), "ssm_dt_bias", (None,)),
        ("kda_norm_scale", delta, (c.kda_head_dim,), "ones", (None,)),
        ("kda_out", delta, (c.kda_heads, c.kda_head_dim, m), into_residual, ("ssm_heads", "head_dim", "embed")),
        # a gated short convolution: [B | C | X] in one projection, whole on every device
        ("sconv_in", sconv, (m, 3 * m), "normal", ("embed", None)),
        ("sconv_w", sconv, (m, c.sconv_taps), "conv", (None, None)),
        ("sconv_out", sconv, (m, m), into_residual, (None, "embed")),
        ("w_gate", dense, (m, c.d_ff_dense), "normal", ("embed", "mlp")),
        ("w_up", dense, (m, c.d_ff_dense), "normal", ("embed", "mlp")),
        ("w_down", dense, (c.d_ff_dense, m), into_residual, ("mlp", "embed")),
        # replicated, as moe.logical_axes has it
        ("router", experts, (m, c.n_experts), "router", (None, None)),
        ("expert_bias", experts and c.router_select_bias, (c.n_experts,), "zeros", (None,)),
        ("we_gate", experts and gated, (held, m, c.d_ff), "normal", ("expert", "embed", "mlp")),
        ("we_up", experts, (held, m, c.d_ff), "normal", ("expert", "embed", "mlp")),
        ("we_down", experts, (held, c.d_ff, m), into_residual, ("expert", "mlp", "embed")),
        ("ws_gate", experts and gated and shared > 0, (m, shared), "normal", ("embed", "mlp")),
        ("ws_up", experts and shared > 0, (m, shared), "normal", ("embed", "mlp")),
        ("ws_down", experts and shared > 0, (shared, m), into_residual, ("mlp", "embed")),
    ]
    return {name: (shape, how, axes) for name, has, shape, how, axes in leaves if has}


def mtp_kind(config: MixedStackConfig) -> LayerKind:
    """The multi-token prediction module's block: an expert layer with the
    attention of the stack's last layer."""
    return LayerKind(layer_kinds(config)[-1].attention, "experts")


def _mtp_shapes(config: MixedStackConfig) -> Dict[str, Tuple[Tuple[int, ...], Any, Any]]:
    """`_layer_shapes` of the module's leaves outside its block: the norms on
    the next token's embedding and on the stack's output, the projection that
    joins the two (their concatenation, embedding first, -> the stream), and
    the norm before the shared head."""
    m = config.d_model
    return {"enorm_scale": ((m,), "ones", (None,)), "hnorm_scale": ((m,), "ones", (None,)),
            "eh_proj": ((2 * m, m), "normal", (None, "embed")),
            "norm_scale": ((m,), "ones", (None,))}


def init_params(config: MixedStackConfig, key: jax.Array) -> Params:
    """The repo's initialisation (N(0, 0.02), over the root of the stack's
    sublayers, sqrt(2 L) where every layer has two, for a projection into the
    residual stream that no norm follows; norms 1 but those on a sublayer's
    output, which start at POST_NORM_GAIN; the selection bias 0), and a
    state-space mixer's own leaves as its family starts them (A = -U(1, 16) a
    head; the step's bias the inverse softplus of a step drawn log-uniformly
    in [ssm_dt_min, ssm_dt_max] and no smaller than ssm_dt_floor; D and the
    gated norm 1; the convolution's taps U(+-1 / sqrt(taps)) and its bias 0),
    stacked a run: params["runs"][r][p][leaf] has the run's repeats in
    front."""
    c = config
    pd = c.param_dtype
    std = 0.02
    sublayers = sum(kind.sublayers for kind in layer_kinds(c))
    deviation = {"normal": std, "into_residual": std / math.sqrt(sublayers),
                 "router": c.router_std}
    constant = {"ones": 1.0, "post": POST_NORM_GAIN, "zeros": 0.0}

    def leaf(k, shape, how, repeats):
        shape = (repeats, *shape)
        if how in deviation:
            return deviation[how] * jax.random.normal(k, shape, pd)
        if how == "conv":
            bound = 1.0 / math.sqrt(shape[-1])
            return jax.random.uniform(k, shape, pd, -bound, bound)
        if how == "ssm_a_log":
            return jnp.log(jax.random.uniform(k, shape, pd, 1.0, 16.0))
        if how == "ssm_dt_bias":
            low, high = math.log(c.ssm_dt_min), math.log(c.ssm_dt_max)
            step = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, pd, low, high)), c.ssm_dt_floor)
            return step + jnp.log(-jnp.expm1(-step))       # softplus(bias) = step
        return jnp.full(shape, constant[how], pd)

    runs = []
    for r, run in enumerate(stack_runs(layer_kinds(c))):
        period = []
        for p, kind in enumerate(run.kinds):
            shapes = _layer_shapes(c, kind)
            keys = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, r), p), len(shapes))
            period.append({name: leaf(k, shape, how, run.repeats)
                           for k, (name, (shape, how, _)) in zip(keys, shapes.items())})
        runs.append(period)
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 10**6))
    params = {
        "wte": c.embedding_std * jax.random.normal(k_embed, (c.vocab_size, c.d_model), pd),
        "runs": runs,
        "lnf_scale": jnp.ones((c.d_model,), pd),
    }
    if not c.tie_embeddings:
        params["lm_head"] = std * jax.random.normal(k_head, (c.d_model, c.vocab_size), pd)
    if c.mtp_modules:
        # the module's own leaves without a leading axis, its block's stacked as a run of one
        own, block = _mtp_shapes(c), _layer_shapes(c, mtp_kind(c))
        keys = iter(jax.random.split(jax.random.fold_in(key, 10**6 + 1), len(own) + len(block)))
        params["mtp"] = {
            **{name: leaf(next(keys), shape, how, 1)[0] for name, (shape, how, _) in own.items()},
            "block": {name: leaf(next(keys), shape, how, 1) for name, (shape, how, _) in block.items()}}
    return params


def logical_axes(config: MixedStackConfig) -> Params:
    def stacked(kind):
        return {name: ("layers", *axes) for name, (_, _, axes) in _layer_shapes(config, kind).items()}

    axes = {
        "wte": ("vocab", "embed"),
        "runs": [[stacked(kind) for kind in run.kinds] for run in stack_runs(layer_kinds(config))],
        "lnf_scale": (None,),
    }
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if config.mtp_modules:
        axes["mtp"] = {**{name: leaf_axes for name, (_, _, leaf_axes) in _mtp_shapes(config).items()},
                       "block": stacked(mtp_kind(config))}
    return axes


# -------------------------------------------------------------------- forward


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gate_xbc_dt(projected, inner: int, conv: int):
    """[z | xBC | dt], the in-projection's output -> (the WHOLE array for the
    gate, the WHOLE array for the convolution, dt). `ssd.gated_group_norm`
    reads z at the first `inner` features of the whole and
    `ssd.causal_conv1d` xBC at the `conv` after them: a slice in front of a
    kernel is a copy, and with it XLA writes the projection transposed
    (PERF.md section 6, PR 50). The transpose joins the three cotangents in
    ONE concatenate, as `jnp.split`'s does, whatever read either whole."""
    return projected, projected, projected[..., inner + conv:]


def _gate_xbc_dt_fwd(projected, inner, conv):
    return _gate_xbc_dt(projected, inner, conv), None


def _gate_xbc_dt_bwd(inner, conv, _, cotangents):
    # a slice of each whole's cotangent, not of their sum: a kernel's is a pad
    # of its columns, and XLA reads a slice of a pad as the columns or as zeros
    gate, xbc, step = cotangents
    z, conv_in, rest = (gate[..., at] + xbc[..., at]
                        for at in (slice(0, inner), slice(inner, inner + conv), slice(inner + conv, None)))
    return (jnp.concatenate([z, conv_in, rest + step], axis=-1),)


_gate_xbc_dt.defvjp(_gate_xbc_dt_fwd, _gate_xbc_dt_bwd)


def _ssm_sublayer(x, lp, config):
    """A Mamba-2 mixer + residual on (B, S, E), the scope `ssm`: [z | xBC |
    dt] = norm(x) W_in (`ssm.in_proj`); xBC through the causal depthwise
    convolution and silu (`ssm.conv`, ops/ssd.causal_conv1d: on a TPU two
    kernels that read xBC out of the projection as it is and write x, B and
    C apart); the step softplus(dt + bias) in float32
    and the selective scan of the heads' x with their group's B and C
    (`ssm.scan`, ops/ssd.ssd_scan); y silu(z) through an RMS norm over each
    group's features (`ssm.gate_norm`, ops/ssd.gated_group_norm: the gate
    first, then the norm; on a TPU two kernels on the flat (B, S, H P) y and
    the projection as it is, elsewhere ops/layers.rmsnorm on the view by
    groups); W_out on the flat features and the residual (`ssm.out_proj`).
    -> (x, the most negative cumulative log-decay inside a chunk)."""
    c = config
    dt = c.dtype
    b, s, _ = x.shape
    heads, p, groups, n = c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state
    inner = heads * p
    with jax.named_scope("ssm"):
        with jax.named_scope("ssm.in_proj"):
            u = _norm(x, lp["ln1_scale"], None, c.norm, c.norm_eps)
            projected = checkpoint_name(jnp.einsum("bse,ef->bsf", u, lp["ssm_in"].astype(dt)), "ssm_in_proj")
            z, xbc, step = _gate_xbc_dt(projected, inner, c.ssm_conv_width)
        with jax.named_scope("ssm.conv"):
            # x, B and C as an output each: slices of one would be copies in front of the scan's kernels
            xs, bs, cs = ssd.causal_conv1d(xbc, lp["ssm_conv_w"], lp["ssm_conv_b"], offset=inner,
                                           splits=(inner, groups * n, groups * n))
        with jax.named_scope("ssm.scan"):
            step = jax.nn.softplus(step.astype(jnp.float32) + lp["ssm_dt_bias"].astype(jnp.float32))
            y = ssd.ssd_scan(xs.reshape(b, s, heads, p), step, lp["ssm_a_log"], bs.reshape(b, s, groups, n),
                             cs.reshape(b, s, groups, n), lp["ssm_d"], chunk=c.ssm_chunk)
            decay_min = jax.lax.stop_gradient(
                ssd.log_decay_chunk_min(step, lp["ssm_a_log"], c.ssm_chunk))
        with jax.named_scope("ssm.gate_norm"):
            y = ssd.gated_group_norm(y.reshape(b, s, inner), z, lp["ssm_norm_scale"].reshape(inner), groups=groups,
                                     eps=1e-6 if c.norm_eps is None else c.norm_eps)    # None: rmsnorm's own
        with jax.named_scope("ssm.out_proj"):
            out = jnp.einsum("bsf,fe->bse", y, lp["ssm_out"].astype(dt).reshape(inner, -1))
            return x + out, decay_min


# inside the root of q's and k's L2 norms
_KDA_L2_EPS = 1e-6


def _kda_sublayer(x, lp, config):
    """A Kimi-Delta-Attention mixer + residual on (B, S, E), the scope `kda`:
    [q | k | v | f] = norm(x) W_in and [beta | gate] = norm(x) W_bg, one logit
    a head each (`kda.in_proj`); q, k and v through a causal depthwise
    convolution and silu (`kda.conv`, ops/ssd.causal_conv1d with no bias: the
    state-space mixer's, kernels and rule); the chunked delta rule on those
    as they are, flat (B, S, H D), with f, the two logits a head as the small
    matmul wrote them, `A_log`, the gate's bias and the norm's scale
    (`kda.chunk`, ops/kda.kda_rule, which makes of them what the recurrence
    takes, on a TPU in its kernels' VMEM and in `jnp` elsewhere: q and k
    L2-normalised a head, q over sqrt(head_dim); the log-decay a channel,
    `kda_gate_lower_bound` x sigmoid(exp(A_log) (f + dt_bias)) in float32;
    beta = sigmoid; and which returns the rule's output through an RMS norm
    over each head's features times sigmoid of the head's ONE gate logit,
    flat: on a TPU `kda_fwd`'s last lines and `kda_bwd`'s first, elsewhere
    operations under `kda.gate_norm` inside `kda.chunk`); W_out on the flat
    features and the residual (`kda.out_proj`). No positions. -> (x, the most
    negative cumulative log-decay inside a chunk)."""
    c = config
    dt = c.dtype
    heads = c.kda_heads
    inner = heads * c.kda_head_dim
    with jax.named_scope("kda"):
        with jax.named_scope("kda.in_proj"):
            u = _norm(x, lp["ln1_scale"], None, c.norm, c.norm_eps)
            projected = checkpoint_name(jnp.einsum("bse,ef->bsf", u, lp["kda_in"].astype(dt)), "kda_in_proj")
            # the matmul's float32 accumulator is what the sigmoids take: XLA's fusion carried it there before it
            # was asked to, and the logits are a kernel's operand, which is written as the program says (WHOLE:
            # the kernels read beta's lanes and the gate's; a slice in front of a kernel is a copy)
            beta_gate = jnp.einsum("bse,ef->bsf", u, lp["kda_bg"].astype(dt), preferred_element_type=jnp.float32)
            # [q | k | v | f]: the WHOLE array for q's and k's convolution and for v's (two calls: the
            # kernels take at most 8,192 channels a step), and f; `_gate_xbc_dt`'s reasons
            for_qk, for_v, f = _gate_xbc_dt(projected, 2 * inner, inner)
        with jax.named_scope("kda.conv"):
            taps = lp["kda_conv_w"]
            q, k = ssd.causal_conv1d(for_qk, taps[:2 * inner], jnp.zeros((2 * inner,), jnp.float32),
                                     splits=(inner, inner))
            v = ssd.causal_conv1d(for_v, taps[2 * inner:], jnp.zeros((inner,), jnp.float32), offset=2 * inner)
        with jax.named_scope("kda.chunk"):
            out, decay_min = kda.kda_rule(q, k, v, f, beta_gate, lp["kda_a_log"], lp["kda_dt_bias"],
                                          lp["kda_norm_scale"], eps=_KDA_L2_EPS,
                                          norm_eps=1e-6 if c.norm_eps is None else c.norm_eps,
                                          chunk=c.kda_chunk, lower_bound=c.kda_gate_lower_bound)
        with jax.named_scope("kda.out_proj"):
            out = jnp.einsum("bsf,fe->bse", out, lp["kda_out"].astype(dt).reshape(inner, -1))
            return checkpoint_name(x + out, "kda_residual"), decay_min


def _sconv_sublayer(x, lp, config):
    """A gated short-convolution mixer + residual on (B, S, E), the scope
    `sconv`: [B | C | X] = norm(x) W_in (`sconv.in_proj`); C * conv(B * X)
    on the projection as it is, `sconv_taps` causal depthwise taps a channel
    with no bias and no activation (`sconv.conv`, ops/short_conv.
    gated_short_conv: both gates and the taps); W_out and the residual
    (`sconv.out_proj`). No positions."""
    c = config
    dt = c.dtype
    with jax.named_scope("sconv"):
        with jax.named_scope("sconv.in_proj"):
            u = _norm(x, lp["ln1_scale"], None, c.norm, c.norm_eps)
            bcx = checkpoint_name(jnp.einsum("bse,ef->bsf", u, lp["sconv_in"].astype(dt)), "sconv_in_proj")
        with jax.named_scope("sconv.conv"):
            y = checkpoint_name(short_conv.gated_short_conv(bcx, lp["sconv_w"]), "sconv_conv_out")
        with jax.named_scope("sconv.out_proj"):
            out = jnp.einsum("bsf,fe->bse", y, lp["sconv_out"].astype(dt))
            return checkpoint_name(x + out, "sconv_residual")


def _block(x, lp, config, kind: LayerKind, rope_tables, positions, remat_saved=()):
    """One layer on (B, S, E), the sublayers its kind names: x + mixer(norm(x))
    (attention of either kind, a state-space mixer, a delta-rule one or a gated
    short convolution), then
    the same around the MLP (dense or experts), each sublayer's output through
    a norm of its own where the configuration has them. -> (x, the layer's
    scalars: an expert layer's, a state-space mixer's
    `ssm_log_decay_chunk_min`, a delta-rule mixer's `kda_log_decay_chunk_min`)."""
    c = config
    if c.frozen_leaves:
        lp = {name: jax.lax.stop_gradient(w) if name in c.frozen_leaves else w
              for name, w in lp.items()}
    # the layer's input is kept for the backward pass whatever is recomputed,
    # so a router that reads it costs no tensor carried past the attention
    router_input = x if c.router_input == "attention" else None
    scalars = {}
    if kind.attention == "ssm":
        x, scalars["ssm_log_decay_chunk_min"] = _ssm_sublayer(x, lp, c)
    elif kind.attention == "kda":
        x, scalars["kda_log_decay_chunk_min"] = _kda_sublayer(x, lp, c)
    elif kind.attention == "sconv":
        x = _sconv_sublayer(x, lp, c)
    elif kind.attention != "none":
        x = attention_sublayer(     # the scope `attn.window` or `attn.full`, by the window
            x, lp, c, None if kind.attention == "full" and not c.attn_full_rope else rope_tables, positions,
            window=c.sliding_window if kind.attention == "sliding" else None, remat_saved=remat_saved)
    if kind.mlp == "dense":
        x = mlp_sublayer(x, lp, c)
    if kind.mlp != "experts":
        return x, scalars
    with jax.named_scope("moe"):
        out, routed = moe_mlp(_norm(x, lp["ln2_scale"], None, c.norm, c.norm_eps), lp, c,
                              router_input=router_input)
        if c.sandwich_norm:
            out = _norm(out, lp["ln2_post_scale"], None, c.norm, c.norm_eps)
        x = x + out
    routed.pop("aux")  # this family trains on the cross entropy alone
    return x, {**scalars, **routed, "load": load_max_over_mean(routed["load"])}


def forward_hidden(
    params: Params,
    tokens: jax.Array,
    config: MixedStackConfig,
    *,
    positions: Optional[jax.Array] = None,
    remat_saved: Tuple[str, ...] = (),
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Forward up to the LM head: (B, S) -> ((B, S, E), what the expert layers
    report: `moe_load_max_over_mean` and `moe_passes` of the worst layer,
    `moe_rows_held` and `moe_rows_held_share` (of the T k routed rows) as the
    mean over the expert layers, as is `moe_act_live_share`, the percentage of
    the held ReGLU experts' hidden units that the ReLU leaves non-zero on the
    rows sent here; what the state-space layers report: `ssm_log_decay_chunk_min`
    of the worst layer, and the delta-rule layers `kda_log_decay_chunk_min`).
    The rotary table is built for the sequence at hand, not for `max_seq`."""
    c = config
    dt = c.dtype
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = params["wte"].astype(dt)[tokens]
        if c.scale_embedding:
            x = x * jnp.asarray(math.sqrt(c.d_model), dt)
    rope_tables = rope_frequencies(c.rotary_dims, s, c.rope_theta)

    reports: List[Dict[str, jax.Array]] = []
    for run, period_params in zip(stack_runs(layer_kinds(c)), params["runs"]):
        def period(x, period_lp, kinds=run.kinds):
            scalars = []
            for kind, lp in zip(kinds, period_lp):
                def block_fn(x, lp, kind=kind):
                    return _block(x, lp, c, kind, rope_tables, positions, remat_saved)

                if c.remat:
                    block_fn = checkpoint_block(block_fn, remat_saved)
                x, layer_scalars = block_fn(x, lp)
                if layer_scalars:
                    scalars.append(layer_scalars)
            return x, scalars

        if run.repeats == 1:
            x, scalars = period(x, jax.tree.map(lambda w: w[0], period_params))
        else:
            x, scalars = jax.lax.scan(period, x, period_params, unroll=c.scan_unroll)
        reports.extend(scalars)      # under a scan each scalar is (repeats,)
    with jax.named_scope("head"):
        x = _norm(x, params["lnf_scale"], None, c.norm, c.norm_eps)
    report = _expert_layers_report([r for r in reports if "load" in r], c, b * s)
    for name in ("ssm_log_decay_chunk_min", "kda_log_decay_chunk_min"):
        decays = [jnp.ravel(r[name]) for r in reports if name in r]
        if decays:
            report[name] = jnp.min(jnp.concatenate(decays))
    return x, report


def _expert_layers_report(reports: List[Dict[str, jax.Array]], config: MixedStackConfig,
                          tokens: int) -> Dict[str, jax.Array]:
    """What `forward_hidden` says of its expert layers, from their scalars (a
    scanned run's are (repeats,) each); {} for none."""
    c = config
    if not reports:
        return {}
    # a layer's scalars may hold its mixer's too, which not every expert layer has
    every = {name: jnp.concatenate([jnp.ravel(r[name]) for r in reports])
             for name in reports[0] if all(name in r for r in reports)}
    out = {"moe_load_max_over_mean": jnp.max(every["load"])}
    if "moe_rows_held" in every:
        rows_held = jnp.mean(every["moe_rows_held"])
        out.update(moe_rows_held=rows_held,
                   moe_rows_held_share=100.0 * rows_held / (tokens * c.top_k),
                   moe_passes=jnp.max(every["moe_passes"]))
    if "moe_act_live_units" in every:
        out["moe_act_live_share"] = jnp.mean(
            100.0 * every["moe_act_live_units"] / jnp.maximum(every["moe_rows_held"] * c.d_ff, 1.0))
    return out


def mtp_hidden(
    params: Params,
    hidden: jax.Array,
    next_tokens: jax.Array,
    config: MixedStackConfig,
    routers: Dict[str, jax.Array],
    *,
    remat_saved: Tuple[str, ...] = (),
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The multi-token prediction module on `forward_hidden`'s output (B, S,
    E) and the tokens that FOLLOW its positions (B, S): [norm_e(embedding of
    the next token) ; norm_h(hidden)] `eh_proj`, one block of `mtp_kind` (its
    checkpoint keeps what the stack's blocks keep), a norm: -> ((B, S, E) for
    the shared head, which then predicts the token after next; `routers`,
    `forward_hidden`'s report, with this block's expert layer counted as one
    more of the stack's)."""
    c = config
    dt = c.dtype
    mp = params["mtp"]
    b, s = next_tokens.shape
    with jax.named_scope("embed"):
        embedded = params["wte"].astype(dt)[next_tokens]
        if c.scale_embedding:
            embedded = embedded * jnp.asarray(math.sqrt(c.d_model), dt)
    joined = jnp.concatenate([_norm(embedded, mp["enorm_scale"], None, c.norm, c.norm_eps),
                              _norm(hidden, mp["hnorm_scale"], None, c.norm, c.norm_eps)], axis=-1)
    x = jnp.einsum("bsf,fe->bse", joined, mp["eh_proj"].astype(dt))
    rope_tables = rope_frequencies(c.rotary_dims, s, c.rope_theta)

    def block_fn(x, lp):
        return _block(x, lp, c, mtp_kind(c), rope_tables, None, remat_saved)

    if c.remat:
        block_fn = checkpoint_block(block_fn, remat_saved)
    x, scalars = block_fn(x, jax.tree.map(lambda w: w[0], mp["block"]))
    x = _norm(x, mp["norm_scale"], None, c.norm, c.norm_eps)
    # the block's layer beside the stack's: the worst of both, and the means
    # weighted by the layers they are of
    mine, layers = _expert_layers_report([scalars], c, b * s), _expert_layers(c)
    worst = ("moe_load_max_over_mean", "moe_passes")
    return x, {name: (jnp.maximum(routers[name], value) if name in worst
                      else (layers * routers[name] + value) / (layers + 1))
               for name, value in mine.items()} if layers else mine


def _expert_layers(config: MixedStackConfig) -> int:
    return sum(kind.mlp == "experts" for kind in layer_kinds(config))


# What a kept routing (`moe.ROUTING`) is worth beside a kept matmul output, from
# forced plans on the chip (PERF.md section 6, PR 60). The router's float32
# matmul at `Precision.HIGHEST` is six bfloat16 passes (`moe.route` recomputed,
# 2.0 ms a step of `train-ling3flash-4k` over 6 layers of 4,096 tokens: 16
# MFLOP a token at the chip's peak, where six passes count 15.7)
_ROUTER_MATMUL_PASSES = 6
# and FLOPs that take as long as selecting over ONE of a token's scores and
# sorting its choices: the group limit's two top-k's, the masked top-k, the
# gates' gather and the rows' sort are no arithmetic a matmul's rate measures.
# Recomputed, `moe.select` 4.0 + the dispatch's sort 1.5 ms a step on that cell
# (512 scores a token: 96 k a score; kept, the step is 8.2 ms shorter); on
# `train-trinity-mini-8k` and `train-glm47flash-8k` (128 and 64 scores, no
# groups) `moe.select` recomputed is 5.1 and 3.4 ms a step: 120 k and 128 k
_ROUTING_SELECT_FLOPS_PER_SCORE = 100_000
# The held experts' grouped matmuls' share of the chip's peak, by which the
# FLOPs a kept buffer spares count for their time (`moe_held_gmm_roofline`
# 77.4 on `train-lfm2moe-8k`: ledger, PR 63)
_HELD_GMM_SHARE_OF_PEAK = 0.77


def _expert_costs(config: MixedStackConfig, split: Callable[[str], int], tokens: int) -> Dict[str, Any]:
    """An expert layer's MLP as `transformer.mlp_costs` gives a dense one's,
    a row (a token) and device at an even routing: the router, the shared
    expert, the held experts' grouped matmuls on the rows sent here (three
    matrices an expert, or the two of one that is not gated), and the buffer
    those rows pass through. Its candidates (PERF.md section 6, PR 60: each
    forced alone on the chip beside what `train-ling3flash-4k` kept before,
    a step of 234.9 ms). The routing, ONE name for all of it (`moe.ROUTING`:
    the router's float32 logits, the chosen experts and their scores and, of
    held experts, the sorted rows' order, 4 bytes each; the held experts'
    ends are a few integers a layer): with it the backward pass neither runs
    the router's matmul again nor the group limit, the top-k, the gather or
    the sort; 8.2 ms for 53 MB, the first loss the same to the bit. And the
    shared expert's gate and up (up alone where it is not gated), a matmul's
    output each and worth it as a dense layer's are (`transformer.mlp_costs`):
    0.6 ms for 75 MB there. And, of a layer that holds a part of the experts,
    the buffer its first pass wrote (`moe.held_buffer_names`: the gathered
    rows, the experts' gate and up, the down projection's output and the
    slots' tables; a later pass is computed again whatever is kept). Forced on
    the chip of `train-lfm2moe-8k`, each part with the tables beside what the
    cell kept (PERF.md section 6, PR 67; the refused PR 66's builder made
    the sweep: a step of 271.0 ms, 34,816 slots of which 32,768 hold rows at
    twice the even share, four layers): the tables alone 1.4 ms for 2.4 MB,
    with the gathered rows 4.3 for 0.57 GB, with gate and up 6.3 for 1.00 GB,
    with the output 4.8 for 0.57 GB, gate, up and the output 7.8, the rows,
    gate and up 7.4, ALL 10.3 ms for 2.14 GB (the peak 70.3 -> 78.5% of the
    chip): every part pays beside every other, so they are ONE candidate.
    With it the backward pass runs none of the layer's 12
    forward grouped matmuls again (9.6 ms), no gather, search or table, and
    what the recomputed pass keeps of the layer is the norm, the weights'
    casts and the activation (an elementwise pass from gate and up); a Pallas
    kernel writes gate, up and the output, so each kept one is a copy in the
    forward pass (4.3 ms of the 14.6 spared). Its `width` is the slots', the
    grouped matmuls' tile a held expert included, over the device's `tokens`;
    its `worth` the experts' matmuls at an even routing at the kernels' share
    of the peak less a copy in and out of every byte a kernel wrote
    (`transformer._KEPT_KERNEL_FLOPS_PER_BYTE`). The gather, the tables and
    the combine it spares with them are NOT priced (as the row-wise work of
    an attention layer's operands is not, `transformer.attention_costs`):
    priced, the candidate would stand before the attention kernel's output on
    the cells whose chip is full and push it out. So the worth is low, and
    right in its sign where the matmuls are: on `train-ling3flash-4k` 3,072
    slots, two thirds of them the tiles' padding, stand for 512 rows (~64 an
    expert), the copies of 6.1 KB a token in and out outweigh 1.5 MFLOP of
    matmuls that take microseconds, the worth is negative and the rule never
    tries it (forced there by PR 60, the rows, gate and up: 0.9 ms of a 215 ms
    step for 146 MB; by PR 66's builder, all of it: 1.7 ms of 202.3 for 0.22
    GB, what the formula leaves unpriced and a third of what that cell's line
    resolves); on the cells with 36,864-row buffers it is 0.2-0.45 GB a layer,
    positive, and refused for room."""
    c = config
    shared = c.shared_expert_width // split("ws_up") if c.shared_expert_width else 0
    d_ff = c.d_ff // split("we_up")
    matrices = len(c.expert_weights)
    rows_here = c.top_k * c.n_experts_held / c.n_experts   # (token, choice) pairs a token
    itemsize = jnp.dtype(c.dtype).itemsize
    router, shared_matmul = 2 * c.d_model * c.n_experts, 2 * c.d_model * shared
    # float32 logits; the experts, their scores and (held: the sorted rows' order) 4 bytes a choice
    routing = 4 * (c.n_experts + (2 if c.held_experts is None else 3) * c.top_k) // itemsize
    experts_matmuls = 2 * c.d_model * matrices * rows_here * d_ff
    buffer = ()
    if c.held_experts is not None:
        # the first pass's slots (moe._held_experts), the grouped matmuls' tile a held expert among them, over
        # this device's tokens: a slot's gathered row and output, its (gate and) up, its row and gate 4 bytes each
        tile = gmm_tile_rows()
        slots = held_buffer_rows(c, tokens, tile) + c.n_experts_held * tile
        written = c.d_model + (matrices - 1) * d_ff         # by a kernel: each kept one is copied in and out
        buffer = (RematCandidate(
            held_buffer_names(c), -(-slots * (c.d_model + written + 8 // itemsize) // tokens), int(experts_matmuls),
            int(experts_matmuls / _HELD_GMM_SHARE_OF_PEAK
                - 2 * slots * written * itemsize / tokens * _KEPT_KERNEL_FLOPS_PER_BYTE), False, ()),)
    return {
        "flops": int(router + matrices * shared_matmul + experts_matmuls),
        # both norms' outputs, the layer's, the residual; the router's float32
        # scores; the shared expert's (gate,) up and activation; a buffer row's
        # input, (gate,) up, activation and output
        "width": int(4 * c.d_model + c.n_experts * 4 // itemsize + matrices * shared
                     + min(_HELD_BUFFER_SHARES * rows_here, c.top_k) * (2 * c.d_model + matrices * d_ff)),
        "candidates": (
            RematCandidate((ROUTING,), routing, router,
                           _ROUTER_MATMUL_PASSES * router + _ROUTING_SELECT_FLOPS_PER_SCORE * c.n_experts, False, ()),
            *(RematCandidate((name.replace("we_", "moe_shared_"),), shared, shared_matmul, shared_matmul, False, ())
              for name in c.expert_weights[:-1] if shared),
            *buffer),
    }


# a layer's absent sublayer, in `block_costs`
_NO_SUBLAYER: Dict[str, Any] = {"flops": 0, "width": 0, "candidates": ()}
# The scan's share of the chip's peak, by which the operations it spares count
# for their time beside matmuls that run near the peak (as
# transformer._FLASH_SHARE_OF_PEAK does for the attention kernels), by the form
# that runs (`ops/ssd.resolve_scan_impl`): from the chip at the
# `train-nemotron3nano-8k` cell's shapes, one forward scan of 2 x 8,192 tokens,
# 55.8 GFLOP as computed, in 6.57 ms as XLA einsums (PERF.md section 6, PR 48)
# and in 1.63 ms as `ssd_fwd` with the layouts around it (PR 49)
_SSD_SHARE_OF_PEAK = {"xla_chunked": 0.043, "pallas": 0.174}


def _ssm_costs(config: MixedStackConfig) -> Dict[str, Any]:
    """`_ssm_sublayer`'s part of `block_costs`, a layer and token (whole on
    every device: its weights are not split). Two candidates. The scan's
    output WITH the states its backward pass starts from (`ssm_scan_out`,
    `ssm_chunk_states` of ops/ssd: the kernels keep the state that entered
    every chunk in the activations' dtype, the XLA form a float32 state a
    block of chunks): with both the backward pass does not run the scan
    forward again; with one of them alone it must. And the in-projection's
    output (`ssm_in_proj`: z, xBC and dt are one matmul's), worth its matmul
    as a dense layer's gate and up are: the identity that `jax.checkpoint`
    puts on a kept value fuses into the matmul's output, and the scanned
    run's kept projection is written feature-minor straight into its stack
    (on the `train-nemotron3nano-8k` cell 22.0 ms of a 392.6 ms step for 1.35
    GB, the 21.2 the four recomputed projections took and the copy of PERF.md
    row 49 (h)). The convolution's and the gated norm's outputs are NOT named:
    a Pallas kernel writes them, so keeping one is a whole copy that nothing
    fuses, and with the stack's writes the step took 3.3 and 1.1 ms MORE for
    0.81 and 0.54 GB (PERF.md section 6, PR 57)."""
    c = config
    heads, p, n, chunk = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_chunk
    inner, conv = heads * p, c.ssm_conv_width
    itemsize = jnp.dtype(c.dtype).itemsize
    # as ops/ssd computes it: C B^T and the weighted x inside a chunk, a chunk's
    # state, the read-out of the state that entered it
    scan = 2 * chunk * c.ssm_groups * n + 2 * chunk * inner + 4 * inner * n
    impl = ssd.resolve_scan_impl(chunk=chunk, heads=heads, groups=c.ssm_groups, head_dim=p, state=n)
    # the kept states, in features of the activations' dtype a token
    state = (inner * n // chunk if impl == "pallas"
             else -(-inner * n * 4 // (chunk * ssd.BLOCK_CHUNKS * itemsize)))
    projected = inner + conv + heads    # [z | xBC | dt], one matmul's output
    in_proj = 2 * c.d_model * projected
    return {
        "flops": in_proj + 2 * c.ssm_conv_kernel * conv + scan + 2 * inner * c.d_model,
        # the norm's output, the sublayer's, the residual; the projection; the
        # convolution's output (its float32 sum is fused away); the scan's
        # operands cut into blocks; its output and the gated, normed one (both
        # in the activations' dtype: the norm's kernels hold the float32
        # product in VMEM, and the XLA form's float32 copies are the CPU's). On
        # the chip the whole-block step of the cell peaked at 13.87 GB when
        # every part was XLA's (PR 48: 1.75 copies of 41.9 k features a row,
        # where this counts 42.9 k). Since the three pairs of kernels it peaks
        # at 12.89 GB where `step_peak_bytes` says 13.85, and with the plan the
        # rule makes now (the projection kept too, 2.65 GB in all) at 15.00 GB
        # = 88.7% where it says 15.37: high by 0.96 and 0.37 GB, inside the
        # ceiling's 1.10 GB of margin and on its safe side (PERF.md section 6,
        # PR 57), so the count stands
        "width": 3 * c.d_model + projected + conv + (inner + conv) + 2 * inner,
        "candidates": (
            RematCandidate(("ssm_scan_out", "ssm_chunk_states"), inner + state, scan,
                           int(scan / _SSD_SHARE_OF_PEAK[impl]), False, ()),
            RematCandidate(("ssm_in_proj",), projected, in_proj, in_proj, False, ()),
        ),
    }


# `_SSD_SHARE_OF_PEAK` for the delta rule's chunked form, by the form that
# runs (`ops/kda.resolve_kda_impl`): from the chip at the `train-ling3flash-4k`
# cell's shapes, one forward of 4,096 tokens, 31.1 GFLOP as `_kda_costs` counts
# it. The XLA einsums: 8.02 ms alone and 8.13 ms a layer in the step (PERF.md
# section 6, PR 55). The kernel `kda_fwd`, which is what keeping both names
# spares: 1.86 ms a layer in the step, writing the states (PERF.md section 5
# and 6, PR 56)
_KDA_SHARE_OF_PEAK = {"xla_chunked": 0.02, "pallas": 0.085}


def _kda_costs(config: MixedStackConfig, mlp_follows: bool) -> Dict[str, Any]:
    """`_kda_sublayer`'s part of `block_costs`, a layer and token (whole on
    every device). Three candidates: the rule's output, gated and normed as
    the out-projection reads it, WITH the float32 states that entered its
    chunks and o as the norm's transpose reads it (`kda_chunk_out`,
    `kda_chunk_states`, `kda_chunk_o` of ops/kda: with the three the backward
    pass does not run the rule forward again; since PR 63 the norm a head
    under the head's gate is the rule's own last step, in the kernels where
    o already is, so what this candidate spares holds the norm's arithmetic
    too and the norm's output has no candidate of its own; the bytes are
    what the two candidates held together); the in-projection's output
    (`kda_in_proj`: q, k, v and f, one matmul's; `_ssm_costs` says why it is
    worth its matmul: on the `train-ling3flash-4k` cell 12.8 ms of a 273.2 ms
    step for 0.81 GB); and, where an MLP or an expert layer follows the mixer
    (`mlp_follows`), the stream after the out-projection (`kda_residual`, an
    attention layer's `attn_residual`: worth that matmul; 5.1 ms for 0.13 GB
    forced alone on that cell's chip, PERF.md section 6, PR 60). With all
    kept, the recomputed pass of a delta-rule block still runs the input
    norm's statistics, the small projection and the convolutions.
    The small beta and gate projection beside the in-projection is NOT
    named: kept, its
    bfloat16 rounding was forced where XLA's fusion carried the matmul's
    float32 into the sigmoids, and the cell's first loss moved by 1e-4
    (since PR 58 the program asks for that float32 itself); recomputing it
    is 7 us a layer.
    Nor are the convolved q, k and v: their copies cost the step 1.1 ms more
    than the convolutions' second run takes (PERF.md section 6, PR 57)."""
    c = config
    heads, d, chunk = c.kda_heads, c.kda_head_dim, c.kda_chunk
    inner = heads * d
    itemsize = jnp.dtype(c.dtype).itemsize
    # as ops/kda computes it, a token and head: the two pairwise decays and the
    # products with the inverse (chunk x D each), the inverse's merges, and
    # five products with a D x D state or its update
    rule = heads * (2 * chunk * (4 * d + chunk) + 10 * d * d)
    state = -(-inner * d * 4 // (chunk * itemsize))     # the kept float32 states, in features a token
    impl = kda.resolve_kda_impl(chunk=chunk, d_k=d, d_v=d, lower_bound=c.kda_gate_lower_bound)
    projected = 4 * inner               # [q | k | v | f], one matmul's output
    in_proj, out_proj = 2 * c.d_model * projected, 2 * inner * c.d_model
    gate_norm = 8 * inner               # a square, a sum, the root's, the scale's and the gate's products
    return {
        "flops": (in_proj + 2 * c.d_model * 2 * heads + 2 * c.kda_conv_kernel * 3 * inner + rule
                  + gate_norm + out_proj),
        # the norm's output, the sublayer's, the residual; the projection; the
        # convolved q, k, v; the normalised q and k and the float32 log-decay
        # (the XLA form's arrays: the kernels make them in VMEM); the chunks'
        # states; the rule's output and the gated, normed one. On the
        # chip the cell's whole-block step peaked at 13.24 GB and the plan the
        # rule makes (everything named kept, 2.01 GB) at 13.42 GB = 79.4%, where
        # `step_peak_bytes` said 14.64 and 14.66 (PR 57; with the kernels making
        # their own arguments the plan peaks at 13.34 GB and it says 14.42): high
        # by 1.1-1.4 GB, more than any width here accounts for (run 0's whole
        # block term is 1.48 GB).
        # What it over-counts is the moment, not a width: it holds every
        # unrolled layer's gradients beside the LAST block's backward pass,
        # where on the chip a layer's gradients come as its activations go
        # (PERF.md section 7). The safe side, and it changes no decision here
        "width": (3 * c.d_model + projected + 3 * inner
                  + (0 if impl == "pallas" else 2 * inner + inner * 4 // itemsize) + state + 2 * inner),
        "candidates": (
            RematCandidate(("kda_chunk_out", "kda_chunk_states", "kda_chunk_o"), 2 * inner + state,
                           rule + gate_norm, int(rule / _KDA_SHARE_OF_PEAK[impl]), False, ()),
            RematCandidate(("kda_in_proj",), projected, in_proj, in_proj, False, ()),
            *((RematCandidate(("kda_residual",), c.d_model, out_proj, out_proj, False, ()),) if mlp_follows else ()),
        ),
    }


# FLOPs that take as long as making a feature of the gated convolution's output
# again: a memory-bound pass that reads the projection's three thirds and writes
# one (8 bytes a feature in bfloat16; the chip's peaks are 240 FLOPs to a byte).
# From the peaks, not from a forced plan
_SCONV_FLOPS_PER_FEATURE = 2_000


def _sconv_costs(config: MixedStackConfig, mlp_follows: bool) -> Dict[str, Any]:
    """`_sconv_sublayer`'s part of `block_costs`, a layer and token (whole on
    every device). Three candidates: the in-projection's output (`sconv_in_proj`:
    B, C and X are one matmul's, worth that matmul as a state-space mixer's
    is); the op's output as the out-projection reads it (`sconv_conv_out`: an
    XLA fusion writes it, so the kept value is no copy; worth the memory-bound
    pass that makes it again); and, where an MLP or an expert layer follows
    the mixer, the stream after the out-projection (`sconv_residual`, an
    attention layer's `attn_residual`: worth that matmul)."""
    c = config
    m = c.d_model
    itemsize = jnp.dtype(c.dtype).itemsize
    in_proj, out_proj = 2 * m * 3 * m, 2 * m * m
    conv = (2 * c.sconv_taps + 1) * m       # two gates, a multiplication a tap, the taps' sum
    return {
        "flops": in_proj + conv + out_proj,
        # the norm's output, the sublayer's, the residual; the projection; the
        # float32 product B * X padded by the taps' reach; the op's output
        "width": 3 * m + 3 * m + m * 4 // itemsize + m,
        "candidates": (
            RematCandidate(("sconv_in_proj",), 3 * m, in_proj, in_proj, False, ()),
            RematCandidate(("sconv_conv_out",), m, conv, _SCONV_FLOPS_PER_FEATURE * m, False, ()),
            *((RematCandidate(("sconv_residual",), m, out_proj, out_proj, False, ()),) if mlp_follows else ()),
        ),
    }


def block_costs(
    config: MixedStackConfig, seq: int, split: Callable[[str], int] = lambda weight: 1,
    tokens: Optional[int] = None,
) -> Dict[str, Any]:
    """`transformer.block_costs` for a stack of mixed layers: its runs as
    `forward_hidden` walks them (one with repeats is a scan), each with the
    costs of the kinds of layer it has. A candidate counts the layers of the
    kinds that write it: the attention output and the residual after it every
    layer (a windowed layer's scores are cheaper than a full one's), gate and
    up the dense layers, the routing, the shared expert's projections and the
    held experts' buffer the expert layers (the multi-token prediction
    module's block is one more). `tokens`: a device's tokens a step, which a
    held layer's buffer is sized from (None: one sequence's)."""
    c = config

    def kind_costs(kind: LayerKind):
        mixer = (_ssm_costs(c) if kind.attention == "ssm"
                 else _kda_costs(c, kind.mlp != "none") if kind.attention == "kda"
                 else _sconv_costs(c, kind.mlp != "none") if kind.attention == "sconv"
                 else _NO_SUBLAYER if kind.attention == "none"
                 else attention_costs(c, seq, split, c.sliding_window if kind.attention == "sliding" else None))
        mlp = (mlp_costs(c, split, c.d_ff_dense) if kind.mlp == "dense"
               else _expert_costs(c, split, tokens or seq) if kind.mlp == "experts" else _NO_SUBLAYER)
        return mixer, mlp

    runs = [
        StackRun(run.repeats > 1, ("runs", r), tuple(
            (n * run.repeats, *kind_costs(kind)) for kind, n in collections.Counter(run.kinds).items()),
            period=len(run.kinds))
        for r, run in enumerate(stack_runs(layer_kinds(c)))]
    if c.mtp_modules:
        # the module's block: one more layer, run after the stack and on its own
        runs.append(StackRun(False, ("mtp", "block"), ((1, *kind_costs(mtp_kind(c))),)))
    return stack_costs(runs)


def plan(config: MixedStackConfig, batch: int, seq: int) -> Dict[str, Any]:
    """What the stack resolves to for a step of `batch` rows of `seq` tokens,
    for callers that report it (LMTrainer's `train.init.step_fn` span): the
    layers' kinds in order (`dS dS eS eF ...`), the window and the windowed
    kernels' sub-tile walk (the full layers' is `attention_plan`'s, which the
    trainer writes for every model) or, of a latent-attention stack, the ranks
    of its latents, the features of a head that rotate and the head size; a
    state-space mixer's sizes and its scan's form (`ops/ssd.scan_plan`); the
    multi-token prediction module and its loss's weight where there is one;
    the router's form and the expert layer's (`moe.moe_plan`)."""
    c = config
    kinds = layer_kinds(c)
    out = {"layer_kinds": " ".join(kind.code for kind in kinds)}
    if any(kind.attention == "ssm" for kind in kinds):
        inner, group_states = c.ssm_heads * c.ssm_head_dim, c.ssm_groups * c.ssm_state
        out.update(ssm_heads=c.ssm_heads, ssm_head_dim=c.ssm_head_dim, ssm_state=c.ssm_state,
                   ssm_groups=c.ssm_groups, ssm_conv_kernel=c.ssm_conv_kernel,
                   **ssd.scan_plan(seq, c.ssm_chunk, heads=c.ssm_heads, groups=c.ssm_groups,
                                   head_dim=c.ssm_head_dim, state=c.ssm_state),
                   **ssd.gate_norm_plan(batch * seq, inner, c.ssm_groups),
                   **ssd.conv_plan(seq, c.ssm_conv_width, c.ssm_conv_kernel, inner,
                                   (inner, group_states, group_states)))
    if any(kind.attention == "kda" for kind in kinds):
        inner = c.kda_heads * c.kda_head_dim
        convs = [ssd.conv_plan(seq, *sizes)["ssm_conv_impl"] for sizes in (
            (2 * inner, c.kda_conv_kernel, 0, (inner, inner)), (inner, c.kda_conv_kernel, 2 * inner))]
        out.update(kda_heads=c.kda_heads, kda_head_dim=c.kda_head_dim, kda_conv_kernel=c.kda_conv_kernel,
                   kda_gate_lower_bound=c.kda_gate_lower_bound, kda_conv_impl="+".join(sorted(set(convs))),
                   **kda.kda_plan(c.kda_chunk, heads=c.kda_heads, d_k=c.kda_head_dim, d_v=c.kda_head_dim,
                                  lower_bound=c.kda_gate_lower_bound))
    if any(kind.attention == "sconv" for kind in kinds):
        out.update(sconv_channels=c.d_model, sconv_taps=c.sconv_taps,
                   **short_conv.sconv_plan(seq, c.d_model, c.sconv_taps))
    if c.attn_full_rope:
        out["attn_full_rope"] = True
    if c.tie_embeddings:
        out["tie_embeddings"] = True
    if c.latent_attention:
        out.update(attn_latent_q_rank=c.q_lora_rank, attn_latent_kv_rank=c.kv_lora_rank,
                   attn_rope_dims=c.rotary_dims, attn_head_dim=c.head_dim)
        if c.value_dim != c.head_dim:
            out.update(attn_latent_v_dim=c.value_dim, attn_kernel_head_dim=c.kernel_head_dim)
    elif any(kind.attention == "sliding" for kind in kinds):
        windowed = attention_plan(seq, causal=c.causal, implementation=c.attn_impl,
                                  window=c.sliding_window)
        out.update({"attn_window": c.sliding_window},
                   **{name.replace("attn_subtiles", "attn_window_subtiles"): value
                      for name, value in windowed.items()
                      if name.startswith(("attn_subtiles", "attn_window"))})
    if c.mtp_modules:
        out.update(mtp_modules=c.mtp_modules, mtp_loss_weight=c.mtp_loss_weight)
    out.update({
        "moe_router": c.router_score,
        "moe_router_input": c.router_input,
        "moe_experts_routed": c.n_experts,
        "moe_shared_width": c.shared_expert_width,
    })
    if _expert_layers(c):
        out.update(moe_plan(c, batch, seq))
    return out
