"""Mixture-of-Experts transformer (Mixtral and OLMoE families).

The reference only reaches MoE through vLLM engine internals (SURVEY.md
§2.4: expert parallel "absent as a framework feature"). Here the expert
layer has two forms, and the mesh alone chooses between them (the context
mesh, or the one the expert weights are sharded over: `_mesh_of`):

- dropless (every mesh without an `ep` axis, one chip included): the
  T x k (token, choice) rows are sorted by expert, gathered into one
  buffer in which every expert's rows are contiguous, pushed through
  three grouped matmuls over the ragged groups (ops/grouped_matmul:
  the `moe_gmm_*` Pallas kernels on a TPU, `ragged_dot` elsewhere), and
  gathered back and gated. Shapes are static whatever the routing, no
  token is ever dropped, and nothing has a capacity. Under a mesh every
  device does this for the tokens it holds (`shard_map` over the data,
  sequence and tp axes), so one path serves 1..N chips.
- GShard dense dispatch (`ep > 1`): experts are a mesh axis,
  expert-stacked weights carry the "expert" logical axis, and the
  dispatch/combine einsums give XLA the contraction structure it needs
  to insert the all-to-alls over ICI on its own. Tokens over
  `capacity_factor` are dropped. A dropless expert-parallel exchange
  needs a four-chip cell to be judged on (PERF.md, Open questions).

The router is float32; the gates are the top-k probabilities as they are
(OLMoE, `norm_topk_prob=False`) or renormalised over the chosen k
(Mixtral). The load-balancing loss is E * sum_e f_e P_e with f_e the
share of (token, choice) pairs routed to e and P_e the mean router
probability (1 at perfect balance).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops import rope_frequencies, swiglu
from ..ops.grouped_matmul import TILES, gmm_tile_rows, grouped_matmul, resolve_gmm_impl
from ..ops.moe_rows_sum import rows_sum, rows_sum_tile, take_token_rows, token_tile_bounds
from ..parallel.mesh import DATA_AXES
from .transformer import (
    Params,
    TransformerConfig,
    _norm,
    attention_sublayer,
    checkpoint_block,
    init_params as _dense_init,
    logical_axes as _dense_axes,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig(TransformerConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0  # read by the GShard path (ep > 1) alone
    router_aux_coeff: float = 0.01
    norm_topk_prob: bool = True  # gates renormalised over the chosen k (Mixtral)
    # the router's form, all of it data of the architecture: the scores that
    # are ranked and gated with ("softmax" over the experts, or "sigmoid" of
    # each logit), whether a bias `expert_bias` (a parameter with no gradient)
    # is added to them for the SELECTION alone, and a scale on the gates
    router_score: str = "softmax"
    router_select_bias: bool = False
    route_scale: float = 1.0
    # what is added to the sum of the chosen scores before the gates are
    # renormalised over it (`norm_topk_prob`)
    route_norm_eps: float = 1e-9
    # group-limited routing: the experts in `route_groups` groups of neighbours,
    # of which the `route_groups_kept` best (a group's score the sum of its two
    # largest selection scores) are the only ones the top-k is taken from
    # (1, 1: no limit)
    route_groups: int = 1
    route_groups_kept: int = 1
    # a dense expert of this width on every token, added to the routed sum (0:
    # none): a SwiGLU beside gated experts, relu(up)^2 beside those that are not
    shared_expert_width: int = 0
    # a routed expert's unit between the grouped matmuls, in either form of the
    # layer: gated, "swiglu" (silu(gate) * up) or "reglu" (relu(gate) * up),
    # three matrices an expert; or not gated, "relu2" (relu(up)^2), two: such
    # an expert has no `we_gate` and its shared expert no `ws_gate`
    expert_act: str = "swiglu"
    # the range [first, last) of the published experts this device holds, the
    # others lying on further chips (None: all `n_experts`). The router keeps
    # its `n_experts` outputs and its top-k over all of them, the gates are
    # normalised over all k chosen, and the layer computes the sum over the
    # chosen experts that are held
    held_experts: Optional[Tuple[int, int]] = None

    @property
    def n_experts_held(self) -> int:
        first, last = self.held_experts or (0, self.n_experts)
        return last - first

    @property
    def expert_weights(self) -> Tuple[str, ...]:
        """The leaves of the routed experts, in the order the layer takes them."""
        _expert_act(self)
        return ("we_gate", "we_up", "we_down") if self.expert_act in _GATED_ACTS else ("we_up", "we_down")


def mixtral_8x7b() -> MoEConfig:
    """Mixtral 8x7B — BASELINE config 3 (expert parallelism)."""
    return MoEConfig(
        vocab_size=32000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        max_seq=8192,
        pos_emb="rope",
        norm="rmsnorm",
        act="swiglu",
        use_bias=False,
        tie_embeddings=False,
        rope_theta=1e6,
        remat=True,
        n_experts=8,
        top_k=2,
    )


def olmoe_1b_7b() -> MoEConfig:
    """OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json): 64
    experts of width 1024, top-8 gates not renormalised, QK-norm, MHA."""
    return MoEConfig(
        vocab_size=50304,
        d_model=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=16,
        d_ff=1024,
        max_seq=4096,
        pos_emb="rope",
        norm="rmsnorm",
        act="swiglu",
        use_bias=False,
        tie_embeddings=False,
        rope_theta=10000.0,
        qk_norm=True,
        norm_eps=1e-5,
        n_experts=64,
        top_k=8,
        norm_topk_prob=False,
    )


def moe_tiny() -> MoEConfig:
    """4-layer 4-expert toy for CI (divisible by ep=2/tp=2 test meshes)."""
    return MoEConfig(
        vocab_size=256,
        d_model=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq=128,
        pos_emb="rope",
        norm="rmsnorm",
        act="swiglu",
        use_bias=False,
        tie_embeddings=False,
        dtype=jnp.float32,
        n_experts=4,
        top_k=2,
    )


# ----------------------------------------------------------------------- init


def init_params(config: MoEConfig, key: jax.Array) -> Params:
    """Dense skeleton + per-expert MLP stacks (L, E_exp, ...)."""
    base = _dense_init(config, key)
    blocks = base["blocks"]
    for name in ("w_up", "w_down", "w_gate", "b_up", "b_down"):
        blocks.pop(name, None)
    c = config
    pd = c.param_dtype
    std = 0.02
    res_std = std / math.sqrt(2 * c.n_layers)
    keys = jax.random.split(jax.random.fold_in(key, 99), 4)
    L, E = c.n_layers, c.n_experts
    blocks["router"] = (std * jax.random.normal(keys[0], (L, c.d_model, E))).astype(pd)
    E = c.n_experts_held
    if "we_gate" in c.expert_weights:
        blocks["we_gate"] = (std * jax.random.normal(keys[1], (L, E, c.d_model, c.d_ff))).astype(pd)
    blocks["we_up"] = (std * jax.random.normal(keys[2], (L, E, c.d_model, c.d_ff))).astype(pd)
    blocks["we_down"] = (res_std * jax.random.normal(keys[3], (L, E, c.d_ff, c.d_model))).astype(pd)
    return base


def logical_axes(config: MoEConfig) -> Params:
    axes = _dense_axes(config)
    blocks = axes["blocks"]
    for name in ("w_up", "w_down", "w_gate", "b_up", "b_down"):
        blocks.pop(name, None)
    # replicated: 0.13 M weights a layer, and an embed-sharded router makes
    # GSPMD reshard the float32 activations for its gradient
    blocks["router"] = ("layers", None, None)
    if "we_gate" in config.expert_weights:
        blocks["we_gate"] = ("layers", "expert", "embed", "mlp")
    blocks["we_up"] = ("layers", "expert", "embed", "mlp")
    blocks["we_down"] = ("layers", "expert", "mlp", "embed")
    return axes


# -------------------------------------------------------------------- routing


def topk_dispatch(
    probs: jax.Array, top_k: int, capacity: int, normalize: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """GShard dense dispatch. probs (B, S, E) → dispatch (B,S,E,C) {0,1},
    combine (B,S,E,C) gate-weighted; tokens over capacity are dropped."""
    num_experts = probs.shape[-1]
    weights, idx = jax.lax.top_k(probs, top_k)  # (B,S,k)
    if normalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-9)
    onehot = jax.nn.one_hot(idx, num_experts, dtype=probs.dtype)  # (B,S,k,E)
    b, s, k, e = onehot.shape
    # queue position of each (token, choice) within its expert, in (S·k) order
    flat = onehot.transpose(0, 2, 1, 3).reshape(b, k * s, e)
    pos_flat = jnp.cumsum(flat, axis=1) - flat
    pos = pos_flat.reshape(b, k, s, e).transpose(0, 2, 1, 3)  # (B,S,k,E)
    pos = pos.astype(jnp.int32)
    keep = (pos < capacity).astype(probs.dtype) * onehot
    pos_onehot = jax.nn.one_hot(
        jnp.clip(pos, 0, capacity - 1), capacity, dtype=probs.dtype
    )  # (B,S,k,E,C)
    dispatch = jnp.einsum("bske,bskec->bsec", keep, pos_onehot)
    combine = jnp.einsum("bsk,bske,bskec->bsec", weights, keep, pos_onehot)
    return dispatch, combine


class DroplessLayout(NamedTuple):
    """Where each (token, choice) row sits in the expert-sorted buffer.
    Row r = t * k + j is token t's j-th choice; the buffer has P slots,
    expert e owning `padded_sizes[e]` of them in a row, of which the first
    `sizes[e]` hold rows and the rest are zero rows."""

    sizes: jax.Array         # (E,) rows routed to each expert
    padded_sizes: jax.Array  # (E,) the same, as positive multiples of the tile
    slot_row: jax.Array      # (P,) the row a slot holds; T * k where none
    row_slot: jax.Array      # (T * k,) the slot of each row


def dropless_layout(expert_idx: jax.Array, n_experts: int, tile_rows: int) -> DroplessLayout:
    """expert_idx (T, k) int → the layout. A stable sort of the rows by
    expert, group sizes from the sorted ids, every group padded to a
    positive multiple of `tile_rows` (ops/grouped_matmul's contract); P =
    T·k rounded up + E·tile_rows bounds every routing, so shapes are static."""
    flat = expert_idx.reshape(-1).astype(jnp.int32)
    rows = flat.shape[0]
    slots = -(-rows // tile_rows) * tile_rows + n_experts * tile_rows
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)  # sorted position → row
    sorted_expert = flat[order]
    ends = jnp.searchsorted(
        sorted_expert, jnp.arange(n_experts, dtype=jnp.int32), side="right").astype(jnp.int32)
    sizes = jnp.diff(ends, prepend=0)
    starts = ends - sizes
    padded = -(-jnp.maximum(sizes, 1) // tile_rows) * tile_rows
    padded_ends = jnp.cumsum(padded)
    padded_starts = padded_ends - padded
    # slot → row: both maps are built from gathers and a sort, no scatter
    slot = jnp.arange(slots, dtype=jnp.int32)
    slot_expert = jnp.minimum(
        jnp.searchsorted(padded_ends, slot, side="right"), n_experts - 1)
    rank = slot - padded_starts[slot_expert]
    held = rank < sizes[slot_expert]
    slot_row = jnp.where(
        held, order[jnp.clip(starts[slot_expert] + rank, 0, rows - 1)], rows)
    slot_sorted = padded_starts[sorted_expert] + jnp.arange(rows, dtype=jnp.int32) - starts[sorted_expert]
    _, row_slot = jax.lax.sort((order, slot_sorted), num_keys=1)
    return DroplessLayout(sizes, padded.astype(jnp.int32), slot_row.astype(jnp.int32),
                          row_slot.astype(jnp.int32))


@jax.custom_vjp
def _take_rows(x: jax.Array, idx: jax.Array, readers: jax.Array) -> jax.Array:
    """y[b] = x[idx[b]], zero where idx[b] is out of range. `readers`
    (A, m) names for each row of x the m rows of y that read it (out of
    range: none), so that the transpose is a gather as well: a scatter-add
    of 10^5 wide rows is what a TPU does worst."""
    del readers
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


def _take_rows_fwd(x, idx, readers):
    return _take_rows(x, idx, readers), readers


def _take_rows_bwd(readers, dy):
    a, m = readers.shape
    dx = jnp.take(dy, readers.reshape(-1), axis=0, mode="fill", fill_value=0)
    return dx.reshape(a, m, dy.shape[-1]).sum(axis=1).astype(dy.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _mesh_of(weights: Optional[jax.Array] = None):
    """The mesh the expert layer is traced for: the context mesh
    (make_train_step traces its step inside `use_abstract_mesh`), or, with
    no context, the mesh the expert weights are sharded over, which their
    type carries through scan, remat and grad. So parameters sharded over
    `ep` take the expert-parallel form whether or not the caller set a
    mesh. (A jit that names shardings only in `in_shardings`, with no
    context mesh, shows the trace neither: set the mesh there.)"""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty and weights is not None:
        mesh = jax.typeof(weights).sharding.mesh
    return mesh


def expert_parallel(mesh) -> bool:
    """Whether `mesh` has an `ep` axis: the one thing that chooses the
    GShard form over the dropless one."""
    return (not mesh.empty) and mesh.shape.get("ep", 1) > 1


def moe_plan(config: "MoEConfig", batch: int, seq: int) -> Dict[str, Any]:
    """What the expert layer runs for a step of `batch` rows of `seq` tokens
    under the context mesh, for callers that report it (LMTrainer's
    `train.init.step_fn` span)."""
    if expert_parallel(_mesh_of()):
        impl, tile = "gshard_dense", 0
    else:
        gmm = resolve_gmm_impl()
        impl, tile = {"pallas": "gmm_pallas", "xla": "ragged_dot"}[gmm], gmm_tile_rows(gmm)
    plan = {
        "moe_impl": impl, "moe_experts": config.n_experts, "moe_top_k": config.top_k,
        "moe_expert_act": config.expert_act,
        "moe_route_groups": config.route_groups, "moe_route_groups_kept": config.route_groups_kept,
        "moe_rows_per_step": batch * seq * config.top_k, "moe_gmm_tile_rows": tile,
    }
    if config.held_experts is not None:
        mesh = _mesh_of()
        # a device's tokens: the layer runs once a device on the tokens it holds
        ways = 1 if mesh.empty else math.prod(
            mesh.shape.get(a, 1) for a in (*DATA_AXES, "sp"))
        mine = (config, batch * seq // ways, max(tile, 1))
        plan.update(moe_experts_held=config.n_experts_held,
                    moe_held_buffer_rows=held_buffer_rows(*mine),
                    moe_held_passes_most=held_passes_most(*mine))
        if impl != "gshard_dense":  # which refuses held experts
            # how a token's held rows are added up (`_held_experts`): as the matmuls run
            plan["moe_held_row_sum"] = {
                "gmm_pallas": "rows_sum_pallas", "ragged_dot": "scatter_add"}[impl]
    return plan


def _reglu(gate, up):
    return jax.nn.relu(gate) * up


def _relu2(up):
    return jnp.square(jax.nn.relu(up))


_GATED_ACTS = {"swiglu": swiglu, "reglu": _reglu}      # (gate, up) -> hidden
_EXPERT_ACTS = {**_GATED_ACTS, "relu2": _relu2}        # relu2: (up) -> hidden


def _expert_act(config: "MoEConfig"):
    try:
        return _EXPERT_ACTS[config.expert_act]
    except KeyError:
        raise ValueError(f"unknown expert activation: {config.expert_act!r}") from None


def _expert(config: "MoEConfig", project, weights):
    """One routed expert unit of either form: `project(w, name)` is the input
    through the up (or gate) matrix `w`, `name` the buffer's name for that
    output, and the hidden units go through the last of `weights` by the
    caller. -> (the hidden units, the gate's projection or None where the unit
    is not gated)."""
    act = _expert_act(config)
    if len(weights) == 2:
        return act(project(weights[0], BUFFER_UP)), None
    gate = project(weights[0], BUFFER_GATE)
    return act(gate, project(weights[1], BUFFER_UP)), gate


# What the layer's backward pass reads of its routing, under ONE `checkpoint_name`
# (a step that recomputes its blocks keeps all of it or none:
# models/mixed_stack._expert_costs): the router's float32 logits, the chosen
# experts, their scores and, of a layer that holds a part of the experts, the
# order of its sorted rows with the ends of each expert's. Every value the
# backward pass takes of the routing is read off those, so with them kept the
# pass neither multiplies by the router again nor selects nor sorts
ROUTING = "moe_routing"
# What the backward pass reads of the FIRST pass through a held layer's buffer
# (`_held_experts`; a later pass is computed again from its inputs, whatever is
# kept), each under its own `checkpoint_name` and all of them ONE candidate of
# a recomputing step (models/mixed_stack._expert_costs): the gathered rows
# (the grouped matmuls' left operand for d(weights)), the gate's and the up
# projection's outputs (the activation is an elementwise pass from them), the
# down projection's output (which the gates' cotangent reads) and the slots'
# tables, a few 4-byte words a slot: each slot's row and float32 gate, each
# expert's padded size, the row-sum kernel's bounds (and the grouped matmuls'
# own, `grouped_matmul.TILES`). With all of them kept the backward pass runs no
# grouped matmul of the forward again, gathers no row and rebuilds no table
BUFFER_IN, BUFFER_GATE, BUFFER_UP, BUFFER_OUT, BUFFER_SLOTS = (
    "moe_buffer_in", "moe_buffer_gate", "moe_buffer_up", "moe_buffer_out", "moe_buffer_slots")


def held_buffer_names(config: "MoEConfig") -> Tuple[str, ...]:
    """The names a held layer of this configuration writes: no gate's where
    the expert's unit is not gated."""
    gate = (BUFFER_GATE,) if "we_gate" in config.expert_weights else ()
    return (BUFFER_IN, *gate, BUFFER_UP, BUFFER_OUT, BUFFER_SLOTS, TILES)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _top_k_named(scores, k: int):
    """`jax.lax.top_k` of (T, E) scores with the indices under `ROUTING`, and
    JAX's own derivative for the values (the tangent gathered at the indices,
    the same gather) reading the NAMED indices: its rule reads the top-k's
    own output, which no name reaches, so a recomputing step would select
    again whatever it kept. The lowered program is the plain call's. (Taking
    the indices alone and gathering the values with `take_along_axis`, as the
    select-bias form has to, is one more gather forward: 1.3 ms a layer at 8
    of 64 scores of 16,384 tokens, PERF.md section 6, PR 60.)"""
    gates, experts = jax.lax.top_k(scores, k)
    return gates, checkpoint_name(experts, ROUTING)


@_top_k_named.defjvp
def _top_k_named_jvp(k, primals, tangents):
    # the plain lines again, not a call of `_top_k_named`: a checkpoint's policy does not see into a nested call
    gates, experts = jax.lax.top_k(*primals, k)
    experts = checkpoint_name(experts, ROUTING)
    d_gates = jax.lax.gather(
        tangents[0], experts.reshape(*experts.shape, 1),
        jax.lax.GatherDimensionNumbers(offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
                                       operand_batching_dims=(0,), start_indices_batching_dims=(0,)),
        slice_sizes=(1, 1))
    return (gates, experts), (d_gates, np.zeros(experts.shape, jax.dtypes.float0))


def _route(scores, select, config):
    """scores (T, E) -> (gates (T, k), the chosen experts (T, k)): the k
    largest of `select` (the scores plus the selection bias; None: of the
    scores themselves), gated by their scores, renormalised and scaled as the
    configuration says; the experts and their scores under `ROUTING`."""
    c = config
    if select is None:
        gates, experts = _top_k_named(scores, c.top_k)
    else:
        _, experts = jax.lax.top_k(select, c.top_k)
        experts = checkpoint_name(experts, ROUTING)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    gates = checkpoint_name(gates, ROUTING)
    if c.norm_topk_prob:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + c.route_norm_eps)
    if c.route_scale != 1.0:
        gates = gates * c.route_scale
    return gates, experts


def _group_limited(select, config):
    """The selection scores (..., E) with every expert outside the
    `route_groups_kept` best of the `route_groups` groups at -inf: a group's
    score is the sum of its two largest selection scores."""
    c = config
    groups, kept, size = c.route_groups, c.route_groups_kept, c.n_experts // c.route_groups
    if c.n_experts % groups or not 0 < kept <= groups or size < 2 or kept * size < c.top_k:
        raise ValueError(f"group-limited routing: {c.n_experts} experts in {groups} groups, {kept} kept, "
                         f"top-{c.top_k}: whole groups of two experts or more, and the kept ones hold the top-k")
    grouped = select.reshape(*select.shape[:-1], groups, size)
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)                          # (..., groups)
    _, best = jax.lax.top_k(score, kept)
    keep = jnp.any(best[..., :, None] == jnp.arange(groups), axis=-2)              # (..., groups)
    return jnp.where(keep[..., None], grouped, -jnp.inf).reshape(select.shape)


def _gshard_experts(h, probs, weights, config):
    """(B, S, M) -> expert output (B, S, M), rows per expert (E,)."""
    c, dt = config, config.dtype
    s = h.shape[1]
    capacity = max(1, int(c.capacity_factor * c.top_k * s / c.n_experts))
    dispatch, combine = topk_dispatch(probs, c.top_k, capacity, c.norm_topk_prob)
    # dispatch: (B,S,E,C) x (B,S,M) -> (E,B,C,M); XLA turns the e-sharded
    # contraction into the all-to-all over the ep axis
    expert_in = jnp.einsum("bsec,bsm->ebcm", dispatch.astype(dt), h)
    act, _ = _expert(c, lambda w, _: jnp.einsum("ebcm,emf->ebcf", expert_in, w), weights)
    expert_out = jnp.einsum("ebcf,efm->ebcm", act, weights[-1])
    out = jnp.einsum("ebcm,bsec->bsm", expert_out, combine.astype(dt))
    return out, jnp.sum(dispatch, axis=(0, 1, 3))


def _gated_groups(expert_in, weights, group_sizes, tile, impl, config, interpret=False):
    """The grouped matmuls of the expert-sorted rows `expert_in` (three of a
    gated expert, two of one that is not), each one's output under its name
    of the buffer, and the configuration's unit between them. -> (output, a
    slot's count of hidden units a ReLU gate leaves non-zero; None where the
    unit has no dead ones to count)."""

    def gmm(lhs, w, name):
        return checkpoint_name(grouped_matmul(
            lhs, w, group_sizes, tile_rows=tile, implementation=impl, interpret=interpret), name)

    act, gate = _expert(config, lambda w, name: gmm(expert_in, w, name), weights)
    out = gmm(act, weights[-1], BUFFER_OUT)
    live = None
    if config.expert_act == "reglu":
        live = jnp.sum(jax.lax.stop_gradient(gate) > 0, axis=-1, dtype=jnp.float32)
    return out, live


def _dropless_shard(h, probs, weights, config, select=None):
    """The same contract on the tokens one device holds, no capacity:
    every (token, choice) row is computed. -> (output (B, S, M), rows a
    published expert (E,), what a layer that holds a part of the experts
    reports of itself ({} otherwise))."""
    c = config
    b, s, m = h.shape
    tokens = b * s
    impl = resolve_gmm_impl()
    tile = gmm_tile_rows(impl)
    with jax.named_scope("moe.select"):
        gates, experts = _route(
            probs.reshape(tokens, c.n_experts),
            None if select is None else select.reshape(tokens, c.n_experts), c)  # (T, k)
    if c.held_experts is not None:
        out, held = _held_experts(h.reshape(tokens, m), gates, experts, weights, c, tile, impl)
        with jax.named_scope("moe.select"):
            load = jnp.sum(jax.nn.one_hot(experts, c.n_experts, dtype=jnp.float32), axis=(0, 1))
        return out.astype(c.dtype).reshape(b, s, m), load, held
    with jax.named_scope("moe.dispatch"):
        layout = dropless_layout(experts, c.n_experts, tile)
        # slot -> token: T (out of range, a zero row) where the slot holds none
        expert_in = _take_rows(
            h.reshape(tokens, m), layout.slot_row // c.top_k,
            layout.row_slot.reshape(tokens, c.top_k))
    with jax.named_scope("moe.experts"):
        expert_out, _ = _gated_groups(expert_in, weights, layout.padded_sizes, tile, impl, c)
    with jax.named_scope("moe.combine"):
        chosen = _take_rows(expert_out, layout.row_slot, layout.slot_row[:, None])
        # gated in float32; one fused pass over the gathered rows
        chosen = chosen.reshape(tokens, c.top_k, m).astype(jnp.float32)
        out = jnp.sum(gates[..., None] * chosen, axis=1)
    return out.astype(c.dtype).reshape(b, s, m), layout.sizes.astype(jnp.float32), {}


# ------------------------------------------------- a part of the experts held
# The buffer of a layer that holds `held` of `published` experts, in shares of
# the rows it is sent when routing is even (T k held / published): twice that
# holds nearly every step of the cell that runs it (PERF.md section 6, PR 33:
# 8.5-16.1% of the rows at the seeded weights against an even 12.5%; one layer
# of one seed passed 25% once, and took a second pass), and a routing that
# sends more is computed in further passes through the same buffer, never
# dropped. At the cell's shapes the layer's forward and backward take 36.1 ms
# with it and 58.1 ms with the buffer that holds every routing in one pass.
_HELD_BUFFER_SHARES = 2.0


def held_buffer_rows(config: MoEConfig, tokens: int, tile: int) -> int:
    """Rows of (token, choice) pairs one pass of the held layer takes."""
    rows = tokens * config.top_k
    even = rows * config.n_experts_held / config.n_experts
    return min(-(-int(_HELD_BUFFER_SHARES * even) // tile) * tile, -(-rows // tile) * tile)


def held_passes_most(config: MoEConfig, tokens: int, tile: int) -> int:
    """Passes that hold every routing: all T k rows sent here."""
    return -(-tokens * config.top_k // held_buffer_rows(config, tokens, tile))


def _held_experts(h, gates, experts, weights, config, tile, impl):
    """h (T, M), gates and published expert ids (T, k) -> (sum over each
    token's chosen experts THAT ARE HELD of gate x expert(h), float32 (T, M);
    {"moe_rows_held", "moe_passes"} and, for ReGLU experts,
    "moe_act_live_units": the hidden units the ReLU left non-zero, over the
    slots that hold a row).

    The (token, choice) rows are sorted by expert, those routed to an absent
    expert last; they are never gathered, multiplied or combined. The held
    rows go through the grouped matmuls `held_buffer_rows` at a time:
    pass p takes the sorted rows [p R, (p + 1) R), whose groups are what is
    left of each expert's rows there, so every pass has the layout
    `ops/grouped_matmul` asks for and the same static shapes. The first pass
    always runs and its sums go INTO the later ones: a later pass runs
    (`lax.cond`) only if rows are left for it and adds to what it is handed,
    one that has none hands it back, and one test stands around all of them,
    so a step whose rows fit the buffer evaluates one predicate a layer. The
    backward pass does the same with the first pass's cotangents of `h`, the
    gates and the weights (`all_passes`, a `custom_vjp`: a later pass is
    computed again there from its inputs, nothing else is kept for it; what
    the FIRST pass writes and its transpose reads carries the buffer's names,
    `held_buffer_names`, for a checkpoint around the layer to keep). So a
    pass not taken fills nothing and adds nothing, forward or backward (a
    `cond` that returns the pass's own sums for the caller to add fills a zero
    of the output's size a pass, a zero cotangent an input and zeros for a
    checkpointed pass's kept inputs: 8% of a step, PERF.md section 5, PR 42).
    No routing drops a row: `held_passes_most` passes hold all T k.

    A token's rows are added up as `impl` says, the grouped matmuls' own: under
    "pallas" by `ops/moe_rows_sum` (the combine, and the transpose of the
    dispatch's gather: a pass's slots are the kernel's layout as they stand,
    an expert's rows a group whose tokens ascend), interpreted off a TPU as
    the attention kernels are; under "xla" by a gather and a scatter-add,
    which is also what the kernel's tests compare it with."""
    c = config
    tokens, m = h.shape
    k, n = c.top_k, c.n_experts_held
    rows = tokens * k
    first_held = c.held_experts[0]
    buffer_rows = held_buffer_rows(c, tokens, tile)
    slots = buffer_rows + n * tile
    kernel = impl == "pallas"
    interpret = kernel and jax.default_backend() != "tpu"
    with jax.named_scope("moe.dispatch"):
        local = experts.reshape(-1).astype(jnp.int32) - first_held
        flat = jnp.where((local >= 0) & (local < n), local, n)      # absent: sorted last
        # sorted position -> row; both arguments of `all_passes`, whose backward pass keeps them
        order = checkpoint_name(jnp.argsort(flat, stable=True).astype(jnp.int32), ROUTING)
        sorted_expert = flat[order]
        ends = checkpoint_name(jnp.searchsorted(
            sorted_expert, jnp.arange(n, dtype=jnp.int32), side="right").astype(jnp.int32), ROUTING)
        flat_gates = gates.reshape(-1)

    def one_pass(h, flat_gates, weights, order, ends, start):
        with jax.named_scope("moe.dispatch"):
            slot = jnp.arange(slots, dtype=jnp.int32)
            pass_ends = jnp.clip(ends - start, 0, buffer_rows)
            sizes = jnp.diff(pass_ends, prepend=0)
            first_position = start + pass_ends - sizes     # of each expert's rows in this pass
            padded = -(-jnp.maximum(sizes, 1) // tile) * tile
            padded_ends = jnp.cumsum(padded)
            padded_starts = padded_ends - padded
            slot_expert = jnp.minimum(jnp.searchsorted(padded_ends, slot, side="right"), n - 1)
            rank = slot - padded_starts[slot_expert]
            slot_row = checkpoint_name(jnp.where(
                rank < sizes[slot_expert],
                order[jnp.clip(first_position[slot_expert] + rank, 0, rows - 1)], rows), BUFFER_SLOTS)
            padded = checkpoint_name(padded.astype(jnp.int32), BUFFER_SLOTS)
            # slot -> token: T (out of range: read as a zero row, dropped when written)
            # where the slot holds none
            slot_token = slot_row // k
            if kernel:
                bounds = checkpoint_name(token_tile_bounds(
                    slot_token, slot_expert, n, tokens, rows_sum_tile(tokens, m)), BUFFER_SLOTS)
                expert_in = take_token_rows(h, slot_token, bounds, interpret=interpret)
            else:
                expert_in = jnp.take(h, slot_token, axis=0, mode="fill", fill_value=0)
            expert_in = checkpoint_name(expert_in, BUFFER_IN)
        with jax.named_scope("moe.experts"):
            expert_out, live = _gated_groups(expert_in, weights, padded, tile, impl, c, interpret)
        with jax.named_scope("moe.combine"):
            slot_gate = checkpoint_name(
                jnp.take(flat_gates, slot_row, mode="fill", fill_value=0), BUFFER_SLOTS)
            if kernel:
                out = rows_sum(expert_out, slot_gate, slot_token, bounds, tokens,
                               interpret=interpret)
            else:
                gated = slot_gate[:, None] * expert_out.astype(jnp.float32)
                out = jnp.zeros((tokens, m), jnp.float32).at[slot_token].add(gated, mode="drop")
        if live is None:
            return (out,)
        return out, jnp.sum(jnp.where(slot_row < rows, live, 0.0))

    later_starts = np.arange(1, held_passes_most(c, tokens, tile), dtype=np.int32) * buffer_rows

    def through_later_passes(sums, add_pass, ends):
        """`sums` through every pass after the first that has rows left for
        it, `add_pass(sums, start)` each; handed back as it came by a pass
        that has none, and by one test for all of them where the first pass
        took every row."""
        if not later_starts.size:
            return sums
        rows_held = ends[-1]

        def later(sums, start):
            return jax.lax.cond(start < rows_held, add_pass, lambda sums, start: sums,
                                sums, start), None

        with jax.named_scope("moe.passes"):
            return jax.lax.cond(
                rows_held > buffer_rows,
                lambda sums: jax.lax.scan(later, sums, later_starts)[0], lambda sums: sums, sums)

    def add(sums, more):
        return jax.tree.map(jnp.add, sums, more)

    # (the output, then what a pass counted of itself), summed over the passes.
    # Its own rule for the backward pass, because a `cond` differentiated by
    # JAX's rule answers a pass not taken with a zero for `h`, the gates and
    # each weight stack, which the caller then adds: here the first pass's
    # cotangents ARE the sums a later pass adds to, so nothing is filled
    def all_passes_fwd(h, flat_gates, weights, order, ends):
        def add_pass(total, start):
            return add(total, one_pass(h, flat_gates, weights, order, ends, start))

        # the first pass keeps what JAX's own rule keeps of it; a later one its inputs
        total, first_vjp = jax.vjp(
            lambda *inputs: one_pass(*inputs, order, ends, jnp.int32(0)), h, flat_gates, weights)
        kept = (first_vjp, h, flat_gates, weights, order, ends)
        return through_later_passes(total, add_pass, ends), kept

    def all_passes_bwd(kept, d_total):
        first_vjp, h, flat_gates, weights, order, ends = kept

        def add_pass(grads, start):  # the pass again, then its transpose
            _, pass_vjp = jax.vjp(
                lambda *inputs: one_pass(*inputs, order, ends, start), h, flat_gates, weights)
            return add(grads, pass_vjp(d_total))

        return (*through_later_passes(first_vjp(d_total), add_pass, ends), None, None)

    all_passes = jax.custom_vjp(lambda *inputs: all_passes_fwd(*inputs)[0])
    all_passes.defvjp(all_passes_fwd, all_passes_bwd)
    total = all_passes(h, flat_gates, weights, order, ends)
    rows_held = ends[-1]
    passes = jnp.maximum(-(-rows_held // buffer_rows), 1)
    report = {"moe_rows_held": rows_held.astype(jnp.float32),
              "moe_passes": passes.astype(jnp.float32)}
    if len(total) > 1:
        report["moe_act_live_units"] = total[1]
    return total[0], report


def _dropless_experts(h, probs, weights, config, mesh, select=None):
    """`_dropless_shard` once a device. GSPMD cannot partition a Mosaic
    call, and a sort over every shard's rows is nothing it should be
    given: under a mesh each device sorts and computes the tokens it
    holds (batch over the data axes, sequence over sp) with its tp slice
    of every expert, as ops/attention._per_shard does for the flash
    kernels. The partial outputs add up over tp, the group sizes over the
    token axes. With no mesh, one device, or inside somebody else's
    shard_map the layer is called as it is."""
    if mesh.empty or mesh.manual_axes or mesh.size == 1:
        return _dropless_shard(h, probs, weights, config, select)
    batch = tuple(a for a in DATA_AXES if mesh.shape[a] > 1)
    seq = "sp" if mesh.shape.get("sp", 1) > 1 else None
    tp = "tp" if mesh.shape.get("tp", 1) > 1 else None
    token_axes = batch + ((seq,) if seq else ())

    def shard(h, probs, weights, *select):
        out, sizes, held = _dropless_shard(h, probs, weights, config, *select)
        if tp:
            out = jax.lax.psum(out, tp)
        if token_axes:
            sizes = jax.lax.psum(sizes, token_axes)
            # rows add up over the devices' tokens; the passes are the slowest device's
            held = {name: (jax.lax.pmax if name == "moe_passes" else jax.lax.psum)(value, token_axes)
                    for name, value in held.items()}
        return out, sizes, held

    tok = P(batch or None, seq, None)
    held_names = ()
    if config.held_experts is not None:
        held_names = ("moe_rows_held", "moe_passes",
                      *(("moe_act_live_units",) if config.expert_act == "reglu" else ()))
    return jax.shard_map(
        shard, mesh=mesh,
        in_specs=(tok, tok, (*(P(None, None, tp),) * (len(weights) - 1), P(None, tp, None)),
                  *((tok,) if select is not None else ())),
        out_specs=(tok, P(), {name: P() for name in held_names}), check_vma=False,
    )(h, probs, weights, *((select,) if select is not None else ()))


def moe_mlp(
    h: jax.Array, lp: Params, config: MoEConfig, router_input: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The expert layer on normed activations (B, S, M): router, routed
    experts (those held here) and the shared expert. `router_input` is the
    (B, S, M) tensor the router reads where that is not the experts' own `h`
    (an architecture that routes a layer before its attention). -> (output,
    the layer's scalars: `aux` (the load-balancing loss), `load` (rows a
    published expert; `load_max_over_mean` of them is what is reported) and,
    where a part of the experts is held, `moe_rows_held`, `moe_passes` and,
    for ReGLU experts, `moe_act_live_units`)."""
    c = config
    with jax.named_scope("moe.route"):
        # float32 in earnest: a TPU's default float32 matmul is one bfloat16
        # pass, which rounds the router's weights and flips near-ties
        router_logits = checkpoint_name(jnp.einsum(
            "bsm,me->bse", (h if router_input is None else router_input).astype(jnp.float32),
            lp["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST,
        ), ROUTING)
        if c.router_score == "softmax":
            probs = jax.nn.softmax(router_logits, axis=-1)
        elif c.router_score == "sigmoid":
            probs = jax.nn.sigmoid(router_logits)
        else:
            raise ValueError(f"unknown router score: {c.router_score!r}")
        select = None
        if c.router_select_bias:
            select = probs + jax.lax.stop_gradient(lp["expert_bias"].astype(jnp.float32))
        if c.route_groups > 1:
            select = _group_limited(probs if select is None else select, c)
    weights = tuple(lp[name].astype(c.dtype) for name in c.expert_weights)
    mesh = _mesh_of(lp["we_up"])
    if expert_parallel(mesh):
        if select is not None or c.held_experts is not None or c.route_scale != 1.0:
            raise NotImplementedError("the GShard form routes by the plain top-k of the scores")
        out, load = _gshard_experts(h, probs, weights, c)
        held = {}
    else:
        out, load, held = _dropless_experts(h, probs, weights, c, mesh, select)
    if c.shared_expert_width:
        with jax.named_scope("moe.shared"):
            shared = tuple(lp[name.replace("we_", "ws_")].astype(c.dtype) for name in c.expert_weights)
            # (gate,) up: a matmul's output each, under `moe_shared_gate` / `moe_shared_up`
            wide = [checkpoint_name(jnp.einsum("bsm,mf->bsf", h, w), name.replace("we_", "moe_shared_"))
                    for name, w in zip(c.expert_weights, shared[:-1])]
            act = swiglu(*wide) if len(wide) == 2 else _expert_act(c)(*wide)
            out = out + jnp.einsum("bsf,fm->bsm", act, shared[-1])
    # E * sum_e f_e P_e: f_e the share of the (token, choice) pairs routed to
    # e (it carries no gradient; under GShard, of those kept), P_e the mean
    # router probability
    with jax.named_scope("moe.select"):
        share = load / (probs.shape[0] * probs.shape[1] * c.top_k)
        aux = c.n_experts * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))
    return out, {"aux": aux, "load": load, **held}


def load_max_over_mean(load: jax.Array) -> jax.Array:
    """The largest expert's rows over the mean, of rows a published expert."""
    return jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9)


def moe_mlp_sublayer(
    x: jax.Array, lp: Params, config: MoEConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pre-norm MoE FFN + residual, the scope `moe`; returns (out, aux_loss,
    the largest expert's rows over the mean)."""
    c = config
    with jax.named_scope("moe"):
        h = _norm(x, lp["ln2_scale"], lp.get("ln2_bias"), c.norm, c.norm_eps)
        out, scalars = moe_mlp(h, lp, c)
        return x + out, scalars["aux"], load_max_over_mean(scalars["load"])


# -------------------------------------------------------------------- forward


def forward_hidden(
    params: Params,
    tokens: jax.Array,
    config: MoEConfig,
    *,
    positions: Optional[jax.Array] = None,
    remat_saved: Tuple[str, ...] = (),
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Forward up to the LM head: (B, S) → ((B, S, E), what the routers
    report: `router_aux_loss` summed over the layers and
    `moe_load_max_over_mean` of the worst layer). `remat_saved` as in
    transformer.forward_hidden (this family names no candidates of its own,
    so train/lm.py asks for the whole block)."""
    c = config
    dt = c.dtype
    _, s = tokens.shape
    with jax.named_scope("embed"):
        x = params["wte"].astype(dt)[tokens]
        if c.pos_emb == "learned":
            x = x + params["wpe"].astype(dt)[None, :s]
    rope_tables = None
    if c.pos_emb != "learned":
        rope_tables = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    def block_fn(carry, lp):
        x = attention_sublayer(carry, lp, c, rope_tables, positions)
        x, aux, load = moe_mlp_sublayer(x, lp, c)
        return x, (aux, load)

    if c.remat:
        block_fn = checkpoint_block(block_fn, remat_saved)
    x, (aux_per_layer, load_per_layer) = jax.lax.scan(
        block_fn, x, params["blocks"], unroll=c.scan_unroll)
    with jax.named_scope("head"):
        x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), c.norm, c.norm_eps)
    return x, {
        "router_aux_loss": jnp.sum(aux_per_layer),
        "moe_load_max_over_mean": jnp.max(load_per_layer),
    }
