"""LM training compute core: sharded TrainState + jitted train/eval steps.

Reference parity: the torch DDP/FSDP training loop that user code brings to
Ray Train (/root/reference/python/ray/train/torch/config.py:153 sets up
`dist.init_process_group`; the actual optimizer step is torch). TPU-native,
the entire step — forward, backward, optimizer, grad clip — is ONE jitted
XLA program over the mesh: FSDP/ZeRO-3 is the `fsdp` sharding on params and
optimizer moments (XLA inserts the all-gathers/reduce-scatters), DP is the
batch axis sharding, TP the head/mlp axes. No NCCL, no wrapper classes.

`infer_state_specs` maps optimizer-state leaves to parameter PartitionSpecs
by tree-path suffix matching, so any optax optimizer whose state mirrors the
param tree (adam mu/nu, sgd momentum, ...) shards correctly without
per-optimizer code.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..models import model_family
from ..models.transformer import TransformerConfig, lm_head_weights
from ..ops import cross_entropy_loss, losses
from ..ops.losses import (
    auto_loss_chunk,
    fused_linear_cross_entropy,
    fused_multihead_cross_entropy,
    multihead_cross_entropy,
    multihead_targets,
)
from ..parallel.mesh import DATA_AXES
from ..parallel.sequence_parallel import stream_shards
from ..parallel.sharding import LogicalRules, default_rules, tree_specs


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array
    # always None, and read by nothing. Checkpoints written up to PR 28
    # record this (then optional) field as an empty entry, and orbax
    # refuses to restore them into a state that lacks it
    # (tests/test_train.py::test_checkpoint_of_a_state_with_the_empty_ef_field_restores)
    ef: Any = None


# ------------------------------------------------------- state spec inference


def _paths_and_leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(tuple(str(k) for k in path), leaf) for path, leaf in flat]


def infer_state_specs(abstract_state: Any, param_specs: Any) -> Any:
    """PartitionSpec tree for a TrainState: params get their rule-derived
    specs; optimizer-state leaves whose tree-path suffix matches a param
    path (and whose shape matches) inherit that param's spec; everything
    else (counts, scalars, rng) is replicated."""
    param_flat = _paths_and_leaves(param_specs)
    by_path: Dict[tuple, PartitionSpec] = {p: s for p, s in param_flat}

    def spec_for(path: tuple, leaf) -> PartitionSpec:
        for start in range(len(path)):
            suffix = path[start:]
            if suffix in by_path:
                return by_path[suffix]
        return PartitionSpec()

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_state)
    specs = [
        spec_for(tuple(str(k) for k in path), leaf) for path, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


def _sharding_tree(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


# --------------------------------------------------------------- constructors


def default_optimizer(
    learning_rate: float = 3e-4,
    *,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
) -> optax.GradientTransformation:
    """AdamW + cosine schedule + global-norm clip (the GPT/Llama recipe)."""
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=learning_rate,
        warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1),
        end_value=learning_rate * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def _state_builder(config: TransformerConfig, optimizer: optax.GradientTransformation):
    family = model_family(config)

    def build(k):
        params = family.init_params(config, k)
        opt_state = optimizer.init(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            rng=jax.random.fold_in(k, 1),
        )

    return build


def abstract_train_state(
    config: TransformerConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: Optional[LogicalRules] = None,
) -> Tuple[TrainState, Any]:
    """(the shapes of the TrainState that `create_train_state` builds, its
    shardings), with nothing allocated: enough to lower a step, or to ask
    one what it would trace."""
    param_specs = tree_specs(model_family(config).logical_axes(config), rules or default_rules())
    abstract = jax.eval_shape(_state_builder(config, optimizer), jax.random.PRNGKey(0))
    spec_tree = infer_state_specs(abstract, param_specs)
    # the params subtree must carry the full rule-derived specs
    spec_tree = dataclasses.replace(spec_tree, params=param_specs)
    return abstract, _sharding_tree(spec_tree, mesh)


def create_train_state(
    config: TransformerConfig,
    optimizer: optax.GradientTransformation,
    key: jax.Array,
    mesh: Mesh,
    rules: Optional[LogicalRules] = None,
) -> Tuple[TrainState, Any]:
    """Initialize a TrainState directly into its sharded layout: init runs
    under jit with out_shardings, so each device materializes only its
    shard — an 8B model initializes without ever forming a host copy.

    Returns (state, state_shardings)."""
    _, shardings = abstract_train_state(config, optimizer, mesh, rules)
    state = jax.jit(_state_builder(config, optimizer), out_shardings=shardings)(key)
    return state, shardings


# ------------------------------------------------ what a remat step keeps
# Constants of `auto_remat_saved` and of the estimate `make_train_step` gives
# it, set from two cells' measured peaks on v5e chips of 16.91 GB (PERF.md
# section 6, PR 30 and PR 34): Mistral-7B's widths on four chips, fsdp=2 x
# tp=2, 8 scanned layers, 12 x 1,024 rows a device (whole block 11.145 GB,
# estimate 11.06; gate, up and the residual kept 14.658, estimate 14.68), and
# one chip's share of Trinity-Mini, 2 scanned and 4 unrolled layers, 2 x 8,192
# rows (the attention outputs kept 15.670 GB, estimate 15.73). The share of
# the device that whatever is kept leaves free is `losses.HBM_FREE_FRACTION`,
# which the head's rule shares.
# The backward pass of one block, in copies of the activations it writes:
# Mistral's peak less state, gradients and the scan's carries was 2.29 GB, the
# block's activations 1.26 GB (and chunking the head did not lower it, PR 29):
# 1.82; Trinity's with the attention outputs kept 2.94 of 1.71: 1.72
_REMAT_BLOCK_COPIES = 1.75
# FLOPs that take as long as moving one byte through a tensor-parallel
# all-reduce, which is how a spared all-reduce counts beside spared matmuls:
# keeping the residual took 23.2 ms off the step beside gate and up (32.0 on
# its own), 8.8 of them the output projection's: 14.4 ms for 805 MB. Where the
# stream's sequence lies over `tp` (PR 54) the spared sum is a reduce-scatter
# of half the bytes into half the bytes kept: the same price a byte kept
_ALL_REDUCE_FLOPS_PER_BYTE = 3600


def auto_remat_saved(
    candidates: Tuple[Any, ...],
    *,
    rows: int,
    itemsize: int,
    peak_bytes: Callable[[Tuple[Any, ...]], float],
    hbm_bytes: Optional[int] = None,
) -> Tuple[Tuple[Any, ...], int]:
    """Which of its blocks' `candidates` (models/transformer.RematCandidate)
    a step that recomputes its blocks keeps across the forward pass, and the
    bytes a device holds for them (their shapes': on the chip a kept value
    cost 0.96 to 1.00 of that): those worth keeping, one by one in order of
    their worth per byte (a spared sum over `tp` counts as the FLOPs of its
    time), each one with which `peak_bytes(kept)`, the estimate of the step's peak,
    still leaves `losses.HBM_FREE_FRACTION` of the device free. `rows` are a
    device's tokens a step. Nothing live is probed but the device's size, so
    the same model, mesh and batch always get the same program; an unknown
    size (CPU) keeps nothing: the whole-block step is the one that fits
    wherever anything does."""
    if hbm_bytes is None:
        hbm_bytes = losses.device_hbm_bytes()
    if not hbm_bytes:
        return (), 0

    def worth_per_byte(c) -> float:
        return c.worth / (c.width * itemsize) + c.tp_sum * _ALL_REDUCE_FLOPS_PER_BYTE

    kept: Tuple[Any, ...] = ()
    for c in sorted(candidates, key=worth_per_byte, reverse=True):
        if worth_per_byte(c) > 0 and peak_bytes(kept + (c,)) <= (1 - losses.HBM_FREE_FRACTION) * hbm_bytes:
            kept += (c,)
    return kept, sum(sum(c.layers) * rows * c.width * itemsize for c in kept)


def step_peak_bytes(
    kept: Tuple[Any, ...], *, rows: int, itemsize: int, always: float, logits: float,
    runs: Tuple[Dict[str, Any], ...],
) -> float:
    """The estimate of a recomputing step's peak on a device, with `kept`
    held across the forward pass: the largest of its moments. `always` is
    there all along (the device's share of the state, the gradients of what
    is outside the blocks and, accumulating, every gradient). The head's
    moment adds every kept value, every block's input and the head's
    `logits`. The backward pass of a run of the stack (`runs`, in the
    forward's order, each with its `gradients`, its blocks' `inputs`, one
    block's backward pass `block`, and `scanned`) adds the gradients of the
    runs behind it, the kept values and inputs of the runs before it, one
    block's backward pass, and of the run itself: scanned, its stacked
    gradients beside its kept values and inputs; unrolled, the larger of the
    two, since a layer's kept values go as its gradients come. An iteration
    of a scan's backward pass also holds its OWN layers' slices of all three
    (cut out of the stacked kept values and inputs, not yet written into the
    stacked gradients): one layer's are part of `block` as it was calibrated
    (every scan measured then ran one layer an iteration), the other
    `period` - 1 layers' are added. For the compiler the `train-smallthinker-16k`
    step, 2 iterations of 4 layers, needed 17.04 GB with everything kept and
    the head chunked, 2.30 GB over the estimate without them; they are 1.68."""
    held = [sum(c.layers[r] * rows * c.width * itemsize for c in kept) for r in range(len(runs))]
    inputs = [run["inputs"] for run in runs]
    moments = [always + sum(held) + sum(inputs) + logits]
    for r, run in enumerate(runs):
        mine = (run["gradients"], held[r] + inputs[r])
        moments.append(always + sum(held[:r]) + sum(inputs[:r])
                       + sum(later["gradients"] for later in runs[r + 1:])
                       + run["block"] + (sum(mine) if run["scanned"] else max(mine))
                       + (sum(mine) * (run["period"] - 1) / run["layers"] if run["scanned"] else 0))
    return max(moments)


def _model_split(sharding: NamedSharding, dims: slice) -> int:
    """The number of devices that share these dimensions of a parameter over
    the mesh's model axes, which is how the features its matmul writes are
    split. (Over a data axis a parameter is only stored, ZeRO-style: its
    matmul runs on the gathered whole.)"""
    axes = [a for entry in tuple(sharding.spec)[dims] if entry
            for a in ((entry,) if isinstance(entry, str) else entry)]
    return math.prod(sharding.mesh.shape[a] for a in axes if a not in DATA_AXES)


# what every model's step reports; a family's own scalars come beside them
CORE_STEP_METRICS = ("loss", "grad_norm", "num_tokens")


def lm_loss(
    params: Any, tokens: jax.Array, config: TransformerConfig, *,
    chunk: int = 0, z_loss_coeff: float = 0.0, remat_saved: Tuple[str, ...] = (),
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The training objective on a (B, S + 1) batch, for every model
    family: next-token cross entropy (the head chunked by `chunk` rows of
    the sequence, 0 = dense) plus, for a MoE model, `router_aux_coeff`
    times the routers' load-balancing loss and, for a model with a multi-token
    prediction module (`config.mtp_modules`), `mtp_loss_weight` times that
    module's loss: from the stack's output and the NEXT token's embedding
    the module predicts the token after next, through the same head in the
    same form, on all S positions with the last (which has no such target)
    masked out. `remat_saved`: what a model with `config.remat` keeps of
    each block. Returns (objective, the step's scalars: `loss` = the
    next-token cross entropy alone, so that every model's losses mean the
    same, `num_tokens`, the routers', and `mtp_loss`).

    A model of several next-token heads (`config.pred_heads` > 1: the EvaByte
    family) has another objective (`_multihead_lm_loss`)."""
    targets = tokens[:, 1:]
    family = model_family(config)
    hidden, routers = family.forward_hidden(
        params, tokens[:, :-1], config, remat_saved=remat_saved)
    if config.pred_heads > 1:
        return _multihead_lm_loss(params, tokens, hidden, routers, config, chunk, z_loss_coeff)

    def head_loss(hidden, targets, mask=None):
        with jax.named_scope("head"):
            head = lm_head_weights(params, config)
            if chunk:
                return fused_linear_cross_entropy(
                    hidden, head, targets, mask=mask, chunk=chunk, z_loss_coeff=z_loss_coeff)
            logits = jnp.einsum("bse,ev->bsv", hidden, head)
            return cross_entropy_loss(logits, targets, mask=mask, z_loss_coeff=z_loss_coeff)

    loss, ntok = head_loss(hidden, targets)
    objective, scalars = loss, {}
    if getattr(config, "mtp_modules", 0):
        with jax.named_scope("mtp"):
            mtp_hidden, routers = family.mtp_hidden(
                params, hidden, targets, config, routers, remat_saved=remat_saved)
            # position i predicts token i + 2; the last position has none
            has_target = jnp.broadcast_to(jnp.arange(targets.shape[1]) < targets.shape[1] - 1,
                                          targets.shape)
            mtp_loss, _ = head_loss(mtp_hidden, jnp.roll(targets, -1, axis=1), has_target)
        objective = objective + config.mtp_loss_weight * mtp_loss
        scalars["mtp_loss"] = mtp_loss
    if "router_aux_loss" in routers:
        objective = objective + config.router_aux_coeff * routers["router_aux_loss"]
    return objective, {"loss": loss, "num_tokens": ntok, **scalars, **routers}


def _multihead_lm_loss(params, tokens, hidden, routers, config, chunk, z_loss_coeff):
    """`lm_loss` of a model whose ONE head matrix (E, pred_heads x V) is
    `pred_heads` next-token heads, head n scoring position t against token
    t + 1 + n: the mean over the heads of each head's mean cross entropy over
    the positions that have its target, under the scope `head.multibyte`
    inside `head`, with float32 logits. Scalars: `loss` = the objective (what
    is trained, and compared with the reference's), `loss_next_byte` = head
    0's, the number comparable with every other model's loss, and
    `loss_last_head`."""
    if z_loss_coeff or getattr(config, "mtp_modules", 0) or "router_aux_loss" in routers:
        raise NotImplementedError("several next-token heads: no z-loss, no multi-token "
                                  "prediction module and no router's loss beside them")
    heads = config.pred_heads
    with jax.named_scope("head"), jax.named_scope("head.multibyte"):
        targets, has_target = multihead_targets(tokens, heads)
        head = lm_head_weights(params, config)
        if chunk:
            objective, per_head = fused_multihead_cross_entropy(
                hidden, head, targets, has_target, chunk=chunk)
        else:
            per_head = multihead_cross_entropy(
                jnp.einsum("bse,ev->bsv", hidden, head, preferred_element_type=jnp.float32),
                targets, has_target)
            objective = jnp.mean(per_head)
    ntok = jnp.asarray(targets.shape[0] * targets.shape[1], jnp.float32)
    return objective, {"loss": objective, "num_tokens": ntok, "loss_next_byte": per_head[0],
                       "loss_last_head": per_head[-1], **routers}


def make_train_step(
    config: TransformerConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    state_shardings: Any,
    z_loss_coeff: float = 0.0,
    grad_accum: int = 1,
    loss_chunk: Optional[int] = None,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict[str, jax.Array]]]:
    """One jitted SPMD training step. batch = {"tokens": (B, S+1) int32,
    optional "mask": (B, S)} sharded batch-over-data-axes. TrainState is
    donated: params/moments update in place in HBM.

    loss_chunk > 0 fuses the LM head with the loss over sequence chunks
    of that size (fused_linear_cross_entropy): the (B, S, V) logits —
    the peak-memory hog at LM vocab sizes — never materialize, and each
    chunk's logits are built once (its dx and its share of the head's
    gradient are computed in the same scan step: three head matmuls a
    chunk, as the dense head runs over the whole batch). None (default):
    on a device whose size is known the fused head, with the largest chunk
    (the whole sequence first) that the head's moment has room for
    (ops.losses.auto_loss_chunk; on a v5e the dense head lost 3.0% to 4.1%
    to every chunk tried on gpt2-small, PERF.md section 6, PR 46), and the
    dense head on one of unknown size (the CPU); 0 forces the dense head,
    the plain form that the fused one's tests compare with."""
    batch_sharding = NamedSharding(mesh, PartitionSpec(DATA_AXES, None))
    metric_sharding = NamedSharding(mesh, PartitionSpec())
    # batch rows per device, for the loss-chunk heuristic
    data_shards = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)

    family = model_family(config)
    decided: Dict[Tuple[int, ...], Tuple[int, Dict[str, Any]]] = {}

    def device_bytes(tree, shardings) -> int:
        return sum(math.prod(sh.shard_shape(x.shape)) * x.dtype.itemsize
                   for x, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(shardings)))

    def device_batch(tokens_shape) -> int:
        return max(tokens_shape[0] // grad_accum // max(data_shards, 1), 1)

    def model_split(weight: str, dims: slice) -> int:
        """`_model_split` of the parameter of that name, wherever the family
        keeps it (a block parameter is (layers, input, *output) in each)."""
        flat, _ = jax.tree_util.tree_flatten_with_path(state_shardings.params)
        return _model_split(
            next(sh for path, sh in flat if getattr(path[-1], "key", None) == weight), dims)

    def head_and_remat_for(tokens_shape, state: TrainState) -> Tuple[int, Dict[str, Any]]:
        """(the head's form, what the blocks keep) for a (B, S + 1) batch of
        the whole step: ONE decision, since the head's logits and the kept
        values ask for the same room; made the first time the shape is asked
        for (by a caller or by the step's trace) and kept, so that what is
        reported is what runs. Everything is counted from `state`'s shapes,
        the shardings and the device's size. First the head
        (`auto_loss_chunk`): beside a device's share of the state and the
        gradients it is told what the blocks hold when the head runs: every
        activation they write where nothing is recomputed (`block_costs`
        names a block's width; a family that names none counts none), their
        inputs where they are. Then, with that head's logits in the
        estimate of the step's peak (`step_peak_bytes`), what a recomputing
        step keeps (`auto_remat_saved`). On a v5e the six cells' heads are
        one chunk each, the whole sequence (PERF.md section 6, PR 46). A
        `loss_chunk` the caller gave stays."""
        shape = tuple(tokens_shape)
        if shape in decided:
            return decided[shape]
        batch, seq = device_batch(shape), shape[1] - 1
        rows, itemsize = batch * seq, jnp.dtype(config.dtype).itemsize
        # a block's input is a row of the residual stream, which a family may
        # carry in another dtype than it computes in
        stream_bytes = config.d_model * jnp.dtype(config.stream_dtype).itemsize
        resident = device_bytes(state, state_shardings)
        # the gradients and, where they are summed over microbatches, their accumulator
        gradients = device_bytes(state.params, state_shardings.params) * (2 if grad_accum > 1 else 1)
        # a block's matmuls by their weights' shardings; the stream between
        # sublayers by the mesh, as the models constrain it when they are traced
        stream_split = stream_shards(mesh.abstract_mesh, shape[0] // grad_accum, seq)
        costs = family.block_costs(
            config, seq, lambda weight: (stream_split if weight == "stream"
                                         else model_split(weight, slice(2, None))), rows,
        ) if family.block_costs else None
        # the logits a position: every next-token head's
        vocab = config.vocab_size * config.pred_heads // (
            model_split("lm_head", slice(1, None)) if "lm_head" in state_shardings.params
            else model_split("wte", slice(0, 1)))
        # what the blocks hold when the head runs: their inputs where they are
        # recomputed (what is kept beside those is decided after the head),
        # else every activation they write
        activations = sum(
            run["layers"] * rows * (stream_bytes // run["stream_split"] if config.remat
                                    else run["width"] * itemsize)
            for run in costs["runs"]) if costs else 0
        chunk = loss_chunk
        if chunk is None:
            chunk = auto_loss_chunk(batch, seq, vocab, resident_bytes=resident,
                                    step_bytes=gradients + activations)
        plan = {"remat": "whole_block" if config.remat else "off", "remat_saved": (),
                "remat_saved_bytes": 0, "remat_saved_by_run": (), "remat_saved_bytes_by_run": (),
                "remat_recomputed_flops_share": None if config.remat else 0.0}
        if config.remat and costs:

            def under(tree, path):
                return functools.reduce(lambda sub, key: sub[key], path, tree)

            runs = tuple({
                "scanned": run["scanned"], "period": run["period"], "layers": run["layers"],
                "gradients": device_bytes(under(state.params, run["params"]),
                                          under(state_shardings.params, run["params"])),
                "inputs": run["layers"] * rows * stream_bytes // run["stream_split"],
                "block": _REMAT_BLOCK_COPIES * rows * run["width"] * itemsize,
            } for run in costs["runs"])
            always = resident + gradients - sum(run["gradients"] for run in runs)
            kept, kept_bytes = auto_remat_saved(
                costs["candidates"], rows=rows, itemsize=itemsize,
                peak_bytes=functools.partial(
                    step_peak_bytes, rows=rows, itemsize=itemsize, always=always,
                    logits=losses.loss_logits_bytes(batch, seq, vocab, chunk), runs=runs))
            plan.update(
                remat="selective" if kept else "whole_block",
                remat_saved=tuple(name for c in kept for name in c.names),
                remat_saved_bytes=kept_bytes,
                remat_saved_by_run=tuple(
                    tuple(name for c in kept if c.layers[r] for name in c.names) for r in range(len(runs))),
                remat_saved_bytes_by_run=tuple(
                    sum(c.layers[r] * rows * c.width * itemsize for c in kept) for r in range(len(runs))),
                remat_recomputed_flops_share=(
                    costs["recomputed_flops"] - sum(sum(c.layers) * c.flops for c in kept))
                / costs["flops"])
        decided[shape] = chunk, plan
        return decided[shape]

    def loss_chunk_for(tokens_shape, state: TrainState) -> int:
        """The head's form for a (B, S + 1) batch of the whole step: 0 = the
        dense head, else the chunk (`head_and_remat_for`)."""
        return head_and_remat_for(tokens_shape, state)[0]

    def remat_plan_for(tokens_shape, state: TrainState) -> Dict[str, Any]:
        """What the step's blocks keep across the forward pass for a
        (B, S + 1) batch (`head_and_remat_for`): `remat` (`off`: nothing is
        recomputed; `whole_block`; `selective`), `remat_saved` (the
        `checkpoint_name`s kept), `remat_saved_bytes` (a device), both again a
        run of the stack in the forward's order (`remat_saved_by_run`: the
        names that run's layers write; `remat_saved_bytes_by_run`; no runs
        where nothing is recomputed) and `remat_recomputed_flops_share` (of the
        blocks' forward pass, run again in the backward; None for a family
        that names no candidates)."""
        return head_and_remat_for(tokens_shape, state)[1]

    def microbatch_grads(loss_fn, params, tokens):
        """(the step's scalars: `loss`, `num_tokens` and the routers', grads
        of the objective `loss_fn(params, tokens)`)."""
        if grad_accum == 1:
            (_, scalars), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, tokens)
            return scalars, grads

        mb_tokens = tokens.reshape(
            grad_accum, tokens.shape[0] // grad_accum, *tokens.shape[1:]
        )

        def body(carry, mb):
            acc_scalars, acc_grads = carry
            (_, scalars), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            return (jax.tree.map(jnp.add, acc_scalars, scalars),
                    jax.tree.map(jnp.add, acc_grads, grads)), None

        zero = jax.eval_shape(lambda: loss_fn(params, mb_tokens[0])[1])
        zero_scalars = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), zero)
        zero_grads = jax.tree.map(jnp.zeros_like, params)
        (scalars, grads), _ = jax.lax.scan(
            body, (zero_scalars, zero_grads), mb_tokens
        )
        scale = 1.0 / grad_accum
        # tokens add up over the microbatches; every other scalar is a mean
        scalars = {k: v if k == "num_tokens" else v * scale for k, v in scalars.items()}
        return scalars, jax.tree.map(lambda g: g * scale, grads)

    # The two scopes split the `device` bucket of train/steplog's waterfall;
    # with the sublayers' they are util/profiling.STEP_SCOPES, the closed set
    # by which a device profile's time is read (`profiling.scope_seconds`).
    def step_fn(state: TrainState, batch: Dict[str, jax.Array]):
        tokens = batch["tokens"]
        # the head's form and what the blocks keep, decided for this shape
        loss_fn = functools.partial(
            lm_loss, config=config, z_loss_coeff=z_loss_coeff,
            chunk=loss_chunk_for(tokens.shape, state),
            remat_saved=remat_plan_for(tokens.shape, state)["remat_saved"])
        with jax.named_scope("steplog.fwd_bwd_compute"):
            scalars, grads = microbatch_grads(loss_fn, state.params, tokens)
        with jax.named_scope("steplog.optimizer_update"):
            updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
            rng=jax.random.fold_in(state.rng, state.step),
        )
        metrics = {k: v.astype(jnp.float32) for k, v in scalars.items()}
        metrics["grad_norm"] = gnorm.astype(jnp.float32)
        return new_state, metrics

    def step_under_mesh(state: TrainState, batch: Dict[str, jax.Array]):
        # traced with the mesh as context: Pallas kernels inside the model
        # (ops/attention._per_shard) shard_map themselves over it, which
        # GSPMD cannot do for them
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return step_fn(state, batch)

    step = jax.jit(
        step_under_mesh,
        in_shardings=(state_shardings, {"tokens": batch_sharding}),
        out_shardings=(state_shardings, metric_sharding),  # every metric, whatever the family's
        donate_argnums=(0,),
    )
    step.loss_chunk_for = loss_chunk_for
    step.remat_plan_for = remat_plan_for
    return step


def make_eval_step(config: TransformerConfig, mesh: Mesh, state_shardings: Any):
    batch_sharding = NamedSharding(mesh, PartitionSpec(DATA_AXES, None))

    family = model_family(config)

    def eval_fn(state: TrainState, batch):
        tokens = batch["tokens"]
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):  # see make_train_step
            hidden, _ = family.forward_hidden(state.params, tokens[:, :-1], config)
            # of several next-token heads the first: the next token's
            head = lm_head_weights(state.params, config)[:, :config.vocab_size]
            logits = jnp.einsum("bse,ev->bsv", hidden, head)
        loss, ntok = cross_entropy_loss(logits, tokens[:, 1:])
        return {"eval_loss": loss.astype(jnp.float32), "num_tokens": ntok}

    return jax.jit(eval_fn, in_shardings=(state_shardings, {"tokens": batch_sharding}))
