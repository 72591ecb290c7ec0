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
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..models import model_family
from ..models.transformer import TransformerConfig, lm_head_weights
from ..ops import cross_entropy_loss
from ..ops.losses import auto_loss_chunk, fused_linear_cross_entropy
from ..parallel.mesh import DATA_AXES
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
    rules = rules or default_rules()
    family = model_family(config)
    param_specs = tree_specs(family.logical_axes(config), rules)

    def build(k):
        params = family.init_params(config, k)
        opt_state = optimizer.init(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            rng=jax.random.fold_in(k, 1),
        )

    abstract = jax.eval_shape(build, key)
    spec_tree = infer_state_specs(abstract, param_specs)
    # the params subtree must carry the full rule-derived specs
    spec_tree = dataclasses.replace(spec_tree, params=param_specs)
    shardings = _sharding_tree(spec_tree, mesh)
    state = jax.jit(build, out_shardings=shardings)(key)
    return state, shardings


# what every model's step reports; a family's own scalars come beside them
CORE_STEP_METRICS = ("loss", "grad_norm", "num_tokens")


def lm_loss(
    params: Any, tokens: jax.Array, config: TransformerConfig, *,
    chunk: int = 0, z_loss_coeff: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The training objective on a (B, S + 1) batch, for every model
    family: next-token cross entropy (the head chunked by `chunk` rows of
    the sequence, 0 = dense) plus, for a MoE model, `router_aux_coeff`
    times the routers' load-balancing loss. Returns (objective, the
    step's scalars: `loss` = the cross entropy alone, so that a dense and
    a sparse model's losses mean the same, `num_tokens`, and the
    routers')."""
    targets = tokens[:, 1:]
    hidden, routers = model_family(config).forward_hidden(params, tokens[:, :-1], config)
    head = lm_head_weights(params, config)
    if chunk:
        loss, ntok = fused_linear_cross_entropy(
            hidden, head, targets, chunk=chunk, z_loss_coeff=z_loss_coeff)
    else:
        logits = jnp.einsum("bse,ev->bsv", hidden, head)
        loss, ntok = cross_entropy_loss(logits, targets, z_loss_coeff=z_loss_coeff)
    objective = loss
    if routers:
        objective = loss + config.router_aux_coeff * routers["router_aux_loss"]
    return objective, {"loss": loss, "num_tokens": ntok, **routers}


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
    chunk, as the dense head runs over the whole batch). None (default)
    auto-selects via ops.losses.auto_loss_chunk (logits HBM estimate vs
    the device limit); 0 forces the dense path."""
    batch_sharding = NamedSharding(mesh, PartitionSpec(DATA_AXES, None))
    metric_sharding = NamedSharding(mesh, PartitionSpec())
    # batch rows per device, for the loss-chunk heuristic
    data_shards = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)

    chunks: Dict[Tuple[int, ...], int] = {}

    def loss_chunk_for(tokens_shape, state: TrainState) -> int:
        """The head's form for a (B, S + 1) batch of the whole step: decided
        the first time the shape is asked for (by a caller or by the step's
        trace) and kept, so that what is reported is what runs. What a
        device holds beside the logits is counted from `state`'s shapes
        and the shardings: its share of the state, and of the gradients."""
        if loss_chunk is not None:
            return loss_chunk
        shape = tuple(tokens_shape)
        if shape not in chunks:
            def device_bytes(tree, shardings):
                return sum(math.prod(sh.shard_shape(x.shape)) * x.dtype.itemsize
                           for x, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(shardings)))

            chunks[shape] = auto_loss_chunk(
                max(shape[0] // grad_accum // max(data_shards, 1), 1), shape[1] - 1,
                config.vocab_size,
                resident_bytes=device_bytes(state, state_shardings),
                step_bytes=device_bytes(state.params, state_shardings.params),
            )
        return chunks[shape]

    def loss_fn(params, tokens, chunk):
        return lm_loss(params, tokens, config, chunk=chunk, z_loss_coeff=z_loss_coeff)

    def microbatch_grads(params, tokens, chunk):
        """(the step's scalars: `loss`, `num_tokens` and the routers', grads
        of the objective)."""
        if grad_accum == 1:
            (_, scalars), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, tokens, chunk
            )
            return scalars, grads

        mb_tokens = tokens.reshape(
            grad_accum, tokens.shape[0] // grad_accum, *tokens.shape[1:]
        )

        def body(carry, mb):
            acc_scalars, acc_grads = carry
            (_, scalars), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb, chunk)
            return (jax.tree.map(jnp.add, acc_scalars, scalars),
                    jax.tree.map(jnp.add, acc_grads, grads)), None

        zero = jax.eval_shape(lambda: loss_fn(params, mb_tokens[0], chunk)[1])
        zero_scalars = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), zero)
        zero_grads = jax.tree.map(jnp.zeros_like, params)
        (scalars, grads), _ = jax.lax.scan(
            body, (zero_scalars, zero_grads), mb_tokens
        )
        scale = 1.0 / grad_accum
        # tokens add up over the microbatches; every other scalar is a mean
        scalars = {k: v if k == "num_tokens" else v * scale for k, v in scalars.items()}
        return scalars, jax.tree.map(lambda g: g * scale, grads)

    # named_scope labels match the train/steplog STEP_PHASES so device
    # traces (`ray_tpu profile`) line up with the step-phase waterfall
    def step_fn(state: TrainState, batch: Dict[str, jax.Array]):
        tokens = batch["tokens"]
        chunk = loss_chunk_for(tokens.shape, state)
        with jax.named_scope("steplog.fwd_bwd_compute"):
            scalars, grads = microbatch_grads(state.params, tokens, chunk)
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
    return step


def make_eval_step(config: TransformerConfig, mesh: Mesh, state_shardings: Any):
    batch_sharding = NamedSharding(mesh, PartitionSpec(DATA_AXES, None))

    family = model_family(config)

    def eval_fn(state: TrainState, batch):
        tokens = batch["tokens"]
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):  # see make_train_step
            hidden, _ = family.forward_hidden(state.params, tokens[:, :-1], config)
            logits = jnp.einsum("bse,ev->bsv", hidden, lm_head_weights(state.params, config))
        loss, ntok = cross_entropy_loss(logits, tokens[:, 1:])
        return {"eval_loss": loss.astype(jnp.float32), "num_tokens": ntok}

    return jax.jit(eval_fn, in_shardings=(state_shardings, {"tokens": batch_sharding}))
