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
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
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
    # error-feedback residual of the int8-quantized gradient sync, rows
    # layout (dp, dp, k) per param leaf — None (an empty subtree) unless
    # dp_allreduce_dtype="int8", so existing checkpoints keep their shape
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


# ----------------------------------------------- cross-replica rows layout
#
# The explicit data-parallel sync paths (quantized all-reduce, sharded
# weight update — arxiv 2004.13336) move each gradient/param leaf through a
# (n, k) "rows" layout: flatten, zero-pad to n*k with k a multiple of the
# quantizer block, reshape — row r is the chunk replica r owns. Padding
# lanes stay exactly zero through adam (zero grad -> zero update), so the
# round trip is lossless.


def _rows_k(size: int, n: int, block: int) -> int:
    k = -(-size // n)
    return -(-k // block) * block


def _to_rows(x: jax.Array, n: int, block: int) -> jax.Array:
    k = _rows_k(x.size, n, block)
    flat = x.reshape(-1).astype(jnp.float32)
    return jnp.pad(flat, (0, n * k - x.size)).reshape(n, k)


def _from_rows(rows: jax.Array, like: jax.Array) -> jax.Array:
    return rows.reshape(-1)[: like.size].reshape(like.shape).astype(like.dtype)


def _check_pure_dp(param_specs: Any) -> None:
    """The explicit dp sync paths assume params replicated across `dp` —
    they move whole leaves through the rows layout. (fsdp/tp sharding is
    XLA's own in-graph business and stays on the standard jit path.)"""

    def mentions_dp(spec: PartitionSpec) -> bool:
        for entry in spec:
            if entry == "dp" or (isinstance(entry, tuple) and "dp" in entry):
                return True
        return False

    bad = [
        s for s in jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
        )
        if mentions_dp(s)
    ]
    if bad:
        raise ValueError(
            "explicit dp sync (dp_shard_update / int8 all-reduce) requires "
            f"params replicated over the dp axis; got specs {bad[:3]}"
        )


# --------------------------------------------------------------- constructors


def clip_by_global_norm_sharded(
    max_norm: float, axis: str
) -> optax.GradientTransformation:
    """optax.clip_by_global_norm for updates that are SHARDS of the global
    tree (the dp_shard_update path): the sum of squares is psum'd over the
    shard axis so the trigger and scale match the replicated clip exactly.
    Only valid under shard_map with `axis` manual."""

    def update_fn(updates, state, params=None):
        del params
        sumsq = sum(
            jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(updates)
        )
        g_norm = jnp.sqrt(lax.psum(sumsq, axis))
        trigger = jnp.squeeze(g_norm < max_norm)
        updates = jax.tree.map(
            lambda t: lax.select(trigger, t, (t / g_norm.astype(t.dtype)) * max_norm),
            updates,
        )
        return updates, state

    return optax.GradientTransformation(
        lambda params: optax.EmptyState(), update_fn
    )


def default_optimizer(
    learning_rate: float = 3e-4,
    *,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    shard_axis: Optional[str] = None,
) -> optax.GradientTransformation:
    """AdamW + cosine schedule + global-norm clip (the GPT/Llama recipe).

    shard_axis: set to the dp mesh axis when the optimizer will run on
    cross-replica shards (dp_shard_update) — the global-norm clip then
    psums the squared norm across shards instead of under-reading it."""
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=learning_rate,
        warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1),
        end_value=learning_rate * 0.1,
    )
    clip = (
        clip_by_global_norm_sharded(grad_clip, shard_axis)
        if shard_axis
        else optax.clip_by_global_norm(grad_clip)
    )
    return optax.chain(
        clip,
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def create_train_state(
    config: TransformerConfig,
    optimizer: optax.GradientTransformation,
    key: jax.Array,
    mesh: Mesh,
    rules: Optional[LogicalRules] = None,
    *,
    dp_shard_update: bool = False,
    dp_error_feedback: bool = False,
    dp_quant_block: Optional[int] = None,
) -> Tuple[TrainState, Any]:
    """Initialize a TrainState directly into its sharded layout: init runs
    under jit with out_shardings, so each device materializes only its
    shard — an 8B model initializes without ever forming a host copy.

    dp_shard_update stores the optimizer state in the cross-replica rows
    layout, sharded over dp (each replica keeps 1/n of the Adam moments —
    arxiv 2004.13336); dp_error_feedback adds the int8-sync residual
    buffer, also dp-sharded (one full-rows error matrix per replica).

    Returns (state, state_shardings)."""
    rules = rules or default_rules()
    family = model_family(config)
    param_specs = tree_specs(family.logical_axes(config), rules)
    n_dp = mesh.shape.get("dp", 1)
    if dp_quant_block is None:
        from ..core.config import cfg

        dp_quant_block = cfg.dp_quant_block
    if dp_shard_update or dp_error_feedback:
        _check_pure_dp(param_specs)

    def build(k):
        params = family.init_params(config, k)
        if dp_shard_update:
            rows_template = jax.tree.map(
                lambda p: jnp.zeros(
                    (n_dp, _rows_k(p.size, n_dp, dp_quant_block)), jnp.float32
                ),
                params,
            )
            opt_state = optimizer.init(rows_template)
        else:
            opt_state = optimizer.init(params)
        ef = None
        if dp_error_feedback:
            ef = jax.tree.map(
                lambda p: jnp.zeros(
                    (n_dp, n_dp, _rows_k(p.size, n_dp, dp_quant_block)),
                    jnp.float32,
                ),
                params,
            )
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            rng=jax.random.fold_in(k, 1),
            ef=ef,
        )

    abstract = jax.eval_shape(build, key)
    spec_tree = infer_state_specs(abstract, param_specs)
    # the params subtree must carry the full rule-derived specs
    spec_tree = dataclasses.replace(spec_tree, params=param_specs)
    if dp_shard_update:
        # rows-layout optimizer leaves shard over dp on their leading axis;
        # scalars (adam count, schedule step) stay replicated
        spec_tree = dataclasses.replace(
            spec_tree,
            opt_state=jax.tree.map(
                lambda leaf: PartitionSpec("dp")
                if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == n_dp
                else PartitionSpec(),
                abstract.opt_state,
            ),
        )
    if dp_error_feedback:
        spec_tree = dataclasses.replace(
            spec_tree,
            ef=jax.tree.map(lambda _: PartitionSpec("dp"), abstract.ef),
        )
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
    dp_allreduce_dtype: Optional[str] = None,
    dp_shard_update: Optional[bool] = None,
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
    the device limit); 0 forces the dense path.

    dp_allreduce_dtype / dp_shard_update (None = read cfg flags) move the
    data-parallel gradient sync onto the explicit shard_map path:
    "int8" block-quantizes the all-reduce wire with error feedback
    (EQuARX), dp_shard_update reduce-scatters grads and shards the weight
    update + Adam state across replicas (reduce-scatter -> shard-local
    update -> all-gather params, arxiv 2004.13336). Both require a
    pure-dp mesh and a state built by create_train_state with matching
    flags."""
    from ..core.config import cfg

    if dp_allreduce_dtype is None:
        dp_allreduce_dtype = cfg.dp_allreduce_dtype
    if dp_shard_update is None:
        dp_shard_update = cfg.dp_shard_update
    n_dp = mesh.shape.get("dp", 1)
    explicit_dp = (dp_shard_update or dp_allreduce_dtype == "int8") and n_dp > 1

    batch_sharding = NamedSharding(mesh, PartitionSpec(DATA_AXES, None))
    metric_sharding = NamedSharding(mesh, PartitionSpec())
    # batch rows per device, for the loss-chunk heuristic (asked with the
    # whole step's shapes on both paths)
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

    if explicit_dp:
        step = _make_explicit_dp_step(
            optimizer, mesh, state_shardings, microbatch_grads, loss_chunk_for,
            dp_allreduce_dtype=dp_allreduce_dtype,
            dp_shard_update=dp_shard_update,
            dp_quant_block=cfg.dp_quant_block,
            batch_sharding=batch_sharding,
            metric_sharding=metric_sharding,
        )
        step.loss_chunk_for = loss_chunk_for
        return step

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
            ef=state.ef,
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


def _make_explicit_dp_step(
    optimizer, mesh, state_shardings, microbatch_grads, loss_chunk_for, *,
    dp_allreduce_dtype, dp_shard_update, dp_quant_block,
    batch_sharding, metric_sharding,
):
    """The explicit data-parallel step: grads sync through hand-built
    collectives under shard_map instead of XLA's implicit partitioning.

    Per replica: local grads -> rows layout -> [int8-quantized] all-reduce
    or reduce-scatter -> (replicated | shard-local) optimizer update ->
    [all-gather params]. Error feedback keeps the int8 wire honest: each
    replica's quantization residual re-enters its next-step gradient."""
    from ..parallel.collectives import (
        quantized_psum_rows,
        quantized_psum_scatter_rows,
    )

    axis = "dp"
    n = mesh.shape[axis]
    others = [a for a in mesh.axis_names if a != axis and mesh.shape[a] > 1]
    if others:
        raise ValueError(
            f"explicit dp sync requires a pure-dp mesh; axes {others} have "
            "size > 1 (fsdp/tp sharding already syncs through XLA's own "
            "collectives on the standard path)"
        )
    quantized = dp_allreduce_dtype == "int8"
    if dp_allreduce_dtype not in ("f32", "int8"):
        raise ValueError(f"unknown dp_allreduce_dtype {dp_allreduce_dtype!r}")

    state_specs = jax.tree.map(
        lambda s: s.spec, state_shardings,
        is_leaf=lambda x: isinstance(x, NamedSharding),
    )
    batch_specs = {"tokens": batch_sharding.spec}

    # named_scope labels match the train/steplog STEP_PHASES so device
    # traces line up with the step-phase waterfall (the host can only
    # ESTIMATE dp_sync; the trace scope is where the truth lives)
    def local_step(chunk: int, state: TrainState, batch: Dict[str, jax.Array]):
        tokens = batch["tokens"]
        with jax.named_scope("steplog.fwd_bwd_compute"):
            scalars, grads = microbatch_grads(state.params, tokens, chunk)
        grows = jax.tree.map(lambda g: _to_rows(g, n, dp_quant_block), grads)
        if quantized:
            if state.ef is None:
                raise ValueError(
                    "int8 dp all-reduce needs the error-feedback buffer; "
                    "build the state with create_train_state("
                    "dp_error_feedback=True)"
                )
            ef_local = jax.tree.map(lambda e: e[0], state.ef)
            grows = jax.tree.map(jnp.add, grows, ef_local)

        if dp_shard_update:
            if quantized:
                with jax.named_scope("steplog.dp_sync"):
                    synced = jax.tree.map(
                        lambda r: quantized_psum_scatter_rows(
                            r, axis, block=dp_quant_block
                        ),
                        grows,
                    )
                own = jax.tree.map(lambda se: se[0] / n, synced,
                                   is_leaf=lambda x: isinstance(x, tuple))
                new_ef = jax.tree.map(lambda se: se[1][None], synced,
                                      is_leaf=lambda x: isinstance(x, tuple))
            else:
                own = jax.tree.map(
                    lambda r: lax.psum_scatter(
                        r, axis, scatter_dimension=0, tiled=True
                    )[0] / n,
                    grows,
                )
                new_ef = state.ef
            my = lax.axis_index(axis)
            p_shard = jax.tree.map(
                lambda p: _to_rows(p, n, dp_quant_block)[my], state.params
            )
            # rows-layout opt leaves arrive as (1, k) dp shards; scalars
            # (adam count, schedule step) arrive whole
            opt_local = jax.tree.map(
                lambda x: x[0] if getattr(x, "ndim", 0) >= 2 and x.shape[0] == 1 else x,
                state.opt_state,
            )
            sumsq = sum(
                jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(own)
            )
            gnorm = jnp.sqrt(lax.psum(sumsq, axis))
            updates, new_opt_local = optimizer.update(own, opt_local, p_shard)
            new_p_shard = optax.apply_updates(p_shard, updates)
            new_rows = jax.tree.map(
                lambda s_: lax.all_gather(s_, axis, axis=0, tiled=False),
                new_p_shard,
            )
            new_params = jax.tree.map(
                lambda r, p: _from_rows(r, p), new_rows, state.params
            )
            new_opt = jax.tree.map(
                lambda x: x[None] if getattr(x, "ndim", 0) >= 1 else x,
                new_opt_local,
            )
        else:
            with jax.named_scope("steplog.dp_sync"):
                synced = jax.tree.map(
                    lambda r: quantized_psum_rows(r, axis, block=dp_quant_block),
                    grows,
                )
            new_ef = jax.tree.map(lambda se: se[1][None], synced,
                                  is_leaf=lambda x: isinstance(x, tuple))
            g_sync = jax.tree.map(
                lambda se, g: _from_rows(se[0] / n, g), synced, grads,
                is_leaf=lambda x: isinstance(x, tuple),
            )
            gnorm = optax.global_norm(g_sync)
            updates, new_opt = optimizer.update(
                g_sync, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)

        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
            rng=jax.random.fold_in(state.rng, state.step),
            ef=new_ef,
        )
        # tokens add up over the replicas; every other scalar is a mean
        metrics = {
            k: (lax.psum if k == "num_tokens" else lax.pmean)(v, axis).astype(jnp.float32)
            for k, v in scalars.items()
        }
        metrics["grad_norm"] = gnorm.astype(jnp.float32)
        return new_state, metrics

    def sharded(state: TrainState, batch: Dict[str, jax.Array]):
        # the head's form is decided on the whole step's shapes, outside
        chunk = loss_chunk_for(batch["tokens"].shape, state)
        return shard_map(
            partial(local_step, chunk), mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, PartitionSpec()),
            check_vma=False,
        )(state, batch)

    return jax.jit(
        sharded,
        in_shardings=(state_shardings, {"tokens": batch_sharding}),
        out_shardings=(state_shardings, metric_sharding),
        donate_argnums=(0,),
    )


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
