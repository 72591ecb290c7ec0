"""Sharding-rule engine: logical axis names → mesh PartitionSpecs.

The reference has no equivalent — sharding there is whatever torch FSDP/
DeepSpeed/vLLM do internally (SURVEY.md §2.4). TPU-native, partitioning is a
*compiler annotation*: every parameter carries logical axis names (e.g.
("embed", "mlp")) and a rule table maps logical names to mesh axes. Change
the rule table and the same model runs DP, FSDP, TP, or any combination —
the Megatron/GSPMD insight that parallelism is configuration, not code.

Two rule systems compose:
- logical rules: [("embed", "fsdp"), ("mlp", "tp"), ...] applied to
  logical-axis tuples (the common path for models built in this repo)
- path-regex rules: [(r".*attn/wq", P("fsdp", "tp")), ...] applied to
  parameter tree paths (escape hatch for imported/foreign pytrees)

The rules place PARAMETERS. Activations follow from them by propagation, but
for one: on a mesh with `tp` > 1 the residual stream between sublayers is
constrained to P(("dp", "fsdp"), "tp", None), its sequence axis over `tp`
(parallel/sequence_parallel.py: `constrain_stream`, called by
models/transformer.py at the embedding's output and after each sublayer's
residual add; the matmuls on either side of it are written out there as rings
over `tp`). What decides is the context mesh's `tp` size as the step is
traced, and nothing else: no rule, flag or configuration key.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MeshAxis = Union[None, str, Tuple[str, ...]]
LogicalRules = Sequence[Tuple[str, MeshAxis]]

P = PartitionSpec


# ---------------------------------------------------------------- rule tables
# Standard tables for the canonical mesh axes (mesh.AXIS_ORDER). Batch-like
# logical axes map to the data axes; hidden dims shard over fsdp (ZeRO-3
# style) and/or tp (Megatron style); experts over ep; sequence over sp.

def default_rules() -> List[Tuple[str, MeshAxis]]:
    return [
        ("batch", ("dp", "fsdp")),
        ("seq", "sp"),
        ("kv_seq", None),          # ring attention shards kv blocks manually
        ("embed", "fsdp"),         # param hidden dim: ZeRO-3 shard
        ("heads", "tp"),           # attention heads: Megatron split
        ("kv_heads", "tp"),
        ("ssm_heads", None),       # a state-space mixer's heads: whole on every device so far
        ("head_dim", None),
        ("mlp", "tp"),             # ffn hidden: Megatron split
        ("vocab", "tp"),
        ("expert", "ep"),
        ("layers", None),          # scanned layer axis stays unsharded
        ("stage", "pp"),
    ]


def override_rules(base: LogicalRules, **overrides: MeshAxis) -> List[Tuple[str, MeshAxis]]:
    out = [(k, overrides.pop(k)) if k in overrides else (k, v) for k, v in base]
    out.extend(overrides.items())
    return out


# ------------------------------------------------------------- logical system


def logical_to_spec(logical_axes: Sequence[Optional[str]], rules: LogicalRules) -> PartitionSpec:
    """Map a tuple of logical axis names to a PartitionSpec.

    Guarantees no mesh axis is used twice in one spec (XLA requirement); a
    later logical axis that would reuse a mesh axis falls back to None
    (replicated on that dim) — same resolution order as flax's
    logical partitioning.
    """
    table = dict(rules)
    used: set = set()
    out: List[MeshAxis] = []
    for name in logical_axes:
        if name is None:
            out.append(None)
            continue
        axes = table.get(name)
        if axes is None:
            out.append(None)
            continue
        axes_tuple = (axes,) if isinstance(axes, str) else tuple(axes)
        free = tuple(a for a in axes_tuple if a not in used)
        if not free:
            out.append(None)
            continue
        used.update(free)
        out.append(free if len(free) > 1 else free[0])
    return PartitionSpec(*out)


def tree_specs(logical_tree: Any, rules: LogicalRules) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical_to_spec(axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )


def tree_shardings(logical_tree: Any, rules: LogicalRules, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        tree_specs(logical_tree, rules),
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def shard_tree(tree: Any, logical_tree: Any, rules: LogicalRules, mesh: Mesh) -> Any:
    """Device_put a parameter pytree according to its logical axes."""
    shardings = tree_shardings(logical_tree, rules, mesh)
    return jax.device_put(tree, shardings)


# ---------------------------------------------------------------- path system


def path_specs(tree: Any, path_rules: Sequence[Tuple[str, PartitionSpec]]) -> Any:
    """PartitionSpec per leaf by regex match on '/'-joined tree path."""
    compiled = [(re.compile(pat), spec) for pat, spec in path_rules]

    def spec_for(path: str) -> PartitionSpec:
        # regex *search* semantics (t5x-style): a rule matches anywhere in
        # the '/'-joined path; anchor with ^...$ for an exact match.
        for pat, spec in compiled:
            if pat.search(path):
                return spec
        return PartitionSpec()

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    treedef = jax.tree.structure(tree)
    specs = [
        spec_for("/".join(_key_str(k) for k in path)) for path, _leaf in flat
    ]
    return jax.tree.unflatten(treedef, specs)


def _key_str(key) -> str:
    if hasattr(key, "key"):
        return str(key.key)
    if hasattr(key, "idx"):
        return str(key.idx)
    if hasattr(key, "name"):
        return str(key.name)
    return str(key)


# ------------------------------------------------------------------ utilities


def validate_divisibility(shape: Sequence[int], spec: PartitionSpec, mesh: Mesh, name: str = "") -> None:
    """Raise early (with a readable message) if a dim doesn't divide by its
    mesh axes — XLA's error for this is notoriously opaque."""
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is None:
            continue
        axes_tuple = (axes,) if isinstance(axes, str) else tuple(axes)
        total = 1
        for a in axes_tuple:
            total *= mesh.shape[a]
        if dim % total != 0:
            raise ValueError(
                f"{name}: dim of size {dim} not divisible by mesh axes "
                f"{axes_tuple} (product {total})"
            )
