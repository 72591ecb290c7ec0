"""Collectives: XLA-compiled groups over mesh axes.

Parity surface: /root/reference/python/ray/util/collective/collective.py
(init_collective_group :123, allreduce :268, allgather, reducescatter,
broadcast, barrier, send/recv :541/604) with NCCL/Gloo backends.

TPU-native inversion: a collective is not a runtime service call — it is a
compiled XLA op over a mesh axis, scheduled by the compiler onto ICI. Two
usage modes:

1. **In-graph** (the fast path): inside shard_map'd/jitted code use the
   `psum/pmean/all_gather/ppermute/...` aliases below; XLA fuses and
   schedules them. This is where NCCL's entire role goes.
2. **Eager groups** (parity with the reference's out-of-band API): a
   `CollectiveGroup` wraps a mesh axis and exposes eager allreduce/
   broadcast/etc. on device arrays — each call is a tiny jitted program.
   Useful for control-plane math (metric reduction, elastic re-meshing
   checks), NOT for the training hot loop.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

# In-graph aliases (use under shard_map; axis_name is the mesh axis).
psum = lax.psum
pmean = lax.pmean
pmax = lax.pmax
pmin = lax.pmin
ppermute = lax.ppermute
all_gather = lax.all_gather
psum_scatter = lax.psum_scatter
all_to_all = lax.all_to_all
axis_index = lax.axis_index


class CollectiveGroup:
    """Eager collectives over one or more axes of a registered mesh.

    Reference parity: one CollectiveGroup ≈ one NCCL communicator
    (nccl_collective_group.py), but membership is a mesh axis, creation is
    free (no rendezvous), and the transport is whatever XLA picked (ICI
    within a slice, DCN across).
    """

    def __init__(self, mesh: Mesh, axis: str = "dp", name: str = "default"):
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self.name = name
        # jit cache keyed by (kind, spec, extras): eager collectives are
        # called per-step for metric reduction — a fresh closure per call
        # would retrace + recompile every time.
        self._jitted: Dict[tuple, callable] = {}

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]

    def _spec_for(self, x: jax.Array) -> PartitionSpec:
        # Eager arrays may carry any sharding; we operate on whatever spec
        # they have and reduce over self.axis. The mesh must be the *same*
        # mesh (device assignment included), not merely the same shape.
        sharding = x.sharding
        if isinstance(sharding, NamedSharding) and sharding.mesh == self.mesh:
            return sharding.spec
        return PartitionSpec()

    def _mentions_axis(self, entry) -> bool:
        if entry == self.axis:
            return True
        return isinstance(entry, tuple) and self.axis in entry

    def _drop_axis(self, spec: PartitionSpec) -> PartitionSpec:
        """Replace occurrences of the group axis with None (post-gather the
        dimension is no longer sharded over it)."""
        out = []
        for entry in spec:
            if entry == self.axis:
                out.append(None)
            elif isinstance(entry, tuple):
                kept = tuple(a for a in entry if a != self.axis)
                out.append(kept if kept else None)
            else:
                out.append(entry)
        return PartitionSpec(*out)

    def _get_jitted(self, key: tuple, build) -> callable:
        fn = self._jitted.get(key)
        if fn is None:
            fn = jax.jit(build())
            self._jitted[key] = fn
        return fn

    def allreduce(self, x: jax.Array, op: str = "sum") -> jax.Array:
        spec = self._spec_for(x)
        fn = {"sum": psum, "mean": pmean, "max": pmax, "min": pmin}[op]

        def build():
            @partial(
                shard_map, mesh=self.mesh, in_specs=spec, out_specs=spec,
                check_vma=False,
            )
            def _reduce(v):
                return fn(v, self.axis)

            return _reduce

        return self._get_jitted(("allreduce", op, spec), build)(x)

    def broadcast(self, x: jax.Array, root: int = 0) -> jax.Array:
        spec = self._spec_for(x)
        out_spec = self._drop_axis(spec)

        def build():
            @partial(
                shard_map, mesh=self.mesh, in_specs=spec,
                out_specs=out_spec, check_vma=False,
            )
            def _bcast(v):
                idx = lax.axis_index(self.axis)
                mask = (idx == root).astype(v.dtype)
                # sum(v * one_hot(root)) == v@root everywhere: a broadcast as
                # a reduction, which XLA lowers to an ICI broadcast.
                return lax.psum(v * mask, self.axis)

            return _bcast

        return self._get_jitted(("broadcast", root, spec), build)(x)

    def allgather(self, x: jax.Array) -> jax.Array:
        """Gather shards along a new leading axis of size `group size`."""
        spec = self._spec_for(x)
        # Trailing dims lose their group-axis sharding: each member now holds
        # the full gathered copy along that dim.
        out_spec = PartitionSpec(None, *self._drop_axis(spec))

        def build():
            @partial(
                shard_map, mesh=self.mesh, in_specs=spec,
                out_specs=out_spec, check_vma=False,
            )
            def _gather(v):
                return all_gather(v, self.axis, axis=0)

            return _gather

        return self._get_jitted(("allgather", spec), build)(x)

    def reducescatter(self, x: jax.Array) -> jax.Array:
        """Sum over the group, scattering the leading dim across members."""
        spec = self._spec_for(x)
        if any(self._mentions_axis(e) for e in spec):
            raise ValueError(
                f"reducescatter input must not already be sharded over the "
                f"group axis {self.axis!r}; got spec {spec}"
            )
        first = spec[0] if len(spec) else None
        if first is None:
            dim0 = self.axis
        elif isinstance(first, tuple):
            dim0 = (self.axis, *first)
        else:
            dim0 = (self.axis, first)
        out_spec = PartitionSpec(dim0, *spec[1:])

        def build():
            @partial(
                shard_map, mesh=self.mesh, in_specs=spec,
                out_specs=out_spec, check_vma=False,
            )
            def _rs(v):
                return psum_scatter(v, self.axis, scatter_dimension=0, tiled=True)

            return _rs

        return self._get_jitted(("reducescatter", spec), build)(x)

    def barrier(self) -> None:
        """Complete when every member has entered: a 1-element psum."""
        token = jnp.zeros((), jnp.int32)

        def build():
            @partial(
                shard_map, mesh=self.mesh, in_specs=P(), out_specs=P(),
                check_vma=False,
            )
            def _bar(v):
                return psum(v, self.axis)

            return _bar

        self._get_jitted(("barrier",), build)(token).block_until_ready()


# --------------------------------------------- quantized (int8) collectives
#
# EQuARX-style block-quantized all-reduce (PAPERS.md, arxiv 2506.17615) for
# the data-parallel gradient sync: the wire carries int8 values plus one f32
# scale per `block` elements instead of full-precision tensors — a ~3.7x
# byte reduction at block 512 — while the reduction itself runs in f32.
# Layout convention: the operand is a (n, k) "rows" matrix where n is the
# group size and row r is the chunk destined to member r; the all-reduce is
#     quantize -> all_to_all (int8 wire) -> dequant+sum   (reduce-scatter)
#     -> requantize own row -> all_gather (int8 wire) -> dequant
# Both quantization stages return their error so callers can keep an
# error-feedback buffer (the residual re-enters next step's gradient, which
# is what makes deterministic-rounding int8 training converge).
# These are IN-GRAPH primitives: call under shard_map with a manual axis.


def quantize_int8_block(x: jax.Array, block: int = 512):
    """Blockwise int8 quantization along the last axis. Returns (values
    int8, scales f32 with last dim x.shape[-1]//block). Last axis must be a
    multiple of `block`; zero blocks get scale 1 (values are all 0)."""
    if x.shape[-1] % block:
        raise ValueError(f"last axis {x.shape[-1]} not divisible by block {block}")
    shaped = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // block, block)
    amax = jnp.max(jnp.abs(shaped), axis=-1)
    scales = jnp.where(amax == 0.0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(shaped / scales[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scales


def dequantize_int8_block(values: jax.Array, scales: jax.Array) -> jax.Array:
    block = values.shape[-1] // scales.shape[-1]
    shaped = values.astype(jnp.float32).reshape(
        *values.shape[:-1], scales.shape[-1], block
    )
    return (shaped * scales[..., None]).reshape(values.shape)


def quantized_psum_scatter_rows(x: jax.Array, axis_name: str, *, block: int = 512):
    """Reduce-scatter of a (n, k) rows matrix with int8 wire traffic.
    Returns (own_row (k,) f32 — the summed row this member owns — and the
    local quantization error (n, k) for error feedback)."""
    q, s = quantize_int8_block(x, block)
    err = x.astype(jnp.float32) - dequantize_int8_block(q, s)
    qx = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=True)
    sx = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0, tiled=True)
    own = jnp.sum(dequantize_int8_block(qx, sx), axis=0)
    return own, err


def quantized_psum_rows(x: jax.Array, axis_name: str, *, block: int = 512):
    """Full all-reduce of a (n, k) rows matrix with int8 wire traffic.
    Returns (reduced (n, k) f32 — bit-identical on every member — and the
    combined local quantization error (n, k) for error feedback: stage-1
    errors everywhere plus this member's stage-2 error on its own row)."""
    own, err = quantized_psum_scatter_rows(x, axis_name, block=block)
    q2, s2 = quantize_int8_block(own[None], block)
    err2 = own - dequantize_int8_block(q2, s2)[0]
    qg = lax.all_gather(q2[0], axis_name, axis=0, tiled=False)
    sg = lax.all_gather(s2[0], axis_name, axis=0, tiled=False)
    reduced = dequantize_int8_block(qg, sg)
    my = lax.axis_index(axis_name)
    err = err.at[my].add(err2)
    return reduced, err


def dp_sync_bytes(
    n_params: int,
    n_replicas: int,
    *,
    mode: str = "f32",
    shard_update: bool = False,
    block: int = 512,
    param_bytes: int = 4,
) -> int:
    """Per-replica wire bytes one data-parallel sync moves per step (ring
    collective accounting: each stage ships (n-1)/n of the payload). The
    number bench.py publishes as `dp_sync_bytes`."""
    if n_replicas <= 1:
        return 0
    f = (n_replicas - 1) / n_replicas
    scales = 4 * -(-n_params // block)
    if mode == "int8":
        grad_stage = f * (n_params + scales)          # int8 values + f32 scales
        gather_stage = f * (n_params + scales)
    else:
        grad_stage = f * n_params * param_bytes       # reduce-scatter half
        gather_stage = f * n_params * param_bytes     # all-gather half
    if shard_update:
        # grads only reduce-scatter; the gather ships updated params f32
        return int(grad_stage + f * n_params * param_bytes)
    return int(grad_stage + gather_stage)


# -------------------------------------------------------------- group manager


class _GroupManager:
    """Named collective groups (reference: GroupManager collective.py:40)."""

    def __init__(self):
        self._groups: Dict[str, CollectiveGroup] = {}
        self._lock = threading.Lock()

    def create(self, mesh: Mesh, axis: str, name: str) -> CollectiveGroup:
        with self._lock:
            if name in self._groups:
                raise ValueError(f"collective group {name!r} exists")
            group = CollectiveGroup(mesh, axis, name)
            self._groups[name] = group
            return group

    def get(self, name: str) -> CollectiveGroup:
        with self._lock:
            return self._groups[name]

    def destroy(self, name: str) -> None:
        with self._lock:
            self._groups.pop(name, None)


_manager = _GroupManager()


def init_collective_group(mesh: Mesh, axis: str = "dp", group_name: str = "default") -> CollectiveGroup:
    """Parity with reference init_collective_group (collective.py:123)."""
    return _manager.create(mesh, axis, group_name)


def get_group(group_name: str = "default") -> CollectiveGroup:
    return _manager.get(group_name)


def destroy_collective_group(group_name: str = "default") -> None:
    _manager.destroy(group_name)


def allreduce(x: jax.Array, group_name: str = "default", op: str = "sum") -> jax.Array:
    return _manager.get(group_name).allreduce(x, op)


def broadcast(x: jax.Array, root: int = 0, group_name: str = "default") -> jax.Array:
    return _manager.get(group_name).broadcast(x, root)


def allgather(x: jax.Array, group_name: str = "default") -> jax.Array:
    return _manager.get(group_name).allgather(x)


def reducescatter(x: jax.Array, group_name: str = "default") -> jax.Array:
    return _manager.get(group_name).reducescatter(x)


def barrier(group_name: str = "default") -> None:
    _manager.get(group_name).barrier()
