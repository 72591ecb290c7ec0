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
