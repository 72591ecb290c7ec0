"""Resource model: named float resources with TPU-topology awareness.

The reference models resources as named float maps with special handling for
accelerators (/root/reference/src/ray/common/scheduling/ and
python/ray/_private/accelerators/tpu.py:109 TPUAcceleratorManager). The key
TPU trick we keep: a pod/slice advertises one `TPU-<topology>-head` resource
so SPMD gangs can be scheduled atomically onto whole slices
(reference accelerators/tpu.py:375).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional

_EPS = 1e-9

ResourceDict = Dict[str, float]


class ResourceSet:
    """A thread-safe bag of named float resources supporting acquire/release."""

    def __init__(self, total: ResourceDict):
        self._total = dict(total)
        self._available = dict(total)
        # Consumers poll (scheduler dispatch loop / actor placement loop)
        # rather than wait on a condition: acquisition spans *multiple*
        # candidate ResourceSets, so no single CV is a correct wake signal.
        self._lock = threading.Lock()
        # Optional callback fired after every release (outside the lock):
        # the cluster agent hangs its admission-queue drain here so a
        # LOCAL task/actor freeing this node's ledger also admits queued
        # remote arrivals — not only remote completions.
        self.on_release = None
        # A closed pool admits nothing new (a removed PG bundle: running
        # work may still release into it, but restarts/new leases must
        # fail instead of drawing from detached capacity).
        self.closed = False

    @property
    def total(self) -> ResourceDict:
        return dict(self._total)

    def available(self) -> ResourceDict:
        with self._lock:
            return dict(self._available)

    def can_ever_fit(self, request: ResourceDict) -> bool:
        if self.closed:
            return False
        return all(self._total.get(k, 0.0) + _EPS >= v for k, v in request.items())

    def try_acquire(self, request: ResourceDict) -> bool:
        with self._lock:
            if self.closed:
                return False
            if all(self._available.get(k, 0.0) + _EPS >= v for k, v in request.items()):
                for k, v in request.items():
                    self._available[k] = self._available.get(k, 0.0) - v
                return True
            return False

    def release(self, request: ResourceDict) -> None:
        with self._lock:
            for k, v in request.items():
                self._available[k] = min(
                    self._total.get(k, 0.0), self._available.get(k, 0.0) + v
                )
        cb = self.on_release
        if cb is not None:
            cb()

    def add_capacity(self, extra: ResourceDict) -> None:
        with self._lock:
            for k, v in extra.items():
                self._total[k] = self._total.get(k, 0.0) + v
                self._available[k] = self._available.get(k, 0.0) + v

    def remove_capacity(self, extra: ResourceDict) -> None:
        with self._lock:
            for k, v in extra.items():
                self._total[k] = max(0.0, self._total.get(k, 0.0) - v)
                self._available[k] = max(0.0, self._available.get(k, 0.0) - v)


def _pod_env_hosts() -> int:
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return max(1, len([h for h in hostnames.split(",") if h.strip()]))


def _pod_env_resources() -> Optional[ResourceDict]:
    """TPU resources from the pod environment, trusted BEFORE JAX.

    On GKE/GCE TPU VMs the runtime sets TPU_ACCELERATOR_TYPE (e.g.
    "v4-16", "v5litepod-8"), TPU_VISIBLE_CHIPS ("0,1,2,3" — the chips
    this container may touch), and for multi-host slices TPU_WORKER_ID /
    TPU_WORKER_HOSTNAMES. Mirrors the reference TPUAcceleratorManager
    (accelerators/tpu.py:109 visible-chips handling; :375 pod-type →
    `TPU-<type>-head` synthesized ONLY on worker 0, which is what makes
    whole-slice gang scheduling expressible as one resource demand).
    Returns None when the environment says nothing (fall back to JAX).
    """
    acc_type = os.environ.get("TPU_ACCELERATOR_TYPE")
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if not acc_type and not visible:
        return None
    n_hosts = _pod_env_hosts()
    clamped = False
    if visible is not None:
        chips = float(len([c for c in visible.split(",") if c.strip()]))
    else:  # type-derived (clamped below alongside the visible path)
        # Only the type is known. The numeric suffix counts TENSORCORES
        # for v2/v3/v4/v5p (2 per chip) but CHIPS for v5litepod/v5e/v6e —
        # the same generation table the reference TPUAcceleratorManager
        # keys on. Per-host chips = slice chips / worker count.
        chips = 4.0
        if acc_type and "-" in acc_type:
            try:
                gen = acc_type.split("-", 1)[0].lower()
                total = int(acc_type.rsplit("-", 1)[1])
                cores_per_chip = 2 if gen in ("v2", "v3", "v4", "v5p") else 1
                slice_chips = max(1, total // cores_per_chip)
                chips = float(max(1, slice_chips // n_hosts))
            except ValueError:
                pass
    # TPU_TOPOLOGY ("1x1", "2x4", "2x2x4") counts the chips actually
    # attached SLICE-WIDE; its per-host share wins when SMALLER than
    # either the type-derived count OR the visible-chips list:
    # environments that advertise a slice but attach a sub-slice
    # (GKE subslicing, a one-chip machine cut from a four-chip host)
    # must not over-report — 4 num_tpus=1 tasks would contend for 1
    # real chip (observed: v5litepod-4 type with 1x1 topology = one
    # chip). `clamped` also
    # suppresses the slice-head resource below: a sub-slice is not the
    # advertised slice.
    topology = os.environ.get("TPU_TOPOLOGY", "")
    if topology:
        try:
            import math

            topo_chips = math.prod(
                int(d) for d in topology.lower().split("x")
            )
            per_host = max(1, topo_chips // n_hosts)
            if topo_chips >= 1 and per_host < chips:
                chips = float(per_host)
                clamped = True
        except ValueError:
            pass
    out: ResourceDict = {"TPU": chips}
    if acc_type and not clamped:
        # One head resource per slice: a gang reserves the whole pod by
        # demanding {"TPU-<type>-head": 1}. A CLAMPED node is a
        # sub-slice, not the advertised slice — synthesizing the slice
        # head there would schedule a full-slice gang onto fewer real
        # chips than it demands.
        try:
            worker_id = int(os.environ.get("TPU_WORKER_ID", "0") or 0)
        except ValueError:
            worker_id = 0  # malformed env must not brick node startup
        if worker_id == 0:
            out[f"TPU-{acc_type}-head"] = 1.0
    return out


def detect_tpu_resources() -> ResourceDict:
    """Detect TPU chips on this host. Two sources:

    - the pod environment (TPU_ACCELERATOR_TYPE / TPU_VISIBLE_CHIPS /
      TPU_WORKER_ID — the GKE/GCE contract), which says what the slice IS
      and synthesizes the slice-head resource;
    - JAX (platform "tpu"), which says what this process actually HOLDS,
      without forcing a jax import at package-import time.

    On one host JAX is the truth and the environment a claim: a machine
    cut from a bigger host advertises the whole host (observed on the
    v5e builders: `v5litepod-4`, `TPU_TOPOLOGY=2x2`, one chip attached),
    and four `num_tpus=1` tasks must not be let onto one chip, so the
    claim is clamped to what JAX holds (a clamped node is a sub-slice: no
    slice head). A multi-host slice's other hosts cannot be counted from
    here: the environment stands. Asking JAX makes THIS process the
    chip's owner; a process that must not open the chip says so
    (`--no-tpu`, `detect_accelerators=False`, `num_tpus=`).

    Returns e.g. {"TPU": 4.0, "TPU-v5p-8-head": 1.0} on a v5p host.
    """
    from .config import cfg

    if cfg.force_no_tpu:
        return {}
    env = _pod_env_resources()
    if env is not None and _pod_env_hosts() > 1:
        return env
    import importlib.util

    if importlib.util.find_spec("jax") is None:  # pragma: no cover
        return env or {}
    import jax

    # A backend that fails to come up is raised, not read as "no chip": a
    # machine whose TPU is held by another process, or whose runtime is
    # broken, must not quietly become a CPU node (CPU harnesses say so:
    # detect_accelerators=False / cfg.force_no_tpu).
    devs = [d for d in jax.local_devices() if d.platform == "tpu"]
    if env is not None:
        if devs and len(devs) < env["TPU"]:
            return {"TPU": float(len(devs))}
        return env
    if not devs:
        return {}
    kinds = {getattr(d, "device_kind", "tpu") for d in devs}
    kind = sorted(kinds)[0].replace(" ", "-")
    if kind.startswith("TPU-"):
        kind = kind[len("TPU-"):]
    return {
        "TPU": float(len(devs)),
        f"TPU-{kind}-{len(devs)}-head": 1.0,
    }


def detect_host_memory() -> float:
    """Total host memory in bytes (sysconf; 8 GiB fallback) — the
    reference sizes a node's `memory` resource from the real host too."""
    try:
        return float(os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE"))
    except (ValueError, OSError, AttributeError):  # pragma: no cover
        return float(8 << 30)


def default_node_resources(
    num_cpus: Optional[int] = None,
    num_tpus: Optional[int] = None,
    resources: Optional[ResourceDict] = None,
    detect_accelerators: bool = True,
) -> ResourceDict:
    out: ResourceDict = {}
    out["CPU"] = float(num_cpus if num_cpus is not None else os.cpu_count() or 1)
    if num_tpus is not None:
        out["TPU"] = float(num_tpus)
    elif detect_accelerators:
        out.update(detect_tpu_resources())
    mem = detect_host_memory()
    # 70% schedulable, like the reference's default memory headroom
    out["memory"] = float(int(mem * 0.7))
    out["object_store_memory"] = float(min(int(mem * 0.2), 8 << 30))
    if resources:
        out.update({k: float(v) for k, v in resources.items()})
    return out
