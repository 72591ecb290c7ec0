"""The one mark ring, and the registry of the planes that ship theirs.

A MARK is a flat dict a recorder writes once: a flight-recorder event
(util/events.py), a request phase mark (serve/reqlog.py), a step phase
mark (train/steplog.py). Each of those planes holds a ``MarkRing`` and
keeps what is its own (a sink, an index, its views); the ring knows
nothing of who uses it.

A plane whose marks the head should answer for cluster-wide registers
itself here at import (``register_federated``): core/cluster.py ships
every registered plane's tail into the plane's GCS namespace and
util/state.py reads the tables back, and neither imports a plane to do
so. A process that never imported a plane's package has no such plane.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional

# One value everywhere in the program, so constants and not flags.
FEDERATE_BATCH = 256   # marks a node ships per plane per stats period
TABLE_CAP = 2000       # marks a node keeps per plane in the GCS table
RING_MARKS = 4096      # a forensics plane's ring (the flight recorder: 10,000)
INDEX_ENTRIES = 1024   # summaries a plane indexes beside its ring


def _default_node() -> Optional[str]:
    """This process's node id hex (util/logs sets it at runtime init):
    what a mark is attributed to unless its writer names another."""
    from . import logs

    return logs._node_hex


class MarkRing:
    """A bounded ring of marks under one lock, numbered by ``seq``.

    ``lock`` is re-entrant and public: a plane holds it around a check
    and the ``append`` that depends on it, or around several appends.
    ``on_append(rec)`` runs under it for every mark, after the stamps:
    how a plane keeps an index or a sink in step with the ring."""

    def __init__(self, capacity: int = RING_MARKS,
                 on_append: Optional[Callable[[Dict[str, Any]], None]] = None):
        self.lock = threading.RLock()
        self._buf: "deque[Dict[str, Any]]" = deque(maxlen=capacity)
        self._seq = 0
        self._on_append = on_append

    def append(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        """Stamp and keep one mark: ``seq`` always; ``ts`` (wall, for
        placing marks of several nodes), ``mono`` (monotonic, for
        intervals inside one process) and ``node`` where the record has
        none. A full ring evicts its oldest mark."""
        node = rec.get("node")
        if node is None:
            node = _default_node()
        with self.lock:
            self._seq += 1
            rec["seq"] = self._seq
            if rec.get("ts") is None:
                rec["ts"] = time.time()
            if rec.get("mono") is None:
                rec["mono"] = time.monotonic()
            rec["node"] = node
            self._buf.append(rec)
            if self._on_append is not None:
                self._on_append(rec)
        return rec

    def since(self, seq: int = 0,
              max_n: Optional[int] = None) -> List[Dict[str, Any]]:
        """The OLDEST max_n marks with seq greater than `seq`, oldest
        first: the federation cursor's walk (it never skips a mark the
        way a tail limit would; a slow shipper takes more periods)."""
        with self.lock:
            return [m for m in self._buf if m["seq"] > seq][:max_n]

    def stats(self) -> Dict[str, int]:
        with self.lock:
            return {"seq": self._seq, "buffered": len(self._buf)}

    def clear(self) -> None:
        """Drop the buffered marks; ``seq`` goes on counting, so a
        federation cursor stays valid."""
        with self.lock:
            self._buf.clear()


class FederatedPlane(NamedTuple):
    name: str                       # the key in a node's federation_lag
    namespace: str                  # the GCS KV namespace of its table
    ring: Callable[[], MarkRing]    # the process's ring, made on first use
    enabled: Callable[[], bool]     # False: nothing ships, no lag reported


_planes: Dict[str, FederatedPlane] = {}


def register_federated(name: str, namespace: str,
                       ring_getter: Callable[[], MarkRing],
                       enabled: Callable[[], bool] = lambda: True) -> None:
    """Called by a plane's module at import (idempotent by name)."""
    _planes[name] = FederatedPlane(name, namespace, ring_getter, enabled)


def federated_planes() -> List[FederatedPlane]:
    """The planes this process has loaded, in registration order."""
    return list(_planes.values())
