"""The prepared fleet of a net, kept across ``run_fsi`` calls.

Partitioning, the comm plans, the worker artifacts (with the compute
backend's padded operands) and the fleet's device panels depend only on the
net, ``P``, the partition and the backend — the paper's a-priori offline
work.  ``run_fsi`` prepares them on the first call and finds them again on
every later call with the same:

* net object, and the identity of each layer's ``indptr`` / ``indices`` /
  ``data`` arrays;
* ``P``;
* partition: the passed ``PartitionResult`` object, or
  ``(partition_method, seed)`` when ``run_fsi`` partitions the net itself;
* backend class and ``state_key``, and for a mesh backend the mesh's axis
  name and flat device ids (``with_mesh`` builds a new backend per call, so
  the backend object itself is no key).

Contract: a net's layer arrays are not mutated in place once it has run,
the same as model weights.  Replace an array (or build a new net) and the
next call prepares anew.  Nothing cached depends on a call's inputs, batch,
channel or latency model.

An entry dies with its net (``weakref.finalize``) and holds the keyed
arrays and the partition, so no id in a live key is reused.  At most
``MAXSIZE`` entries stay, least recently used first out.
"""

from __future__ import annotations

import collections
import dataclasses
import weakref
from typing import Any, List, NamedTuple, Optional

__all__ = ["PreparedFleet", "FleetCacheInfo", "fleet_key", "lookup", "store",
           "clear_fleet_cache", "fleet_cache_info"]

MAXSIZE = 4


@dataclasses.dataclass
class PreparedFleet:
    """What ``run_fsi`` prepares once per net: read-only on every call."""

    partition: Any                  # PartitionResult
    plans: List[Any]                # LayerCommPlan per layer
    artifacts: List[Any]            # WorkerArtifacts per worker
    fleet_states: Optional[List[Any]]  # device panels per layer, or None
    imbalance: float
    keep: tuple = ()                # the keyed layer arrays, alive with the entry
    finalizer: Optional[weakref.finalize] = dataclasses.field(
        default=None, repr=False)


class FleetCacheInfo(NamedTuple):
    hits: int
    misses: int
    maxsize: int
    entries: int


_entries: "collections.OrderedDict[tuple, PreparedFleet]" = \
    collections.OrderedDict()
_hits = 0
_misses = 0


def fleet_key(net, P: int, partition, partition_method: str, seed: int,
              backend) -> tuple:
    """The key of ``net``'s prepared fleet for one ``run_fsi`` call."""
    layers = tuple((id(W.indptr), id(W.indices), id(W.data))
                   for W in net.layers)
    part = (("given", id(partition)) if partition is not None
            else (partition_method, seed))
    mesh = None
    if hasattr(backend, "with_mesh"):
        m = backend.mesh
        mesh = (backend.axis_name, tuple(int(d.id) for d in m.devices.flat))
    return (id(net), layers, int(P), part, type(backend),
            getattr(backend, "state_key", backend.name), mesh)


def lookup(key: tuple) -> Optional[PreparedFleet]:
    """The prepared fleet under ``key``, counted as a hit or a miss."""
    global _hits, _misses
    fleet = _entries.get(key)
    if fleet is None:
        _misses += 1
        return None
    _hits += 1
    _entries.move_to_end(key)
    return fleet


def store(key: tuple, net, fleet: PreparedFleet) -> PreparedFleet:
    """Keep ``fleet`` under ``key`` until ``net`` dies, the entry is
    evicted, or the cache is cleared.  A net that takes no weak reference
    is never kept."""
    try:
        fin = weakref.finalize(net, _entries.pop, key, None)
    except TypeError:
        return fleet
    fin.atexit = False
    fleet.keep = tuple(a for W in net.layers
                       for a in (W.indptr, W.indices, W.data))
    fleet.finalizer = fin
    _entries[key] = fleet
    while len(_entries) > MAXSIZE:
        _, evicted = _entries.popitem(last=False)
        evicted.finalizer.detach()
    return fleet


def clear_fleet_cache() -> None:
    """Drop every prepared fleet and zero the counts."""
    global _hits, _misses
    for fleet in _entries.values():
        fleet.finalizer.detach()
    _entries.clear()
    _hits = _misses = 0


def fleet_cache_info() -> FleetCacheInfo:
    """Hits, misses, the bound and the entries held, as ``lru_cache`` has."""
    return FleetCacheInfo(_hits, _misses, MAXSIZE, len(_entries))
