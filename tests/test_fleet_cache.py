"""The prepared fleet ``run_fsi`` keeps across calls (``faas/fleet_cache``).

A second call on the same net and partition finds the fleet; its output,
metrics, bill and wire bytes are bit-identical to a cold call's; a new net,
partition, ``P``, backend configuration or mesh prepares anew; and a net
that dies takes its entry with it.  On the numpy backends and on
``pallas-bsr-sharded`` (the Pallas interpreter off a TPU)."""

from __future__ import annotations

import dataclasses
import gc
import types

import numpy as np
import pytest

from repro.core.partitioner import partition_network
from repro.data.graphchallenge import make_inputs, make_sparse_dnn
from repro.faas import fleet_cache
from repro.faas.simulator import (
    FaultPlan,
    clear_fleet_cache,
    fleet_cache_info,
    run_fsi,
)

N, LAYERS, P, BATCH = 128, 4, 4, 8
BACKENDS = ["numpy-csr", "numpy-fast", "pallas-bsr-sharded"]


@pytest.fixture(autouse=True)
def _empty_cache():
    clear_fleet_cache()
    yield
    clear_fleet_cache()


def _net(seed: int = 0):
    return make_sparse_dnn(N, n_layers=LAYERS, seed=seed)


def _x0():
    return make_inputs(N, BATCH, seed=1)


def _call(net, backend="numpy-fast", **kw):
    # A random partition: the hypergraph partitioner cuts this small net
    # nowhere, and then no worker sends.
    kw.setdefault("P", P)
    kw.setdefault("partition_method", "random")
    if backend == "pallas-bsr-sharded":
        pytest.importorskip("jax")
    return run_fsi(net, _x0(), memory_mb=2000, compute_backend=backend, **kw)


def _assert_same(a, b):
    np.testing.assert_array_equal(a.output, b.output)
    assert a.metrics == b.metrics
    assert a.cost == b.cost
    assert a.wire_exchange_bytes == b.wire_exchange_bytes
    assert a.raw_exchange_bytes == b.raw_exchange_bytes
    np.testing.assert_array_equal(a.worker_times, b.worker_times)


@pytest.mark.parametrize("backend", BACKENDS)
def test_second_call_hits(backend):
    net = _net()
    first = _call(net, backend)
    assert fleet_cache_info()[:2] == (0, 1)
    again = _call(net, backend)
    info = fleet_cache_info()
    assert (info.hits, info.misses, info.entries) == (1, 1, 1)
    assert again.partition is first.partition


@pytest.mark.parametrize("backend", ["numpy-fast", "pallas-bsr-sharded"])
@pytest.mark.parametrize("path", [
    {"channel": "queue"},
    {"channel": "object"},
    {"channel": "auto"},
    {"channel": "object", "faults": FaultPlan(kills=((1, 2, "send"),))},
], ids=["queue", "object", "auto", "faults"])
def test_hit_is_bit_identical_to_cold(backend, path):
    net = _net()
    cold = _call(net, backend, **path)
    hit = _call(net, backend, **path)
    assert fleet_cache_info().hits == 1
    clear_fleet_cache()
    recold = _call(net, backend, **path)
    assert fleet_cache_info()[:2] == (0, 1)
    _assert_same(hit, cold)
    _assert_same(hit, recold)


def test_passed_partition_hits_and_mesh_call_hits():
    """The benchmark's pattern: one partition object and one mesh, passed
    on every call; ``with_mesh`` builds a new backend each time."""
    pytest.importorskip("jax")
    from repro.launch.mesh import make_worker_mesh

    net = _net()
    part = partition_network(net.layers, P, method="random", seed=0)
    mesh = make_worker_mesh(1)
    x0 = _x0()
    runs = [run_fsi(net, x0, P=P, memory_mb=2000, partition=part, mesh=mesh,
                    compute_backend="pallas-bsr-sharded") for _ in range(3)]
    assert fleet_cache_info()[:2] == (2, 1)
    for r in runs[1:]:
        _assert_same(r, runs[0])


@pytest.mark.parametrize("change", [
    "net", "layer_array", "partition", "partition_seed", "P", "backend",
    "backend_config",
])
def test_new_preparation_misses(change):
    pytest.importorskip("jax")
    from repro.core.backends import PallasBsrShardedBackend

    net = _net()
    part = partition_network(net.layers, P, method="random", seed=0)
    base = dict(partition=part, backend=PallasBsrShardedBackend())
    _call(net, **base)
    kw = dict(base)
    if change == "net":
        net = dataclasses.replace(net, layers=list(net.layers))
    elif change == "layer_array":
        net.layers[1] = dataclasses.replace(net.layers[1],
                                            data=net.layers[1].data.copy())
    elif change == "partition":
        kw["partition"] = partition_network(net.layers, P, method="random",
                                            seed=0)
    elif change == "partition_seed":
        kw.pop("partition")
        _call(net, backend=kw["backend"])           # (random, 0): a miss
        kw["seed"] = 1
    elif change == "P":
        kw.update(P=2, partition=partition_network(net.layers, 2,
                                                   method="random", seed=0))
    elif change == "backend":
        kw["backend"] = "numpy-fast"
    else:
        kw["backend"] = PallasBsrShardedBackend(dispatch="vmap")
    misses = fleet_cache_info().misses
    _call(net, **kw)
    info = fleet_cache_info()
    assert (info.hits, info.misses) == (0, misses + 1)


def test_mesh_devices_and_axis_are_in_the_key():
    """Meshes that differ only in their devices, or in the axis name, key
    different fleets (stand-in meshes: one host device suffices)."""
    pytest.importorskip("jax")
    from repro.core.backends import PallasBsrShardedBackend

    def mesh(dev_id, axis="worker"):
        return types.SimpleNamespace(
            shape={axis: 1},
            devices=np.array([types.SimpleNamespace(id=dev_id)]))

    net = _net()
    key = lambda be: fleet_cache.fleet_key(  # noqa: E731
        net, P, None, "random", 0, be)
    a = key(PallasBsrShardedBackend(mesh=mesh(0)))
    assert a == key(PallasBsrShardedBackend(mesh=mesh(0)))
    assert a != key(PallasBsrShardedBackend(mesh=mesh(1)))
    assert a != key(PallasBsrShardedBackend(mesh=mesh(0, "w"), axis_name="w"))


def test_dropping_the_net_frees_its_entry():
    def run_and_drop():
        net = _net()
        _call(net)
        assert fleet_cache_info().entries == 1

    run_and_drop()
    gc.collect()
    assert fleet_cache_info().entries == 0


def test_bounded_and_serial_untouched():
    net = _net()
    for p in range(2, 3 + fleet_cache.MAXSIZE):
        _call(net, P=p)
    info = fleet_cache_info()
    assert (info.misses, info.entries) == (fleet_cache.MAXSIZE + 1,
                                           fleet_cache.MAXSIZE)
    _call(net, P=1)
    _call(net, channel="serial")
    assert fleet_cache_info() == info
    _call(net, P=2)                                   # evicted first
    assert fleet_cache_info().misses == info.misses + 1
