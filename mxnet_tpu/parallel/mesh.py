"""Device-mesh construction and sharding helpers.

The mesh is the TPU analogue of the reference's device topology handling
(src/kvstore/gpu_topology.h computes reduce trees from the PCIe/NVLink
link matrix) — on TPU the ICI torus topology is XLA's problem; we only
name the axes and choose their sizes.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def create_mesh(axes=None, devices=None):
    """Build a `jax.sharding.Mesh`.

    Parameters
    ----------
    axes : dict[str, int] | None
        Ordered mapping of axis name -> size, e.g. ``{"dp": 2, "tp": 4}``.
        ``-1`` for at most one axis means "all remaining devices".
        Default: all devices on a single ``"dp"`` axis.
    devices : sequence of jax devices, optional
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if axes is None:
        axes = {"dp": n}
    axes = dict(axes)
    known = [s for s in axes.values() if s != -1]
    wild = [k for k, s in axes.items() if s == -1]
    if len(wild) > 1:
        raise ValueError("at most one axis may be -1")
    prod = math.prod(known) if known else 1
    if wild:
        if n % prod:
            raise ValueError(f"{n} devices not divisible by {prod}")
        axes[wild[0]] = n // prod
        prod = n
    if prod != n:
        raise ValueError(f"mesh {axes} needs {prod} devices, have {n}")
    arr = np.array(devices).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


def auto_mesh_shape(n, axis_names=("dp", "tp", "sp")):
    """Factor `n` devices over the given axes, biggest axis first.

    Used by dry-run harnesses to get a non-trivial multi-axis mesh out of
    any device count: 8 -> {"dp": 2, "tp": 2, "sp": 2}, 4 -> {"dp": 2,
    "tp": 2, "sp": 1}, 6 -> {"dp": 3, "tp": 2, "sp": 1}.
    """
    shape = {a: 1 for a in axis_names}
    names = list(axis_names)
    i = 0
    rem = n
    while rem > 1:
        # smallest prime factor of rem goes to the current axis
        f = next((p for p in range(2, int(rem ** 0.5) + 1) if rem % p == 0),
                 rem)
        shape[names[i % len(names)]] *= f
        rem //= f
        i += 1
    return shape


def mesh_sharding(mesh, *spec):
    """`NamedSharding(mesh, PartitionSpec(*spec))` shorthand."""
    return NamedSharding(mesh, P(*spec))


# re-exported so in-repo call sites (parallel/, bench, tools, tests)
# spell it ``parallel.shard_map(f, mesh=, in_specs=, out_specs=,
# check_vma=)``
shard_map = jax.shard_map


def replica_devices(n, devices=None, exclude=()):
    """Device assignment for ``n`` replicas (serving lanes, ensemble
    members), degrading gracefully when the local mesh is smaller than
    asked — the SNIPPETS [2] mesh-shape fallback applied to a 1-D
    replica axis: replicas wrap around the available devices, so the
    same registration code serves a pod slice and a single chip.

    ``exclude`` removes devices already committed elsewhere (the
    gateway passes the union of its tp mesh-slice devices): wrapped
    lanes place on what remains, and only when NOTHING remains do
    they fall back onto the excluded set — with ``degraded`` forced
    True, so a replicated lane can never silently share a device
    with a tp slice (the overlap is always flagged).

    Returns ``(devices_list, degraded)`` where ``degraded`` is True
    when replicas had to share devices (with each other or with the
    excluded set)."""
    devs = list(devices if devices is not None else jax.local_devices())
    if not devs:
        raise ValueError("replica_devices: no local devices")
    excluded = {str(d) for d in exclude}
    pool = [d for d in devs if str(d) not in excluded]
    if not pool:
        # every device is held by a slice: serve anyway (degrade, do
        # not refuse), but the overlap is explicit in the flag
        return [devs[i % len(devs)] for i in range(n)], True
    return [pool[i % len(pool)] for i in range(n)], n > len(pool)


def replica_slices(n, tp, devices=None, exclude=()):
    """`replica_devices` generalized to mesh *slices*: ``n`` replica
    lanes of ``tp`` devices each — each slice hosts one tp-sharded
    SPMD program (a model bigger than one chip), carved from disjoint
    contiguous runs of the device list. The layout plane's serving
    placement: slices never overlap each other or ``exclude`` unless
    the returned ``degraded`` flag says so.

    Returns ``(slices, degraded)`` — ``slices`` a list of ``n``
    tuples of ``tp`` DISTINCT devices (a mesh cannot repeat a
    device); ``degraded`` True when slices had to share devices.
    Raises when even one slice cannot be formed from distinct
    devices."""
    n, tp = int(n), int(tp)
    if n < 1 or tp < 1:
        raise ValueError(
            f"replica_slices: need n >= 1 slices of tp >= 1 devices, "
            f"got n={n}, tp={tp}")
    devs = list(devices if devices is not None else jax.local_devices())
    excluded = {str(d) for d in exclude}
    pool = [d for d in devs if str(d) not in excluded]
    degraded = False
    if len(pool) < tp:
        # cannot carve even one slice from the free pool: fall back
        # to the full device list (flagged), or refuse when the host
        # genuinely has fewer devices than one slice needs
        if len(devs) < tp:
            raise ValueError(
                f"replica_slices: cannot carve a tp={tp} slice from "
                f"{len(devs)} device(s) — a mesh cannot repeat a "
                "device")
        pool = devs
        degraded = True
    slices = []
    for i in range(n):
        start = i * tp
        if start + tp <= len(pool):
            slices.append(tuple(pool[start:start + tp]))
        else:
            # wrap: slices start sharing devices — degraded by
            # definition (each slice still holds tp DISTINCT devices)
            degraded = True
            slices.append(tuple(pool[(start + j) % len(pool)]
                                for j in range(tp)))
    return slices, degraded


def free_pool(devices=None, held=()):
    """The devices NOT named in ``held`` (string identity, order
    preserved) — the cluster plane's view of what a workload may place
    on: the gateway filters its base pool by the DeviceLedger's
    foreign holdings before picking lanes, so the ``exclude=``
    discipline above extends across workloads, not just across this
    gateway's own slices."""
    devs = list(devices if devices is not None else jax.local_devices())
    held_names = {str(d) for d in held}
    return [d for d in devs if str(d) not in held_names]


# degraded-wrap warnings already emitted, keyed (ask, devices): the
# serving autoscaler re-enters replica_devices on EVERY scale event,
# and a per-call warning for the same unchanged wrap is log spam, not
# signal — each distinct (ask, devices) combination warns exactly once
_DEGRADE_WARNED = set()


def should_warn_degraded(n, devices):
    """True exactly once per (ask, devices) combination — callers that
    log the degraded-wrap warning (serving gateway, autoscaler) gate on
    this so a scale storm cannot re-log the same degradation."""
    key = (int(n), tuple(str(d) for d in devices))
    if key in _DEGRADE_WARNED:
        return False
    _DEGRADE_WARNED.add(key)
    return True


def _reset_degrade_warnings():
    """Test hook: forget which (ask, devices) wraps already warned."""
    _DEGRADE_WARNED.clear()


def shard_batch(batch, mesh, axis="dp"):
    """Place a host batch onto the mesh, sharded along the leading dim.

    The TPU equivalent of `DataParallelExecutorGroup.decide_slices`
    (ref: python/mxnet/module/executor_group.py:281-310): instead of
    slicing per-context copies, one `device_put` with a NamedSharding
    splits the batch across the `dp` axis and replicates it over the
    others.
    """
    sh = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), batch)
