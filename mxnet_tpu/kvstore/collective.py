"""Collective data plane for dist KVStore — XLA collectives over ICI/DCN.

This is SURVEY.md §5.8's north-star contract: dense `dist_device_sync`
does NOT bounce tensors through a parameter server — every push is an
in-step all-reduce across the multi-process device mesh, compiled by XLA
onto ICI (intra-slice) / DCN (cross-slice) exactly like the reference's
`dist_device_sync` aggregates on GPUs over NCCL instead of on the PS
(ref: src/kvstore/kvstore_dist.h comm_ device reduce; kvstore.cc:55).

Process bootstrap rides `jax.distributed`: the launcher (tools/launch.py)
exports DMLC_PS_ROOT_URI/PORT + DMLC_NUM_WORKER + DMLC_WORKER_ID, and
worker 0's jax coordination service doubles as the rendezvous — no
server processes at all (launch with `-s 0`).
"""
from __future__ import annotations

import os
import threading

import numpy as np

from ..base import MXNetError

_lock = threading.Lock()
_instance = None


class CollectiveConn:
    """Per-process singleton wrapping the jax.distributed global mesh."""

    def __init__(self):
        import jax

        uri = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT")
        n = int(os.environ.get("DMLC_NUM_WORKER", "1"))
        rank = int(os.environ.get("DMLC_WORKER_ID", "0"))
        # check the distributed-runtime state WITHOUT touching the XLA
        # backend (jax.process_count() would initialize it and make a
        # late jax.distributed.initialize impossible)
        if n > 1 and not jax.distributed.is_initialized():
            if not (uri and port):
                raise MXNetError(
                    "collective kvstore needs DMLC_PS_ROOT_URI/PORT (set "
                    "by tools/launch.py) or a pre-initialized "
                    "jax.distributed runtime")
            try:
                jax.distributed.initialize(
                    coordinator_address=f"{uri}:{port}",
                    num_processes=n, process_id=rank)
            except RuntimeError as e:
                raise MXNetError(
                    "cannot join the collective mesh: the XLA backend was "
                    "already initialized before the dist kvstore was "
                    "created. Import mxnet_tpu with the DMLC_* launcher "
                    "env set (tools/launch.py -s 0 does this), so the "
                    "mesh forms at import time.") from e
        self.rank = jax.process_index()
        self.num_workers = jax.process_count()
        # one representative device per process forms the reduce mesh;
        # XLA routes the collective over ICI/DCN between them. (Per-host
        # multi-device replicas are already reduced by the in-step psum
        # of the SPMD executor before a kvstore push.)
        per_proc = {}
        for d in jax.devices():
            per_proc.setdefault(d.process_index, d)
        self._mesh_devices = np.array(
            [per_proc[p] for p in sorted(per_proc)])
        from jax.sharding import Mesh
        self._mesh = Mesh(self._mesh_devices, ("proc",))
        self._reducers = {}
        self._jax = jax

    @classmethod
    def get(cls):
        global _instance
        with _lock:
            if _instance is None:
                _instance = cls()
            return _instance

    def _reducer(self, shape, dtype):
        key = (shape, str(dtype))
        if key not in self._reducers:
            import jax
            import jax.numpy as jnp

            # the device-group plumbing is the layout plane's one
            # spelling (parallel/layout.collective_shardings): stacked
            # worker slices in, replicated reduction out — the same
            # vocabulary the train-step and serving placements read
            from ..parallel.layout import collective_shardings
            in_sh, out_sh = collective_shardings(self._mesh)
            self._reducers[key] = (
                in_sh,
                jax.jit(lambda x: jnp.sum(x, axis=0),
                        out_shardings=out_sh))
        return self._reducers[key]

    def allreduce(self, value):
        """Sum `value` across all worker processes; returns numpy.

        One global array is formed with a leading process axis and
        reduced with out_shardings=replicated — XLA lowers this to an
        all-reduce over the mesh links (the literal psum-over-ICI the
        survey prescribes). Reduction runs in the value's own dtype;
        64-bit payloads are reduced under enable_x64 (jax's default
        canonicalization would silently truncate them to 32 bits)."""
        local = np.asarray(value)
        if local.dtype.itemsize == 8:
            with self._jax.enable_x64(True):
                in_sh, reduce_fn = self._reducer(local.shape, local.dtype)
                garr = self._jax.make_array_from_process_local_data(
                    in_sh, local[None],
                    (self.num_workers,) + local.shape)
                return np.asarray(reduce_fn(garr))
        in_sh, reduce_fn = self._reducer(local.shape, local.dtype)
        garr = self._jax.make_array_from_process_local_data(
            in_sh, local[None],
            (self.num_workers,) + local.shape)
        return np.asarray(reduce_fn(garr))

    def broadcast(self, value, root=0):
        """Value from `root` replicated to every process (reference
        kvstore Init semantics: rank 0 seeds, everyone pulls)."""
        local = np.asarray(value)
        if self.rank != root:
            local = np.zeros_like(local)
        return self.allreduce(local)

    def barrier(self):
        """BSP fence: a 1-element all-reduce every process must join."""
        self.allreduce(np.zeros((1,), np.float32))
