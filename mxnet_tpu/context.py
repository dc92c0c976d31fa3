"""Device context — the seam where ``mx.tpu()`` lives.

TPU-native analogue of the reference Context (ref: include/mxnet/base.h:133-159,
python/mxnet/context.py). Device types: cpu, gpu (alias kept for API parity),
tpu (the native accelerator of this framework). A Context resolves to a concrete
``jax.Device``; under the virtual CPU mesh used by tests, ``tpu(i)`` resolves to
the i-th default-backend device so the same model code runs everywhere.
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

_context_stack = threading.local()


class Context:
    """Execution device. Use via mx.cpu() / mx.gpu() / mx.tpu()."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 5: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type = device_type.device_type
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_type = device_type
            self.device_id = device_id

    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- resolution to a concrete jax device ------------------------------
    @property
    def jax_device(self):
        """Resolve to a jax.Device.

        ``tpu``/``gpu`` map onto the default accelerator backend; when that
        backend is absent (e.g. CPU-only test runs with a virtual mesh) they
        fall back to the default platform so models are device-portable.
        """
        devs = jax.devices()
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                cpus = jax.devices("cpu")
            except RuntimeError:
                cpus = devs
            return cpus[min(self.device_id, len(cpus) - 1)]
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self}: only {len(devs)} device(s) visible to the runtime"
            )
        return devs[self.device_id]

    def empty_cache(self):
        # PJRT owns the device allocator; nothing to flush explicitly.
        return


    @classmethod
    def default_ctx(cls):
        if not hasattr(cls._default_ctx, "value"):
            cls._default_ctx.value = Context("cpu", 0)
        return cls._default_ctx.value

    def __enter__(self):
        if not hasattr(_context_stack, "stack"):
            _context_stack.stack = []
        _context_stack.stack.append(Context.default_ctx())
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = _context_stack.stack.pop()
        return False


def dp_mesh(ctx_list):
    """A 1-axis 'dp' Mesh over a context list, or None when the entries
    don't resolve to distinct jax devices (cpu(0) listed twice,
    oversubscribed ids). Shared by Module binding and gluon
    split_and_load so both agree on what forms a data-parallel mesh."""
    import numpy as np
    from jax.sharding import Mesh

    devices = [Context(c).jax_device for c in ctx_list]
    if len(set(devices)) != len(devices):
        return None
    return Mesh(np.array(devices), ("dp",))


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    return Context("gpu", device_id)


def tpu(device_id=0):
    """The native accelerator context of this framework (north star: mx.tpu())."""
    return Context("tpu", device_id)


def current_context():
    return Context.default_ctx()


def num_gpus():
    try:
        return len(jax.devices("gpu"))
    except RuntimeError:
        return 0


def num_tpus():
    try:
        return len(jax.devices("tpu"))
    except RuntimeError:
        return 0


def ctx_from_jax_device(dev):
    plat = getattr(dev, "platform", "cpu")
    if plat == "cpu":
        return Context("cpu", dev.id)
    if plat == "gpu":
        return Context("gpu", dev.id)
    return Context("tpu", dev.id)
