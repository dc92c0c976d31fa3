"""Gluon utilities (ref: python/mxnet/gluon/utils.py)."""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from ..ndarray import NDArray, array


def split_data(data, num_slice, batch_axis=0, even_split=True):
    size = data.shape[batch_axis]
    if even_split and size % num_slice != 0:
        raise MXNetError(
            f"cannot evenly split batch of {size} into {num_slice} slices")
    step = size // num_slice
    slices = []
    for i in range(num_slice):
        begin = i * step
        end = (i + 1) * step if i < num_slice - 1 else size
        if batch_axis == 0:
            slices.append(data[begin:end])
        else:
            from ..ndarray import slice_axis
            slices.append(slice_axis(data, axis=batch_axis, begin=begin,
                                     end=end))
    return slices


def recompute(fn, x):
    """``fn(x)`` for a block or function of one NDArray, rematerialised:
    inside a hybridized block's trace, differentiating the result keeps
    ``x`` (and the parameters ``fn`` reads) alone, and ``fn`` runs again
    in the backward pass (``jax.checkpoint``). What ``fn`` computes in
    between (its float32 intermediates, its products' inputs) is not
    kept. Anywhere else (eager calls, symbolic traces) it is ``fn(x)``.
    ``fn`` must not defer an aux-state update: a value made inside does
    not leave but through the result."""
    import jax

    if not isinstance(x, NDArray) or not isinstance(x._data, jax.core.Tracer):
        return fn(x)
    return NDArray(jax.checkpoint(lambda d: fn(NDArray(d))._data)(x._data))


def split_and_load(data, ctx_list, batch_axis=0, even_split=True):
    """Lay the batch out across the contexts (ref: utils.py
    split_and_load).

    The reference returns one slice per device and runs K separate
    forward/backwards. The TPU-native equivalent is SPMD: the batch is
    placed ONCE, sharded over a 'dp' mesh built from ``ctx_list``, and
    returned as a single-element list — the usual
    ``for x in split_and_load(...)`` loop then runs one XLA program over
    all devices, with the gradient all-reduce inserted by the
    partitioner instead of KVStore Reduce (SURVEY.md §7 design stance).
    """
    if not isinstance(data, NDArray):
        data = array(data)
    if len(ctx_list) == 1:
        return [data.as_in_context(ctx_list[0])]
    from ..context import dp_mesh
    uneven = data.shape[batch_axis] % len(ctx_list) != 0
    if even_split and uneven:
        raise MXNetError(
            f"cannot evenly split batch of {data.shape[batch_axis]} "
            f"across {len(ctx_list)} devices")
    mesh = None if uneven else dp_mesh(ctx_list)
    if mesh is None:
        # repeated devices can't form a mesh, and GSPMD needs the batch
        # axis divisible by the mesh — plain slicing for parity in both
        # cases (the reference's uneven [3,3,2,2]-style slices)
        slices = split_data(data, len(ctx_list), batch_axis, even_split)
        return [s.as_in_context(ctx)
                for s, ctx in zip(slices, ctx_list)]
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(*([None] * batch_axis + ["dp"]))
    return [NDArray(jax.device_put(data._data,
                                   NamedSharding(mesh, spec)))]


def clip_global_norm(arrays, max_norm, check_isfinite=True):
    """Rescale arrays so the joint L2 norm <= max_norm (ref: utils.py)."""
    total = 0.0
    for a in arrays:
        n = float((a * a).sum().asscalar())
        total += n
    total = np.sqrt(total)
    if check_isfinite and not np.isfinite(total):
        import warnings
        warnings.warn("nan or inf found in gradients")
    scale = max_norm / (total + 1e-8)
    if scale < 1.0:
        for a in arrays:
            a *= scale
    return total


def check_sha1(filename, sha1_hash):
    import hashlib
    sha1 = hashlib.sha1()
    with open(filename, "rb") as f:
        while True:
            data = f.read(1048576)
            if not data:
                break
            sha1.update(data)
    return sha1.hexdigest() == sha1_hash


def download(url, path=None, overwrite=False, sha1_hash=None):
    raise MXNetError("this environment has no network egress; place files "
                     "locally and load them directly")
