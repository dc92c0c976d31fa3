"""Distributed parallelism for TPU meshes.

This package is the TPU-native answer to the reference's entire
distribution stack (SURVEY.md §2.3): KVStore local/device/dist_sync
(src/kvstore/comm.h, kvstore_nccl.h, kvstore_dist.h) collapse into XLA
collectives over a `jax.sharding.Mesh` — psum over ICI inside the jitted
step replaces NCCL allreduce and the ps-lite push/pull hop. On top of the
reference's data-parallel + manual-model-parallel grid, this adds the
parallelism kinds the reference lacks (SURVEY.md §2.3 item 7): tensor
parallelism, sequence/context parallelism (ring attention + Ulysses
all-to-all), expert parallelism, and pipeline parallelism — all SPMD over
named mesh axes.

Two composition styles, used where each is idiomatic:

- **GSPMD**: `jit` with `NamedSharding` annotations on params/data; XLA
  inserts the collectives (train_step.py). This is the scaling-book
  recipe: pick a mesh, annotate, let the compiler do layout.
- **shard_map**: explicit per-device programs with hand-placed
  `ppermute`/`all_to_all`/`psum` where the communication schedule IS the
  algorithm (ring attention, MoE dispatch, pipeline).
"""
from .mesh import (create_mesh, auto_mesh_shape, mesh_sharding,
                   replica_devices, replica_slices, shard_batch,
                   shard_map)
from .layout import (SpecLayout, collective_shardings, dryrun_report,
                     zero_shard_leaf)
from .collectives import (allreduce, allgather, alltoall, axis_index,
                          axis_size, ppermute_next, reduce_scatter)
from .ring_attention import ring_attention
from .ulysses import ulysses_attention
from .tensor_parallel import (column_parallel_dense, row_parallel_dense,
                              tp_mlp)
from .pipeline import pipeline_apply
from .moe import experts_held, moe_ffn, moe_ffn_ep, route
from .train_step import (make_sharded_train_step,
                         make_zero_train_step, sgd_update)

__all__ = [
    "create_mesh", "auto_mesh_shape", "mesh_sharding", "shard_batch",
    "shard_map", "replica_devices", "replica_slices",
    "SpecLayout", "collective_shardings", "dryrun_report",
    "zero_shard_leaf",
    "allreduce", "allgather", "alltoall", "axis_index", "axis_size",
    "ppermute_next", "reduce_scatter",
    "ring_attention", "ulysses_attention",
    "column_parallel_dense", "row_parallel_dense", "tp_mlp",
    "pipeline_apply", "route", "experts_held", "moe_ffn", "moe_ffn_ep",
    "make_sharded_train_step", "make_zero_train_step", "sgd_update",
]
