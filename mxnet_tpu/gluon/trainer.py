"""Trainer (ref: python/mxnet/gluon/trainer.py:27).

Applies an Optimizer to a set of Parameters. The reference wires kvstore
Reduce/Broadcast between devices; here single-host multi-device DP runs
through the sharded jit step (parallel.data_parallel) and the kvstore seam is
kept for the update_on_kvstore policy and the dist/sparse paths.
"""
from __future__ import annotations

from .. import optimizer as opt_mod
from .. import tracing as _tracing
from ..base import MXNetError
from ..telemetry import step as _tm_step
from .parameter import Parameter, ParameterDict


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be a list/dict of Parameters")
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError(f"invalid parameter {p}")
            self._param2idx[p.name] = i
            self._params.append(p)
        optimizer_params = optimizer_params or {}
        self._scale = optimizer_params.get("rescale_grad", 1.0)
        self._optimizer = opt_mod.create(optimizer, param_idx2name={
            i: p.name for i, p in enumerate(self._params)},
            **optimizer_params)
        self._updaters = opt_mod.get_updater(self._optimizer)
        self._kvstore_type = kvstore
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._compression_params = compression_params
        self._kv_initialized = False
        self._params_to_init = list(self._params)

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _init_kvstore(self):
        from ..kvstore.kvstore import KVStore as _KVStore
        if isinstance(self._kvstore_type, _KVStore):
            # the reference accepts a ready KVStore instance as well as a
            # type string (gluon/trainer.py _init_kvstore)
            self._kvstore = self._kvstore_type
        elif self._kvstore_type and self._kvstore_type != "None" and \
                str(self._kvstore_type).startswith("dist"):
            from .. import kvstore as kv_mod
            self._kvstore = kv_mod.create(self._kvstore_type)
        if self._kvstore is not None:
            if self._compression_params:
                self._kvstore.set_gradient_compression(
                    self._compression_params)
            if self._update_on_kvstore is None:
                self._update_on_kvstore = True
            if self._update_on_kvstore:
                self._kvstore.set_optimizer(self._optimizer)
            for i, p in enumerate(self._params):
                if p._data is not None:
                    self._kvstore.init(i, p.data())
                    if not self._update_on_kvstore:
                        # worker-side updates never pull params from the
                        # store afterwards, so the init broadcast (rank
                        # 0's values) must land here or replicas diverge
                        # from their own random inits (ref: trainer.py
                        # _init_params pulls after init)
                        self._kvstore.pull(i, out=p.data())
        self._kv_initialized = True

    def step(self, batch_size, ignore_stale_grad=False):
        """rescale by 1/batch_size, allreduce (mesh DP: already summed by
        psum in the sharded step), update."""
        # rescale BEFORE kvstore init: update_on_kvstore pickles the
        # optimizer to the server on first step, and the server must see
        # the batch scaling or dist updates explode by batch_size
        self._optimizer.rescale_grad = self._scale / batch_size
        # root span per optimizer step: the comm/compute children under
        # it are what trace_merge's straggler report groups by step
        n = self._step_count = getattr(self, "_step_count", -1) + 1
        from ..profiling import health as _health
        with _tracing.span("trainer_step", cat="step", step=n) as sp:
            try:
                if not self._kv_initialized:
                    self._init_kvstore()
                self._sync_server_rescale()
                self._allreduce_grads()
                self._update(ignore_stale_grad)
            except Exception as e:
                # an allocation failure mid-step leaves the combined
                # memory postmortem (ranked buffers + census + flight
                # dump) before propagating
                from ..profiling import memory as _mem
                _mem.maybe_oom_postmortem(e, source="trainer_step")
                raise
            # health boundary INSIDE the step span: lagged loss-EWMA /
            # grad-norm / nonfinite attrs land on the span so
            # trace_merge can name the rank that went unhealthy; a
            # MXTPU_HEALTH=raise trip surfaces here, at the boundary.
            # Its one read-back, of the previous step's probe table,
            # is also all that keeps this loop from running more than
            # a step ahead of the device
            with _tracing.span("trainer.health"):
                _health.step_boundary("trainer", span=sp)
        # one boundary per optimizer step: charges the data/comm/compile
        # time accumulated since the previous step to this one
        # (telemetry/step.py; wall-clock only, no host sync). Manual
        # loops with long gaps between steps (eval phases, user pauses)
        # should call telemetry.step.reset() at loop start so the first
        # interval doesn't span the gap — Module.fit does this per epoch
        _tm_step.step_boundary("trainer")

    def _sync_server_rescale(self):
        """Re-ship the optimizer when the batch scale changes after the
        first step (e.g. a short final batch) — the server-side updater
        would otherwise keep applying the stale rescale_grad."""
        if self._kvstore is None or not self._update_on_kvstore:
            return
        shipped = getattr(self, "_shipped_rescale", None)
        if shipped is None:
            self._shipped_rescale = self._optimizer.rescale_grad
        elif shipped != self._optimizer.rescale_grad:
            self._kvstore.set_optimizer(self._optimizer)
            self._shipped_rescale = self._optimizer.rescale_grad

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is not None and not self._update_on_kvstore:
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    self._kvstore.push(i, p.grad())
                    self._kvstore.pull(i, p.grad())

    def update(self, batch_size, ignore_stale_grad=False):
        self._optimizer.rescale_grad = self._scale / batch_size
        if not self._kv_initialized:
            self._init_kvstore()
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        from ..profiling import health as _health
        # one probe per step: the post-allreduce gradients, updated
        # weights and (for update-to-weight ratios) the pre-update
        # weights — updates are functional and donate no weight, so the
        # old array stays reachable with no copy. commit() is ONE cached
        # jitted dispatch covering the sentry counts AND the norm
        # telemetry, read back by the next step's boundary; the
        # per-index Updater check is suppressed underneath it.
        probe = _health.step_probe()
        with _tracing.span("trainer.update"), _health.updater_covered():
            live = []
            for i, p in enumerate(self._params):
                if p.grad_req == "null":
                    continue
                if p._data is None:
                    if not ignore_stale_grad:
                        raise MXNetError(
                            f"parameter {p.name} not initialized "
                            "before step()")
                    continue
                live.append((i, p))
            # pre-update weights only when the probe computes update
            # ratios: with MXTPU_HEALTH_NORMS=0 holding them would pin
            # a superseded copy of every weight for nothing
            olds = [p.data()._data for _, p in live] \
                if probe is not None and probe.wants_norms else None
            if self._kvstore is not None and self._update_on_kvstore:
                for i, p in live:
                    self._kvstore.push(i, p.grad())
                    self._kvstore.pull(i, p.data())
            else:
                # the whole tree in one optimizer program; what cannot
                # fuse goes per index inside (Updater.update_tree)
                self._updaters.update_tree(
                    [(i, p.grad(), p.data()) for i, p in live])
            if probe is not None:
                for (_, p), old in zip(live, olds or [None] * len(live)):
                    probe.add(p.name, p.data(), p.grad(), weight_before=old)
        if probe is not None:
            # what the default-on health plane adds to the step: one
            # more jitted program over every (weight, gradient) pair
            with _tracing.span("trainer.health"):
                probe.commit()

    def save_states(self, fname):
        """Optimizer state checkpoint (ref: trainer.py save_states). When the
        optimizer runs on the kvstore, the live state is the kvstore's
        Updater, not the local one. Written atomically with a CRC
        manifest entry (checkpoint.atomic_write) so a preemption
        mid-write can never leave a torn .states file."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is not None and self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=False)
        else:
            from ..checkpoint import atomic_write
            with atomic_write(fname) as f:
                f.write(self._updaters.get_states())

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is not None and self._update_on_kvstore:
            # no verify here: kvstore.load_optimizer_states CRC-checks
            # the same file — doing it twice doubles the resume I/O
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
            # mirror the loaded state into the LOCAL updater too: a
            # later fall back to local update (kvstore torn down,
            # update_on_kvstore flipped off) must not resume from the
            # stale pre-load state it would otherwise still hold
            self._updaters.set_states(
                self._kvstore._updater.get_states(dump_optimizer=False))
            self._updaters.optimizer = self._optimizer
        else:
            from ..checkpoint import verify
            verify(fname)
            with open(fname, "rb") as f:
                self._updaters.set_states(f.read())
            # set_states may swap in a pickled optimizer (states dumped
            # with dump_optimizer=True); keep the trainer's handle — and
            # with it set_learning_rate() — pointed at the live object
            self._optimizer = self._updaters.optimizer
