"""MXL002 — no device→host syncs in training/serving hot paths.

``engine.py`` exists to keep the PJRT async stream full: eager op
dispatch returns futures, and the device works ahead of the Python
thread. A single ``asnumpy()``/``block_until_ready()``/``waitall()``
inside ``Trainer.step``, ``Module.forward/backward``, an optimizer
``update`` or a kvstore ``push/pull`` drains that stream once per
batch — the silent 2-10x step-time cliff the reference avoided with
its threaded engine. Sites that *must* sync (the native TCP transport
serializes to host; profiler-gated serialization) carry a baseline
entry or an inline disable with the justification.
"""
from __future__ import annotations

import ast

from ..lint import Rule

# (path predicate, hot method names, module-local sync helper names) —
# the framework's per-batch paths. Per-scope extra names keep module
# spellings (metric.py's _as_np wrapper) out of the global rule
_SCOPES = (
    ("mxnet_tpu/gluon/trainer.py",
     {"step", "update", "_update", "allreduce_grads", "_allreduce_grads"},
     set()),
    ("mxnet_tpu/module/",
     {"forward", "backward", "update", "forward_backward"}, set()),
    ("mxnet_tpu/executor.py", {"forward", "backward"}, set()),
    ("mxnet_tpu/optimizer/", {"update", "update_multi_precision"}, set()),
    ("mxnet_tpu/kvstore/",
     {"push", "pull", "row_sparse_pull", "pushpull",
      "_push_impl", "_pull_impl"}, set()),
    ("mxnet_tpu/metric.py", {"update"}, {"_as_np"}),
    # the Monitor tap runs inside every monitored executor forward —
    # a sync in stat_helper would stall each tapped tensor; toc() is
    # the sanctioned read point and stays off this list
    ("mxnet_tpu/monitor.py", {"stat_helper", "tic", "install"}, set()),
    # the input pipeline's per-batch paths: parent-side ring pulls and
    # the device feeder run once per training batch — a sync here
    # serializes host decode against device compute, the exact overlap
    # the pipeline exists to create (io/pipeline.py)
    ("mxnet_tpu/io/pipeline.py",
     {"next", "_pull", "_release", "iter_next", "get", "_feed",
      "_to_device", "to_device"}, set()),
    # the telemetry recorders themselves run inside every hot path
    # above — a sync hiding in inc()/observe()/step_boundary() would
    # stall each instrumented seam at once. Drains are read-time only
    # (snapshot/value), never in these recording methods. The
    # timeline/SLO plane joins: a sync in a frame tick or a windowed
    # query (rate/quantile/burn) would multiply into every window it
    # observes — recorders read SNAPSHOTS only, never the device.
    ("mxnet_tpu/telemetry/",
     {"inc", "dec", "set", "set_max", "inc_lazy", "set_lazy",
      "observe", "observe_lazy", "_push_lazy", "add_data_wait",
      "add_comm", "add_compile", "step_boundary",
      "_on_event_duration",
      "tick", "bounds", "rate", "mean", "quantile", "over_fraction",
      "delta", "delta_quantile", "delta_over", "stats_of",
      "evaluate", "burn", "slo_burn", "_window_err_frac",
      "_agg_hist", "_agg_counter"}, set()),
    # the tracing recorders run inside every instrumented seam above;
    # a sync in span open/close would stall each traced hot path
    ("mxnet_tpu/tracing/",
     {"__enter__", "__exit__", "span", "span_at", "record_span",
      "set_attr", "heartbeat", "_touch", "_observe_span"}, set()),
    # profiling recorders: ledger pricing and the xplane reader run on
    # artifacts AFTER measurement — a device sync creeping into them
    # would perturb the very steps they attribute. The PR 7 memory
    # recorders join the list: role tagging runs inside optimizer
    # updates and io __next__, and the
    # census reads shard METADATA only — an asnumpy in either would
    # stall every tagged hot path at once. The model-health sentry's
    # recording methods (check / observe_loss / norm add+commit /
    # step_boundary) run inside executor forward/backward, Trainer
    # _update and the sharded step — they dispatch lazy reduces ONLY;
    # folding reads retired buffers (the trainer probe's table one
    # step late, in _fold_tables), and the sanctioned read
    # points (flush, snapshot_doc, nan_postmortem, the first-NaN
    # localizer) stay off this list by design
    ("mxnet_tpu/profiling/",
     {"build_ledger", "instr_cost", "measure_ops",
      "mfu_estimate", "attribute_op_name",
      "group_by_op", "tag_role", "tag_tree", "role_of",
      "check", "check_scalar", "observe_loss", "_nonfinite_count",
      "_accumulate", "add", "commit", "step_probe", "step_boundary",
      "_fold_entries", "_fold_tables", "_fold_loss", "_trip",
      "live_census", "buffer_intervals", "build_memory_ledger",
      "group_buffers_by_op", "_sweep_peak",
      "classify_spans", "collect", "_clip", "_overlap_ns",
      # tailpath: the per-request critical-path joiner/recorder runs
      # on serving reply paths — span-dict arithmetic only, a device
      # sync here would stall the scheduler loop it attributes
      "attribute_request", "join_spans", "ingest_spans"}, set()),
    # the cost-tracked partitioner runs at TRACE/bind time: selector
    # growth, cluster pricing (abstract lowering only — ShapeDtype
    # structs, never arrays) and the gate decision. A device sync here
    # would execute real work during graph partitioning and stall
    # every costed bind; pricing must stay purely abstract
    ("mxnet_tpu/subgraph/",
     {"select", "select_input", "select_output", "filter",
      "partition_graph", "_partition_one", "create_subgraph_node",
      "price_program", "price_cluster", "__call__", "_memo_key",
      "build_report", "partition_graph_costed"}, set()),
    # the layout plane: role/spec resolution runs at registration,
    # bind, scale-out and dry-run time and must stay ABSTRACT — a
    # device sync inside resolve/fit/report would execute real work
    # while deciding where work should go (placement prices metadata:
    # shapes, dtypes, mesh axes — never array values)
    ("mxnet_tpu/parallel/layout.py",
     {"role_of", "spec_for", "resolve", "resolve_specs", "zero_specs",
      "_fit_spec", "report", "collective_shardings",
      "collectives_summary", "dryrun_report"}, set()),
    # replica/slice placement is the same doctrine one level down:
    # picking devices for lanes is list arithmetic over device
    # handles, never a device round-trip
    ("mxnet_tpu/parallel/mesh.py",
     {"replica_devices", "replica_slices", "mesh_sharding"}, set()),
    # mesh-sliced serving lanes: dispatch of a padded batch is ONE
    # SPMD program per slice; run()'s np.asarray IS the reply's host
    # transfer (outputs are replicated — the gather is a local read)
    # and stays legal exactly like Replica._run_batch's. NOTE: listed
    # before the general serving/ scope — first prefix match wins.
    ("mxnet_tpu/serving/sharded.py",
     {"run", "warmup", "compile_symbol_forward_sharded",
      "placement_report", "_maybe_report"}, set()),
    # the generative decode plane's hot paths run once per TOKEN, not
    # per request: scheduler step + prefill, cache alloc/free/
    # reservation, token emission, and admission. A sync in any of
    # them serializes every in-flight generation stream at once.
    # (GenLane._host_tokens IS the token reply transfer — generated
    # ids must reach the host to stream to clients — and lives outside
    # this list by design, exactly like Replica._run_batch's reply.)
    # NOTE: listed before the general serving/ scope — first prefix
    # match wins.
    # ... and the decode-failover hot paths: salvage/land stay
    # device-side end to end (gather -> device_put -> scatter), and
    # the recovery bookkeeping (_recover_requests, admission re-
    # reservation, migration landing) must never read a device array —
    # a sync there would stall every surviving stream to rescue one.
    ("mxnet_tpu/serving/generate/",
     {"submit_generate", "try_admit", "_step", "_prefill", "_emit",
      "_observe_pool", "_observe_depth", "ensure_position", "extend",
      "adopt", "alloc", "free", "reserve", "unreserve", "blocks_for",
      "used_blocks", "reserved_blocks", "swap", "prefill",
      "decode", "salvage", "land", "_start", "_land_migration",
      "_pop_admissions", "_recover_requests", "_recover_inflight",
      "_evacuate"}, set()),
    # the elasticity plane's hot paths: the membership poll runs
    # BETWEEN training steps (a sync there would fence the pipeline
    # every boundary just to read a directory), and the autoscaler's
    # decision loop must read host-side EWMAs and histogram bucket
    # counts ONLY — never device arrays (a decision that synced would
    # stall serving to decide how to serve). The reshape path itself
    # (quiesce/gather/census) is sanctioned sync territory by design
    # and stays off this list.
    ("mxnet_tpu/elastic/",
     {"poll", "view", "announce", "leave", "mark_dead",
      "observe", "decide", "tick", "_queue_depth", "_slo_burn",
      "_ceiling", "train_step"}, set()),
    # the cluster plane's ledger/lending hot paths: lease bookkeeping
    # (acquire/release/resize + every introspection read) runs under
    # the ledger lock from client threads, the autoscaler daemon and
    # the lending scheduler at once — a device sync inside any of them
    # would stall every workload's placement behind one device read.
    # The lend/reclaim protocol legs DRIVE trainer.reshape (sanctioned
    # sync territory, like elastic/'s reshape path) and stay off this
    # list by design; the bookkeeping around them must stay sync-free.
    ("mxnet_tpu/cluster/",
     {"acquire", "release", "resize", "ensure", "release_devices",
      "note", "free_devices", "usable_devices", "foreign_devices",
      "owner_of", "leases", "holdings", "find_lease", "expired",
      "verify_conservation", "device_seconds", "_accrue", "_snapshot",
      "_journal", "active_borrows", "borrowed_devices", "can_lend",
      "check_leases", "on_capped", "on_cold", "_budget_healthy",
      "step_boundary", "hold", "_record"}, set()),
    # the serving gateway's per-request paths: admission + enqueue run
    # in every client thread, coalescing + reply recording in every
    # replica scheduler — a sync in any of them serializes the whole
    # request stream behind one device read. (Replica._run_batch's
    # np.asarray IS the reply's host transfer and lives outside this
    # list by design.)
    ("mxnet_tpu/serving/",
     {"submit", "infer", "_admit", "put", "take_batch", "requeue",
      "_scoop", "depth", "pending_rows", "_reply", "_observe_rate",
      "estimate_latency_s", "pad_batch", "pick_bucket",
      "submit_generate"}, set()),
    # the lock witness recorder runs inside EVERY instrumented lock
    # acquisition across serving/cluster — a device sync (or sleep,
    # via MXL009) here would multiply into every critical section it
    # observes, invalidating the <5% overhead bound the tier-1 suite
    # enforces
    ("mxnet_tpu/analysis/witness.py",
     {"record_acquire", "record_release", "record_wait", "acquire",
      "release", "wait", "wait_for", "notify", "notify_all",
      "register", "held"}, set()),
)

# calls that block on (or copy from) the device stream
_SYNC_ATTRS = {"asnumpy", "wait_to_read", "block_until_ready", "waitall"}
_SYNC_NAMES = {"waitall", "block_until_ready"}


def _hot_scope(path):
    for prefix, methods, extra in _SCOPES:
        if path.startswith(prefix):
            return methods, _SYNC_NAMES | extra
    return None, None


class HostSyncRule(Rule):
    code = "MXL002"
    name = "host-sync-hot-path"
    description = ("no asnumpy/wait_to_read/block_until_ready/waitall in "
                   "Trainer.step / Module.forward+backward / optimizer "
                   "update / kvstore push+pull / metric update")

    def check_module(self, path, tree, lines):
        methods, sync_names = _hot_scope(path)
        if methods is None:
            return
        # top-level and class-level defs whose name marks a hot path
        for scope in ast.walk(tree):
            if not isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if scope.name not in methods:
                continue
            for node in ast.walk(scope):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                sync = None
                if isinstance(func, ast.Attribute) and \
                        func.attr in _SYNC_ATTRS:
                    sync = func.attr
                elif isinstance(func, ast.Name) and func.id in sync_names:
                    sync = func.id
                if sync is not None:
                    yield self.finding(
                        path, node,
                        f"hot path {scope.name!r} calls {sync}() — stalls "
                        "the PJRT async stream once per batch (keep the "
                        "value on device; sync at read/report time "
                        "instead)", lines)
