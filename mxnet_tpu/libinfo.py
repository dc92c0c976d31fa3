"""Runtime feature discovery + the canonical environment-variable list
(ref: python/mxnet/libinfo.py find_lib_path/__version__;
python/mxnet/runtime.py Features; docs/faq/env_var.md).

    >>> import mxnet_tpu as mx
    >>> mx.libinfo.features()          # what this build can do
    >>> mx.libinfo.env_vars()          # every honored env var + value
    >>> mx.libinfo.find_lib_path()     # built native libraries
"""
from __future__ import annotations

import os


def __getattr__(name):
    if name == "__version__":
        # single source of truth: the package (avoids two literals
        # drifting on a version bump)
        from mxnet_tpu import __version__ as v
        return v
    raise AttributeError(name)

# every environment variable the framework reads, with where it acts —
# the docs/faq/env_var.md analogue, kept next to the code so it cannot
# drift silently. (DMLC_* come from tools/launch.py's tracker contract.)
_ENV_VARS = {
    "MXNET_ENGINE_TYPE": (
        "ThreadedEnginePerDevice | NaiveEngine — NaiveEngine serializes "
        "every op (determinism/race-debug switch; engine.py)"),
    "MXNET_CPU_WORKER_NTHREADS": (
        "host worker threads for the native engine and decode pools "
        "(_native/core.cc, io pipeline)"),
    "MXNET_SUBGRAPH_BACKEND": (
        "graph-partition backend applied at bind, e.g. XLA "
        "(symbol.simple_bind; subgraph/xla_fuse.py)"),
    "MXNET_PROFILER_AUTOSTART": (
        "1 = profiling from import, chrome-trace on exit (profiler.py)"),
    "MXNET_HOME": (
        "root for local data: model store weights, text embeddings "
        "(default ~/.mxnet_tpu)"),
    "MXNET_KVSTORE_BIGARRAY_BOUND": (
        "elements above which dist pushes are sliced across servers "
        "(kvstore/dist.py)"),
    "MXNET_KVSTORE_REQUEST_TIMEOUT_MS": (
        "client-side dist request timeout; a dead server fails the job "
        "instead of hanging it (kvstore/dist.py)"),
    "MXNET_KVSTORE_RECOVERY_BUDGET_MS": (
        "total wall-clock a worker may spend recovering one failed dist "
        "request (reconnect + idempotent resend loop); 0 = legacy "
        "fail-fast (kvstore/dist.py, docs/robustness.md)"),
    "MXNET_KVSTORE_RECOVERY_BACKOFF_MS": (
        "initial reconnect backoff, doubled per attempt with ±25% "
        "jitter (default 50; kvstore/fault.py BackoffSchedule)"),
    "MXNET_KVSTORE_RECOVERY_BACKOFF_MAX_MS": (
        "backoff growth cap (default 2000; kvstore/fault.py)"),
    "MXNET_KVSTORE_RECOVERY_GRACE_MS": (
        "server-side: how long a missing worker may stay gone before "
        "the job degrades; defaults to the recovery budget "
        "(kvstore/dist.py run_server)"),
    "MXNET_KVSTORE_FAULT_PLAN": (
        "deterministic fault-injection plan, e.g. "
        "drop_conn@round=3;kill_server@round=5 "
        "(kvstore/fault.py, docs/robustness.md)"),
    "MXNET_KVSTORE_SNAPSHOT_PATH": (
        "server-side: SIGTERM snapshots the whole server state here and "
        "a restart restores it; set automatically by tools/launch.py "
        "--restart-policy=server (kvstore/dist.py run_server)"),
    "DMLC_ROLE": "worker|server — set per process by tools/launch.py",
    "DMLC_PS_ROOT_URI": "rendezvous host (launch.py tracker contract)",
    "DMLC_PS_ROOT_PORT": "rendezvous port; with -s 0 it is the "
                         "jax.distributed coordinator",
    "DMLC_NUM_WORKER": "worker count in the dist job",
    "DMLC_NUM_SERVER": "server count; 0 = collective data plane",
    "DMLC_WORKER_ID": "this worker's rank",
    "DMLC_SERVER_ID": "this server's index",
    "MXNET_TEST_SEED": (
        "pins unseeded framework RNG draws (weight init, dropout) for "
        "the whole process — the reference test harness's determinism "
        "contract (random.py)"),
    "MXTPU_NO_SERVER_AUTOINIT": (
        "1 = do NOT enter the server loop at import in a "
        "DMLC_ROLE=server process (the reference always enters; "
        "kvstore_server.py)"),
    "MXNET_CHECKPOINT_MANIFEST": (
        "0 disables the CRC32 MANIFEST.json that atomic checkpoint "
        "writes record and loads verify; worker resume still works but "
        "without CRC proof (default on; checkpoint.py, "
        "docs/robustness.md)"),
    "MXNET_WORKER_CHECKPOINT_DIR": (
        "per-worker directory for CheckpointManager training-state "
        "checkpoints; set automatically by tools/launch.py "
        "--restart-policy=worker so a respawned worker auto-resumes "
        "(checkpoint.py)"),
    "MXNET_WORKER_RESTARTS": (
        "how many times tools/launch.py has respawned this worker "
        "after preemption (set by the launcher; recorded in resume "
        "telemetry, checkpoint.py)"),
    "MXNET_GRAPH_VALIDATE": (
        "Symbol.validate() gate in simple_bind: warn (default) logs "
        "pre-bind graph findings, error raises, 0/off disables "
        "(symbol/symbol.py, analysis/graph.py)"),
    "MXTPU_IO_HOST_ENGINE": (
        "1 (default) schedules io pipeline decode/prefetch on the "
        "native host engine; 0 = plain thread fallback (io/io.py)"),
    "MXTPU_IO_WORKERS": (
        "decode worker PROCESSES for the sharded input pipeline; the "
        "default num_workers of ImageRecordIter and the "
        "ShardedRecordPipeline (0 = stay in-process; io/pipeline.py, "
        "docs/io.md)"),
    "MXTPU_IO_RING_BATCHES": (
        "batch slots per worker in the shared-memory ring (default 3; "
        "bounds decode run-ahead and host memory: "
        "workers x slots x batch bytes; io/pipeline.py)"),
    "MXTPU_IO_READAHEAD_MB": (
        "raw-byte readahead per streaming shard reader (default 64); "
        "background chunk reads overlap record parse + decode "
        "(recordio.RecordIOStreamReader, io/_pipeline_worker.py)"),
    "MXTPU_IO_PREFETCH_DEVICE": (
        "1 = double-buffered device prefetch by default: "
        "gluon DataLoader and Module.fit wrap their batch streams in "
        "the device feeder (jax.device_put of batch k+1 during step "
        "k); per-call prefetch_to_device= overrides (io/pipeline.py, "
        "docs/io.md)"),
    "MXTPU_TELEMETRY": (
        "0 disables the metrics registry's hot-path instrumentation "
        "(op dispatch, io wait, kvstore bytes, step breakdown); "
        "default on (telemetry/, docs/observability.md)"),
    "MXTPU_TELEMETRY_FLUSH_SEC": (
        ">0 starts a daemon thread writing a JSON metric snapshot "
        "every N seconds to MXTPU_TELEMETRY_FILE (telemetry/__init__)"),
    "MXTPU_TELEMETRY_FILE": (
        "periodic-flush destination, atomically replaced each flush "
        "(default telemetry.json, or telemetry.<role><rank>.json "
        "inside a launch.py job so processes sharing a cwd don't "
        "overwrite each other; telemetry/__init__)"),
    "MXTPU_TELEMETRY_VERBOSE": (
        "1 logs a one-line summary to stderr at every telemetry flush "
        "(telemetry/__init__)"),
    "MXTPU_TRACE_SAMPLE": (
        "trace-level sampling probability for the span layer, 0..1 "
        "(default 1; 0 disables span recording entirely — the flight "
        "recorder then has nothing to dump; tracing/)"),
    "MXTPU_TRACE_RING": (
        "closed spans retained per thread ring (default 2048; "
        "tracing/)"),
    "MXTPU_TRACE_FILE": (
        "tracing.export.write_trace default path (default trace.json, "
        "or trace.<role><rank>.json inside a launch.py job; "
        "tracing/export.py)"),
    "MXTPU_HANG_TIMEOUT_SEC": (
        ">0 arms the hang watchdog at flight-recorder install: a step "
        "with no span activity for this long dumps in-flight spans + "
        "thread stacks (tracing/flight.py)"),
    "MXTPU_FLIGHT_PATH": (
        "flight-recorder dump destination (atomic file write; default "
        "stderr; tracing/flight.py)"),
    "MXTPU_MEMORY_CENSUS": (
        "0 disables the live-array memory census: role tagging at the "
        "NDArray/optimizer/io seams and the mx_memory_* snapshot "
        "collector (default on; profiling/memory.py, "
        "docs/observability.md)"),
    "MXTPU_OOM_DUMP_PATH": (
        "OOM postmortem destination — an XLA RESOURCE_EXHAUSTED at "
        "the executor/trainer/sharded-step seams writes the ranked "
        "peak-liveness table + census + flight dump here (default "
        "oom_postmortem.json; profiling/memory.py)"),
    "MXTPU_SERVING_MAX_WAIT_MS": (
        "default continuous-batcher coalescing window per model: a "
        "request never waits longer than this for batch-mates before "
        "dispatching partial, so bs=1 latency is bounded (default 5; "
        "serving/batcher.py, docs/serving.md)"),
    "MXTPU_SERVING_MAX_QUEUE": (
        "default per-model queue-depth limit; submissions beyond it "
        "fast-reject with reason queue_full (default 256; "
        "serving/gateway.py)"),
    "MXTPU_SERVING_SLO_MS": (
        "default per-model latency budget: a request whose estimated "
        "e2e latency (EWMA service rate x backlog) would exceed it "
        "fast-rejects with reason slo; 0 disables (default 0; "
        "serving/gateway.py)"),
    "MXTPU_SERVING_REPLICAS": (
        "default replica count per registered model; degrades "
        "gracefully when fewer local devices exist (default 1; "
        "serving/gateway.py)"),
    "MXTPU_SERVING_HEALTH_SEC": (
        ">0 starts the gateway health-probe daemon at this period: "
        "failed replicas drain, recovered ones rejoin (default 0 = "
        "manual check_health(); serving/gateway.py)"),
    "MXTPU_SERVING_TP": (
        "default tensor-parallel width for registered models/"
        "generators: >= 2 makes every replica a MESH SLICE of that "
        "many devices serving one SPMD program per batch, parameters "
        "placed from the layout plane's role table (default 0 = "
        "single-device lanes; serving/sharded.py, parallel/layout.py, "
        "docs/serving.md)"),
    "MXTPU_LAYOUT_TABLE": (
        "path to a JSON layout-table override (SpecLayout.to_json "
        "format): SpecLayout.default() — the table serving slices, "
        "the sharded decode plane, and the dry-run CLI resolve "
        "through — loads it instead of the built-in role table "
        "(default unset; parallel/layout.py)"),
    "MXTPU_LAYOUT_REPORT": (
        "path: every sharded serving lane writes its per-parameter "
        "placement report (role/spec/per-device bytes, the "
        "layout_report document shape) here at registration, "
        "atomically (default unset; serving/sharded.py)"),
    "MXTPU_GEN_BLOCK_TOKENS": (
        "default KV-cache block size in tokens for registered "
        "generators — the paged-attention page granularity (default "
        "16; serving/generate/, docs/serving.md)"),
    "MXTPU_GEN_MAX_BLOCKS": (
        "default KV block-pool size per generator replica lane; "
        "block 0 is the reserved pad sink, and admission fast-rejects "
        "kv_cache_full when the pool cannot cover a request's token "
        "budget (default 256; serving/generate/kvcache.py)"),
    "MXTPU_GEN_MAX_NEW_TOKENS": (
        "default + cap for a generation request's max_new_tokens — "
        "bounds the block-table width the compiled decode step is "
        "traced with (default 64; serving/gateway.py "
        "register_generator)"),
    "MXTPU_GEN_MAX_RECOVERIES": (
        "decode failover budget: how many lane losses one in-flight "
        "generation survives (KV-block migration / deterministic "
        "replay) before degrading to a fast lane_lost reject "
        "(default 2; serving/generate/scheduler.py, "
        "docs/robustness.md)"),
    "MXTPU_GEN_RECOVERY_BACKOFF_MS": (
        "backoff base in ms between REPEAT recoveries of the same "
        "generation request, doubling per rescue and capped at 40x "
        "base — the first rescue is always immediate (default 50; "
        "serving/generate/scheduler.py)"),
    "MXTPU_FUSE_COST": (
        "0 disables cost-tracked partitioning at bind: "
        "MXNET_SUBGRAPH_BACKEND then applies the always-fire pattern "
        "pass instead of pricing each cluster with the flop/byte + "
        "liveness ledgers (default on when shapes are known; "
        "subgraph/cost.py, docs/architecture.md)"),
    "MXTPU_FUSE_MIN_SAVE": (
        "fractional roofline-time saving a candidate cluster must "
        "show to fuse (default 0.02 — a rewrite that buys <2% of the "
        "cluster's est_s stays unfused; subgraph/cost.py CostGate)"),
    "MXTPU_FUSE_MEM_SLACK_MB": (
        "absolute peak-live-bytes growth (MB) a fusing cluster may "
        "cost before the memory currency rejects it; the gate always "
        "tolerates 1% relative noise on top (default 0; "
        "subgraph/cost.py CostGate)"),
    "MXTPU_FUSE_REPORT": (
        "path: every cost-tracked partition pass writes its decision "
        "trail (the partition cost report, rendered by "
        "tools/mfu_report.py) here (default unset; subgraph/cost.py)"),
    "MXTPU_KERNEL_FUSED_OPT": (
        "route sgd_mom_update/adam_update through the fused Pallas "
        "one-pass update kernel: 1/0/auto (default auto = chip "
        "backends only; the jnp path is the CPU hot path and the "
        "kernel's numerics oracle; ops/optimizer_ops.py, "
        "ops/pallas_kernels.py)"),
    "MXTPU_HEALTH": (
        "model-health plane gate/policy: 0 = every hook a no-op, "
        "1/warn (default) = sentry + telemetry + postmortem then "
        "continue, raise = a nonfinite fold raises NonfiniteError at "
        "the step boundary (profiling/health.py, "
        "docs/observability.md)"),
    "MXTPU_HEALTH_DUMP_PATH": (
        "first-NaN postmortem destination — a sentry trip writes the "
        "offending-op localization + ranked grad norms + loss state "
        "+ RNG + flight dump here (default nan_postmortem.json; "
        "profiling/health.py)"),
    "MXTPU_HEALTH_NORMS": (
        "0 drops the norm half of the per-step probe program "
        "(per-group weight/grad norms + update-to-weight ratios and "
        "the pre-update weight capture); the nonfinite sentry stays "
        "on (default on; profiling/health.py, gluon/trainer.py)"),
    "MXTPU_HEALTH_ANOMALY_Z": (
        "z-score threshold for the loss-spike anomaly detector over "
        "the folded loss EWMA (default 6; profiling/health.py)"),
    "MXTPU_KERNEL_INT8_EPILOGUE": (
        "0 routes the fused INT8 conv epilogue (_sg_xla_quant_conv) "
        "through plain ops/quantized.py requantize+act instead of "
        "ops/pallas_kernels.quantized_conv_epilogue (default auto — "
        "the wrapper itself falls back off-chip; subgraph/rules.py)"),
    "MXTPU_ELASTIC_DIR": (
        "membership directory of an elastic job: workers announce "
        "join/leave as member-<rank>.json files here and the "
        "generation counter lives beside them (default unset = not "
        "an elastic job; elastic/membership.py, docs/robustness.md)"),
    "MXTPU_ELASTIC_POLL_SEC": (
        "serving autoscaler decision period when started as a daemon "
        "(default 2; elastic/autoscale.py)"),
    "MXTPU_ELASTIC_MIN_REPLICAS": (
        "autoscaler floor: scale-in never retires below this many "
        "serving lanes (default 1; elastic/autoscale.py)"),
    "MXTPU_ELASTIC_MAX_REPLICAS": (
        "autoscaler ceiling before the degraded-wrap cap: scale-out "
        "never builds past this many lanes (default 4; "
        "elastic/autoscale.py)"),
    "MXTPU_ELASTIC_QUEUE_HIGH": (
        "per-replica queue-depth EWMA high watermark — sustained "
        "pressure above queue_high x replicas scales out; the low "
        "watermark defaults to a quarter of it (default 8; "
        "elastic/autoscale.py)"),
    "MXTPU_ELASTIC_P99_BUDGET_MS": (
        "autoscaler latency budget: a windowed e2e p99 estimate "
        "(mx_serving_latency_seconds bucket deltas) above it is "
        "scale-out pressure; 0 disables the latency input (default "
        "0; elastic/autoscale.py)"),
    "MXTPU_ELASTIC_COOLDOWN_SEC": (
        "minimum seconds between a scale event and the next "
        "scale-in — hysteresis so bursty load cannot flap the fleet "
        "(default 30; elastic/autoscale.py)"),
    "MXTPU_LEND_DEADLINE_SEC": (
        "device-lending lease deadline: chips borrowed from training "
        "for serving are due back after this many seconds — a "
        "borrower that has not returned (or never reported ready) by "
        "then is revoked and the chips reshape back into training "
        "(default 60; cluster/lending.py)"),
    "MXTPU_LEND_MIN_TRAIN_DP": (
        "training dp floor for device lending: a lend that would "
        "shrink the ElasticTrainer below this many shards is refused "
        "(default 1; cluster/lending.py)"),
    "MXTPU_LEND_RECLAIM_BACKOFF_MS": (
        "total backoff budget for one lend/reclaim protocol leg: "
        "bounds the step-boundary quiesce wait, reshape retries, and "
        "how much of an injected reclaim_timeout borrower drain is "
        "honored (default 5000; cluster/lending.py)"),
    "MXTPU_LOCK_WITNESS": (
        "set to 1 to patch the framework's lock constructors with the "
        "dynamic lock-order witness: every acquisition edge and "
        "held-across-Condition.wait hazard is recorded and dumped as a "
        "lockgraph artifact at exit (default 0; analysis/witness.py)"),
    "MXTPU_LOCK_WITNESS_PATH": (
        "where the lock witness writes its lockgraph JSON artifact at "
        "process exit (default ./lockgraph.json; analysis/witness.py)"),
    "MXTPU_TIMELINE_WINDOW": (
        "frames the in-process metric timeline retains: each tick "
        "records one registry snapshot into a bounded ring and the "
        "oldest frame past this cap is evicted (default 128; "
        "telemetry/timeline.py)"),
    "MXTPU_TIMELINE_SEC": (
        "period of the timeline's background frame recorder: > 0 "
        "starts a daemon that ticks the process timeline every this "
        "many seconds when telemetry is enabled; <= 0 leaves ticking "
        "explicit (default 0; telemetry/timeline.py)"),
    "MXTPU_SLO_FILE": (
        "JSON file declaring the SLO objectives the burn-rate tracker "
        "evaluates (a list of objective dicts, same keys as "
        "slo.DEFAULT_OBJECTIVES); unset uses the built-in inter-token "
        "p99 / e2e p99 / rejection-rate trio (default unset; "
        "telemetry/slo.py)"),
    "MXTPU_TAIL_ENABLE": (
        "1 = the serving schedulers stamp per-request critical-path "
        "decision events and the tail joiner attributes them; 0 "
        "disables the whole tail-attribution plane (default 1; "
        "profiling/tailpath.py, docs/observability.md)"),
    "MXTPU_TAIL_WINDOW": (
        "completed requests the tail aggregator retains in its "
        "sliding window before the oldest is evicted (default 512; "
        "profiling/tailpath.py)"),
    "MXTPU_TAIL_SLOW_FRAC": (
        "fraction of the windowed requests treated as the slow "
        "cohort whose blame bins rank the tail drivers (default 0.1 "
        "= slowest decile; profiling/tailpath.py)"),
    "MXTPU_TAIL_ARTIFACT": (
        "path a tail/v1 attribution artifact is dumped to by "
        "consumers that honor it (serving_bench --tail-json "
        "overrides; default unset = no auto-dump; "
        "profiling/tailpath.py, tools/serving_bench.py)"),
}


def env_vars():
    """{name: (current value or None, description)} for every honored
    environment variable."""
    return {k: (os.environ.get(k), v) for k, v in _ENV_VARS.items()}


def find_lib_path():
    """Paths of the built native libraries (ref: libinfo.py
    find_lib_path — there it locates libmxnet.so; here the runtime is
    jax + the _native components)."""
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "_native")
    return sorted(
        os.path.join(here, f) for f in os.listdir(here)
        if f.endswith(".so"))


class Feature:
    def __init__(self, name, enabled, detail=""):
        self.name = name
        self.enabled = bool(enabled)
        self.detail = detail

    def __repr__(self):
        mark = "✔" if self.enabled else "✖"
        return f"{mark} {self.name}" + (f" ({self.detail})"
                                        if self.detail else "")


def features():
    """Runtime feature flags (ref: python/mxnet/runtime.py Features —
    there compile-time USE_* flags; here what this host can actually
    do)."""
    import jax

    feats = []
    try:
        devs = jax.devices()
        plat = devs[0].platform
    except Exception:  # noqa: BLE001 — backend init can fail headless
        devs, plat = [], "none"
    feats.append(Feature("TPU", plat == "tpu",
                         f"{len(devs)} x {plat}"))
    feats.append(Feature("MULTI_DEVICE", len(devs) > 1,
                         f"{len(devs)} devices"))
    from .base import get_env
    feats.append(Feature("NAIVE_ENGINE",
                         get_env("MXNET_ENGINE_TYPE", "") == "NaiveEngine"))

    def _native_ok(loader):
        try:
            return loader() is not None
        except Exception:  # noqa: BLE001 — missing toolchain/headers
            return False

    from . import _native
    feats.append(Feature("NATIVE_CORE", _native_ok(_native.load_core),
                         "host storage pool + dependency engine"))
    feats.append(Feature("NATIVE_COMM", _native_ok(_native.load_comm),
                         "TCP parameter-server transport"))
    feats.append(Feature("NATIVE_IMGDEC", _native_ok(_native.load_imgdec),
                         "libjpeg batch decoder"))
    try:
        import PIL  # noqa: F401
        feats.append(Feature("PIL", True))
    except ImportError:
        feats.append(Feature("PIL", False))
    return feats
