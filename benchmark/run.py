"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: ``BENCHMARK.json`` names
the cell, ``workloads/<cell>.json`` names its configuration, its driver
and its traffic parameters, ``configs/<config>.json`` holds the sizes,
``drivers/<driver>.py`` builds, warms and runs the window,
``reference/<family>.py`` is the plain reference, and each per-layer
metric is read by ``layer_metrics/<metric>.py``. A new cell or metric is
new files and new entries; no file here has to change.

Stays off JAX until the device gate. Without a TPU, or with fewer chips
than the cell asks for, it exits 2 and prints no result. The last line
of standard output is the result object; the numbers that decided
``correct`` are its last key and the last lines of standard error.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_by_path(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(root, name):
    """(BENCHMARK.json, the cell's workload file, its configuration)."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit("no workload %r in BENCHMARK.json" % name)
    base = os.path.join(root, bench["paths"][0])
    workload = read_json(os.path.join(base, "workloads", name + ".json"))
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg = read_json(os.path.join(root, conf["file"]))
    return bench, entry, workload, cfg


def applies(metric, cell, bench):
    """Does the cell report this metric? By its own ``workloads`` list,
    or, without one, wherever the end-to-end metric it moves is
    reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = next(m for m in bench["end_to_end"]
                     if m["name"] == metric["moves"])
        return applies(moved, cell, bench)
    return True


class CompileMeter:
    """XLA backend compilations seen through jax.monitoring (a hit in
    the persistent cache counts as one too)."""

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **_kw):
        if event == COMPILE_EVENT:
            self.count += 1


class Tracer:
    """The traced slice: a profiler capture with the benchmark's window
    annotation laid around it, on the profiler's own clock."""

    def __init__(self, directory):
        self.directory = directory

    def __enter__(self):
        import jax

        from benchmark.lib import xplane

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
        self._span.__enter__()
        self.t0_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        import jax

        self.t1_ns = time.monotonic_ns()
        self._span.__exit__(*exc)
        jax.profiler.stop_trace()
        return False


def device_report(devices, chips):
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(root, name, seed, seconds, trace, gate=True, peaks_kind=None,
             out=sys.stdout, err=sys.stderr):
    """One run of one cell. ``gate=False`` and ``peaks_kind`` are for
    the tests, which drive everything but the look for a chip on the
    CPU and price the work against a named chip's peaks."""
    bench, entry, workload, cfg = load_cell(root, name)
    base = os.path.join(root, bench["paths"][0])
    if root not in sys.path:
        sys.path.insert(0, root)

    import jax

    devices = jax.devices()
    if gate and (devices[0].platform != "tpu"
                 or len(devices) < entry["chips"]):
        print("benchmark: %s needs %d TPU device(s); JAX found %r"
              % (name, entry["chips"], devices), file=err)
        return 2

    import mxnet_tpu as mx

    cache_dir = mx.util.enable_compile_cache()
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter)
    print(json.dumps({"cell": name, "seed": seed, "devices": len(devices),
                      "compile_cache": cache_dir}), file=err, flush=True)

    driver = load_by_path(
        os.path.join(base, "drivers", workload["driver"] + ".py"),
        "benchmark_driver_" + workload["driver"])
    cell = driver.Cell(cfg, workload, seed)
    cell.setup()
    setup_s = time.time() - T0
    compiles_setup = meter.count
    trace_dir = os.path.join(root, ".bench_trace", name)
    run = cell.window(float(seconds), Tracer(trace_dir) if trace else None)
    compiles_window = meter.count - compiles_setup
    summary = cell.summary(run)
    device = device_report(devices, entry["chips"])
    cell.release()

    result = {"correct": False, "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": {}, "device": device}
    if trace:
        from benchmark.lib import peaks, xplane

        planes = xplane.load(trace_dir)
        shutil.rmtree(os.path.join(root, ".bench_trace"),
                      ignore_errors=True)
        device.update(xplane.busy(planes))
        ctx = {"cell": name, "cfg": cfg, "workload": workload, "run": run,
               "summary": summary, "planes": planes, "device": device,
               "peaks": peaks.peaks(peaks_kind or device["kind"]),
               "chips": entry["chips"]}
        for m in bench["per_layer"]:
            if not applies(m, name, bench):
                continue
            reader = load_by_path(
                os.path.join(base, "layer_metrics", m["name"] + ".py"),
                "benchmark_metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": xplane.top_ops(planes),
                               "idle_gaps": xplane.idle_gaps(planes)}
    else:
        values = dict(summary["end_to_end"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if applies(m, name, bench):
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}

    from benchmark.lib import compare

    numbers, where = cell.numbers()
    numbers["window_compiles"] = compiles_window
    rows, ok = compare.judge(numbers, workload["limits"])
    result["correct"] = bool(ok)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    print(json.dumps({"setup_s": setup_s, "compiles_setup": compiles_setup,
                      "worst_leaf": where, "detail": cell.detail(),
                      "extra": summary.get("extra")}), file=err)
    for n, v, lim in rows:
        print("check %s = %r limit %r" % (n, v, lim), file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    seconds = args.seconds
    if seconds is None:
        seconds = read_json(os.path.join(root, "BENCHMARK.json"))[
            "run_seconds"]
    return run_cell(root, args.workload, args.seed, seconds, args.trace)


if __name__ == "__main__":
    sys.exit(main())
