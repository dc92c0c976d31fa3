"""Readings that the limits in the workload files are set from.

    python3 benchmark/control.py --workload <cell> --what <what> --seeds 1,2,3

Runs on the machine it is started on, at the cell's own size, several
seeds in one process, and prints one JSON line per seed. ``program`` is
the program against the plain reference (the lower readings);
``control`` is the reference in the nearest precision below the
configuration's put in the program's place (bfloat16 for the float32
training cell, fp8 for the bfloat16 generation cells, with
``control_int8`` beside it); ``half_batch`` is the planted fault of a
training cell. The benchmark's own runs never call
this; benchmark/tests keeps each at a size a test run can hold.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    from benchmark import run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)
    bench, entry, workload, cfg = harness.load_cell(args.root, args.workload)
    import mxnet_tpu as mx

    mx.util.enable_compile_cache()
    driver = harness.load_by_path(
        os.path.join(args.root, bench["paths"][0], "drivers",
                     workload["driver"] + ".py"),
        "benchmark_driver_" + workload["driver"])
    seeds = [int(s) for s in args.seeds.split(",")]
    for row in driver.readings(cfg, workload, seeds, args.what,
                               seconds=args.seconds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
