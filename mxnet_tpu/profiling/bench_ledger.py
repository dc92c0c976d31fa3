"""Cost-ledger pass for ``bench.py`` (``python -m``).

Runs in a throwaway subprocess pinned to the CPU backend (the parent
holds the chip, and a chip belongs to one process), compiles the bench
stage programs there, and writes their cost ledgers to
``MXTPU_LEDGER_OUT`` — so a bench run, failed or not, carries a
cost-model MFU estimate and top-10 op table.

The output file is written atomically after EVERY completed stage:
the parent reads whatever has landed when it needs to emit, and a
deadline kill mid-pass still leaves the finished stages behind.

Stages (``MXTPU_LEDGER_STAGES``, comma-separated):

- ``infer_bf16`` — the headline ``bench.build_forward`` program,
- ``train_bf16`` — the ``bench.build_train`` step (slow compile; runs
  last by default),
- ``tiny``       — a small conv net train step that compiles in
  seconds (the failure-injection test hook).

XLA's optimized HLO is backend-specific, but FLOPs and bytes are
graph properties: the CPU-compiled ledger's *costs* transfer to the
chip; only the fusion boundaries are approximate. The document says
so (``backend`` field).
"""
from __future__ import annotations

import os
import sys
import time


def _tiny_train_step():
    import jax
    import jax.numpy as jnp

    def loss_fn(w1, w2, x):
        y = jax.lax.conv_general_dilated(
            x, w1, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        y = jnp.maximum(y, 0)
        y = jax.lax.conv_general_dilated(
            y, w2, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return jnp.mean(y * y)

    def step(w1, w2, x):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            w1, w2, x)
        return (w1 - 0.01 * grads[0], w2 - 0.01 * grads[1], loss)

    w1 = jnp.zeros((16, 3, 3, 3), jnp.float32)
    w2 = jnp.zeros((16, 16, 3, 3), jnp.float32)
    x = jnp.zeros((8, 3, 32, 32), jnp.float32)
    return jax.jit(step), (w1, w2, x), 8


def _stage_compiled(stage, batch):
    """(compiled, items_per_step) for a bench stage program."""
    import jax
    import jax.numpy as jnp

    import bench

    if stage == "tiny":
        step, args, items = _tiny_train_step()
        return step.lower(*args).compile(), items
    if stage == "infer_bf16":
        fwd, pvals = bench.build_forward(batch)
        data = jnp.zeros((batch, 3, 224, 224), jnp.bfloat16)
        return fwd.lower(pvals, data).compile(), batch
    if stage == "train_bf16":
        step, params, moms = bench.build_train(batch)
        data = jnp.zeros((batch, 3, 224, 224), jnp.bfloat16)
        labels = jnp.zeros((batch,), jnp.int32)
        return step.lower(params, moms, data, labels).compile(), batch
    raise ValueError("unknown ledger stage %r" % (stage,))


def main(argv=None):
    out_path = os.environ.get("MXTPU_LEDGER_OUT") or "bench_ledger.json"
    stages = [s.strip() for s in os.environ.get(
        "MXTPU_LEDGER_STAGES", "infer_bf16,train_bf16").split(",")
        if s.strip()]
    batch = int(os.environ.get("MXTPU_BENCH_BATCH", "128"))

    # repo root (bench.py lives beside mxnet_tpu/) must be importable
    # when launched via `python -m` from elsewhere
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if repo not in sys.path:
        sys.path.insert(0, repo)

    from mxnet_tpu.profiling import hlo as _hlo
    from mxnet_tpu.profiling import ledger
    from mxnet_tpu.profiling import memory as _memory

    doc = {"version": 1, "kind": "bench_cost_ledger",
           "backend": "cpu", "batch": batch, "stages": {}}

    def flush():
        ledger.dump(doc, out_path)

    rc = 0
    for stage in stages:
        stage_t0 = time.time()
        try:
            compiled, items = _stage_compiled(stage, batch)
            # serialize + parse the (megabytes of) optimized HLO once;
            # the flop and memory passes share it
            txt = compiled.as_text()
            mod = _hlo.parse_module(txt)
            led = ledger.from_compiled(compiled, hlo_text=txt,
                                       module=mod)
            summary = ledger.summarize(led)
            summary["gflops_per_item"] = round(
                led["totals"]["flops"] / items / 1e9, 3)
            summary["compile_s"] = round(time.time() - stage_t0, 1)
            try:
                # bounded memory summary (peak live bytes + top-3
                # buffers): rides the same stage record into every
                # bench artifact — success, stale, or failure
                summary["memory"] = _memory.summarize(
                    _memory.from_compiled(compiled, hlo_text=txt,
                                          module=mod), top=3)
            except Exception as e:  # noqa: BLE001 — memory must not
                summary["memory"] = {   # sink the flop ledger
                    "stage_error": repr(e)[:120]}
            doc["stages"][stage] = summary
        except Exception as e:  # noqa: BLE001 — a failed stage must not
            # "stage_error", not "error": bench.py line-level gates
            # treat a top-level '"error"' as a failed measurement
            doc["stages"][stage] = {"stage_error": repr(e)[:300]}
            rc = 1
        flush()
    return rc


if __name__ == "__main__":
    sys.exit(main())
