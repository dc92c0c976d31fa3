#!/usr/bin/env python
"""layout_report — pod-scale dry-run of the layout plane.

    python tools/layout_report.py --dp 8 --tp 8 --stage 2 \\
        --json docs/artifacts/layout_report_YYYYMMDD.json
    python tools/layout_report.py docs/artifacts/layout_report_*.json

Lowering-only validation of a training layout at mesh sizes far beyond
the host's devices: the tool re-execs itself onto a forced-size
virtual CPU mesh (``--xla_force_host_platform_device_count``, the
tests/conftest.py move), resolves a transformer-shaped parameter
pytree through the layout plane's role table
(:class:`mxnet_tpu.parallel.layout.SpecLayout` — tp/fsdp specs for
the params, the arXiv 2004.13336 cross-replica weight-update sharding
for the optimizer state), compiles the ZeRO train step for the full
``dp x tp`` mesh WITHOUT executing a single step, and reports:

- one row per parameter: role, requested spec, mesh-fitted param +
  optimizer-state spec, bytes and per-device bytes;
- the collectives GSPMD actually inserted (per-opcode count + bytes,
  parsed from the compiled HLO with the PR-6 parser).

That makes a dp x tp = 64 layout checkable on a 1-core CI host — the
committed ``docs/artifacts/layout_report_*.json`` is the proof, and
the same document shape serves as the serving slice's placement
report (``MXTPU_LAYOUT_REPORT``). Mirrors ``mfu_report``'s render /
produce / commit workflow (docs/observability.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHILD = "MXTPU_LAYOUT_REPORT_CHILD"


# ---------------------------------------------------------------------------
# model: a transformer-shaped param pytree + pure-jnp loss (the dry-run
# harness prices LAYOUT, not the op registry — plain jnp keeps the
# 64-device compile in seconds)
# ---------------------------------------------------------------------------

def build_param_tree(vocab, d_model, layers, heads, ff_mult=4,
                     seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)

    def w(*shape):
        return rng.normal(0, 0.02, shape).astype(np.float32)

    layer_trees = []
    for _ in range(layers):
        layer_trees.append({
            "ln1_g": np.ones(d_model, np.float32),
            "ln1_b": np.zeros(d_model, np.float32),
            "qkv_w": w(3 * d_model, d_model),
            "qkv_b": np.zeros(3 * d_model, np.float32),
            "proj_w": w(d_model, d_model),
            "proj_b": np.zeros(d_model, np.float32),
            "ln2_g": np.ones(d_model, np.float32),
            "ln2_b": np.zeros(d_model, np.float32),
            "ff1_w": w(ff_mult * d_model, d_model),
            "ff1_b": np.zeros(ff_mult * d_model, np.float32),
            "ff2_w": w(d_model, ff_mult * d_model),
            "ff2_b": np.zeros(d_model, np.float32),
        })
    return {"embed_w": w(vocab, d_model), "layers": layer_trees,
            "lnf_g": np.ones(d_model, np.float32),
            "lnf_b": np.zeros(d_model, np.float32),
            "head_w": w(vocab, d_model)}


def make_loss_fn(heads):
    import jax.numpy as jnp

    def _ln(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    def loss_fn(params, batch):
        tokens = batch["tokens"]                       # (B, T) int32
        x = params["embed_w"][tokens]                  # (B, T, d)
        b, t, d = x.shape
        hd = d // heads
        causal = jnp.tril(jnp.ones((t, t), bool))
        for lp in params["layers"]:
            h = _ln(x, lp["ln1_g"], lp["ln1_b"])
            qkv = h @ lp["qkv_w"].T + lp["qkv_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
            k = k.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
            v = v.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / hd ** 0.5
            s = jnp.where(causal, s, -1e30)
            a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            a = a.transpose(0, 2, 1, 3).reshape(b, t, d)
            x = x + a @ lp["proj_w"].T + lp["proj_b"]
            h2 = _ln(x, lp["ln2_g"], lp["ln2_b"])
            z = jax.nn.relu(h2 @ lp["ff1_w"].T + lp["ff1_b"])
            x = x + z @ lp["ff2_w"].T + lp["ff2_b"]
        h = _ln(x, params["lnf_g"], params["lnf_b"])
        logits = h @ params["head_w"].T                # (B, T, V)
        lse = jax.nn.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(
            logits, tokens[..., None], -1)[..., 0]
        return (lse - tgt).mean()

    import jax
    return loss_fn


# ---------------------------------------------------------------------------
# produce
# ---------------------------------------------------------------------------

def produce(args):
    need = args.dp * args.tp * max(args.fsdp, 1)
    if os.environ.get(_CHILD) != "1":
        # fresh interpreter on a forced-size virtual CPU mesh: the
        # device count is read when jax starts, so env tweaks after its
        # import are too late. JAX_PLATFORMS=cpu keeps it off the chip
        env = dict(os.environ)
        env[_CHILD] = "1"
        env["PYTHONPATH"] = os.pathsep.join(
            [REPO] + [p for p in env.get("PYTHONPATH", "")
                      .split(os.pathsep) if p])
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(
            "--xla_force_host_platform_device_count=%d" % need)
        env["XLA_FLAGS"] = " ".join(flags)
        os.execve(sys.executable,
                  [sys.executable, os.path.abspath(__file__)]
                  + sys.argv[1:], env)

    import jax

    if len(jax.devices()) < need:
        print("layout_report: %d devices forced but %d available"
              % (need, len(jax.devices())), file=sys.stderr)
        return 2
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel import (SpecLayout, create_mesh,
                                    dryrun_report,
                                    make_sharded_train_step)
    from mxnet_tpu.parallel.layout import spec_to_json

    axes = {"data": args.dp}
    if args.fsdp > 1:
        axes["fsdp"] = args.fsdp
    axes["tp"] = args.tp
    mesh = create_mesh(axes)
    layout = SpecLayout.default()
    tree = build_param_tree(args.vocab, args.d_model, args.layers,
                            args.heads)
    t0 = time.perf_counter()
    param_specs = layout.resolve_specs(tree, mesh=mesh)
    state_specs = layout.zero_specs(tree, dp=args.dp, axis="data",
                                    base=param_specs)
    import numpy as np
    batch = {"tokens": np.zeros((args.batch, args.seq), np.int32)}
    loss_fn = make_loss_fn(args.heads)
    step, params0, opt0 = make_sharded_train_step(
        loss_fn, mesh, tree, batch, param_specs=param_specs,
        state_specs=state_specs,
        grad_specs=state_specs if args.stage >= 2 else None,
        batch_specs=P("data"), lr=0.01, momentum=0.9, donate=False)
    lowered = step.__wrapped__.lower(
        params0, opt0,
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch))
    compile_s = time.perf_counter()
    hlo = lowered.compile().as_text()
    compile_s = time.perf_counter() - compile_s

    doc = dryrun_report(
        layout, tree, mesh, hlo_text=hlo,
        extra={
            "generated": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "kind": "train_dryrun",
            "model": {"net": "decoder-lm-d%d-l%d-h%d"
                      % (args.d_model, args.layers, args.heads),
                      "vocab": args.vocab, "batch": args.batch,
                      "seq": args.seq},
            "zero_stage": args.stage,
            "backend": jax.default_backend(),
            "host_cpus": os.cpu_count(),
            "compile_seconds": round(compile_s, 2),
            "resolve_seconds": round(
                time.perf_counter() - t0 - compile_s, 2),
        })
    # one more column per row: the optimizer-state spec (the weight-
    # update sharding) next to the parameter spec
    state_flat = {}

    def _collect(path, spec):
        state_flat[path] = spec
        return spec
    from mxnet_tpu.parallel.layout import _map_with_path
    _map_with_path(state_specs, _collect)
    for row in doc["params"]:
        sp = state_flat.get(row["param"])
        row["state_spec"] = spec_to_json(sp) if sp is not None else None
    return doc


# ---------------------------------------------------------------------------
# render
# ---------------------------------------------------------------------------

def render(doc, out=sys.stdout):
    w = out.write
    mesh = doc.get("mesh") or {}
    w("layout_report — mesh %s (%d devices), zero stage %s\n"
      % ("x".join("%s=%d" % kv for kv in mesh.items()),
         doc.get("devices", 0), doc.get("zero_stage", "-")))
    model = doc.get("model") or {}
    if model:
        w("model %s  batch %s seq %s\n"
          % (model.get("net"), model.get("batch"), model.get("seq")))
    w("%-28s %-14s %-14s %-22s %-22s %12s\n"
      % ("param", "shape", "role", "spec", "state_spec", "bytes/dev"))
    rows = sorted(doc.get("params") or [],
                  key=lambda r: -r.get("bytes", 0))
    for r in rows:
        w("%-28s %-14s %-14s %-22s %-22s %12d\n"
          % (r["param"][-28:], "x".join(map(str, r["shape"])),
             r["role"], json.dumps(r.get("fitted_spec")),
             json.dumps(r.get("state_spec")),
             r.get("per_device_bytes", 0)))
    w("total %d params, %.2f MB, %.2f MB/device (params)\n"
      % (len(rows), doc.get("total_bytes", 0) / 2 ** 20,
         doc.get("per_device_param_bytes", 0) / 2 ** 20))
    coll = doc.get("collectives") or {}
    w("collectives inserted: %d\n" % coll.get("total", 0))
    for op, row in (coll.get("by_op") or {}).items():
        w("  %-22s x%-4d %10.2f KB\n"
          % (op, row["count"], row["bytes"] / 1024))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="layout_report", description=__doc__.splitlines()[0])
    ap.add_argument("report", nargs="?", default=None,
                    help="render a committed layout_report JSON")
    ap.add_argument("--dp", type=int, default=8,
                    help="data-parallel mesh axis size (8)")
    ap.add_argument("--tp", type=int, default=8,
                    help="tensor-parallel mesh axis size (8)")
    ap.add_argument("--fsdp", type=int, default=1,
                    help="fsdp mesh axis size (1 = absent)")
    ap.add_argument("--stage", type=int, default=2,
                    choices=(1, 2), help="ZeRO stage to lower (2)")
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--d-model", dest="d_model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--json", default=None,
                    help="write the artifact here (atomic)")
    args = ap.parse_args(argv)

    if args.report:
        with open(args.report, encoding="utf-8") as f:
            return render(json.load(f))
    doc = produce(args)
    if isinstance(doc, int):
        return doc
    render(doc)
    if args.json:
        tmp = args.json + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(json.dumps(doc, indent=1) + "\n")
        os.replace(tmp, args.json)
        print("wrote %s" % args.json, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
