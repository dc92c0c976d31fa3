"""Performance attribution: *where* the chip time goes.

The third observability layer (telemetry = how much, tracing =
why/when): per-op/per-fusion FLOPs, HBM bytes and time, keyed back to
framework op names and fusion rules.

- :mod:`~mxnet_tpu.profiling.hlo` — optimized-HLO parser + analytic
  per-instruction cost model (stdlib-only),
- :mod:`~mxnet_tpu.profiling.ledger` — the cost ledger: build, price,
  attribute, diff,
- :mod:`~mxnet_tpu.profiling.xplane` — ``jax.profiler`` xplane
  protobuf reader (stdlib-only) + measured per-op device time,
- :mod:`~mxnet_tpu.profiling.memory` — the memory axis: static
  liveness ledger over compiled HLO (peak live bytes + ranked buffer
  table), live-array census with role tagging (per device shard), and
  the OOM postmortem artifact,
- :mod:`~mxnet_tpu.profiling.health` — the numerics axis: sync-free
  nonfinite sentry at the framework seams, gradient/update-ratio
  telemetry, loss-anomaly detection, the first-NaN postmortem, and
  drift fingerprints.

CLI: ``tools/mfu_report.py`` (table / --diff / --hlo),
``tools/memory_report.py`` (table / --diff / --capture / --hlo) and
``tools/health_report.py`` (table / --diff / --postmortem).
Env: ``MXTPU_MEMORY_CENSUS``, ``MXTPU_OOM_DUMP_PATH``,
``MXTPU_HEALTH``, ``MXTPU_HEALTH_DUMP_PATH``, ``MXTPU_HEALTH_NORMS``,
``MXTPU_HEALTH_ANOMALY_Z`` — registered in ``libinfo._ENV_VARS``,
documented in ``docs/observability.md`` ("MFU accounting & roofline",
"Memory accounting", "Model health").
"""
from __future__ import annotations

from . import hlo
from . import ledger
from . import xplane
from . import memory
from . import health
from .ledger import build_ledger, from_compiled, from_fn, mfu_estimate
from .memory import (build_memory_ledger, live_census, tag_role,
                     tag_tree, maybe_oom_postmortem, oom_postmortem)
from .health import (fingerprint_params, nan_postmortem,
                     localize_first_nonfinite, NonfiniteError)

__all__ = ["hlo", "ledger", "xplane", "memory", "health",
           "build_ledger", "from_compiled", "from_fn", "mfu_estimate",
           "build_memory_ledger", "live_census", "tag_role", "tag_tree",
           "maybe_oom_postmortem", "oom_postmortem",
           "fingerprint_params", "nan_postmortem",
           "localize_first_nonfinite", "NonfiniteError"]
