"""Model-health plane: nonfinite sentry, gradient/update telemetry,
loss-anomaly detection, first-NaN postmortem, drift fingerprints.

PRs 4-7 built four observability layers that all answer *performance*
questions (how much / why / where / which bytes); this module is the
*numerics* axis — "is the model still training correctly?". The
reference's instrument here is ``Monitor`` (per-op output statistics
through the executor monitor callback, ref: python/mxnet/monitor.py +
graph_executor.cc:1294); the TPU-native counterpart must answer the
same question WITHOUT host syncs, so it is built on the metric.py
accumulate-on-device / drain-at-read pattern in four layers:

1. **Nonfinite sentry** (:func:`check`): the framework seams —
   executor forward/backward, gluon ``Trainer.step`` gradients, the
   optimizer ``Updater``, the sharded train step — hand their output
   trees in; the sentry dispatches ONE fused nonfinite-count reduce
   per seam (a lazy device scalar, never read here) into a bounded
   pending window. :func:`step_boundary` folds only entries older
   than the window — dispatched many steps ago, so ``int()`` is a
   ready-buffer read, not a pipeline stall (the gluon trainer's two
   counts travel in its probe's table instead: layer 2). A nonzero
   fold *trips*
   the sentry: the first-NaN postmortem is written and the configured
   policy (warn / raise) applies.

2. **Training-health telemetry**: global grad norm, per-parameter-
   group weight/grad norms and update-to-weight ratios, and the
   trainer seam's two nonfinite counts — computed by ONE jitted
   program in ``Trainer._update`` (:class:`StepProbe`) that returns
   ONE float32 table: the counts, the global norm, then a
   ``[groups, 3]`` block. ``commit()`` dispatches it and queues the
   table; nothing of the step is read there. :func:`step_boundary`
   folds the table dispatched ONE boundary earlier (``_TABLE_LAG``)
   with a single read-back and publishes everything from host
   floats: trips for ``trainer_grad`` / ``trainer_param`` under the
   table's own step, the ``mx_health_*`` gauges by ``set()``, one
   ``mx_health_update_to_weight`` observation per group and step.
   Between folds a gauge shows the step before last; ``flush()``
   folds what is pending. The lag is one step on purpose: the host
   never waits on the step it has just dispatched, a trip stays
   within a step of its cause, and **this read is the only thing
   that bounds a gluon loop's run-ahead** — a device-bound loop
   waits here for step n-1, and with no wait at all it queues steps
   until device memory runs out. Loss lands through
   :func:`observe_loss` and feeds an EWMA with z-score **spike** and
   flat-line **plateau** anomaly detection on the folded (host)
   values.

3. **First-NaN postmortem** (:func:`nan_postmortem`): the memory
   axis's OOM postmortem, for numerics. When the sentry trips at an
   executor seam, :func:`localize_first_nonfinite` replays the
   executor's per-op monitor pass (every internal tensor, one jitted
   program) and BINARY-SEARCHES the topo-ordered prefix for the first
   op whose output is nonfinite — the prefix predicate "any nonfinite
   in internals[:k]" is monotone, so log2(n) tiny device reads replace
   an n-tensor transfer. One atomic artifact lands at
   ``MXTPU_HEALTH_DUMP_PATH``: offending op + named-scope attribution
   (the ``mx.<Op>`` channel the cost/memory ledgers key on), its input
   stats, the ranked per-group grad-norm table, RNG state from the
   checkpoint layer's vocabulary, and a flight-recorder snapshot.

4. **Drift fingerprints** (:func:`fingerprint_params`): a blake2b
   digest over a deterministically-ordered pytree flatten — one
   vocabulary for the bit-identical-resume tests, the chaos suite's
   bounded-drift assertions, and cross-backend ``consistency.py``
   rows. This is a *read-time* API (it materializes every leaf);
   never call it per hot-path step.

Env: ``MXTPU_HEALTH`` (0 = every hook a no-op; 1/warn = default;
raise = trip raises :class:`NonfiniteError`), ``MXTPU_HEALTH_DUMP_PATH``
(postmortem destination), ``MXTPU_HEALTH_NORMS`` (0 disables the norm
telemetry; the sentry stays), ``MXTPU_HEALTH_ANOMALY_Z`` (loss-spike
z-score threshold). CLI: ``tools/health_report.py`` (table / --diff /
--postmortem). Docs: docs/observability.md "Model health".
"""
from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import sys
import threading
import time

HEALTH_DOC_VERSION = 1
NAN_POSTMORTEM_VERSION = 1

# completed steps a sentry scalar buffers before it folds: entries
# fold _FOLD_LAG boundaries after their dispatch, so int() is a
# ready-buffer read of a long-retired tiny reduce, never a stall on
# in-flight compute (metric.py's _PENDING_WINDOW rationale, counted
# in steps here because one step may hold many per-source checks)
_FOLD_LAG = 4

# the trainer probe's table folds ONE boundary after its dispatch: at
# the boundary of step n the host fetches step n-1's table. One, on
# purpose: the host never waits on the step it has just dispatched, a
# trip or a raise stays within a step of its cause, and this fetch is
# the only thing that bounds how far a gluon loop runs ahead of the
# device (a device-bound loop waits here for step n-1; with no wait at
# all it queues steps until device memory runs out)
_TABLE_LAG = 1

# tables a loop may queue without reaching a boundary (Trainer.update
# called alone never does): commit() folds the overflow, each a read
# of a program dispatched this many commits ago
_MAX_TABLES = 8

# a nonfinite count crosses in the float32 table as two 16-bit halves,
# each exact in float32, so counts past 2**24 fold exact
_COUNT_HALF = 1 << 16
_TABLE_HEAD = 4          # grad count hi, lo; param count hi, lo


class NonfiniteError(ArithmeticError):
    """Raised at a step boundary under MXTPU_HEALTH=raise when the
    sentry folded a nonzero nonfinite count. Carries the postmortem
    document (``.postmortem``) when one was written."""

    def __init__(self, msg, postmortem=None):
        super().__init__(msg)
        self.postmortem = postmortem


# -- gates ------------------------------------------------------------------
def _parse_policy(raw):
    raw = (raw or "1").strip().lower()
    if raw in ("0", "off", "false", "no"):
        return None
    if raw in ("raise", "fatal"):
        return "raise"
    return "warn"


_policy = [_parse_policy(os.environ.get("MXTPU_HEALTH"))]
_norms = [os.environ.get("MXTPU_HEALTH_NORMS", "1") not in (
    "0", "off", "false")]


def enabled():
    """MXTPU_HEALTH gate (default on). Cached at import — the seams
    run per step, so the gate must be one list read, not an environ
    lookup."""
    return _policy[0] is not None


def policy():
    """'warn' | 'raise' | None (disabled)."""
    return _policy[0]


def set_enabled(on):
    """Flip the health plane at runtime. ``on`` may be a bool or a
    policy string ('warn'/'raise'/'0')."""
    if isinstance(on, str):
        _policy[0] = _parse_policy(on)
    else:
        _policy[0] = "warn" if on else None


def norms_enabled():
    """MXTPU_HEALTH_NORMS gate for the per-group norm telemetry."""
    return enabled() and _norms[0]


def set_norms_enabled(on):
    _norms[0] = bool(on)


def anomaly_z():
    try:
        return float(os.environ.get("MXTPU_HEALTH_ANOMALY_Z", "6"))
    except ValueError:
        return 6.0


def dump_path():
    return os.environ.get("MXTPU_HEALTH_DUMP_PATH") or \
        "nan_postmortem.json"


# -- telemetry families -----------------------------------------------------
def _lazy_met():
    from ..telemetry import metrics as _tm
    return _tm, _tm.lazy_metrics(lambda reg: {
        "nonfinite": reg.counter(
            "mx_health_nonfinite_total",
            "nonfinite (NaN/Inf) values folded by the sentry, by "
            "framework seam", labelnames=("source",)),
        "trips": reg.counter(
            "mx_health_trips_total",
            "sentry trips (first nonzero fold per burst)").labels(),
        "loss": reg.gauge(
            "mx_health_loss", "last folded training loss").labels(),
        "loss_ewma": reg.gauge(
            "mx_health_loss_ewma",
            "EWMA of the folded training loss").labels(),
        "anomalies": reg.counter(
            "mx_health_loss_anomalies_total",
            "loss anomalies detected (z-score spike / flat-line "
            "plateau)", labelnames=("kind",)),
        "grad_norm": reg.gauge(
            "mx_health_grad_norm",
            "global gradient L2 norm (lazy; folded at snapshot)"
            ).labels(),
        "group_weight": reg.gauge(
            "mx_health_weight_norm",
            "per-parameter-group weight L2 norm",
            labelnames=("group",)),
        "group_grad": reg.gauge(
            "mx_health_grad_norm_group",
            "per-parameter-group gradient L2 norm",
            labelnames=("group",)),
        "group_ratio": reg.gauge(
            "mx_health_update_ratio",
            "per-parameter-group update-to-weight norm ratio "
            "||dw||/||w||", labelnames=("group",)),
        "ratio_hist": reg.histogram(
            "mx_health_update_to_weight",
            "distribution of per-group update-to-weight ratios "
            "(dimensionless)",
            buckets=(1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0)),
    })


_met_box = []


def _met():
    """(telemetry.metrics module, built metric bundle) — memoized."""
    if not _met_box:
        _met_box.append(_lazy_met())
    tm, lazy = _met_box[0]
    return tm, lazy()


# -- sentry state -----------------------------------------------------------
class _HealthState:
    def __init__(self):
        self.lock = threading.Lock()
        self.step = 0                    # boundaries observed
        self.open = {}                   # {source: lazy count} this step
        # ONE localizer slot per source, overwritten every check: the
        # closure pins its step's inputs (weights + batch) for replay,
        # so holding one per banked entry would keep ~_FOLD_LAG
        # superseded copies of the model alive. A lagged trip replays
        # the NEWEST payload instead — nonfinite state persists across
        # steps, so the first-op attribution stands, and the pinned
        # memory is bounded to one step per seam.
        self.latest_loc = {}             # {source: localizer}
        self.pending = []                # [(step, {src: lazy count})]
        self.loss_pending = []           # [(step, scalar)]
        self.nonfinite_total = 0
        self.raised_total = 0            # nonfinites already raised for
        self.by_source = {}
        self.first_trip = None
        self.last_postmortem = -10.0     # monotonic; burst coalescing
        # loss EWMA / anomaly detection (folded host values only)
        self.loss_last = None
        self.loss_ewma = None
        self.loss_var = 0.0
        self.loss_n = 0
        self.anomalies = []              # bounded record of events
        self.plateau_run = 0
        self.plateau_fired = False
        # last folded norm table {group: {...}} + global grad norm
        self.norm_groups = {}
        self.grad_norm = None
        self.norm_pending = []           # [(step, groups, table)]
        self.last_doc = None             # most recent postmortem doc


_state = _HealthState()

# loss EWMA decay + anomaly warmup/plateau knobs (docs/observability.md
# "Model health" documents the semantics; the z threshold is the env)
_EWMA_ALPHA = 0.05
_ANOMALY_WARMUP = 20
_PLATEAU_EPS = 1e-5
_PLATEAU_STEPS = 25


def _nonfinite_count(tree):
    """One fused lazy device scalar: total nonfinite values across the
    float leaves of ``tree``. Dispatch only — never read here."""
    import jax
    import jax.numpy as jnp

    total = None
    for leaf in jax.tree_util.tree_leaves(tree):
        data = getattr(leaf, "_data", leaf)
        dt = getattr(data, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.inexact):
            continue
        c = jnp.sum(~jnp.isfinite(data))
        total = c if total is None else total + c
    return total


def _accumulate(source, scalar, localize=None):
    """Bank a lazy nonfinite-count scalar into this step's per-source
    bucket (a lazy add — one scalar per source per step on device)."""
    source = str(source)
    st = _state
    with st.lock:
        prev = st.open.get(source)
        st.open[source] = scalar if prev is None else prev + scalar
        if localize is not None:
            st.latest_loc[source] = localize


def check(source, tree, localize=None):
    """Sentry seam: count nonfinites in ``tree`` as a lazy device
    scalar and accumulate it into this step's per-source bucket.
    ``localize`` is an optional zero-arg callable returning a
    first-op localization dict (the executor seams pass a closure
    over :func:`localize_first_nonfinite`); it is invoked only if the
    bucket folds nonzero. No-op when MXTPU_HEALTH=0."""
    if not enabled():
        return
    total = _nonfinite_count(tree)
    if total is None:
        return
    _accumulate(source, total, localize)


def check_scalar(source, value, localize=None):
    """Sentry seam for a single scalar (a sharded step's loss)."""
    check(source, [value], localize=localize)


def observe_loss(value):
    """Buffer a (possibly lazy) per-step training loss; folded
    _FOLD_LAG boundaries later into the EWMA + anomaly detector.
    No-op when disabled."""
    if not enabled():
        return
    data = getattr(value, "_data", value)
    st = _state
    with st.lock:
        st.loss_pending.append((st.step, data))


def _fold_entries(entries, boundary=None):
    """Fold ready sentry step-buckets to host; nonzero counts trip.
    ``boundary`` names the boundary doing the folding (trainer /
    module_fit / sharded_train_step / flush) — recorded on the trip
    so triage knows which loop surfaced it."""
    for step, by_source in entries:
        for source, scalar in by_source.items():
            try:
                n = int(scalar)
            except (TypeError, ValueError, OverflowError):
                continue
            if n > 0:
                _trip(step, source, n, boundary=boundary)


def _trip(step, source, count, boundary=None):
    st = _state
    tm, met = _met()
    with st.lock:
        localize = st.latest_loc.get(source)
        st.nonfinite_total += count
        st.by_source[source] = st.by_source.get(source, 0) + count
        first = st.first_trip is None
        if first:
            st.first_trip = {"step": step, "source": source,
                             "count": count, "ts": time.time(),
                             "folded_by": boundary}
    if tm.enabled():
        met["nonfinite"].labels(source=source).inc(count)
    doc = None
    now = time.monotonic()
    with st.lock:
        burst = now - st.last_postmortem < 1.0
        if not burst:
            st.last_postmortem = now
    if tm.enabled() and not burst:
        # one trip event per burst (matches the postmortem coalescing,
        # so dashboards count bursts, not every poisoned step)
        met["trips"].inc()
    if not burst:
        # one artifact per failure burst (the OOM postmortem's
        # coalescing rule): a poisoned run trips every step
        doc = nan_postmortem(step=step, source=source, count=count,
                             localize=localize)
        with st.lock:
            st.last_doc = doc
    # the raise policy is enforced at step_boundary(), never here: a
    # window-overflow fold inside a seam's dispatch path must not turn
    # that seam into the raise site
    print("[mxtpu] health: nonfinite values detected: %d at seam %r "
          "(step %d)%s"
          % (count, source, step,
             " — postmortem at %s" % doc.get("path")
             if doc and doc.get("path") else ""),
          file=sys.stderr, flush=True)


def _fold_loss(step, value):
    try:
        x = float(value)
    except (TypeError, ValueError, OverflowError):
        return
    st = _state
    tm, met = _met()
    kind = None
    with st.lock:
        st.loss_last = x
        st.loss_n += 1
        if x != x or x in (float("inf"), float("-inf")):
            pass  # nonfinite loss: the sentry seam owns that signal
        elif st.loss_ewma is None:
            st.loss_ewma = x
        else:
            dev = x - st.loss_ewma
            std = st.loss_var ** 0.5
            if st.loss_n > _ANOMALY_WARMUP:
                if std > 0 and abs(dev) / std > anomaly_z():
                    kind = "spike"
                elif abs(dev) <= _PLATEAU_EPS * max(abs(st.loss_ewma),
                                                   1e-12):
                    st.plateau_run += 1
                    if st.plateau_run >= _PLATEAU_STEPS and \
                            not st.plateau_fired:
                        kind = "plateau"
                        st.plateau_fired = True
                else:
                    st.plateau_run = 0
                    st.plateau_fired = False
            st.loss_ewma += _EWMA_ALPHA * dev
            st.loss_var = ((1 - _EWMA_ALPHA) *
                           (st.loss_var + _EWMA_ALPHA * dev * dev))
        if kind is not None:
            st.anomalies.append({"step": step, "kind": kind,
                                 "loss": x, "ewma": st.loss_ewma})
            del st.anomalies[:-32]
        ewma = st.loss_ewma
    if tm.enabled():
        met["loss"].set(x)
        if ewma is not None:
            met["loss_ewma"].set(ewma)
        if kind is not None:
            met["anomalies"].labels(kind=kind).inc()


# -- per-group norm telemetry ----------------------------------------------
_GROUP_SUFFIXES = ("_weight", "_bias", "_gamma", "_beta", "_mean",
                   "_var")


def group_of(name):
    """Parameter-group key: the parameter name with its trailing
    role suffix stripped (dense0_weight and dense0_bias share the
    'dense0' group, matching how users reason about layers)."""
    for suf in _GROUP_SUFFIXES:
        if name.endswith(suf):
            return name[:-len(suf)] or name
    return name


@functools.lru_cache(maxsize=64)
def _plan(names):
    """``names`` (a trainer's leaves, in order) -> (group names in
    first-occurrence order, each leaf's group as an index into them).
    Resolved once per tuple of names: a loop hands the same one every
    step."""
    index = {}
    group_idx = tuple(index.setdefault(group_of(n), len(index))
                      for n in names)
    return tuple(index), group_idx


@functools.lru_cache(maxsize=64)
def _group_series(groups):
    """[(weight, grad, ratio) gauge series] per group of ``groups``.
    Series are zeroed in place by a registry reset, so the handles
    stay valid for the process."""
    _tm, met = _met()
    return [(met["group_weight"].labels(group=g),
             met["group_grad"].labels(group=g),
             met["group_ratio"].labels(group=g)) for g in groups]


@functools.lru_cache(maxsize=64)
def _probe_program(group_idx, want_norms):
    """One jitted program computing the WHOLE per-step probe and
    returning it as ONE float32 table, so a fold is one read-back:

        [grad_nf hi, lo, param_nf hi, lo]          (_TABLE_HEAD)
        [grad_norm]                                 (``want_norms``)
        [weight_norm, grad_norm, update_ratio] * G  (``want_norms``)

    ``group_idx`` is the parameter→group partition as INDICES (not
    names — two nets whose layers differ only in auto-generated name
    counters share one executable; jit itself re-specializes on leaf
    shapes/dtypes), group g's row at 3g. After the first step this is
    ONE cached dispatch per step — XLA fuses the dozens of tiny
    reduces the eager version would dispatch one by one."""
    import jax
    import jax.numpy as jnp

    def halves(count):
        return [(count // _COUNT_HALF).astype(jnp.float32),
                (count % _COUNT_HALF).astype(jnp.float32)]

    def fn(ws, gs, olds):
        gnf = pnf = jnp.int32(0)
        total_g2 = jnp.float32(0)
        acc = [[jnp.float32(0)] * 3 for _ in range(max(group_idx) + 1)]
        for gi, w, g, old in zip(group_idx, ws, gs, olds):
            w32 = w.astype(jnp.float32)
            g32 = g.astype(jnp.float32)
            gnf = gnf + jnp.sum(~jnp.isfinite(g32))
            pnf = pnf + jnp.sum(~jnp.isfinite(w32))
            if want_norms:
                w2 = jnp.sum(w32 * w32)
                g2 = jnp.sum(g32 * g32)
                u32 = w32 - old.astype(jnp.float32)
                u2 = jnp.sum(u32 * u32)
                total_g2 = total_g2 + g2
                a = acc[gi]
                a[0] = a[0] + w2
                a[1] = a[1] + g2
                a[2] = a[2] + u2
        table = halves(gnf) + halves(pnf)
        if want_norms:
            table.append(jnp.sqrt(total_g2))
            for a in acc:
                table += [jnp.sqrt(a[0]), jnp.sqrt(a[1]),
                          jnp.sqrt(a[2]) / jnp.maximum(
                              jnp.sqrt(a[0]), 1e-12)]
        return jnp.stack(table)

    return jax.jit(fn)


class StepProbe:
    """Per-step probe over a trainer's (weight, grad, pre-update
    weight) triples. ``add`` is a python list append; ``commit``
    runs the cached jitted probe program — one dispatch — and queues
    its one table for the boundary that folds it, ``_TABLE_LAG``
    steps later. Nothing of a step is read here."""

    __slots__ = ("_names", "_ws", "_gs", "_olds", "step", "_norms")

    def __init__(self, step, want_norms):
        self.step = step
        self._norms = want_norms
        self._names = []
        self._ws = []
        self._gs = []
        self._olds = []

    @property
    def wants_norms(self):
        """Whether the caller should hand pre-update weights to
        ``add`` — with MXTPU_HEALTH_NORMS=0 capturing them would pin
        a full superseded model copy the program never reads."""
        return self._norms

    def add(self, name, weight, grad, weight_before=None):
        self._names.append(str(name))
        self._ws.append(getattr(weight, "_data", weight))
        self._gs.append(getattr(grad, "_data", grad))
        self._olds.append(getattr(weight_before, "_data",
                                  weight_before))

    def commit(self):
        if not self._ws:
            return
        want = self._norms and all(o is not None for o in self._olds)
        groups, group_idx = _plan(tuple(self._names))
        try:
            table = _probe_program(group_idx, want)(
                self._ws, self._gs, self._olds if want else self._ws)
        except Exception:  # noqa: BLE001 — an unjittable leaf (host
            # numpy of odd dtype) degrades to the plain sentry count
            check("trainer_grad", self._gs)
            check("trainer_param", self._ws)
            return
        st = _state
        with st.lock:
            st.norm_pending.append(
                (self.step, groups if want else None, table))
            over = st.norm_pending[:-_MAX_TABLES]
            del st.norm_pending[:-_MAX_TABLES]
        # only a loop that never reaches a boundary gets here
        _fold_tables(over)


def step_probe(step=None):
    """A :class:`StepProbe` for this step, or None when the health
    plane is off entirely. With MXTPU_HEALTH_NORMS=0 the probe still
    runs the (cheaper) sentry-only program."""
    if not enabled():
        return None
    return StepProbe(_state.step if step is None else step,
                     norms_enabled())


# optimizer Updater calls inside a probe-covered trainer loop skip
# their own per-call check — the probe's one fused program already
# sees every (grad, weight) pair this step
_covered = threading.local()


def updater_is_covered():
    return getattr(_covered, "depth", 0) > 0


@contextlib.contextmanager
def updater_covered():
    _covered.depth = getattr(_covered, "depth", 0) + 1
    try:
        yield
    finally:
        _covered.depth -= 1


def _fold_tables(entries, boundary=None):
    """Fold queued probe tables, oldest first, with ONE read-back
    each. Everything the trainer seam publishes is set here from host
    floats: the per-group norm table and the global gradient norm
    (state and gauges: the newest folded wins), one histogram
    observation per group and folded step, and a trip for a nonzero
    ``trainer_grad`` / ``trainer_param`` count under the table's own
    step. The norms land before the trip, so its postmortem ranks the
    failing step's gradients."""
    if not entries:
        return
    import jax

    st = _state
    tm, met = _met()
    for step, groups, table in entries:
        host = jax.device_get(table).tolist()  # the step's one read-back
        if groups is not None:
            body = host[_TABLE_HEAD + 1:]
            rows = [body[i:i + 3] for i in range(0, len(body), 3)]
            with st.lock:
                st.grad_norm = host[_TABLE_HEAD]
                st.norm_groups = {
                    grp: {"weight_norm": w, "grad_norm": g,
                          "update_ratio": r}
                    for grp, (w, g, r) in zip(groups, rows)}
            if tm.enabled():
                met["grad_norm"].set(host[_TABLE_HEAD])
                for (sw, sg, sr), (w, g, r) in zip(
                        _group_series(groups), rows):
                    sw.set(w)
                    sg.set(g)
                    sr.set(r)
                    met["ratio_hist"].observe(r)
        for source, hi, lo in (("trainer_grad", host[0], host[1]),
                               ("trainer_param", host[2], host[3])):
            n = int(hi) * _COUNT_HALF + int(lo)
            if n > 0:
                _trip(step, source, n, boundary=boundary)


# -- boundaries / folding ---------------------------------------------------
def step_boundary(source="trainer", span=None):
    """Close one health step: bank this step's per-source buckets,
    fold the trainer probe's table dispatched ``_TABLE_LAG`` boundaries
    ago with one read-back (the loop's back-pressure: the host waits
    here until the device has finished that step, never the one in
    flight), fold every banked bucket ≥ _FOLD_LAG boundaries old
    (ready buffers — their reduces retired steps ago), and stamp the
    folded health attrs on the caller's step ``span`` so trace_merge
    can show which rank went unhealthy. Trips (and the raise policy)
    surface HERE, at the boundary, never inside a seam's dispatch
    path."""
    if not enabled():
        return None
    st = _state
    with st.lock:
        if st.open:
            st.pending.append((st.step, st.open))
            st.open = {}
        st.step += 1
        horizon = st.step - _FOLD_LAG
        ready = [e for e in st.pending if e[0] < horizon]
        if ready:
            st.pending = st.pending[len(ready):]
        loss_ready = [e for e in st.loss_pending if e[0] < horizon]
        if loss_ready:
            st.loss_pending = st.loss_pending[len(loss_ready):]
        tables = [e for e in st.norm_pending
                  if e[0] < st.step - _TABLE_LAG]
        if tables:
            st.norm_pending = st.norm_pending[len(tables):]
    for step, v in loss_ready:
        _fold_loss(step, v)
    _fold_tables(tables, boundary=source)
    _fold_entries(ready, boundary=source)
    if span is not None:
        # host state as of the folds above. Only FINITE values land:
        # span attrs flow verbatim into chrome trace event args, where
        # a bare NaN literal would make Perfetto reject the whole
        # document (the nonfinite signal itself rides
        # health_nonfinite)
        with st.lock:
            total = st.nonfinite_total
            attrs = (("loss_ewma", st.loss_ewma),
                     ("grad_norm", st.grad_norm))
        span.set_attr("health_nonfinite", total)
        for key, v in attrs:
            if v is not None and v == v and \
                    v not in (float("inf"), float("-inf")):
                span.set_attr(key, round(v, 6))
    if policy() == "raise":
        with st.lock:
            fresh = st.nonfinite_total > st.raised_total
            st.raised_total = st.nonfinite_total
            trip, doc = st.first_trip, st.last_doc
        # raise only for NEWLY folded nonfinites: a caller that caught
        # the error, skipped the poisoned batch and kept training must
        # not be re-raised at every later (clean) boundary
        if fresh:
            raise NonfiniteError(
                "nonfinite values detected (total %d, first at seam "
                "%r step %s)" % (st.nonfinite_total,
                                 (trip or {}).get("source"),
                                 (trip or {}).get("step")),
                postmortem=doc)
    return None


def flush():
    """Force-fold EVERYTHING pending (a host sync): end-of-run
    verdicts, tests, artifact embedding. Returns :func:`snapshot_doc`.
    """
    st = _state
    with st.lock:
        if st.open:
            st.pending.append((st.step, st.open))
            st.open = {}
        ready, st.pending = st.pending, []
        loss_ready, st.loss_pending = st.loss_pending, []
        tables, st.norm_pending = st.norm_pending, []
    for step, v in loss_ready:
        _fold_loss(step, v)
    _fold_tables(tables, boundary="flush")
    _fold_entries(ready, boundary="flush")
    return snapshot_doc(fold=False)


def snapshot_doc(fold=True):
    """Point-in-time health summary document (the ``health`` embed in
    bench artifacts; health_report's table input)."""
    if fold:
        return flush()
    st = _state
    with st.lock:
        verdict = ("disabled" if not enabled() else
                   "nonfinite" if st.nonfinite_total else "clean")
        doc = {
            "version": HEALTH_DOC_VERSION,
            "kind": "health_summary",
            "enabled": enabled(),
            "policy": policy(),
            "steps": st.step,
            "sentry": {
                "verdict": verdict,
                "nonfinite_total": st.nonfinite_total,
                "by_source": dict(st.by_source),
                "first_trip": (dict(st.first_trip)
                               if st.first_trip else None),
            },
            "loss": {
                "last": st.loss_last,
                "ewma": st.loss_ewma,
                "std": (st.loss_var ** 0.5
                        if st.loss_ewma is not None else None),
                "observed": st.loss_n,
                "anomalies_total": len(st.anomalies),
                "anomalies": list(st.anomalies[-8:]),
            },
            "norms": {
                "grad_norm": st.grad_norm,
                "by_group": {g: dict(v)
                             for g, v in st.norm_groups.items()},
            },
        }
    return doc


def reset():
    """Drop all sentry/loss/norm state (test isolation; the telemetry
    families reset via the registry)."""
    global _state
    _state = _HealthState()


# -- first-NaN localization -------------------------------------------------
def localize_first_nonfinite(executor, arg_vals, aux_vals, key,
                             training=False):
    """Name the FIRST op (topo order) whose output holds a nonfinite.

    Replays the executor's per-op monitor pass once (the reference's
    ExecuteMonCallback internals program — every internal tensor, one
    jitted call, values stay on device), then binary-searches the
    prefix predicate "any nonfinite among internals[:k]". The
    predicate is monotone in k, and each probe reduces the candidate
    prefix on device to ONE bool — log2(n) 1-byte reads instead of
    transferring n tensors. Returns a dict naming the op through the
    named-scope attribution channel, with input/output stats, or None
    when every internal is finite (e.g. the nonfinite appeared only
    in backward)."""
    import jax.numpy as jnp
    import numpy as np

    names, vals = executor._monitor_internals(bool(training))(
        arg_vals, aux_vals, key)
    flags = []
    for v in vals:
        if jnp.issubdtype(v.dtype, jnp.inexact):
            flags.append(jnp.any(~jnp.isfinite(v)))
        else:
            flags.append(jnp.asarray(False))

    probes = [0]

    def prefix_bad(k):
        probes[0] += 1
        return bool(jnp.any(jnp.stack(flags[:k])))  # postmortem sync

    n = len(vals)
    if n == 0 or not prefix_bad(n):
        return None
    lo, hi = 1, n          # invariant: prefix_bad(hi) is True
    while lo < hi:
        mid = (lo + hi) // 2
        if prefix_bad(mid):
            hi = mid
        else:
            lo = mid + 1
    idx = lo - 1
    tensor_name = names[idx]
    node, out_k = executor._symbol.get_internals()._outputs[idx]

    def stats(arr):
        a = np.asarray(arr)
        out = {"shape": list(a.shape), "dtype": str(a.dtype)}
        if np.issubdtype(a.dtype, np.inexact) and a.size:
            finite = a[np.isfinite(a)]
            out["nonfinite"] = int(a.size - finite.size)
            if finite.size:
                out["min"] = float(finite.min())
                out["max"] = float(finite.max())
                out["mean"] = float(finite.mean())
        return out

    by_name = dict(zip(names, vals))
    inputs = []
    for child, k in node.inputs:
        suffix = "_output" if k == 0 else "_output%d" % k
        val = by_name.get(child.name + suffix)
        if val is None:   # graph input (variable / aux)
            val = arg_vals.get(child.name, aux_vals.get(child.name))
        entry = {"name": child.name}
        if val is not None:
            entry.update(stats(val))
        inputs.append(entry)
    return {
        "index": idx,
        "tensor": tensor_name,
        "node": node.name,
        "op": node.op,
        "named_scope": "mx.%s" % node.op if node.op else node.name,
        "attrs": {k: v for k, v in (node.attrs or {}).items()
                  if not k.startswith("__")},
        "probes": probes[0],
        "internals": n,
        "output": stats(vals[idx]),
        "inputs": inputs,
    }


# -- postmortem -------------------------------------------------------------
# zero-arg providers of extra postmortem context, run (guarded) at
# artifact time: Module.fit registers the data iterator's state_dict
# so the artifact pins the exact batch position, mirroring what
# CheckpointManager.save would have captured
_context_providers = {}


def register_postmortem_context(name, provider):
    """Register ``provider()`` to contribute a section to every future
    NaN postmortem under key ``name``; pass None to unregister."""
    if provider is None:
        _context_providers.pop(str(name), None)
    else:
        _context_providers[str(name)] = provider


def nan_postmortem(step=None, source=None, count=None, error=None,
                   localize=None, path=None, extra=None):
    """Write the first-NaN artifact: seam + first offending op (when a
    localizer is available), folded health state (loss EWMA +
    anomalies, ranked grad-norm table), RNG state from the checkpoint
    layer's vocabulary, and a flight-recorder snapshot. Atomic write;
    every section individually guarded — a postmortem must never raise
    over the numerics failure it documents."""
    doc = {"version": NAN_POSTMORTEM_VERSION, "kind": "nan_postmortem",
           "ts": time.time()}
    if source:
        doc["source"] = str(source)[:120]
    if step is not None:
        doc["step"] = int(step)
    # the artifact is written when the lagged fold TRIPS, up to
    # _FOLD_LAG boundaries after the failing step — the RNG/iterator
    # sections below are live state at capture time, offset by
    # (captured_at_step - step) from the failure (triage reads the
    # two fields together; the sync-free contract rules out capturing
    # them inside the hot step itself)
    doc["captured_at_step"] = _state.step
    doc["fold_lag"] = _FOLD_LAG
    if count is not None:
        doc["nonfinite_count"] = int(count)
    if error is not None:
        doc["error"] = str(error)[:800]
    if callable(localize):
        try:
            doc["first_op"] = localize()
        except Exception as e:  # noqa: BLE001 — replay can itself NaN out
            doc["first_op_error"] = repr(e)[:200]
    try:
        # folded state only: a trainer trip folds its own step's norm
        # table just before it lands here
        summary = snapshot_doc(fold=False)
        doc["loss"] = summary["loss"]
        norms = summary["norms"]
        ranked = sorted(
            ((g, v) for g, v in norms["by_group"].items()
             if "grad_norm" in v),
            key=lambda kv: -kv[1]["grad_norm"])
        doc["grad_norms"] = {
            "global": norms["grad_norm"],
            "ranked": [{"group": g, **v} for g, v in ranked[:25]],
        }
        doc["sentry"] = summary["sentry"]
    except Exception as e:  # noqa: BLE001
        doc["health_state_error"] = repr(e)[:200]
    try:
        # the checkpoint layer's vocabulary (CheckpointManager saves
        # exactly these two states): the framework key chain is tiny
        # and lands verbatim; numpy's 624-word Mersenne state is
        # summarized. Captured at ARTIFACT time — see captured_at_step
        from .. import random as random_mod
        import numpy as np
        mx_state = random_mod.get_state()
        np_state = np.random.get_state()
        doc["rng"] = {
            "mx_key": np.asarray(mx_state).ravel().tolist(),
            "numpy": {"algo": str(np_state[0]),
                      "pos": int(np_state[2])},
        }
    except Exception as e:  # noqa: BLE001
        doc["rng_error"] = repr(e)[:200]
    for name, provider in list(_context_providers.items()):
        try:
            doc[name] = provider()
        except Exception as e:  # noqa: BLE001 — context is best-effort
            doc[name + "_error"] = repr(e)[:200]
    if extra:
        doc.update(extra)
    try:
        from ..tracing import flight as _flight
        doc["flight"] = _flight.snapshot(max_spans=10)
    except Exception as e:  # noqa: BLE001
        doc["flight_error"] = repr(e)[:200]
    path = path or dump_path()
    try:
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w", encoding="utf-8") as f:
            # allow_nan=False via pre-sanitization: NaN grad norms ARE
            # this artifact's payload, but bare NaN literals would make
            # the file unreadable to strict parsers (jq, other
            # languages) — they land as "nan"/"inf" strings
            json.dump(_json_sanitize(doc), f)
        os.replace(tmp, path)
        doc["path"] = path
    except OSError as e:
        doc["write_error"] = repr(e)[:200]
        print("[mxtpu] NaN postmortem write failed: %r" % (e,),
              file=sys.stderr, flush=True)
    return doc


def _json_sanitize(v):
    """Nonfinite floats -> their repr ("nan"/"inf" strings), so the
    artifact stays RFC-valid JSON for strict parsers. (Sibling guards:
    telemetry/export._json_safe for the chrome merge, tracing/
    export's local _finite for the standalone counter track.)"""
    if isinstance(v, float) and (
            v != v or v in (float("inf"), float("-inf"))):
        return repr(v)
    if isinstance(v, dict):
        return {k: _json_sanitize(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_json_sanitize(x) for x in v]
    return v


# -- drift fingerprints -----------------------------------------------------
def iter_named_leaves(tree):
    """Deterministically-ordered ``[(path, leaf), ...]`` flatten of a
    dict/list/tuple pytree (None leaves skipped, paths "/"-joined and
    sorted lexicographically). THE one canonical walk: fingerprints
    hash it and the elastic checkpoint/reshard substrate keys its
    payload with it, so a checkpoint's keys and a fingerprint's paths
    agree by construction — two implementations could drift apart and
    silently break the bit-identical-resume contract."""
    leaves = []

    def walk(node, path):
        if isinstance(node, dict):
            for k in sorted(node, key=str):
                walk(node[k], path + (str(k),))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, path + (str(i),))
        elif node is None:
            return
        else:
            leaves.append(("/".join(path), node))

    walk(tree, ())
    leaves.sort(key=lambda kv: kv[0])
    return leaves


def fingerprint_params(tree, digest_size=16):
    """blake2b digest over a deterministically-ordered flatten of a
    parameter pytree (dict/list/tuple of NDArray / jax / numpy
    leaves). Leaf paths sort lexicographically, and each leaf
    contributes path + shape + dtype + raw bytes, so two trees
    fingerprint equal iff they hold bit-identical values under the
    same names — the shared vocabulary for bit-identical-resume,
    chaos bounded-drift, and cross-backend consistency rows.
    Materializes every leaf to host: a checkpoint/verify-time API,
    never a per-step one."""
    import numpy as np

    leaves = iter_named_leaves(tree)
    h = hashlib.blake2b(digest_size=int(digest_size))
    for path, leaf in leaves:
        data = getattr(leaf, "_data", leaf)
        a = np.ascontiguousarray(np.asarray(data))
        h.update(path.encode("utf-8"))
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()
