"""Iteration-level continuous batching for generative decode
(Orca-style: the batch is re-formed every *token*, not every request).

The PR 10 gateway batches one-shot requests: a request joins exactly
one executed batch. Generation breaks that — a 500-token request and
a 5-token request in the same fixed batch would chain the short one
to the long one's tail. Here each replica lane re-forms its in-flight
batch every decode step:

- **join**: waiting requests prefill (one padded prompt each through
  the causal stack, K/V scattered into their pool blocks) and enter
  the running set *between* steps — the very next decode step carries
  them;
- **step**: one token for every running request — tokens/positions/
  block tables stacked to the smallest warmed batch bucket, one
  compiled ``decode`` call, next greedy tokens back;
- **leave**: a request that hits EOS or its ``max_new_tokens`` budget
  retires immediately — its blocks return to the pool *that step*,
  its reply stream closes, and the batch shrinks without stalling
  anyone else.

Admission is the gateway's fast-reject doctrine extended to cache
bytes: a request reserves its worst-case block budget
(``blocks_for(prompt + max_new_tokens)``) at submit; when no lane can
cover it the request raises :class:`RejectedError` with reason
``kv_cache_full`` — in the caller's thread, in microseconds, before
anything queues.

Host syncs: the scheduler's per-step device read is
:meth:`GenLane._host_tokens` — the token *reply transfer*, which by
definition must reach the host (the streaming iterator hands tokens
to clients). Everything else on the step path is host bookkeeping —
the MXL002 lint scope covers it.

**Decode failover** (docs/robustness.md "Decode failover"): a lane
that dies (:meth:`GenLane.kill`), drains (``scale_to`` shrink), or
loses its device to a cluster reclaim evacuates its in-flight
generations instead of failing them — one code path for planned and
unplanned loss. Each evacuated request's KV blocks are salvaged
through :class:`~.migrate.KVMigrator` and landed on a surviving
lane's pool (``mode=migrate``); when the blocks are unsalvageable
the survivor deterministically replays prompt + accepted tokens
(``mode=replay``) — the greedy==reference contract makes the
continuation token-identical either way, and the replayable
``stream()`` iterator gives consumers one seamless sequence. A
per-request budget (``MXTPU_GEN_MAX_RECOVERIES``, backoff base
``MXTPU_GEN_RECOVERY_BACKOFF_MS``) degrades to a fast
``RejectedError(reason="lane_lost")`` when exhausted; re-admission
re-reserves blocks atomically on the target pool, so a full pool
queues the recovery rather than double-booking.
"""
from __future__ import annotations

import threading
from collections import deque

import numpy as np

from ... import tracing
from ...telemetry import metrics as _tm
from ...tracing import clock
from ...base import MXNetError, get_env
from ..batcher import RejectedError, ServingError
from ..variants import default_buckets, pick_bucket
from .kvcache import BlockPool, BlockTable
from .migrate import KVMigrator

_met = _tm.lazy_metrics(lambda reg: {
    "requests": reg.counter(
        "mx_serving_generate_requests_total",
        "admitted generation requests", labelnames=("model",)),
    "rejected": reg.counter(
        "mx_serving_generate_rejected_total",
        "fast-rejected generation requests",
        labelnames=("model", "reason")),
    "tokens": reg.counter(
        "mx_serving_generate_tokens_total",
        "tokens through the decode plane (prefill = prompt tokens "
        "consumed, decode = tokens generated)",
        labelnames=("model", "phase")),
    "steps": reg.counter(
        "mx_serving_generate_steps_total",
        "compiled step executions", labelnames=("model", "phase")),
    "inflight": reg.gauge(
        "mx_serving_generate_inflight",
        "requests in the running decode batch",
        labelnames=("model", "lane")),
    # SAME family the one-shot gateway writes: the elastic autoscaler
    # reads mx_serving_queue_depth{model} for its pressure signal, and
    # a generator that never wrote it would read as eternally idle —
    # the policy would drain healthy decode lanes under load
    "depth": reg.gauge(
        "mx_serving_queue_depth",
        "requests pending in the model queue", labelnames=("model",)),
    # phase = steady | recover: the autoscaler (and anyone reading
    # latency SLOs) can see a failover stall for what it is instead
    # of mistaking it for steady-state degradation
    "ttft": reg.histogram(
        "mx_serving_generate_ttft_seconds",
        "submit -> first token (prefill + queue)",
        labelnames=("model", "phase")),
    "inter_token": reg.histogram(
        "mx_serving_generate_inter_token_seconds",
        "gap between consecutive streamed tokens of one request",
        labelnames=("model", "phase")),
    "recoveries": reg.counter(
        "mx_serving_gen_recoveries_total",
        "in-flight generations recovered onto a surviving lane "
        "(migrate = KV blocks salvaged, replay = deterministic "
        "re-decode of prompt + accepted tokens)",
        labelnames=("model", "mode")),
    "cache_blocks": reg.gauge(
        "mx_serving_generate_cache_blocks",
        "block-pool state per lane",
        labelnames=("model", "lane", "state")),
    "occupancy": reg.histogram(
        "mx_serving_generate_cache_occupancy",
        "used fraction of the block pool, sampled at every decode "
        "step", labelnames=("model",),
        buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0)),
})


class GenRequest:
    """One generation request + its streaming reply.

    ``stream()`` yields token ids as the scheduler emits them;
    ``result(timeout)`` blocks for the full greedy completion. Either
    raises the serving-side error if the request failed."""

    __slots__ = ("model", "prompt", "max_new_tokens", "trace_ctx",
                 "submit_ns", "first_token_ns", "last_token_ns",
                 "tokens", "token_spans", "step_meta", "table",
                 "next_pos", "reserved_blocks", "finish_reason",
                 "recoveries", "recover_spans", "admit_ns",
                 "kv_wait_ns", "queue_cause", "prefill_exec_ns",
                 "prompt_pad", "_kv_wait_t0", "_recover_cause",
                 "_salvage", "_recover_t0", "_recovered", "_cv",
                 "_done", "_error")

    def __init__(self, model, prompt, max_new_tokens, trace_ctx):
        self.model = model
        self.prompt = np.asarray(prompt, np.int32).ravel()
        self.max_new_tokens = int(max_new_tokens)
        self.trace_ctx = trace_ctx
        self.submit_ns = clock.now_ns()
        self.first_token_ns = 0
        self.last_token_ns = 0
        self.tokens = []
        self.token_spans = []
        self.step_meta = []       # (interleave_ns, rows, bucket)/token
        self.table = None
        self.next_pos = 0
        self.reserved_blocks = 0
        self.finish_reason = None
        self.recoveries = 0       # times this request survived a lane
        self.recover_spans = []   # (start_ns, end_ns, attrs) per rescue
        # tail-attribution decision events (profiling/tailpath.py):
        # when the request was first admitted, how long its admission
        # sat blocked on KV budget, and the dominant queue-wait cause
        self.admit_ns = 0
        self.kv_wait_ns = 0
        self.queue_cause = None
        self.prefill_exec_ns = 0
        self.prompt_pad = 0
        self._kv_wait_t0 = 0
        self._recover_cause = None
        self._salvage = None      # KV blocks gathered off a dead lane
        self._recover_t0 = 0
        self._recovered = False   # next emit is the post-rescue token
        self._cv = threading.Condition(threading.Lock())
        self._done = threading.Event()
        self._error = None

    def done(self):
        return self._done.is_set()

    def stream(self):
        """Iterate token ids as they are generated (the streaming
        reply). Replayable: every consumer streams from the first
        token, so a late (or second) reader sees the whole completion
        instead of hanging. Raises on serving-side failure."""
        i = 0
        while True:
            with self._cv:
                while i >= len(self.tokens) and not self._done.is_set():
                    self._cv.wait()
                if i >= len(self.tokens):
                    if self._error is not None:
                        raise self._error
                    return
                tok = self.tokens[i]
            yield tok
            i += 1

    def result(self, timeout=None):
        """Block for the full completion: list of generated token ids."""
        if not self._done.wait(timeout):
            raise ServingError(
                f"generate: request on {self.model!r} timed out after "
                f"{timeout}s (still queued or decoding)")
        if self._error is not None:
            raise self._error
        return list(self.tokens)

    def _push_token(self, tok):
        with self._cv:
            self.tokens.append(tok)
            self._cv.notify_all()

    def _finish(self, error=None):
        # error and done flip under the stream lock: a consumer that
        # checked `_done` while we were between the two writes would
        # wait() forever on a request that already failed — the
        # post-death stream() must observe the terminal error promptly
        with self._cv:
            self._error = error
            self._done.set()
            self._cv.notify_all()


class GenLane:
    """One decode lane: a device-pinned compiled model + block pool +
    the scheduler thread that re-forms its batch every step."""

    def __init__(self, model, idx, device, steps, pool):
        self._model = model
        self.idx = idx
        self.device = device
        self.steps = steps
        self.pool = pool
        self.waiting = deque()
        self.running = []
        self._thread = None
        # a retiring lane takes no new admissions and EVACUATES its
        # waiting+running requests onto the surviving lanes (migrate/
        # replay), then exits so the pool can be released — planned
        # scale-in, chaos kill, and ledger reclaim are one code path
        self.retiring = False
        self.cause = None        # why the lane went away (kill/reclaim)
        self.finalized = False   # pool closed + lane removed (once)

    def start(self):
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"mxtpu-generate-{self._model.name}-l{self.idx}")
        self._thread.start()

    def join(self, timeout=None):
        if self._thread is not None:
            self._thread.join(timeout)

    def kill(self, cause=None):
        """SIGKILL-equivalent lane loss (the chaos seam; also where a
        cluster reclaim revoking this lane's device funnels): stop
        scheduling immediately and evacuate every in-flight
        generation onto the surviving lanes — blocks migrate while
        the pool still answers, replay covers the truly-gone case."""
        m = self._model
        with m.cond:
            if self.retiring:
                return
            self.cause = cause or f"lane {self.idx} killed"
            self.retiring = True
            m.cond.notify_all()

    # -- scheduler loop ------------------------------------------------------
    def _loop(self):
        m = self._model
        while True:
            doomed = None
            admit = []
            with m.cond:
                while True:
                    if m.closed or self.retiring:
                        break
                    admit = self._pop_admissions()
                    if admit or self.running:
                        break
                    # idle, or the queue head is a recovery whose
                    # re-reservation cannot fit yet: wait for a
                    # submit or a retire freeing budget (bounded —
                    # the freeing unreserve may race this probe)
                    m.cond.wait(0.1)
                if m.closed:
                    break
                if self.retiring:
                    doomed = list(self.running) + list(self.waiting)
                    self.running = []
                    self.waiting.clear()
            if doomed is not None:
                # evacuate-then-finalize (outside the cond lock): the
                # scale-in initiator may have given up on its join
                # timeout long ago, and a pool nobody closes is a
                # permanent HBM leak
                self._evacuate(doomed)
                return
            if admit:
                m._observe_depth()     # the waiting set just shrank
            try:
                t_adm = clock.now_ns()
                for req in admit:
                    self._start(req)
                # admission work (prefill/replay/migrate landing) runs
                # BEFORE the next decode step: every already-running
                # request's next token is held behind it — the
                # prefill-interleave stall the tail plane attributes
                # per decode step (profiling/tailpath.py)
                interleave_ns = clock.now_ns() - t_adm if admit else 0
                if self.running:
                    self._step(interleave_ns)
            except Exception as e:  # noqa: BLE001 — a failed step
                # evacuates ITS requests onto the surviving lanes
                # (possibly this one); the lane survives for new work
                self._recover_inflight(admit, e)
        # shutdown: nothing new executes — fail whatever is left
        err = ServingError(
            f"generate: model {m.name!r} shut down before the request "
            "completed")
        self._fail_inflight([], err)

    def _pop_admissions(self):
        """Pop admittable waiting requests (caller holds m.cond). A
        recovery re-queued without a reservation must re-reserve
        atomically HERE, on the pool it will actually decode on — a
        full pool leaves it queued (no double-booking), to be retried
        the moment a retire frees budget."""
        m = self._model
        admit = []
        now = clock.now_ns()
        while self.waiting and \
                len(self.running) + len(admit) < m.max_decode_batch:
            req = self.waiting[0]
            if req.reserved_blocks == 0:
                need = self.pool.blocks_for(
                    len(req.prompt) + req.max_new_tokens)
                if not self.pool.reserve(need):
                    # head blocked on cache budget: from here on its
                    # queue wait is KV pressure, not backlog — the
                    # tail plane bills it to kv_wait
                    if not req._kv_wait_t0:
                        req._kv_wait_t0 = now
                    req.queue_cause = "kv_wait"
                    break
                req.reserved_blocks = need
            if req._kv_wait_t0:
                req.kv_wait_ns += max(now - req._kv_wait_t0, 0)
                req._kv_wait_t0 = 0
            if not req.admit_ns:      # first admission wins: a
                req.admit_ns = now    # recovery re-admission is billed
                                      # to recovery, not queue wait
            if req.queue_cause is None:
                req.queue_cause = "backlog" if (self.running or admit) \
                    else "none"
            self.waiting.popleft()
            admit.append(req)
        if self.waiting and \
                len(self.running) + len(admit) >= m.max_decode_batch:
            head = self.waiting[0]
            if head.queue_cause is None:
                head.queue_cause = "batch_full"
        return admit

    def _evacuate(self, doomed):
        """Retiring/killed lane: hand every admitted request to the
        surviving lanes (migrate preferred, deterministic replay as
        the fallback), then finalize. Planned drains, chaos kills,
        and ledger reclaims all exit through here."""
        m = self._model
        _met()["inflight"].labels(model=m.name,
                                  lane=str(self.idx)).set(0)
        m._observe_depth()
        m._recover_requests(
            self, doomed, self.cause or f"lane {self.idx} retired")
        m._finalize_retired_lane(self)

    def _recover_inflight(self, extra, err):
        """A failed prefill/step: route the affected requests through
        migrate/replay instead of failing them (budget-bounded — a
        persistently failing request degrades to ``lane_lost``)."""
        m = self._model
        with m.cond:
            doomed = list(self.running)
            self.running = []
        seen = set()
        uniq = []
        for req in doomed + [r for r in extra if not r.done()]:
            if id(req) not in seen:
                seen.add(id(req))
                uniq.append(req)
        _met()["inflight"].labels(model=m.name,
                                  lane=str(self.idx)).set(0)
        m._observe_depth()
        m._recover_requests(self, uniq, repr(err))

    def _fail_inflight(self, extra, err):
        m = self._model
        with m.cond:
            doomed = list(self.running) + list(self.waiting) + \
                [r for r in extra if not r.done()]
            self.running = []
            self.waiting.clear()
        # the gauges were last set with a live batch — a failed/closed
        # lane must read 0, not its final batch size forever
        _met()["inflight"].labels(model=m.name,
                                  lane=str(self.idx)).set(0)
        m._observe_depth()
        seen = set()
        for req in doomed:
            # an admitted request can sit in both `running` and
            # `extra` — retire (and close the stream of) each once
            if id(req) in seen or req.done():
                continue
            seen.add(id(req))
            self._retire(req, error=err)

    # -- phases --------------------------------------------------------------
    def _start(self, req):
        """Dispatch one admitted request: land a KV-block migration,
        deterministically replay a recovery, or fresh-prefill."""
        if req._salvage is not None and self._land_migration(req):
            return
        if req.tokens:
            self._replay(req)
        else:
            self._prefill(req)

    def _land_migration(self, req):
        """Scatter the request's salvaged KV blocks into THIS pool and
        rejoin it to the running batch — the migrate recovery mode.
        False when the landing fails (wedged/closed): the caller falls
        back to deterministic replay, which only needs the tokens."""
        m = self._model
        met = _met()
        salvage, req._salvage = req._salvage, None
        try:
            table, handoff = m.migrator.land(salvage, self.pool,
                                             m.table_width)
        except MXNetError:
            return False
        req.table = table
        # between steps the cache holds prompt + tokens[:-1]; the very
        # next decode step feeds tokens[-1] at next_pos — the invariant
        # the migrated table preserves byte-for-byte
        req.next_pos = len(req.prompt) + len(req.tokens) - 1
        now = clock.now_ns()
        req.recover_spans.append((
            req._recover_t0 or now, now,
            {"mode": "migrate", "lane": self.idx,
             "cause": req._recover_cause,
             "blocks": handoff["blocks"],
             "bytes_moved": handoff["bytes_moved"],
             "est_s": handoff["est_s"]}))
        req._recovered = True
        met["recoveries"].labels(model=m.name, mode="migrate").inc()
        self.running.append(req)
        met["inflight"].labels(model=m.name, lane=str(self.idx)).set(
            len(self.running))
        self._observe_pool()
        return True

    def _replay(self, req):
        """Deterministic replay on THIS lane: re-prefill the prompt,
        silently re-decode the already-accepted tokens (no
        re-emission — consumers see one seamless stream), rejoin the
        running batch. The greedy==reference contract makes the
        continuation token-for-token identical to the never-killed
        run; a divergence is a determinism bug and raises."""
        m = self._model
        met = _met()
        accepted = list(req.tokens)
        plen = len(req.prompt)
        tpad = pick_bucket(m.prompt_buckets, plen)
        req.table = BlockTable(self.pool, m.table_width)
        req.table.extend(self.pool.blocks_for(plen))
        tokens = np.zeros(tpad, np.int32)
        tokens[:plen] = req.prompt
        first = int(self._host_tokens(self.steps.prefill(
            tokens, plen,
            req.table.row[:tpad // self.pool.block_tokens])))
        req.next_pos = plen
        if first != accepted[0]:
            raise MXNetError(
                "generate: replay diverged at the first token "
                f"({first} != accepted {accepted[0]}) — greedy decode "
                "must be deterministic")
        # batch-1 silent re-decode through the warmed bucket: feed
        # each accepted token at its original position, checking the
        # re-derived successor — never growing past the executables
        # the lane already compiled
        bucket = pick_bucket(m.decode_buckets, 1)
        for j in range(1, len(accepted)):
            req.table.ensure_position(req.next_pos)
            tkn = np.zeros(bucket, np.int32)
            pos = np.zeros(bucket, np.int32)
            tab = np.zeros((bucket, m.table_width), np.int32)
            tkn[0] = accepted[j - 1]
            pos[0] = req.next_pos
            tab[0] = req.table.row
            nxt = int(self._host_tokens(
                self.steps.decode(tkn, pos, tab))[0])
            req.next_pos += 1
            if nxt != accepted[j]:
                raise MXNetError(
                    f"generate: replay diverged at token {j} "
                    f"({nxt} != accepted {accepted[j]}) — greedy "
                    "decode must be deterministic")
        met["tokens"].labels(model=m.name, phase="replay").inc(
            plen + max(len(accepted) - 1, 0))
        met["steps"].labels(model=m.name, phase="replay").inc(
            len(accepted))
        now = clock.now_ns()
        req.recover_spans.append((
            req._recover_t0 or now, now,
            {"mode": "replay", "lane": self.idx,
             "cause": req._recover_cause,
             "prompt_tokens": plen,
             "replayed_tokens": len(accepted)}))
        req._recovered = True
        met["recoveries"].labels(model=m.name, mode="replay").inc()
        self.running.append(req)
        met["inflight"].labels(model=m.name, lane=str(self.idx)).set(
            len(self.running))
        self._observe_pool()

    def _prefill(self, req):
        """One request's padded prompt through the causal stack; emits
        the first greedy token and joins the running set."""
        m = self._model
        met = _met()
        plen = len(req.prompt)
        tpad = pick_bucket(m.prompt_buckets, plen)
        req.table = BlockTable(self.pool, m.table_width)
        req.table.extend(self.pool.blocks_for(plen))
        tokens = np.zeros(tpad, np.int32)
        tokens[:plen] = req.prompt
        t0 = clock.now_ns()
        tok_dev = self.steps.prefill(
            tokens, plen, req.table.row[:tpad // self.pool.block_tokens])
        tok = int(self._host_tokens(tok_dev))
        req.prefill_exec_ns = clock.now_ns() - t0
        req.prompt_pad = tpad
        req.next_pos = plen
        met["tokens"].labels(model=m.name, phase="prefill").inc(plen)
        met["steps"].labels(model=m.name, phase="prefill").inc()
        self._emit(req, tok, t0, clock.now_ns(), rows=plen, bucket=tpad)
        if req.finish_reason is None:
            self.running.append(req)
            met["inflight"].labels(model=m.name,
                                   lane=str(self.idx)).set(
                len(self.running))
        else:
            self._retire(req)

    def _step(self, interleave_ns=0):
        """One iteration-level decode step over the running batch.
        ``interleave_ns`` is the admission work (prefill/replay) that
        held this step — stamped on every emitted token so the tail
        plane can blame the stall per request."""
        m = self._model
        met = _met()
        live = self.running
        bucket = pick_bucket(m.decode_buckets, len(live))
        tokens = np.zeros(bucket, np.int32)
        positions = np.zeros(bucket, np.int32)
        tables = np.zeros((bucket, m.table_width), np.int32)
        for i, req in enumerate(live):
            req.table.ensure_position(req.next_pos)
            tokens[i] = req.tokens[-1]
            positions[i] = req.next_pos
            tables[i] = req.table.row
        t0 = clock.now_ns()
        toks = self._host_tokens(
            self.steps.decode(tokens, positions, tables))
        t1 = clock.now_ns()
        met["steps"].labels(model=m.name, phase="decode").inc()
        met["tokens"].labels(model=m.name, phase="decode").inc(len(live))
        self._observe_pool()
        finished = []
        for i, req in enumerate(live):
            req.next_pos += 1
            self._emit(req, int(toks[i]), t0, t1,
                       interleave_ns=interleave_ns, rows=len(live),
                       bucket=bucket)
            if req.finish_reason is not None:
                finished.append(req)
        for req in finished:
            live.remove(req)
            self._retire(req)
        met["inflight"].labels(model=m.name, lane=str(self.idx)).set(
            len(live))

    def _host_tokens(self, tok_dev):
        """The token reply transfer: generated ids must reach the host
        to be streamed to clients (and to drive stopping + the next
        step's feed). The ONE sanctioned device read per step —
        everything else on the step path is host bookkeeping."""
        return np.asarray(tok_dev)

    def _emit(self, req, tok, step_start_ns, now_ns, interleave_ns=0,
              rows=1, bucket=1):
        """Record + stream one generated token; marks the request
        finished when it hits EOS or its budget. The step metadata
        (interleave stall, real rows, padded bucket) rides along so
        retirement can stamp it onto the token spans — the tail
        plane's per-step blame inputs."""
        m = self._model
        met = _met()
        phase = "recover" if req._recovered else "steady"
        req._recovered = False
        if not req.tokens:
            req.first_token_ns = now_ns
            met["ttft"].labels(model=m.name, phase=phase).observe(
                (now_ns - req.submit_ns) / 1e9)
        else:
            met["inter_token"].labels(
                model=m.name, phase=phase).observe(
                (now_ns - req.last_token_ns) / 1e9)
        req.last_token_ns = now_ns
        req.token_spans.append((step_start_ns, now_ns))
        req.step_meta.append((interleave_ns, rows, bucket))
        req._push_token(tok)
        if m.eos_id is not None and tok == m.eos_id:
            req.finish_reason = "eos"
        elif len(req.tokens) >= req.max_new_tokens:
            req.finish_reason = "length"

    def _observe_pool(self):
        m = self._model
        met = _met()
        occ = self.pool.occupancy()
        lane = str(self.idx)
        for state in ("used", "free", "reserved"):
            met["cache_blocks"].labels(
                model=m.name, lane=lane, state=state).set(
                occ["%s_blocks" % state])
        met["occupancy"].labels(model=m.name).observe(occ["used_frac"])

    # -- retirement ----------------------------------------------------------
    def _retire(self, req, error=None):
        m = self._model
        if req.table is not None:
            req.table.release()
            req.table = None
        if req.reserved_blocks:
            self.pool.unreserve(req.reserved_blocks)
            req.reserved_blocks = 0
        req._salvage = None
        # the freed reservation may be exactly what a queued recovery
        # on another lane is waiting to re-reserve
        with m.cond:
            m.cond.notify_all()
        self._observe_pool()
        self._record_spans(req, error)
        req._finish(error)

    def _record_spans(self, req, error):
        m = self._model
        trace_id, parent = req.trace_ctx
        if not trace_id:
            return
        end = req.last_token_ns or clock.now_ns()
        admit_wait = max(req.admit_ns - req.submit_ns, 0) \
            if req.admit_ns else 0
        root = tracing.record_span(
            "serving.generate", trace_id, parent, req.submit_ns, end,
            cat="serving",
            attrs={"model": m.name, "lane": self.idx,
                   "prompt_tokens": len(req.prompt),
                   "new_tokens": len(req.tokens),
                   "recoveries": req.recoveries,
                   "queue_cause": req.queue_cause or "none",
                   "finish": ("error" if error is not None
                              else req.finish_reason)})
        if req.first_token_ns:
            tracing.record_span(
                "generate.prefill", trace_id, root, req.submit_ns,
                req.first_token_ns, cat="serving",
                attrs={"prompt_tokens": len(req.prompt),
                       "pad_tokens": req.prompt_pad,
                       "queue_ns": admit_wait,
                       "kv_wait_ns": req.kv_wait_ns,
                       "exec_ns": req.prefill_exec_ns})
        for s, e, attrs in req.recover_spans:
            tracing.record_span("generate.recover", trace_id, root,
                                s, e, cat="serving", attrs=attrs)
        for j, (s, e) in enumerate(req.token_spans):
            attrs = {"index": j}
            if j < len(req.step_meta):
                inter, rows, bucket = req.step_meta[j]
                attrs.update(interleave_ns=inter, rows=rows,
                             bucket=bucket)
            tracing.record_span("generate.token", trace_id, root, s, e,
                                cat="serving", attrs=attrs)


class GenModel:
    """One registered generator: decoder + N lanes + admission state.
    Built by ``Gateway.register_generator``; requests enter through
    :meth:`submit` (usually via the gateway, which owns the reject
    metrics + error messages)."""

    def __init__(self, name, decoder, devices, block_tokens,
                 max_blocks, max_new_tokens, max_decode_batch,
                 max_queue, warmup=True, tp=None, layout=None):
        self.name = name
        self.decoder = decoder
        # tp >= 2: every lane is a mesh slice (devices = list of
        # tp-device tuples); the KV pool shards its heads axis over
        # the slice and the compiled steps run as one SPMD program,
        # parameters placed from the layout plane's role table
        self.tp = tp
        self.layout = layout
        self.eos_id = decoder.eos_id
        self.block_tokens = int(block_tokens)
        self.max_blocks = int(max_blocks)
        self.max_new_tokens = int(max_new_tokens)
        self.max_decode_batch = int(max_decode_batch)
        self.max_queue = int(max_queue)
        self.closed = False
        self.cond = threading.Condition(threading.Lock())
        # decode failover: how many lane losses one request survives
        # before degrading to a fast lane_lost reject, and the backoff
        # base between REPEAT recoveries of the same request (doubling,
        # capped at 40x base — the first rescue is always immediate)
        self.max_recoveries = max(
            int(get_env("MXTPU_GEN_MAX_RECOVERIES", 2, int)), 0)
        self.recovery_backoff_ms = max(
            float(get_env("MXTPU_GEN_RECOVERY_BACKOFF_MS", 50.0,
                          float)), 0.0)
        self.fault_plan = None   # None -> MXNET_KVSTORE_FAULT_PLAN
        self.migrator = KVMigrator(name)
        self.lane_lost_rejections = 0
        self._recovery_round = 0
        bt = self.block_tokens
        max_prompt_pad = _ceil_mul(decoder.max_prompt_tokens, bt)
        # prompt pads: the PR 10 bucket ladder in units of blocks —
        # <2x pad waste, O(log n) prefill executables
        self.prompt_buckets = tuple(
            b * bt for b in default_buckets(max_prompt_pad // bt))
        self.decode_buckets = default_buckets(self.max_decode_batch)
        self.table_width = (max_prompt_pad + _ceil_mul(
            self.max_new_tokens, bt)) // bt
        capacity = self.table_width  # blocks a maximal request needs
        if capacity > self.max_blocks - 1:
            raise ServingError(
                f"generate: model {name!r} needs up to {capacity} "
                f"blocks per request but the pool only has "
                f"{self.max_blocks - 1} usable (raise "
                "MXTPU_GEN_MAX_BLOCKS or lower max_prompt_tokens/"
                "max_new_tokens)")
        self.lanes = []
        self.warmup_seconds = 0.0
        self.executables = 0
        self.degraded = False
        self._warmup_lanes = bool(warmup)
        self._next_idx = 0
        t0 = clock.now_ns()
        for device in devices:
            self.lanes.append(self._build_lane(device))
        self.warmup_seconds = (clock.now_ns() - t0) / 1e9
        for lane in self.lanes:
            lane.start()

    def _build_lane(self, device):
        """One decode lane (pool + compiled steps + scheduler), warmed
        when the model warms — registration and elastic scale-out
        share this, so a scaled-out lane is AOT-compiled exactly like
        a registered one. The caller starts it."""
        from .model import CompiledDecodeSteps
        if isinstance(device, (list, tuple)) and len(device) == 1:
            device = device[0]       # a 1-device "slice" = plain lane
        pool = BlockPool(self.decoder.num_layers,
                         self.decoder.num_heads,
                         self.decoder.head_dim, self.block_tokens,
                         self.max_blocks, device=device,
                         dtype=self.decoder.dtype)
        steps = CompiledDecodeSteps(self.decoder, pool,
                                    self.table_width, device,
                                    layout=self.layout)
        lane = GenLane(self, self._next_idx, device, steps, pool)
        self._next_idx += 1
        if self._warmup_lanes:
            self.executables += self._warmup(lane)
        return lane

    def _warmup(self, lane):
        """AOT-compile every (prefill pad, decode bucket) executable
        with pad-sink-only writes — after this, steady-state decode
        never retraces."""
        n = 0
        bt = self.block_tokens
        for tpad in self.prompt_buckets:
            lane.steps.prefill(np.zeros(tpad, np.int32), 1,
                               np.zeros(tpad // bt, np.int32))
            n += 1
        for b in self.decode_buckets:
            lane.steps.decode(np.zeros(b, np.int32),
                              np.zeros(b, np.int32),
                              np.zeros((b, self.table_width), np.int32))
            n += 1
        return n

    # -- admission -----------------------------------------------------------
    def try_admit(self, req):
        """None and an assigned lane on success, else the rejection
        reason (pure bookkeeping — fast-reject in the caller's
        thread)."""
        if self.closed:
            return "closed"
        with self.cond:
            depth = sum(len(ln.waiting) for ln in self.lanes)
            # retiring lanes drain, they do not admit — their pools
            # are about to be released
            live = [ln for ln in self.lanes if not ln.retiring]
        if not live:
            return "closed"
        if depth >= self.max_queue:
            return "queue_full"
        need = live[0].pool.blocks_for(
            len(req.prompt) + req.max_new_tokens)
        # most-headroom lane first; reservation is atomic per pool, so
        # a racing submit simply falls through to the next lane
        order = sorted(
            live,
            key=lambda ln: ln.pool.reserved_blocks())
        for lane in order:
            if lane.pool.reserve(need):
                req.reserved_blocks = need
                with self.cond:
                    if self.closed:
                        lane.pool.unreserve(need)
                        req.reserved_blocks = 0
                        return "closed"
                    if lane.retiring:
                        # scale-in landed between the reserve and the
                        # enqueue: hand the budget back and try the
                        # next lane
                        lane.pool.unreserve(need)
                        req.reserved_blocks = 0
                        continue
                    lane.waiting.append(req)
                    self.cond.notify_all()
                self._observe_depth()
                return None
        return "kv_cache_full"

    def _observe_depth(self):
        """Publish the waiting count on the shared queue-depth gauge
        (host ints under the cond lock — MXL002-safe)."""
        with self.cond:
            depth = sum(len(ln.waiting) for ln in self.lanes)
        _met()["depth"].labels(model=self.name).set(depth)

    # -- decode failover -----------------------------------------------------
    def _recover_requests(self, src_lane, reqs, cause):
        """Evacuate ``reqs`` off ``src_lane`` onto surviving lanes.

        Per request: enforce the recovery budget (exhaustion = fast
        ``lane_lost`` reject), salvage its KV blocks while the source
        pool still answers (unless a ``replay_storm`` fault forces
        the device-truly-gone case), detach it from the source pool,
        then re-admit on the lane with the most headroom — reserving
        atomically on the target, or queueing unreserved when every
        pool is full (the target's admission loop re-reserves the
        moment a retire frees budget; nothing double-books). Requests
        that never decoded a token just requeue — they lost no state,
        so they spend no budget."""
        from ...kvstore import fault as _fault
        import time as _time
        met = _met()
        reqs = [r for r in reqs if not r.done()]
        if not reqs:
            return
        with self.cond:
            self._recovery_round += 1
            rround = self._recovery_round
        storm = _fault.replay_storm_active(rround, plan=self.fault_plan)
        for req in reqs:
            # typed cause on the eventual generate.recover span: the
            # tail plane bills reclaim/drain pauses separately from
            # unplanned-crash recovery (profiling/tailpath.py)
            req._recover_cause = cause
            if self.closed:
                src_lane._retire(req, error=ServingError(
                    f"generate: model {self.name!r} shut down before "
                    "the request completed"))
                continue
            if req.tokens:
                req.recoveries += 1
                if req.recoveries > self.max_recoveries:
                    with self.cond:
                        self.lane_lost_rejections += 1
                    met["rejected"].labels(model=self.name,
                                           reason="lane_lost").inc()
                    src_lane._retire(req, error=RejectedError(
                        "lane_lost",
                        f"generate: request on {self.name!r} lost its "
                        f"lane {req.recoveries} time(s) ({cause}) and "
                        "exhausted its recovery budget (MXTPU_GEN_MAX_"
                        f"RECOVERIES={self.max_recoveries}); resubmit "
                        "to retry"))
                    continue
                if req.recoveries > 1:
                    # bounded backoff between REPEAT rescues of one
                    # request — a request ping-ponging across dying
                    # lanes must not busy-spin the recovery path. This
                    # is pacing, not polling: nothing signals "retry
                    # now", so an Event wait would just be a sleep that
                    # wakes early (no lock is held across it)
                    # mxlint: disable=MXL009
                    _time.sleep(min(
                        self.recovery_backoff_ms
                        * 2.0 ** (req.recoveries - 2),
                        self.recovery_backoff_ms * 40.0) / 1e3)
                req._recover_t0 = clock.now_ns()
                if req._salvage is None and not storm \
                        and req.table is not None and req.table.blocks:
                    try:
                        req._salvage = self.migrator.salvage(
                            src_lane.pool, req.table.blocks)
                    except MXNetError:
                        req._salvage = None   # replay covers it
            # detach from the source pool (the salvage, when taken,
            # owns its bytes — the pool can close right after)
            if req.table is not None:
                req.table.release()
                req.table = None
            if req.reserved_blocks:
                src_lane.pool.unreserve(req.reserved_blocks)
                req.reserved_blocks = 0
            req.next_pos = 0
            need = src_lane.pool.blocks_for(
                len(req.prompt) + req.max_new_tokens)
            while True:
                with self.cond:
                    live = [ln for ln in self.lanes
                            if not ln.retiring and not ln.finalized]
                if not live:
                    with self.cond:
                        self.lane_lost_rejections += 1
                    met["rejected"].labels(model=self.name,
                                           reason="lane_lost").inc()
                    src_lane._retire(req, error=RejectedError(
                        "lane_lost",
                        f"generate: model {self.name!r} has no "
                        f"surviving decode lanes to recover onto "
                        f"({cause})"))
                    break
                order = sorted(
                    live, key=lambda ln: ln.pool.reserved_blocks())
                target = None
                for ln in order:
                    if ln.pool.reserve(need):
                        req.reserved_blocks = need
                        target = ln
                        break
                if target is None:
                    # kv_cache_full during recovery: queue on the
                    # least-booked lane with NO reservation — its
                    # admission loop re-reserves atomically once a
                    # retire frees budget
                    target = order[0]
                with self.cond:
                    if not target.retiring:
                        target.waiting.append(req)
                        self.cond.notify_all()
                        break
                # the target started retiring between selection and
                # enqueue: hand the budget back and pick again
                if req.reserved_blocks:
                    target.pool.unreserve(req.reserved_blocks)
                    req.reserved_blocks = 0
        self._observe_depth()

    # -- lifecycle -----------------------------------------------------------
    def scale_to(self, n, devices, drain_timeout=30.0):
        """Resize to ``n`` decode lanes (Gateway.scale's generator
        arm). ``devices`` is the full n-lane placement (the gateway's
        picker output). Scale-out builds + warms + starts fresh lanes;
        scale-in retires the newest lanes evacuate-first: each stops
        admitting, hands its waiting+running requests to the surviving
        lanes through the migrate/replay recovery path (planned drains
        and crashes are one code path — no request waits out a drain
        timeout), and releases its KV block pool — the census
        role=kv_cache bytes drop by exactly the retired pools'
        footprint."""
        n = int(n)
        if n < 1:
            raise ServingError(
                f"generate: cannot scale {self.name!r} below 1 lane")
        with self.cond:
            active = [ln for ln in self.lanes if not ln.retiring]
        report = {"model": self.name, "from": len(active), "to": n,
                  "added": 0, "retired": 0, "freed_bytes": 0}
        if n > len(active):
            for device in devices[len(active):n]:
                lane = self._build_lane(device)
                with self.cond:
                    self.lanes.append(lane)
                lane.start()
                report["added"] += 1
        elif n < len(active):
            for lane in active[n:]:
                report["freed_bytes"] += self._retire_lane(
                    lane, timeout=drain_timeout)
                report["retired"] += 1
        return report

    def _retire_lane(self, lane, timeout=30.0):
        """Evacuate-then-retire one lane; returns the pool bytes
        released. The lane hands its admitted requests to the
        surviving lanes (KV blocks migrated, or replayed when
        unsalvageable), then exits and finalizes — typically well
        inside ``timeout``, since nothing waits for decodes to
        finish. A lane that cannot evacuate within ``timeout`` stays
        retiring (no new work) with its pool intact — closing storage
        under an in-flight copy would corrupt live requests — and
        finalizes ITSELF the moment it empties, so a timed-out
        initiator never leaks the pool."""
        from ... import tracing
        with tracing.span("elastic.drain", cat="elastic",
                          model=self.name, lane=lane.idx):
            pending = lane.pool.bytes_total
            with self.cond:
                lane.retiring = True
                self.cond.notify_all()
            lane.join(timeout)
            if lane._thread is not None and lane._thread.is_alive():
                return 0   # still draining: the lane self-finalizes
            # the lane thread usually finalized itself on its way
            # out; this call is the idempotent backstop (and the
            # whole release for lanes retired before ever starting)
            self._finalize_retired_lane(lane)
            return pending

    def _finalize_retired_lane(self, lane):
        """Close the retired lane's pool, drop it from the lane list,
        zero its gauges — exactly once, no matter whether the
        initiator's join or the lane thread's own drained-exit gets
        here first."""
        with self.cond:
            if lane.finalized:
                return 0
            lane.finalized = True
        freed = lane.pool.bytes_total
        lane.pool.close()
        with self.cond:
            if lane in self.lanes:
                self.lanes.remove(lane)
        met = _met()
        ln = str(lane.idx)
        for state in ("used", "free", "reserved"):
            met["cache_blocks"].labels(
                model=self.name, lane=ln, state=state).set(0)
        met["inflight"].labels(model=self.name, lane=ln).set(0)
        return freed

    def close(self):
        with self.cond:
            self.closed = True
            self.cond.notify_all()
        for lane in self.lanes:
            lane.join(timeout=5.0)

    def stats(self):
        with self.cond:
            waiting = sum(len(ln.waiting) for ln in self.lanes)
            running = sum(len(ln.running) for ln in self.lanes)
        return {
            "waiting": waiting,
            "running": running,
            "max_decode_batch": self.max_decode_batch,
            "max_new_tokens": self.max_new_tokens,
            "max_queue": self.max_queue,
            "prompt_buckets": list(self.prompt_buckets),
            "decode_buckets": list(self.decode_buckets),
            "table_width": self.table_width,
            "executables": self.executables,
            "warmup_seconds": round(self.warmup_seconds, 3),
            "degraded": self.degraded,
            "tp": self.tp,
            "recovery": dict(
                self.migrator.stats(),
                max_recoveries=self.max_recoveries,
                lane_lost_rejections=self.lane_lost_rejections),
            "lanes": [
                {"idx": ln.idx, "device": str(ln.device),
                 "retiring": ln.retiring,
                 "pool": ln.pool.occupancy()} for ln in self.lanes],
        }


def _ceil_mul(n, m):
    return ((int(n) + m - 1) // m) * m
