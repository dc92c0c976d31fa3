"""Driver: closed-loop generation through ``Gateway.submit_generate``.

``clients`` threads each submit their next request when the last one
completes. Set-up builds the decoder at the configuration's sizes, loads
the benchmark's weights into it, registers it (which warms every prompt
and decode bucket) and runs the ramp: clients start, and the window
opens once every client has had one request complete. At the window's
end no new request is submitted and those in flight drain outside it.
"""
import gc
import importlib
import threading
import time

import numpy as np

from benchmark.lib import traffic


def _now():
    return time.monotonic_ns()


class Cell:
    MODEL = "lm"

    def __init__(self, cfg, workload, seed):
        self.cfg, self.wl, self.seed = cfg, workload, int(seed)
        self.tp = workload["traffic_params"]
        self.ref = importlib.import_module(
            "benchmark.reference." + cfg["reference"])
        self.records = []
        self._lock = threading.Lock()
        self._next = 0
        self._stop = threading.Event()

    # -- set-up --------------------------------------------------------
    def setup(self):
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu.serving import GenerativeDecoder

        self.jax = jax
        cfg, tp = self.cfg, self.tp
        np.random.seed(self.seed % 2 ** 32)
        mx.random.seed(self.seed % 2 ** 31)
        decoder = GenerativeDecoder(
            cfg["vocab_size"], d_model=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            ff_mult=cfg["ffn_dim"] // cfg["hidden_size"],
            max_prompt_tokens=tp["max_prompt_tokens"], eos_id=None,
            dtype=cfg["dtype"])
        # serving takes no gradient: gluon's own switch drops the
        # gradient buffer that every parameter is born with (as large
        # again as the weights)
        for p in decoder.block.collect_params().values():
            p.grad_req = "null"
        if cfg.get("layer_norm_dtype", "float32") != "float32":
            # gluon's own way to a net that is 16-bit throughout, as the
            # published checkpoint is: without it the LayerNorms stay
            # float32, every activation is promoted, and each write
            # into the 16-bit cache converts the whole pool (PERF.md)
            decoder.block.cast(cfg["layer_norm_dtype"])
        self.load_weights(decoder, self.seed)
        bt = tp["block_tokens"]
        width = -(-tp["max_prompt_tokens"] // bt) + -(-tp["max_new_tokens"] // bt)
        self.gw = mx.serving.Gateway()
        self.gen = self.gw.register_generator(
            self.MODEL, decoder, block_tokens=bt,
            max_blocks=tp["clients"] * width + 1,
            max_new_tokens=tp["max_new_tokens"],
            max_decode_batch=tp["clients"], replicas=1)
        self.decoder = decoder
        self._ramp()

    def reseed(self, seed):
        """Another seed's weights and traffic on the registration that
        is there (benchmark/control.py reads many seeds in one
        process): the lane's compiled steps take their parameters as an
        argument, so the new tree is handed to them."""
        self.seed = int(seed)
        for lane in self.gen.lanes:       # or two sets of weights live
            lane.steps.params = None
        self.load_weights(self.decoder, self.seed)
        for lane in self.gen.lanes:
            lane.steps.params = self.decoder.param_tree()
        self.records, self._next = [], 0
        self._stop = threading.Event()
        self._ramp()

    def _ramp(self):
        tp = self.tp
        self.plan = traffic.order(tp, self.seed)
        self._first_done = [threading.Event() for _ in range(tp["clients"])]
        self.threads = [threading.Thread(target=self._client, args=(i,),
                                         daemon=True)
                        for i in range(tp["clients"])]
        for t in self.threads:
            t.start()
        for ev in self._first_done:       # the ramp
            if not ev.wait(600.0):
                raise RuntimeError("ramp: a client's first request did "
                                   "not complete in 600 s")

    def load_weights(self, decoder, seed):
        """The benchmark's weights, made on the device from the seed in
        the served types, into the program's gluon parameters: one
        jitted call per layer."""
        from mxnet_tpu.ndarray import NDArray

        blk = decoder.block

        def put(param, value):
            if tuple(param.shape) != tuple(value.shape) or \
                    np.dtype(param.dtype) != np.dtype(value.dtype):
                raise RuntimeError(
                    "leaf %s: program %r %s, reference %r %s"
                    % (param.name, param.shape, param.dtype, value.shape,
                       value.dtype))
            param.set_data(NDArray(value))

        ends = self.ref.end_params(seed, self.cfg)
        put(blk.embed.weight, ends["embed_w"])
        put(blk.ln_f.gamma, ends["lnf_g"])
        put(blk.ln_f.beta, ends["lnf_b"])
        put(blk.head.weight, ends["head_w"])
        del ends
        for i, layer in enumerate(blk.layers):
            # a layer at a time: each replaces the program's own draw of
            # that layer, which is freed as it goes
            lp = self.ref.layer_params(seed, self.cfg, i)
            put(layer.ln1.gamma, lp["ln1_g"])
            put(layer.ln1.beta, lp["ln1_b"])
            put(layer.qkv.weight, lp["qkv_w"])
            put(layer.qkv.bias, lp["qkv_b"])
            put(layer.proj.weight, lp["proj_w"])
            put(layer.proj.bias, lp["proj_b"])
            put(layer.ln2.gamma, lp["ln2_g"])
            put(layer.ln2.beta, lp["ln2_b"])
            put(layer.ff1.weight, lp["ff1_w"])
            put(layer.ff1.bias, lp["ff1_b"])
            put(layer.ff2.weight, lp["ff2_w"])
            put(layer.ff2.bias, lp["ff2_b"])

    # -- clients ---------------------------------------------------------
    def _client(self, i):
        vocab = self.cfg["vocab_size"]
        while not self._stop.is_set():
            with self._lock:
                k = self._next
                self._next += 1
            plen, olen = self.plan[k % len(self.plan)]
            prompt = traffic.prompt_tokens(self.seed, k, plen, vocab)
            rec = {"k": k, "prompt_len": plen, "max_new": olen,
                   "req": None, "error": None, "submit_ns": _now()}
            try:
                rec["req"] = self.gw.submit_generate(
                    self.MODEL, prompt, max_new_tokens=olen)
                rec["req"].result(300.0)
            except Exception as e:  # noqa: BLE001 — a failed request
                # is counted, and the client goes on
                rec["error"] = repr(e)
                time.sleep(0.001)
            with self._lock:
                self.records.append(rec)
            self._first_done[i].set()

    # -- the measured window -------------------------------------------
    def window(self, seconds, tracer):
        slice_s = self.wl["trace_seconds"] if tracer else 0.0
        w0 = _now()
        time.sleep(seconds - slice_s)
        w1 = _now()
        traced = (w1, w1)
        if tracer:
            with tracer:
                time.sleep(slice_s)
            traced = (tracer.t0_ns, tracer.t1_ns)
        t_stop = _now()
        self._stop.set()
        for t in self.threads:
            t.join(300.0)
        drain_end = _now()
        with self._lock:
            records = list(self.records)
        requests = []
        for rec in records:
            req = rec.pop("req")    # the program's object goes with it
            row = {"k": rec["k"], "prompt_len": rec["prompt_len"],
                   "max_new": rec["max_new"], "submit_ns": rec["submit_ns"],
                   "token_ns": [], "steps": [], "tokens": [],
                   "ok": rec["error"] is None}
            if req is not None:
                row["submit_ns"] = req.submit_ns
                row["token_ns"] = [e for _, e in req.token_spans]
                row["steps"] = [(s, e) + tuple(meta) for (s, e), meta in
                                zip(req.token_spans, req.step_meta)]
                row["tokens"] = [int(t) for t in req.tokens]
            requests.append(row)
        self._run = {"w0_ns": w0, "w1_ns": w1, "traced_ns": traced,
                     "stop_ns": t_stop, "drain_end_ns": drain_end,
                     "requests": requests}
        return self._run

    def summary(self, run):
        from benchmark.lib import stats

        acc = stats.gen_window(run["requests"], run["w0_ns"], run["w1_ns"],
                               run["drain_end_ns"])
        e2e = {k: acc[k] for k in ("gen_tok_per_s", "gen_ttft_p95_ms",
                                   "gen_gap_p95_ms") if k in acc}
        return {"attempted": acc["attempted"], "failed": acc["failed"],
                "end_to_end": e2e, "extra": acc}

    # -- after the window -----------------------------------------------
    def release(self):
        self.gw.close()
        self.gw = self.gen = self.decoder = self.threads = None
        self.records = []
        gc.collect()

    def sample(self, run_requests, w0, w1):
        """The requests the reference follows: of those submitted in the
        window and finished, the longest and ``check_requests`` - 1
        more, drawn from the seed."""
        done = [r for r in run_requests
                if r["ok"] and r["tokens"] and w0 <= r["submit_ns"] < w1]
        if not done:
            return []
        longest = max(done, key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                           -r["k"]))
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng([self.seed, 3])
        n = min(self.wl["check_requests"] - 1, len(rest))
        picks = rng.choice(len(rest), n, replace=False) if n else []
        return [longest] + [rest[i] for i in sorted(picks)]

    def numbers(self):
        run = self._run
        reqs = run["requests"]
        vocab = self.cfg["vocab_size"]
        bad = sum(1 for r in reqs if r["ok"] and (
            len(r["tokens"]) != r["max_new"]
            or any(t < 0 or t >= vocab for t in r["tokens"])))
        lost = sum(1 for r in reqs if not r["ok"])
        picked = self.sample(reqs, run["w0_ns"], run["stop_ns"])
        gaps = self.served_gaps(picked, self.seed)
        self._detail = {"checked_requests": len(picked),
                        "checked_tokens": len(gaps),
                        "longest": max([r["prompt_len"] + len(r["tokens"])
                                        for r in picked] or [0])}
        numbers = {"served_gap_max": max(gaps) if gaps else float("inf"),
                   "bad_completions": bad, "lost_requests": lost}
        return numbers, {}

    def detail(self):
        return self._detail

    def served_gaps(self, picked, seed, low=None):
        """For every served token of the picked requests, the gap by
        which its reference logit lies below the reference's best; one
        full causal forward of the plain
        reference per request over its prompt and served tokens. With
        ``low`` (the control, benchmark/control.py: ``"fp8"`` or
        ``"int8"``) the tokens judged are those the forward in that
        precision puts first."""
        import jax.numpy as jnp

        tp = self.tp
        width = tp["max_prompt_tokens"] + tp["max_new_tokens"]
        out = []
        for r in picked:
            plen, served = r["prompt_len"], r["tokens"]
            prompt = traffic.prompt_tokens(seed, r["k"], plen,
                                           self.cfg["vocab_size"])
            toks = np.zeros((1, width), np.int32)
            toks[0, :plen] = prompt
            toks[0, plen:plen + len(served)] = served
            pos = np.zeros((1, tp["max_new_tokens"]), np.int32)
            pos[0, :len(served)] = plen - 1 + np.arange(len(served))
            logits = self.ref.logits_at(seed, self.cfg, toks, pos)[0]
            judged = jnp.asarray(served, jnp.int32)
            if low:
                lower = self.ref.logits_at(seed, self.cfg, toks, pos,
                                           low=low)[0]
                judged = jnp.argmax(lower[:len(served)], -1)
            gaps = self.ref.served_gaps(logits[:len(served)], judged)
            out.extend(np.asarray(gaps).tolist())
        return out


def _spread(gaps):
    gaps = sorted(gaps)
    n = len(gaps)
    return {"max": gaps[-1], "mean": sum(gaps) / n, "p90": gaps[int(0.9 * (n - 1))],
            "p99": gaps[int(0.99 * (n - 1))],
            "agree": sum(1 for g in gaps if g == 0.0) / n}


def readings(cfg, workload, seeds, what, seconds=12.0):
    """For benchmark/control.py: ``program`` gives the served tokens'
    widest gap over several seeds in one process (one registration; each
    seed's weights are swapped into the lane, then ramp, a short window
    at the cell's own load, drain, compare). ``control`` (fp8, e4m3) and
    ``control_int8`` give, on the same requests, the widest gap of the
    tokens that the reference in that precision puts first."""
    cell = None
    for seed in seeds:
        if cell is None:
            cell = Cell(cfg, workload, seed)
            cell.setup()
        else:
            cell.reseed(seed)
        run = cell.window(seconds, None)
        picked = cell.sample(run["requests"], run["w0_ns"], run["w1_ns"])
        row = {"seed": seed, "what": what, "requests": len(picked),
               "tokens": sum(len(r["tokens"]) for r in picked)}
        row["served"] = _spread(cell.served_gaps(picked, seed))
        row["served_gap_max"] = row["served"]["max"]
        if what != "program":
            low = {"control": "fp8", "control_int8": "int8"}[what]
            row["control"] = _spread(cell.served_gaps(picked, seed, low))
            row["control_gap_max"] = row["control"]["max"]
        yield row
    cell.release()
